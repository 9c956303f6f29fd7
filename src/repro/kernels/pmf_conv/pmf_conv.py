"""Pallas TPU kernel: batched PMF convolution with deadline truncation.

The dissertation's pruning mechanism spends its overhead convolving PET and
PCT PMFs (§5.5 introduces memoization + impulse compaction to tame it).
The TPU adaptation: impulse compaction normalizes every PMF onto a fixed
``L``-bucket grid, which turns the per-(task, machine) convolutions into a
dense batched computation — this kernel evaluates a whole mapping event's
(batch x machine) chance-of-success matrix in one launch.

Grid: (N / BN,) — one program per batch tile.
Blocks (VMEM): pet (BN, Le), pct (BN, Lo), dl (BN, 1) -> out (BN, Lo),
success (BN, 1).  The inner loop runs Le vector FMAs on (BN, Lo) lanes —
VPU-friendly; Lo is padded to a multiple of 128 (lane width) and the PCT
zero-padded onto it before the launch.

Semantics match ``ref.pmf_conv_ref`` (PEND_DROP, Eq. 5.4):
  out     = conv(pet, pct * [t < dl]) + passthrough(pct * [t >= dl])
  success = sum_{t <= dl} conv(pet, pct * [t < dl])[t]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pet_ref, pct_ref, dl_ref, out_ref, suc_ref):
    pet = pet_ref[...]                       # (BN, Le)
    pct = pct_ref[...]                       # (BN, Lo), zero past Lc
    dl = dl_ref[...]                         # (BN, 1) f32 (deadline index)

    bn, lo = pct.shape
    le = pet.shape[1]
    t_i = jax.lax.broadcasted_iota(jnp.int32, (bn, lo), 1)
    t_o = t_i.astype(jnp.float32)            # Mosaic's iota is integer-only
    ok = (t_o < dl).astype(pct.dtype)
    base = pct * ok                          # truncated PCT on the out grid
    t_e = jax.lax.broadcasted_iota(jnp.int32, (bn, le), 1)

    def body(k, acc):
        # out += pet[:, k] * pct_ok[t - k]: column k of pet by a masked sum
        # (exact: one nonzero term) and the shift by a lane rotate — Mosaic
        # lowers neither a dynamic lane slice nor a gather
        pet_k = jnp.sum(jnp.where(t_e == k, pet, 0.0), axis=1, keepdims=True)
        shifted = jnp.where(t_i >= k, pltpu.roll(base, k, 1), 0.0)
        return acc + pet_k * shifted

    acc = jax.lax.fori_loop(0, le, body,
                            jnp.zeros((bn, lo), jnp.float32))
    suc_ref[...] = jnp.sum(
        jnp.where(t_o <= dl, acc, 0.0), axis=1, keepdims=True)
    out_ref[...] = acc + pct * (1.0 - ok)


def pmf_conv_pallas(pet: jnp.ndarray, pct: jnp.ndarray, dl: jnp.ndarray,
                    block_n: int = 8, interpret: bool = False):
    """Batched PEND_DROP convolution.  pet (N, Le), pct (N, Lc), dl (N,).

    Returns (out (N, Lo), success (N,)); Lo = Lc + Le - 1 padded to 128.
    """
    n, le = pet.shape
    lc = pct.shape[1]
    lo_true = lc + le - 1
    lo = ((lo_true + 127) // 128) * 128
    block_n = min(block_n, n)
    pad_n = (-n) % block_n
    # the PCT rides on the (lane-aligned) output grid, so the kernel never
    # concatenates at an unaligned lane offset
    pct = jnp.pad(pct, ((0, pad_n), (0, lo - lc)))
    if pad_n:
        pet = jnp.pad(pet, ((0, pad_n), (0, 0)))
        dl = jnp.pad(dl, (0, pad_n))
    nn = pet.shape[0]
    dl2 = dl.astype(jnp.float32)[:, None]

    out, suc = pl.pallas_call(
        _kernel,
        grid=(nn // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, le), lambda i: (i, 0)),
            pl.BlockSpec((block_n, lo), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, lo), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nn, lo), jnp.float32),
            jax.ShapeDtypeStruct((nn, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pet.astype(jnp.float32), pct.astype(jnp.float32), dl2)
    return out[:n, :lo_true], jnp.minimum(suc[:n, 0], 1.0)
