"""Pallas TPU kernel: flash-decode GQA attention.

One new token attends over a long KV cache — the serving engine's hot loop
(decode_32k / long_500k shapes).  The XLA fallback materializes the (B, H,
S) score tensor in HBM; this kernel streams KV blocks through VMEM with an
online softmax, so HBM traffic is exactly one read of K/V plus O(B*H*hd).

Grid: (B, Hkv, S / BS) — batch x kv-head x kv-block.  For each (b, g):
  q tile    (G, hd)      G = query heads per kv head (GQA group)
  k/v block (BS, hd)
  carry     m (G,), l (G,), acc (G, hd)  — kept in the output refs between
            sequential grid steps over the kv-block axis (TPU grid is
            executed sequentially per (b, g), making the carry legal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def tune_block_s(s: int, block_s: int = 512, floor: int = 128) -> int:
    """Clamp/autotune the kv block size for a cache of length ``s``.

    Never larger than ``s``, so the last grid block always starts inside
    the valid region and the pad path (``pad_s = (-s) % block_s``) can
    never launch a masked-only block; among power-of-two shrinks down to
    ``floor`` picks the one wasting the least padding (e.g. s=600 keeps
    a 40-row pad at block 128 instead of a 424-row pad at block 512).
    """
    block_s = max(1, min(block_s, s))
    best, best_pad = block_s, (-s) % block_s
    bs = block_s
    while bs // 2 >= min(floor, s) and best_pad:
        bs //= 2
        pad = (-s) % bs
        if pad < best_pad:
            best, best_pad = bs, pad
    return best


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
            block_s: int, hd: int):
    sb = pl.program_id(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0, 0]                     # (G, hd)
    k = k_ref[0, 0]                     # (BS, hd)
    v = v_ref[0, 0]                     # (BS, hd)
    scale = 1.0 / math.sqrt(hd)

    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T) * scale
    pos = sb * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, -1e30)

    m_prev = m_ref[0, 0]                # (G, 1)
    l_prev = l_ref[0, 0]
    m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))[:, None]
    p = jnp.exp(s - m_new)
    p = jnp.where(pos < length, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)     # (G, 1)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc = o_ref[0, 0] * alpha \
        + jnp.dot(p, v.astype(jnp.float32))
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_new
    o_ref[0, 0] = acc

    # normalize on the last block
    @pl.when(sb == pl.num_programs(2) - 1)
    def _done():
        o_ref[0, 0] = o_ref[0, 0] / jnp.maximum(l_ref[0, 0], 1e-20)


def decode_attention_pallas(q, k_cache, v_cache, lengths,
                            block_s: int = 512, interpret: bool = False):
    """q (B, H, hd); k/v (B, S, Hkv, hd); lengths (B,) -> (B, H, hd)."""
    b, s, hkv, hd = k_cache.shape
    h = q.shape[1]
    g = h // hkv
    block_s = tune_block_s(s, block_s)
    pad_s = (-s) % block_s
    if pad_s:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    sp = k_cache.shape[1]
    qg = q.reshape(b, hkv, g, hd)
    # (B, Hkv, S, hd) layout so the kv-head axis is a grid dim
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)

    kernel = functools.partial(_kernel, block_s=block_s, hd=hd)
    # lengths ride as a scalar-prefetch operand (SMEM): a rank-1 VMEM block
    # of one element is not a layout the TPU's (8, 128) tiling accepts
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, sp // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j, k, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda i, j, k, n: (i, j, k, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda i, j, k, n: (i, j, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j, k, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda i, j, k, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda i, j, k, n: (i, j, 0, 0)),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(b, h, hd).astype(q.dtype)


def _paged_kernel(tables_ref, len_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, *, page_size: int, hd: int):
    del tables_ref  # consumed by the BlockSpec index maps (scalar prefetch)
    j = pl.program_id(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0, 0]                     # (G, hd)
    k = k_ref[0, 0]                     # (PS, hd)
    v = v_ref[0, 0]
    scale = 1.0 / math.sqrt(hd)

    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T) * scale
    pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, -1e30)

    m_prev = m_ref[0, 0]                # (G, 1)
    l_prev = l_ref[0, 0]
    m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))[:, None]
    p = jnp.exp(s - m_new)
    p = jnp.where(pos < length, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc = o_ref[0, 0] * alpha + jnp.dot(p, v.astype(jnp.float32))
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_new
    o_ref[0, 0] = acc

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[0, 0] = o_ref[0, 0] / jnp.maximum(l_ref[0, 0], 1e-20)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables, lengths,
                                  interpret: bool = False):
    """Flash-decode over paged (non-contiguous) KV storage.

    q (B, H, hd); k/v_pages (NP, Hkv, PS, hd); block_tables (B, MP) int32
    page indices per sequence; lengths (B,) -> (B, H, hd).

    Same online-softmax carry as the contiguous kernel, but the kv block
    for grid step (b, g, j) is gathered through the block-table ref: the
    BlockSpec index map reads ``tables[b, j]`` via scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``), so each sequence streams its own
    scattered pages through VMEM.  The kv-head axis sits before the page
    slot so that each block is one (PS, hd) tile of one head — the last two
    block dimensions then meet the TPU's (8, 128) tiling rule.  Ragged
    ``lengths`` (the second scalar-prefetch operand) are handled by the
    positional mask — table entries past a sequence's last page may point
    anywhere (conventionally page 0) and contribute nothing.
    """
    np_, hkv, ps, hd = k_pages.shape
    b, h = q.shape[0], q.shape[1]
    g = h // hkv
    mp = block_tables.shape[1]
    qg = q.reshape(b, hkv, g, hd)
    kernel = functools.partial(_paged_kernel, page_size=ps, hd=hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, mp),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j, k, t, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda i, j, k, t, n: (t[i, k], j, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda i, j, k, t, n: (t[i, k], j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j, k, t, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda i, j, k, t, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda i, j, k, t, n: (i, j, 0, 0)),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qg,
      k_pages, v_pages)
    return out.reshape(b, h, hd).astype(q.dtype)
