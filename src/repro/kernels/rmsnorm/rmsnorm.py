"""Pallas TPU kernel: fused RMSNorm.

Two HBM touches per element (read x, write y) instead of XLA's
reduce + broadcast + multiply materializations.  Grid over row tiles;
each block (BR, D) is normalized entirely in VMEM/f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)          # (BR, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y.astype(o_ref.dtype)) * s_ref[...]


def rmsnorm_pallas(x, scale, eps: float = 1e-5, block_rows: int = 256,
                   interpret: bool = False):
    """x (..., D), scale (D,) -> same shape/dtype as x."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n = x2.shape[0]

    kernel = functools.partial(_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
    )(x2, scale)
    return out[:rows].reshape(orig_shape)
