"""Jitted wrappers for the flash-decode kernels.

``interpret=None`` (the default) auto-dispatches: the Pallas kernel is
compiled natively when a real accelerator (TPU/GPU) backs the default
JAX backend and falls back to interpret mode only when none is present,
so real backends never pay the interpreter tax.  ``use_kernel=False``
falls back to the jnp oracle; the paged front door defaults
``use_kernel=None`` → oracle off-accelerator (XLA-compiled gather +
softmax is the fast exact path there) and kernel on TPU/GPU.

The paged kernel walks one grid step per sequence and block of
``pages_per_block(MP, PS)`` table slots, all KV heads at once, copying
only the live blocks' pages into VMEM and skipping the compute of the
rest (``paged_decode_attention_pallas``).  Its one ``pallas_call`` sits
inside ``_paged_decode_attention_jit``, so on the device the kernel is the
``%_paged_decode_attention_jit…`` custom call the trace reduction finds.
"""

from __future__ import annotations

from functools import partial

import jax

from .. import interpret_default
from .decode_attention import (decode_attention_pallas, pages_per_block,
                               paged_decode_attention_pallas, tune_block_s)
from .ref import decode_attention_ref, paged_decode_attention_ref
from ...obs.profiling import profiled

__all__ = ["decode_attention", "paged_decode_attention", "pages_per_block",
           "tune_block_s", "interpret_default"]


@partial(jax.jit, static_argnames=("block_s", "interpret", "use_kernel"))
def _decode_attention_jit(q, k_cache, v_cache, lengths, *, block_s: int,
                          interpret: bool, use_kernel: bool):
    if use_kernel:
        return decode_attention_pallas(q, k_cache, v_cache, lengths,
                                       block_s=block_s, interpret=interpret)
    return decode_attention_ref(q, k_cache, v_cache, lengths)


def decode_attention(q, k_cache, v_cache, lengths, block_s: int = 512,
                     interpret: bool | None = None, use_kernel: bool = True):
    if interpret is None:
        interpret = interpret_default()
    # launches route through the (no-op by default) kernel profiler
    return profiled("decode_attention", _decode_attention_jit,
                    q, k_cache, v_cache, lengths, block_s=block_s,
                    interpret=interpret, use_kernel=use_kernel)


@partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def _paged_decode_attention_jit(q, k_pages, v_pages, block_tables, lengths,
                                *, interpret: bool, use_kernel: bool):
    if use_kernel:
        return paged_decode_attention_pallas(q, k_pages, v_pages,
                                             block_tables, lengths,
                                             interpret=interpret)
    return paged_decode_attention_ref(q, k_pages, v_pages, block_tables,
                                      lengths)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           interpret: bool | None = None,
                           use_kernel: bool | None = None):
    if interpret is None:
        interpret = interpret_default()
    if use_kernel is None:
        use_kernel = not interpret_default()
    return profiled("paged_decode_attention", _paged_decode_attention_jit,
                    q, k_pages, v_pages, block_tables, lengths,
                    interpret=interpret, use_kernel=use_kernel)
