"""Pallas TPU kernels: flash-decode GQA attention, contiguous and paged.

One new token attends over a long KV cache — the serving engine's hot loop
(decode_32k / long_500k shapes).  The XLA fallback materializes the (B, H,
S) score tensor in HBM; these kernels stream KV blocks through VMEM with an
online softmax, so HBM traffic is one read of the live K/V plus O(B*H*hd).

Contiguous (``decode_attention_pallas``), grid (B, Hkv, S / BS) — batch x
kv-head x kv-block.  For each (b, g):
  q tile    (G, hd)      G = query heads per kv head (GQA group)
  k/v block (BS, hd)
  carry     m (G,), l (G,), acc (G, hd)  — kept in the output refs between
            sequential grid steps over the kv-block axis (TPU grid is
            executed sequentially per (b, g), making the carry legal).

Paged (``paged_decode_attention_pallas``), grid (B, MP / ppb) — sequence x
block of ``ppb`` consecutive page-table slots, every kv head at once.  A
step reads its block's pages, whole (Hkv, PS, hd) slices of the pool,
through the block table, and attends q (Hkv, G, hd) to them in one batched
product.  Blocks past a sequence's length are neither copied nor computed,
so the walk follows the live context, not the table's capacity.
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


# tokens a paged grid step may span; the pages per step follow from shapes
BLOCK_TOKENS = 256


def pages_per_block(mp: int, ps: int) -> int:
    """Table pages the paged kernel reads per grid step.

    The largest power of two that divides ``mp`` (pages per table) and
    spans at most ``BLOCK_TOKENS`` tokens of ``ps``-token pages, so a
    table is a whole number of blocks: 16 at 16-token pages and 128-page
    tables, 1 at 3-page tables.
    """
    ppb = 1
    while mp % (2 * ppb) == 0 and 2 * ppb * ps <= BLOCK_TOKENS:
        ppb *= 2
    return ppb


def _paged_kernel(tables_ref, len_ref, q_ref, *refs, ppb: int,
                  page_size: int, hd: int):
    del tables_ref  # consumed by the BlockSpec index maps (scalar prefetch)
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_ref, l_ref = refs[2 * ppb:]
    j = pl.program_id(1)
    bk = ppb * page_size
    length = len_ref[pl.program_id(0)]
    last = jnp.logical_or((j + 1) * bk >= length, j == pl.num_programs(1) - 1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_or(j == 0, j * bk < length))
    def _block():
        q = q_ref[0].astype(jnp.float32)                   # (Hkv, G, hd)
        # the block's pages, (Hkv, PS, hd) each -> (Hkv, ppb * PS, hd)
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)
        v = jnp.concatenate([r[0] for r in v_refs], axis=1)
        s = jnp.einsum("hgd,htd->hgt", q, k.astype(jnp.float32),
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, -1e30)
        m_prev = m_ref[...]                                # (Hkv, G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(pos < length, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        o_ref[0] = o_ref[0] * alpha + jnp.einsum(
            "hgt,htd->hgd", p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _done():
            o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-20)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables, lengths,
                                  interpret: bool = False):
    """Flash-decode over paged (non-contiguous) KV storage.

    q (B, H, hd); k/v_pages (NP, Hkv, PS, hd); block_tables (B, MP) int32
    page indices per sequence; lengths (B,) -> (B, H, hd).

    Grid ``(B, MP // ppb)``: one step per sequence and block of ``ppb``
    consecutive table slots (:func:`pages_per_block`), all KV heads at
    once.  The kernel takes the pool ``ppb`` times each for K and V, one
    operand per slot of the block, whose BlockSpec index map reads the
    slot's page from the block table (``tables[b, j * ppb + p]``, scalar
    prefetch) and copies that whole page, a contiguous (Hkv, PS, hd) slice
    of the pool, into VMEM; the pipeline double-buffers every copy and
    starts the next step's copies before the current step computes.  The
    pages stay where they are: neither the pool nor the tables are
    gathered or relaid out.

    Blocks that start at or past a sequence's length (the second scalar
    prefetch operand) are skipped: their index maps repeat the row's last
    live block, so the pipeline copies nothing, and ``pl.when`` skips the
    compute; only the grid step itself remains.  Block 0 is always live,
    so a padding row of length 1 costs one block.

    Per live step the scores are a batched product over heads, q (Hkv, G,
    hd) against the block's (Hkv, ppb * PS, hd) keys, in f32 from bf16
    pages; the online-softmax carry m, l (Hkv, G, 1) lives in VMEM scratch
    and the accumulator in the output block, normalized on the row's last
    live block.  Positions past ``length`` inside that block are masked,
    so table entries past a sequence's last page may point at any page of
    the pool (conventionally page 0) and contribute nothing; they must be
    valid page indices, since a live block copies all of its pages.

    The pages are copied by the pipeline rather than by manual DMAs from
    an HBM ref because Mosaic refuses a manual copy of a page whose last
    dimension is narrower than a 128-lane tile (SmolLM's head_dim 64).
    """
    np_, hkv, ps, hd = k_pages.shape
    b, h = q.shape[0], q.shape[1]
    g = h // hkv
    mp = block_tables.shape[1]
    ppb = pages_per_block(mp, ps)
    bk = ppb * ps
    qg = q.reshape(b, hkv, g, hd)
    kernel = functools.partial(_paged_kernel, ppb=ppb, page_size=ps, hd=hd)

    def page_spec(p):
        def index(i, j, t, n):
            # a dead block maps to the row's last live one: same block
            # index as the step before, so the pipeline copies nothing
            jj = jnp.minimum(j, jnp.maximum((n[i] + bk - 1) // bk - 1, 0))
            return t[i, jj * ppb + p], 0, 0, 0
        return pl.BlockSpec((1, hkv, ps, hd), index)

    row = pl.BlockSpec((1, hkv, g, hd), lambda i, j, t, n: (i, 0, 0, 0))
    pages = [page_spec(p) for p in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp // ppb),
        in_specs=[row] + pages + pages,
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qg,
      *[k_pages] * ppb, *[v_pages] * ppb)
    return out.reshape(b, h, hd).astype(q.dtype)
