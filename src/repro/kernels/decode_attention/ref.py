"""Pure-jnp oracle for the flash-decode GQA kernel."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """q (B, H, hd); k/v (B, S, Hkv, hd); lengths (B,) -> (B, H, hd)."""
    b, s, hkv, hd = k_cache.shape
    h = q.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg,
                        k_cache.astype(jnp.float32)) * scale
    mask = jnp.arange(s)[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, lengths):
    """Oracle for the paged kernel: gather pages into a contiguous cache.

    q (B, H, hd); k/v_pages (NP, Hkv, PS, hd); block_tables (B, MP);
    lengths (B,) -> (B, H, hd).

    The arithmetic mirrors ``models.layers.decode_attention`` *exactly*
    (scores in the input dtype then cast to f32, probs cast back to the
    value dtype) — not the f32-throughout ``decode_attention_ref`` — so a
    paged decode step is bit-identical to the dense decode step it
    replaces and batched greedy outputs match sequential ones token for
    token.
    """
    np_, hkv, ps, hd = k_pages.shape
    b, mp = block_tables.shape
    h = q.shape[1]
    g = h // hkv

    def gather(pages):  # (B, MP, Hkv, PS, hd) -> (B, MP * PS, Hkv, hd)
        return jnp.swapaxes(pages[block_tables], 2, 3).reshape(
            b, mp * ps, hkv, hd)

    kc, vc = gather(k_pages), gather(v_pages)
    qg = q.reshape(b, hkv, g, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, kc).astype(jnp.float32) * scale
    mask = jnp.arange(mp * ps)[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(vc.dtype), vc)
    return out.reshape(b, h, hd)
