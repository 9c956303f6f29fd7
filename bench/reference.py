"""Plain float32 reference of the served architecture, and its control.

A Llama-style decoder written out in ``jax.numpy``: RMSNorm, rotary
positions (rotate-half), grouped-query causal attention, SwiGLU, and an
output projection (tied to the embedding where the configuration says so).
No kernel, cache or batching of the program is used, nothing of the
program is imported, and the weights are rebuilt from the seed by
``bench.weights``, one layer at a time, so that the reference fits beside
nothing else on the chip.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product otherwise runs in bfloat16 passes.  With ``fp8=True`` every product
instead takes both operands through float8 (e4m3, one absmax scale per
tensor) and accumulates in float32: the precision one step below the
bfloat16 the configuration serves in, which is the control that the
comparison has to reject.

``served_gaps`` reads, for each served token, how far its reference logit
lies below the reference's best logit at that position.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights as W

HI = lax.Precision.HIGHEST
Q_BLOCK = 256
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta: float):
    """x (N, S, H, hd), positions 0..S-1, halves rotated (Llama)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, fp8: bool):
    """Causal GQA attention, q (N, S, H, hd), k/v (N, S, Hkv, hd), in
    blocks of query rows so that the scores of one block fit."""
    n, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(n, s, hkv, g, hd)
    kpos = jnp.arange(s)

    def block(i):
        qb = lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK, axis=1)
        sc = _mm("nqkgd,nskd->nkgqs", qb, k, fp8) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("nkgqs,nskd->nqkgd", p, v, fp8)

    out = lax.map(block, jnp.arange(s // Q_BLOCK))      # (B, N, QB, Hkv, g, hd)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(n, s, h * hd)


@partial(jax.jit, static_argnums=(0, 3))
def _layer_apply(cfg_items: tuple, x, lw, fp8: bool):
    cfg = dict(cfg_items)
    n = W.dims(cfg)
    eps = cfg["norm_eps"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    b, s, _ = x.shape
    xn = _rms(x, lw["ln1"]["scale"], eps)
    q = _mm("nsd,de->nse", xn, lw["attn"]["wq"]["w"], fp8)
    k = _mm("nsd,de->nse", xn, lw["attn"]["wk"]["w"], fp8)
    v = _mm("nsd,de->nse", xn, lw["attn"]["wv"]["w"], fp8)
    q = _rope(q.reshape(b, s, n["h"], n["hd"]), cfg["rope_theta"])
    k = _rope(k.reshape(b, s, n["hkv"], n["hd"]), cfg["rope_theta"])
    v = v.reshape(b, s, n["hkv"], n["hd"])
    a = _attention(q, k, v, fp8)
    x = x + _mm("nse,ed->nsd", a, lw["attn"]["wo"]["w"], fp8)
    xn = _rms(x, lw["ln2"]["scale"], eps)
    hid = jax.nn.silu(_mm("nsd,df->nsf", xn, lw["mlp"]["w_gate"]["w"], fp8)) \
        * _mm("nsd,df->nsf", xn, lw["mlp"]["w_up"]["w"], fp8)
    return x + _mm("nsf,fd->nsd", hid, lw["mlp"]["w_down"]["w"], fp8)


@partial(jax.jit, static_argnums=(0, 6))
def _head(cfg_items: tuple, x, rows, cols, norm, head, fp8: bool):
    """Logits at the gathered (sequence, position) rows; ``cols`` unused
    rows are masked by the caller."""
    cfg = dict(cfg_items)
    h = x[rows, cols]                                    # (R, D)
    h = _rms(h, norm.astype(jnp.float32), cfg["norm_eps"])
    return _mm("rd,dv->rv", h, head.astype(jnp.float32), fp8)


@partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items: tuple, emb, toks):
    return jnp.take(emb, toks, axis=0).astype(jnp.float32)


def logits_at(cfg: dict, seed: int, seqs: np.ndarray, rows: np.ndarray,
              cols: np.ndarray, fp8: bool = False) -> np.ndarray:
    """Reference logits of ``seqs`` (N, S) int32 at positions
    (``rows[i]``, ``cols[i]``), float32 (R, V).  S is a multiple of
    ``Q_BLOCK``; positions past a sequence's end only add padding, which
    causal attention keeps away from the positions read."""
    items = W._freeze(cfg)
    emb, head, norm = W.embed_and_head(cfg, seed)
    x = _embed(items, emb, jnp.asarray(seqs))
    del emb
    for i in range(cfg["n_layers"]):
        x = _layer_apply(items, x, W.layer(cfg, seed, i), fp8)
    out = _head(items, x, jnp.asarray(rows), jnp.asarray(cols), norm, head,
                fp8)
    return np.asarray(out)


def served_gaps(cfg: dict, seed: int, samples, pad_to: int,
                fp8_control: bool = False) -> dict:
    """Gaps of the served tokens against the float32 reference.

    ``samples`` is a list of (prompt ids, served ids).  The reference runs
    once over prompt + served[:-1]; at each position that produced a served
    token it reads ``best - logit[served]`` (0 where the two agree on the
    top token).  With ``fp8_control`` it also runs the float8 control over
    the same positions and reads the gap of the token the control puts
    first: the reading that a program computing in float8 would give.
    """
    n = len(samples)
    seqs = np.zeros((n, pad_to), np.int32)
    rows, cols, served = [], [], []
    for i, (prompt, out) in enumerate(samples):
        full = list(prompt) + list(out[:-1])
        seqs[i, :len(full)] = full
        for j, tok in enumerate(out):
            rows.append(i)
            cols.append(len(prompt) - 1 + j)
            served.append(tok)
    rows, cols = np.asarray(rows, np.int32), np.asarray(cols, np.int32)
    served = np.asarray(served)
    ref = logits_at(cfg, seed, seqs, rows, cols)
    best = ref.max(axis=-1)
    gaps = best - ref[np.arange(len(served)), served]
    res = {"tokens": int(len(served)), "max_gap": float(gaps.max()),
           "argmax_agree": float(np.mean(ref.argmax(-1) == served)),
           "logit_std": float(ref.std())}
    if fp8_control:
        low = logits_at(cfg, seed, seqs, rows, cols, fp8=True).argmax(-1)
        cg = best - ref[np.arange(len(low)), low]
        res["control_max_gap"] = float(cg.max())
        res["control_argmax_agree"] = float(np.mean(low == ref.argmax(-1)))
    return res
