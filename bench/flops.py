"""Operations and bytes of the served model's work, computed from its widths.

Every count is what the algorithm needs, never what a padded launch happens
to touch, so a share of a peak computed from it cannot pass 100% unless the
time leaves out part of the work.  ``cfg`` is the ``model`` block of a
configuration file (keys as in ``bench/configs/*.json``).
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def _dims(cfg: dict):
    d, h, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, hkv, hd


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one decoder layer: the q, k,
    v and output projections and the three SwiGLU matrices."""
    d, h, hkv, hd = _dims(cfg)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * cfg["d_ff"]


def step_flops(cfg: dict, tokens: int, logit_rows: int) -> float:
    """Forward FLOPs of ``tokens`` tokens through every layer's matrices,
    plus the vocabulary projection for ``logit_rows`` rows.

    A prefill chunk projects only its last position onto the vocabulary and
    a decode step every row, so ``logit_rows`` is decode tokens plus chunks.
    Attention scores are left out: they are memory-bound work that the
    paged-attention roofline accounts for, and leaving them out keeps the
    utilisation a lower bound."""
    return 2.0 * (cfg["n_layers"] * layer_matmul_params(cfg) * tokens
                  + cfg["d_model"] * cfg["vocab"] * logit_rows)


def paged_attn_cost(cfg: dict, ctx_tokens: int, decode_tokens: int):
    """(bytes, flops) the paged decode attention needs over all layers.

    ``ctx_tokens`` is the sum, over decode tokens, of the context each one
    attends to (its position plus one).  Each context token's key and value
    are read once per layer in bf16; each decode token's query is read in
    bf16 and its output written in f32.  FLOPs are the two products, q.k
    and p.v, of every query head over every context token."""
    d, h, hkv, hd = _dims(cfg)
    layers = cfg["n_layers"]
    kv_bytes = ctx_tokens * hkv * hd * 2 * BF16
    q_out_bytes = decode_tokens * h * hd * (BF16 + F32)
    flops = ctx_tokens * h * hd * 4
    return layers * (kv_bytes + q_out_bytes), layers * flops


def roofline_seconds(nbytes: float, flops: float, peak: dict):
    """Least time the chip could take, and which bound sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_cmp = flops / peak["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
