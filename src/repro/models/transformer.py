"""Unified model: one init/loss/prefill/decode quartet covering all assigned
families (dense / moe / vlm / hybrid-mamba / xlstm / enc-dec).

Layer stacks are ``lax.scan``-ed over stacked parameters so the lowered HLO
(and the 512-way SPMD compile time) is independent of depth.  Per-layer
bodies are wrapped in ``jax.checkpoint`` when ``cfg.remat``.

The loss never materializes the full (B, S, V) logits tensor: the output
projection + cross-entropy run in sequence chunks (vocabularies here reach
256k — full f32 logits for seamless-m4t at train_4k would be ~67 GB).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..configs.base import ModelConfig
from ..parallel import ctx as pctx
from . import xlstm as xl
from .layers import (apply_rope, attention_apply, attention_init, dense,
                     embed, embed_init, mlp_apply, mlp_init, rmsnorm,
                     rmsnorm_init)
from .moe import moe_apply, moe_init
from .ssm import mamba2_apply, mamba2_init

LOSS_CHUNK = 512


# ===========================================================================
# Parameter init
# ===========================================================================

def _stacked(init_one, key, n: int):
    return jax.vmap(init_one)(jax.random.split(key, n))


def _dense_layer_init(cfg: ModelConfig, d_ff: int):
    def init_one(key):
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": rmsnorm_init(cfg.d_model),
            "attn": attention_init(k1, cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.resolved_head_dim,
                                   cfg.qkv_bias),
            "ln2": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(k2, cfg.d_model, d_ff),
        }
        return p
    return init_one


def _moe_layer_init(cfg: ModelConfig):
    def init_one(key):
        k1, k2 = jax.random.split(key)
        return {
            "ln1": rmsnorm_init(cfg.d_model),
            "attn": attention_init(k1, cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.resolved_head_dim,
                                   cfg.qkv_bias),
            "ln2": rmsnorm_init(cfg.d_model),
            "moe": moe_init(k2, cfg.d_model, cfg.moe),
        }
    return init_one


def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 8)
    params: dict = {"final_ln": rmsnorm_init(cfg.d_model)}
    if cfg.embed_inputs or cfg.family in ("vlm", "encdec", "audio"):
        params["embed"] = embed_init(ks[0], cfg.vocab, cfg.d_model)
    if not cfg.tie_embeddings:
        params["unembed"] = {
            "w": (jax.random.normal(ks[1], (cfg.d_model, cfg.vocab),
                                    jnp.float32)
                  / math.sqrt(cfg.d_model)).astype(jnp.bfloat16)}

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = _stacked(_dense_layer_init(cfg, cfg.d_ff), ks[2],
                                    cfg.n_layers)
    elif fam == "moe":
        period = cfg.moe.layer_period
        if period == 1:
            # layer 0 dense (DeepSeek-MoE), rest MoE
            params["dense0"] = _dense_layer_init(cfg, cfg.d_ff)(ks[2])
            params["layers"] = _stacked(_moe_layer_init(cfg), ks[3],
                                        cfg.n_layers - 1)
        else:
            # interleaved dense/MoE units (llama4: period 2)
            n_units = cfg.n_layers // period
            params["dense_layers"] = _stacked(
                _dense_layer_init(cfg, cfg.d_ff), ks[2], n_units)
            params["layers"] = _stacked(_moe_layer_init(cfg), ks[3], n_units)
    elif fam == "hybrid":
        period = cfg.ssm.attn_period
        n_groups = cfg.n_layers // period
        def mamba_one(key):
            return {"ln": rmsnorm_init(cfg.d_model),
                    "mamba": mamba2_init(key, cfg.d_model, cfg.ssm)}
        params["layers"] = jax.vmap(
            lambda k: jax.vmap(mamba_one)(jax.random.split(k, period))
        )(jax.random.split(ks[2], n_groups))
        params["shared_attn"] = _dense_layer_init(cfg, cfg.d_ff)(ks[3])
    elif fam == "ssm":          # xlstm
        n_pairs = cfg.n_layers // 2
        def pair_one(key):
            k1, k2 = jax.random.split(key)
            return {
                "m_ln": rmsnorm_init(cfg.d_model),
                "mlstm": xl.mlstm_init(k1, cfg.d_model, cfg.n_heads,
                                       cfg.xlstm.proj_factor),
                "s_ln": rmsnorm_init(cfg.d_model),
                "slstm": xl.slstm_init(k2, cfg.d_model, cfg.n_heads),
            }
        params["layers"] = _stacked(pair_one, ks[2], n_pairs)
    elif fam == "encdec":
        def enc_one(key):
            k1, k2 = jax.random.split(key)
            return {
                "ln1": rmsnorm_init(cfg.d_model),
                "attn": attention_init(k1, cfg.d_model, cfg.n_heads,
                                       cfg.n_kv_heads, cfg.resolved_head_dim),
                "ln2": rmsnorm_init(cfg.d_model),
                "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff),
            }
        def dec_one(key):
            k1, k2, k3 = jax.random.split(key, 3)
            return {
                "ln1": rmsnorm_init(cfg.d_model),
                "self_attn": attention_init(k1, cfg.d_model, cfg.n_heads,
                                            cfg.n_kv_heads,
                                            cfg.resolved_head_dim),
                "ln_x": rmsnorm_init(cfg.d_model),
                "cross_attn": attention_init(k2, cfg.d_model, cfg.n_heads,
                                             cfg.n_kv_heads,
                                             cfg.resolved_head_dim),
                "ln2": rmsnorm_init(cfg.d_model),
                "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff),
            }
        params["encoder"] = _stacked(enc_one, ks[2], cfg.encoder_layers)
        params["layers"] = _stacked(dec_one, ks[3], cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam}")
    return params


def init_abstract(cfg: ModelConfig):
    """ShapeDtypeStruct tree of the parameters — no allocation (dry-run)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ===========================================================================
# Blocks (train/prefill path)
# ===========================================================================

def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def _dense_block(cfg):
    def block(x, lp):
        a, _ = attention_apply(lp["attn"], rmsnorm(lp["ln1"], x), cfg)
        x = x + a
        x = x + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], x))
        return x
    return block


def _moe_block(cfg):
    def block(carry, lp):
        x, aux = carry
        a, _ = attention_apply(lp["attn"], rmsnorm(lp["ln1"], x), cfg)
        x = x + a
        h, aux_l = moe_apply(lp["moe"], rmsnorm(lp["ln2"], x), cfg.moe)
        return (x + h, aux + aux_l)
    return block


def _backbone(cfg: ModelConfig, params, x):
    """Hidden states after the layer stack.  x: (B, S, D).  Returns
    (hidden, aux_loss)."""
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)
    x = pctx.shard_hidden(x)

    if fam in ("dense", "vlm"):
        blk = _maybe_remat(_dense_block(cfg), cfg)
        x = lax.scan(lambda h, lp: (blk(h, lp), None), x,
                     params["layers"])[0]
    elif fam == "moe":
        mblk = _maybe_remat(lambda c, lp: _moe_block(cfg)(c, lp), cfg)
        dblk = _maybe_remat(_dense_block(cfg), cfg)
        if cfg.moe.layer_period == 1:
            x = dblk(x, params["dense0"])
            (x, aux), _ = lax.scan(lambda c, lp: (mblk(c, lp), None),
                                   (x, aux), params["layers"])
        else:
            def unit(carry, lps):
                dlp, mlp_ = lps
                x, a = carry
                x = dblk(x, dlp)
                return mblk((x, a), mlp_), None
            (x, aux), _ = lax.scan(unit, (x, aux),
                                   (params["dense_layers"], params["layers"]))
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def mamba_block(h, lp):
            y, _, _ = mamba2_apply(lp["mamba"], rmsnorm(lp["ln"], h), cfg.ssm)
            return h + y
        mamba_block = _maybe_remat(mamba_block, cfg)
        attn_block = _maybe_remat(_dense_block(cfg), cfg)

        def group(h, glp):
            h = lax.scan(lambda hh, lp: (mamba_block(hh, lp), None),
                         h, glp)[0]
            return attn_block(h, shared), None
        x = lax.scan(group, x, params["layers"])[0]
    elif fam == "ssm":
        def pair(h, lp):
            y, _ = xl.mlstm_apply(lp["mlstm"], rmsnorm(lp["m_ln"], h),
                                  cfg.n_heads, chunk=cfg.xlstm.chunk)
            h = h + y
            y, _ = xl.slstm_apply(lp["slstm"], rmsnorm(lp["s_ln"], h))
            return h + y
        pair = _maybe_remat(pair, cfg)
        x = lax.scan(lambda h, lp: (pair(h, lp), None), x,
                     params["layers"])[0]
    else:
        raise ValueError(fam)
    return x, aux


def _encode(cfg, params, enc_embeds):
    def enc_block(h, lp):
        a, _ = attention_apply(lp["attn"], rmsnorm(lp["ln1"], h), cfg,
                               memory=rmsnorm(lp["ln1"], h))
        h = h + a
        return h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h))
    blk = _maybe_remat(enc_block, cfg)
    return lax.scan(lambda h, lp: (blk(h, lp), None), enc_embeds,
                    params["encoder"])[0]


def _decode_stack(cfg, params, x, memory):
    def dec_block(h, lp):
        a, _ = attention_apply(lp["self_attn"], rmsnorm(lp["ln1"], h), cfg)
        h = h + a
        a, _ = attention_apply(lp["cross_attn"], rmsnorm(lp["ln_x"], h), cfg,
                               memory=memory)
        h = h + a
        return h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h))
    blk = _maybe_remat(dec_block, cfg)
    return lax.scan(lambda h, lp: (blk(h, lp), None), x, params["layers"])[0]


# ===========================================================================
# Loss (chunked vocab projection)
# ===========================================================================

def _unembed_matrix(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"]["emb"].T
    return params["unembed"]["w"]


def chunked_loss(cfg, params, hidden, labels):
    """Cross-entropy over sequence chunks; never builds (B,S,V) f32."""
    w = _unembed_matrix(cfg, params)
    b, s, d = hidden.shape
    chunk = min(LOSS_CHUNK, s)
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)),
                         constant_values=-1)
    n = hidden.shape[1] // chunk
    hc = jnp.moveaxis(hidden.reshape(b, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0)

    def step(carry, inp):
        tot, cnt = carry
        h, l = inp
        logits = pctx.shard_logits((h @ w).astype(jnp.float32))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(l, 0)[..., None], axis=-1)[..., 0]
        mask = (l >= 0).astype(jnp.float32)
        return (tot + ((logz - gold) * mask).sum(), cnt + mask.sum()), None

    (tot, cnt), _ = lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


# ===========================================================================
# Public entry points
# ===========================================================================

def loss_fn(cfg: ModelConfig):
    """Returns f(params, batch) -> scalar loss.

    batch: {"tokens": (B,S) i32} or {"embeds": (B,S,D)} (+ optional
    "enc_embeds" for enc-dec), and "labels": (B,S) i32 (-1 = ignore).
    """
    def f(params, batch):
        if cfg.family == "encdec":
            memory = _encode(cfg, params, batch["enc_embeds"])
            x = embed(params["embed"], batch["tokens"])
            hidden = _decode_stack(cfg, params, x, memory)
        else:
            if cfg.embed_inputs:
                x = embed(params["embed"], batch["tokens"])
            else:
                x = batch["embeds"]
            hidden, aux = _backbone(cfg, params, x)
        hidden = rmsnorm(params["final_ln"], hidden)
        loss = chunked_loss(cfg, params, hidden, batch["labels"])
        if cfg.family == "moe":
            loss = loss + 0.01 * aux
        return loss
    return f


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode-step
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Abstract-shape-compatible zero cache."""
    hd = cfg.resolved_head_dim
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        n_attn = cfg.n_layers
        return {
            "k": jnp.zeros((n_attn, batch, max_len, cfg.n_kv_heads, hd),
                           jnp.bfloat16),
            "v": jnp.zeros((n_attn, batch, max_len, cfg.n_kv_heads, hd),
                           jnp.bfloat16),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    if fam == "hybrid":
        period = cfg.ssm.attn_period
        groups = cfg.n_layers // period
        d_inner = cfg.ssm.expand * cfg.d_model
        n_heads = cfg.ssm.n_ssm_heads or max(1, d_inner // 64)
        return {
            "ssm": jnp.zeros((groups, period, batch, n_heads,
                              d_inner // n_heads, cfg.ssm.state_dim),
                             jnp.float32),
            "conv": jnp.zeros((groups, period, batch,
                               cfg.ssm.conv_width - 1, d_inner),
                              jnp.bfloat16),
            "k": jnp.zeros((groups, batch, max_len, cfg.n_kv_heads, hd),
                           jnp.bfloat16),
            "v": jnp.zeros((groups, batch, max_len, cfg.n_kv_heads, hd),
                           jnp.bfloat16),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    if fam == "ssm":
        pairs = cfg.n_layers // 2
        d_inner = int(cfg.xlstm.proj_factor * cfg.d_model)
        hd_m = d_inner // cfg.n_heads
        d = cfg.d_model
        return {
            "C": jnp.zeros((pairs, batch, cfg.n_heads, hd_m, hd_m), jnp.float32),
            "n": jnp.zeros((pairs, batch, cfg.n_heads, hd_m), jnp.float32),
            "m": jnp.full((pairs, batch, cfg.n_heads), -1e30, jnp.float32),
            "sc": jnp.zeros((pairs, batch, d), jnp.float32),
            "sn": jnp.zeros((pairs, batch, d), jnp.float32),
            "sm": jnp.full((pairs, batch, d), -1e30, jnp.float32),
            "sh": jnp.zeros((pairs, batch, d), jnp.float32),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    if fam == "encdec":
        return {
            "k": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd),
                           jnp.bfloat16),
            "v": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd),
                           jnp.bfloat16),
            "ck": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd),
                            jnp.bfloat16),
            "cv": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd),
                            jnp.bfloat16),
            "len": jnp.zeros((batch,), jnp.int32),
            "enc_len": jnp.full((batch,), max_len, jnp.int32),
        }
    raise ValueError(fam)


def decode_fn(cfg: ModelConfig):
    """Returns f(params, cache, tokens) -> (logits, cache).

    tokens: (B,) int32 — the latest token per sequence.  ``cache["len"]``
    holds the current context length per sequence.
    """
    hd = cfg.resolved_head_dim

    def f(params, cache, tokens):
        x = embed(params["embed"], tokens[:, None]) \
            if ("embed" in params) else None
        length = cache["len"]
        positions = length[:, None]
        fam = cfg.family

        if fam in ("dense", "vlm", "moe"):
            # KV caches ride the scan CARRY with in-place slice updates —
            # passing them as scan xs/ys makes XLA double-buffer the whole
            # stacked cache every layer (a 276 GB/chip/token mistake caught
            # in §Perf decode iteration 2)
            def layer_body(h, lp, kc, vc):
                a, (kc, vc) = attention_apply(
                    lp["attn"], rmsnorm(lp["ln1"], h), cfg,
                    positions=positions, kv_cache=(kc, vc), length=length)
                h = h + a
                if "mlp" in lp:
                    h = h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h))
                else:
                    mo, _ = moe_apply(lp["moe"], rmsnorm(lp["ln2"], h),
                                      cfg.moe)
                    h = h + mo
                return h, kc, vc

            def layer(carry, lp):
                h, k_all, v_all, i = carry
                kc = lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
                vc = lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
                h, kc, vc = layer_body(h, lp, kc, vc)
                k_all = lax.dynamic_update_index_in_dim(k_all, kc, i, 0)
                v_all = lax.dynamic_update_index_in_dim(v_all, vc, i, 0)
                return (h, k_all, v_all, i + 1), None

            k_all, v_all = cache["k"], cache["v"]
            if fam == "moe" and cfg.moe.layer_period == 1:
                h, kc0, vc0 = layer_body(x, params["dense0"],
                                         k_all[0], v_all[0])
                k_all = k_all.at[0].set(kc0)
                v_all = v_all.at[0].set(vc0)
                (h, k_all, v_all, _), _ = lax.scan(
                    layer, (h, k_all, v_all, jnp.int32(1)), params["layers"])
            elif fam == "moe":
                nu = cfg.n_layers // cfg.moe.layer_period

                def unit(carry, lps):
                    dlp, mlp_ = lps
                    carry, _ = layer(carry, dlp)
                    h, k_all, v_all, i = carry
                    # MoE layer caches live in the second half of the stack
                    carry = (h, k_all, v_all, i + nu - 1)
                    carry, _ = layer(carry, mlp_)
                    h, k_all, v_all, i = carry
                    return (h, k_all, v_all, i - nu), None
                (h, k_all, v_all, _), _ = lax.scan(
                    unit, (x, k_all, v_all, jnp.int32(0)),
                    (params["dense_layers"], params["layers"]))
            else:
                (h, k_all, v_all, _), _ = lax.scan(
                    layer, (x, k_all, v_all, jnp.int32(0)), params["layers"])
            cache = dict(cache, k=k_all, v=v_all, len=length + 1)

        elif fam == "hybrid":
            shared = params["shared_attn"]

            def mamba_layer(h, inp):
                lp, st, cst = inp
                y, st, cst = mamba2_apply(lp["mamba"], rmsnorm(lp["ln"], h),
                                          cfg.ssm, state=st, conv_state=cst)
                return h + y, (st, cst)

            def group(h, inp):
                glp, gst, gcst, kc, vc = inp
                h, sts = lax.scan(mamba_layer, h, (glp, gst, gcst))
                a, (kc, vc) = attention_apply(
                    shared["attn"], rmsnorm(shared["ln1"], h), cfg,
                    positions=positions, kv_cache=(kc, vc), length=length)
                h = h + a
                h = h + mlp_apply(shared["mlp"], rmsnorm(shared["ln2"], h))
                return h, (sts[0], sts[1], kc, vc)

            h, outs = lax.scan(group, x,
                               (params["layers"], cache["ssm"], cache["conv"],
                                cache["k"], cache["v"]))
            cache = dict(cache, ssm=outs[0], conv=outs[1], k=outs[2],
                         v=outs[3], len=length + 1)

        elif fam == "ssm":
            def pair(h, inp):
                lp, C, n, m, sc, sn, sm, sh = inp
                y, (C, n, m) = xl.mlstm_apply(lp["mlstm"],
                                              rmsnorm(lp["m_ln"], h),
                                              cfg.n_heads, state=(C, n, m))
                h = h + y
                y, (sc, sn, sm, sh) = xl.slstm_apply(
                    lp["slstm"], rmsnorm(lp["s_ln"], h),
                    state=(sc, sn, sm, sh))
                return h + y, (C, n, m, sc, sn, sm, sh)
            h, outs = lax.scan(pair, x,
                               (params["layers"], cache["C"], cache["n"],
                                cache["m"], cache["sc"], cache["sn"],
                                cache["sm"], cache["sh"]))
            cache = dict(cache, C=outs[0], n=outs[1], m=outs[2], sc=outs[3],
                         sn=outs[4], sm=outs[5], sh=outs[6], len=length + 1)

        elif fam == "encdec":
            def dec_layer(h, inp):
                lp, kc, vc, ck, cv = inp
                a, (kc, vc) = attention_apply(
                    lp["self_attn"], rmsnorm(lp["ln1"], h), cfg,
                    positions=positions, kv_cache=(kc, vc), length=length)
                h = h + a
                # cross-attention reads the precomputed memory KV directly
                from .layers import decode_attention, dense as _dense
                xq = _dense(lp["cross_attn"]["wq"], rmsnorm(lp["ln_x"], h))
                bq = xq.shape[0]
                xq = xq.reshape(bq, cfg.n_heads, hd)
                a2 = decode_attention(xq, ck, cv, cache["enc_len"])
                h = h + _dense(lp["cross_attn"]["wo"],
                               a2.reshape(bq, 1, cfg.n_heads * hd))
                return h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h)), (kc, vc)
            h, (new_k, new_v) = lax.scan(
                dec_layer, x, (params["layers"], cache["k"], cache["v"],
                               cache["ck"], cache["cv"]))
            cache = dict(cache, k=new_k, v=new_v, len=length + 1)
        else:
            raise ValueError(fam)

        h = rmsnorm(params["final_ln"], h)
        logits = (h[:, 0] @ _unembed_matrix(cfg, params)).astype(jnp.float32)
        return logits, cache
    return f


def prefill_fn(cfg: ModelConfig, with_cache: bool = True):
    """Returns f(params, batch, max_len) -> (last-token logits, cache).

    The cache is fully populated so ``decode_fn`` can continue generation:
    KV tensors for attention families, SSM/conv (and shared-attn KV) states
    for hybrid, recurrent states for xLSTM, self+cross KV for enc-dec.
    """
    def pad_kv(kv, max_len):
        # (L, B, S, Hkv, hd) -> (L, B, max_len, Hkv, hd)
        pad = max_len - kv.shape[2]
        return jnp.pad(kv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))

    def f(params, batch, max_len: int):
        fam = cfg.family
        if fam == "encdec":
            memory = _encode(cfg, params, batch["enc_embeds"])
            x = embed(params["embed"], batch["tokens"])
            s = x.shape[1]

            def dec_block(h, lp):
                a, kv = attention_apply(lp["self_attn"],
                                        rmsnorm(lp["ln1"], h), cfg,
                                        kv_out=True)
                h = h + a
                a, ckv = attention_apply(lp["cross_attn"],
                                         rmsnorm(lp["ln_x"], h), cfg,
                                         memory=memory, kv_out=True)
                h = h + a
                h = h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h))
                return h, (kv[0], kv[1], ckv[0], ckv[1])
            hidden, kvs = lax.scan(dec_block, x, params["layers"])
            hidden = rmsnorm(params["final_ln"], hidden)
            logits = (hidden[:, -1] @ _unembed_matrix(cfg, params))
            b = x.shape[0]
            cache = {
                "k": pad_kv(kvs[0], max_len), "v": pad_kv(kvs[1], max_len),
                "ck": pad_kv(kvs[2], max_len), "cv": pad_kv(kvs[3], max_len),
                "len": jnp.full((b,), s, jnp.int32),
                "enc_len": jnp.full((b,), memory.shape[1], jnp.int32),
            }
            return logits.astype(jnp.float32), cache

        x = embed(params["embed"], batch["tokens"]) if cfg.embed_inputs \
            else batch["embeds"]
        b, s = x.shape[0], x.shape[1]

        if not with_cache:
            hidden, _ = _backbone(cfg, params, x)
            hidden = rmsnorm(params["final_ln"], hidden)
            logits = hidden[:, -1] @ _unembed_matrix(cfg, params)
            return logits.astype(jnp.float32), None

        if fam in ("dense", "vlm", "moe"):
            def blk(h, lp):
                a, kv = attention_apply(lp["attn"], rmsnorm(lp["ln1"], h),
                                        cfg, kv_out=True)
                h = h + a
                if "mlp" in lp:
                    h = h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h))
                else:
                    mo, _ = moe_apply(lp["moe"], rmsnorm(lp["ln2"], h),
                                      cfg.moe)
                    h = h + mo
                return h, kv

            if fam == "moe" and cfg.moe is not None and cfg.moe.layer_period == 1:
                hidden, kv0 = blk(x, params["dense0"])
                hidden, kvs = lax.scan(blk, hidden, params["layers"])
                ks_ = jnp.concatenate([kv0[0][None], kvs[0]], axis=0)
                vs_ = jnp.concatenate([kv0[1][None], kvs[1]], axis=0)
            elif fam == "moe":
                def unit(h, lps):
                    dlp, mlp_ = lps
                    h, kvd = blk(h, dlp)
                    h, kvm = blk(h, mlp_)
                    return h, (kvd[0], kvd[1], kvm[0], kvm[1])
                hidden, kvs4 = lax.scan(unit, x, (params["dense_layers"],
                                                  params["layers"]))
                ks_ = jnp.concatenate([kvs4[0], kvs4[2]], axis=0)
                vs_ = jnp.concatenate([kvs4[1], kvs4[3]], axis=0)
            else:
                hidden, (ks_, vs_) = lax.scan(blk, x, params["layers"])
            cache = {"k": pad_kv(ks_, max_len), "v": pad_kv(vs_, max_len),
                     "len": jnp.full((b,), s, jnp.int32)}

        elif fam == "hybrid":
            shared = params["shared_attn"]

            def mamba_block(h, lp):
                y, st, cst = mamba2_apply(lp["mamba"], rmsnorm(lp["ln"], h),
                                          cfg.ssm)
                return h + y, (st, cst)

            def group(h, glp):
                h, sts = lax.scan(mamba_block, h, glp)
                a, kv = attention_apply(shared["attn"],
                                        rmsnorm(shared["ln1"], h), cfg,
                                        kv_out=True)
                h = h + a
                h = h + mlp_apply(shared["mlp"], rmsnorm(shared["ln2"], h))
                return h, (sts[0], sts[1], kv[0], kv[1])
            hidden, outs = lax.scan(group, x, params["layers"])
            cache = {
                "ssm": outs[0], "conv": outs[1],
                "k": pad_kv(outs[2], max_len), "v": pad_kv(outs[3], max_len),
                "len": jnp.full((b,), s, jnp.int32),
            }

        elif fam == "ssm":
            def pair(h, lp):
                y, mst = xl.mlstm_apply(lp["mlstm"], rmsnorm(lp["m_ln"], h),
                                        cfg.n_heads,
                                        chunk=cfg.xlstm.chunk)
                h = h + y
                y, sst = xl.slstm_apply(lp["slstm"], rmsnorm(lp["s_ln"], h))
                return h + y, mst + sst
            hidden, outs = lax.scan(pair, x, params["layers"])
            cache = {"C": outs[0], "n": outs[1], "m": outs[2],
                     "sc": outs[3], "sn": outs[4], "sm": outs[5],
                     "sh": outs[6],
                     "len": jnp.full((b,), s, jnp.int32)}
        else:
            raise ValueError(fam)

        hidden = rmsnorm(params["final_ln"], hidden)
        logits = hidden[:, -1] @ _unembed_matrix(cfg, params)
        return logits.astype(jnp.float32), cache
    return f


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int):
    """Zero paged KV pool: one shared page arena per unit.

    Sequences own non-contiguous pages through per-sequence block tables
    (kept host-side by the engine); ``chunk_prefill_fn`` output is written
    into pages and ``paged_decode_fn`` appends + attends through the
    tables.  Attention families only (dense/vlm).

    Layout (L, NP, Hkv, PS, hd): the kv-head axis precedes the page slot,
    so one page of one head is a contiguous (PS, hd) tile — the block the
    paged decode kernel streams.
    """
    if cfg.family not in ("dense", "vlm"):
        raise ValueError(f"paged cache unsupported for family {cfg.family}")
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, hd)
    return {"kp": jnp.zeros(shape, jnp.bfloat16),
            "vp": jnp.zeros(shape, jnp.bfloat16)}


def kv_to_pages(kv: np.ndarray, page_size: int) -> np.ndarray:
    """Host-side (L, S, Hkv, hd) prefill KV -> (L, ceil(S / PS), Hkv, PS, hd)
    pages in the ``init_paged_cache`` layout, zero-padded to whole pages."""
    n_pages = -(-kv.shape[1] // page_size)
    pad = n_pages * page_size - kv.shape[1]
    kv = np.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kv = kv.reshape((kv.shape[0], n_pages, page_size) + kv.shape[2:])
    return kv.transpose(0, 1, 3, 2, 4)


def chunk_prefill_fn(cfg: ModelConfig):
    """Returns f(params, tokens, prefix_k, prefix_v) -> (logits, k_new, v_new).

    One chunk of a chunked prefill: ``tokens`` (B, C) is the next C prompt
    tokens, ``prefix_k``/``prefix_v`` (L, B, P, Hkv, hd) the KV of the P
    tokens already prefilled (RoPE'd at absolute positions 0..P-1 — the
    same contract as ``prefill_from_cache``, of which this is the
    unpadded, resumable core).  Returns last-position logits plus the KV
    of *only the new chunk* (L, B, C, Hkv, hd) so the caller can append it
    to paged storage and feed it back as prefix for the next chunk.
    P=0 reduces to a cold prefill of the first chunk.  Jitted, the program
    is named ``jit_chunk_prefill`` on the device.
    """
    fam = cfg.family
    if fam not in ("dense", "vlm"):
        raise ValueError(f"chunked prefill unsupported for family {fam}")

    def chunk_prefill(params, tokens, prefix_k, prefix_v):
        x = embed(params["embed"], tokens)
        p_len = prefix_k.shape[2]

        def blk(h, inp):
            lp, pk, pv = inp
            a, kv = attention_apply(lp["attn"], rmsnorm(lp["ln1"], h), cfg,
                                    kv_out=True, prefix_kv=(pk, pv),
                                    q_offset=p_len)
            h = h + a
            h = h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h))
            return h, kv

        hidden, (ks_, vs_) = lax.scan(blk, x,
                                      (params["layers"], prefix_k, prefix_v))
        hidden = rmsnorm(params["final_ln"], hidden)
        logits = hidden[:, -1] @ _unembed_matrix(cfg, params)
        # attention_apply returns full-context KV; keep only the new chunk
        return logits.astype(jnp.float32), ks_[:, :, p_len:], vs_[:, :, p_len:]
    return chunk_prefill


def paged_decode_fn(cfg: ModelConfig, use_kernel: bool | None = None):
    """Returns f(params, kp, vp, tables, lens, tokens) -> (logits, kp, vp).

    Batched single-step decode over the paged KV pool: ``tokens`` (B,) are
    the latest tokens of B independent sequences, ``tables`` (B, MP) their
    page tables into the (L, NP, Hkv, PS, hd) pools and ``lens`` (B,)
    their context lengths.  Each step RoPEs/projects the B tokens, writes
    the new KV into page ``tables[b, len // PS]`` slot ``len % PS`` and
    attends through the block tables (``paged_decode_attention``), so all
    active sequences decode in one batched launch regardless of where
    their KV lives.  ``use_kernel`` is passed to that front door (None:
    the Pallas kernel on an accelerator, the jnp oracle elsewhere).
    Jitted, the program is named ``jit_paged_decode_step`` on the device.
    """
    fam = cfg.family
    if fam not in ("dense", "vlm"):
        raise ValueError(f"paged decode unsupported for family {fam}")
    if cfg.sliding_window:
        raise ValueError("paged decode does not support sliding windows")
    from ..kernels.decode_attention.ops import paged_decode_attention
    hd = cfg.resolved_head_dim
    h_, hkv = cfg.n_heads, cfg.n_kv_heads

    def paged_decode_step(params, kp, vp, tables, lens, tokens):
        x = embed(params["embed"], tokens[:, None])          # (B, 1, D)
        b = x.shape[0]
        ps = kp.shape[3]
        positions = lens[:, None]
        rows = jnp.arange(b)
        page = tables[rows, lens // ps]                      # (B,)
        slot = lens % ps

        def layer_body(h, lp, kc, vc):
            xn = rmsnorm(lp["ln1"], h)
            q = dense(lp["attn"]["wq"], xn)
            q = apply_rope(q.reshape(b, 1, h_, hd), positions, cfg.rope_theta)
            k_new = apply_rope(dense(lp["attn"]["wk"], xn)
                               .reshape(b, 1, hkv, hd), positions,
                               cfg.rope_theta)
            v_new = dense(lp["attn"]["wv"], xn).reshape(b, 1, hkv, hd)
            kc = kc.at[page, :, slot].set(k_new[:, 0].astype(kc.dtype))
            vc = vc.at[page, :, slot].set(v_new[:, 0].astype(vc.dtype))
            a = paged_decode_attention(q[:, 0], kc, vc, tables, lens + 1,
                                       use_kernel=use_kernel)
            h = h + dense(lp["attn"]["wo"], a.reshape(b, 1, h_ * hd))
            h = h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h))
            return h, kc, vc

        def layer(carry, lp):
            h, k_all, v_all, i = carry
            kc = lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
            vc = lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
            h, kc, vc = layer_body(h, lp, kc, vc)
            k_all = lax.dynamic_update_index_in_dim(k_all, kc, i, 0)
            v_all = lax.dynamic_update_index_in_dim(v_all, vc, i, 0)
            return (h, k_all, v_all, i + 1), None

        (h, kp, vp, _), _ = lax.scan(
            layer, (x, kp, vp, jnp.int32(0)), params["layers"])
        h = rmsnorm(params["final_ln"], h)
        logits = (h[:, 0] @ _unembed_matrix(cfg, params)).astype(jnp.float32)
        return logits, kp, vp
    return paged_decode_step


def prefill_from_cache(cfg: ModelConfig):
    """Returns f(params, batch, prefix_k, prefix_v, max_len) -> (logits, cache).

    Prefill that *attaches to a cached prompt prefix* (the paged KV prefix
    cache, DESIGN.md §2.4): ``batch["tokens"]`` holds only the uncached
    suffix (B, S); ``prefix_k``/``prefix_v`` are (L, B, P, Hkv, hd) KV
    tensors for the first P prompt tokens, exactly as a previous prefill
    produced them (RoPE already applied at absolute positions 0..P-1).
    Only the S suffix tokens pay compute; the returned cache covers the full
    P+S context so ``decode_fn`` continues identically to a cold prefill.

    Sequence-local attention families only (dense/vlm).  Recurrent-state
    families have no position-indexed cache to attach to, and MoE routing is
    sequence-global (expert capacity is shared across all prompt tokens, so
    a suffix-only prefill drops different tokens than a cold prefill and
    breaks the token-identical-reuse guarantee).
    """
    fam = cfg.family
    if fam not in ("dense", "vlm"):
        raise ValueError(f"prefix-cached prefill unsupported for family {fam}")

    def pad_kv(kv, max_len):
        pad = max_len - kv.shape[2]
        return jnp.pad(kv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))

    def f(params, batch, prefix_k, prefix_v, max_len: int):
        x = embed(params["embed"], batch["tokens"]) if cfg.embed_inputs \
            else batch["embeds"]
        b, s = x.shape[0], x.shape[1]
        p_len = prefix_k.shape[2]

        def blk(h, inp):
            lp, pk, pv = inp
            a, kv = attention_apply(lp["attn"], rmsnorm(lp["ln1"], h), cfg,
                                    kv_out=True, prefix_kv=(pk, pv),
                                    q_offset=p_len)
            h = h + a
            h = h + mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], h))
            return h, kv

        hidden, (ks_, vs_) = lax.scan(blk, x,
                                      (params["layers"], prefix_k, prefix_v))
        cache = {"k": pad_kv(ks_, max_len), "v": pad_kv(vs_, max_len),
                 "len": jnp.full((b,), p_len + s, jnp.int32)}
        hidden = rmsnorm(params["final_ln"], hidden)
        logits = hidden[:, -1] @ _unembed_matrix(cfg, params)
        return logits.astype(jnp.float32), cache
    return f
