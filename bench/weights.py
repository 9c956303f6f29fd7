"""Seeded random weights for a configuration, made by the benchmark.

``make_params`` builds the whole parameter tree on the device in one jitted
call, in bfloat16, the type it is served in, in the layout the served
model reads.  ``layer`` rebuilds the weights of one decoder layer from the
same seed, bit for bit, so that the float32 reference can walk the model
layer by layer without holding it whole and without taking anything from
the program.  ``embedding`` does the same for the token embedding.

Every leaf is drawn from its own key, folded from the seed, the layer and
the leaf's position in ``LAYER_LEAVES``: the values of one layer never
depend on how many layers are built.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

# (path, fan-in key, or None for a norm scale)
LAYER_LEAVES = (
    (("ln1", "scale"), None),
    (("attn", "wq", "w"), "d"),
    (("attn", "wk", "w"), "d"),
    (("attn", "wv", "w"), "d"),
    (("attn", "wo", "w"), "hq"),
    (("ln2", "scale"), None),
    (("mlp", "w_gate", "w"), "d"),
    (("mlp", "w_up", "w"), "d"),
    (("mlp", "w_down", "w"), "f"),
)
EMBED_STD = 0.02
NORM_STD = 0.1


def dims(cfg: dict) -> dict:
    d, h, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "hkv": hkv, "hd": hd, "hq": h * hd,
            "f": cfg["d_ff"], "v": cfg["vocab"], "L": cfg["n_layers"]}


def leaf_shapes(cfg: dict) -> dict:
    n = dims(cfg)
    return {
        ("ln1", "scale"): (n["d"],),
        ("attn", "wq", "w"): (n["d"], n["hq"]),
        ("attn", "wk", "w"): (n["d"], n["hkv"] * n["hd"]),
        ("attn", "wv", "w"): (n["d"], n["hkv"] * n["hd"]),
        ("attn", "wo", "w"): (n["hq"], n["d"]),
        ("ln2", "scale"): (n["d"],),
        ("mlp", "w_gate", "w"): (n["d"], n["f"]),
        ("mlp", "w_up", "w"): (n["d"], n["f"]),
        ("mlp", "w_down", "w"): (n["f"], n["d"]),
    }


def root_key(seed: int):
    """A key from any non-negative whole number: its low and high 32-bit
    words are folded in separately, so seeds past 2**32 do not collide."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _put(tree: dict, path: tuple, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _layer(cfg: dict, key, index) -> dict:
    n, shapes = dims(cfg), leaf_shapes(cfg)
    lk = jax.random.fold_in(key, index)
    tree: dict = {}
    for i, (path, fan) in enumerate(LAYER_LEAVES):
        shape = shapes[path]
        z = jax.random.normal(jax.random.fold_in(lk, i), shape, jnp.float32)
        val = (1.0 + NORM_STD * z if fan is None
               else z / math.sqrt(n[fan])).astype(jnp.bfloat16)
        _put(tree, path, val)
    return tree


def _embedding(cfg: dict, key):
    n = dims(cfg)
    return (jax.random.normal(jax.random.fold_in(key, 1 << 20),
                              (n["v"], n["d"]), jnp.float32)
            * EMBED_STD).astype(jnp.bfloat16)


def _unembedding(cfg: dict, key):
    n = dims(cfg)
    return (jax.random.normal(jax.random.fold_in(key, (1 << 20) + 1),
                              (n["d"], n["v"]), jnp.float32)
            / math.sqrt(n["d"])).astype(jnp.bfloat16)


def _final_norm(cfg: dict, key):
    n = dims(cfg)
    return (1.0 + NORM_STD * jax.random.normal(
        jax.random.fold_in(key, (1 << 20) + 2), (n["d"],), jnp.float32)
    ).astype(jnp.bfloat16)


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted(cfg.items()))


@partial(jax.jit, static_argnums=(0,))
def _make(cfg_items: tuple, key):
    cfg = dict(cfg_items)
    n = dims(cfg)
    layers = jax.vmap(lambda i: _layer(cfg, key, i))(jnp.arange(n["L"]))
    params = {"embed": {"emb": _embedding(cfg, key)},
              "final_ln": {"scale": _final_norm(cfg, key)},
              "layers": layers}
    if not cfg.get("tie_embeddings", False):
        params["unembed"] = {"w": _unembedding(cfg, key)}
    return params


def make_params(cfg: dict, seed: int) -> dict:
    """The whole served parameter tree, on the device, in bfloat16."""
    return _make(_freeze(cfg), root_key(seed))


@partial(jax.jit, static_argnums=(0,))
def _one_layer(cfg_items: tuple, key, index):
    return _layer(dict(cfg_items), key, index)


def layer(cfg: dict, seed: int, index: int) -> dict:
    """Decoder layer ``index``'s weights, identical to ``make_params``'s."""
    return _one_layer(_freeze(cfg), root_key(seed), jnp.int32(index))


@partial(jax.jit, static_argnums=(0,))
def _embed_and_head(cfg_items: tuple, key):
    cfg = dict(cfg_items)
    emb = _embedding(cfg, key)
    head = emb.T if cfg.get("tie_embeddings", False) \
        else _unembedding(cfg, key)
    return emb, head, _final_norm(cfg, key)


def embed_and_head(cfg: dict, seed: int):
    """(token embedding (V, D), output projection (D, V), final norm scale
    (D,)) in bfloat16."""
    return _embed_and_head(_freeze(cfg), root_key(seed))
