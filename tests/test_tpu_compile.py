"""Compile the serving path's kernels and step programs for a TPU v5e.

Nothing runs: each program is lowered and compiled for a described (not
attached) v5e chip, which refuses what the chip's compiler would refuse —
block shapes off the (8, 128) tiling, primitives Mosaic cannot lower,
programs that do not fit.  Shapes are SmolLM-360M's (15 query and 5 KV
heads of head_dim 64, 16-token pages, 2048-token sequences, batch 8).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these
compiles, because an entry written for a described chip cannot be read
back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.pmf_conv.pmf_conv import pmf_conv_pallas
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas
from repro.models import transformer as T

B, H, HKV, HD = 8, 15, 5, 64           # SmolLM-360M heads, batch 8
PS, MAX_LEN = 16, 2048
MP = MAX_LEN // PS                     # pages per sequence
NP = B * MP + 1                        # the engine's arena: + pad page 0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_args(spec, name):
    bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    if name == "paged_decode_attention":
        pages = spec((NP, HKV, PS, HD), bf16)
        return (paged_decode_attention_pallas, spec((B, H, HD), bf16),
                pages, pages, spec((B, MP), i32), spec((B,), i32))
    if name == "decode_attention":
        cache = spec((B, MAX_LEN, HKV, HD), bf16)
        return (decode_attention_pallas, spec((B, H, HD), bf16), cache,
                cache, spec((B,), i32))
    if name == "pmf_conv":
        # the success-chance autoscaler's grid: 32 tasks x 64 buckets
        return (pmf_conv_pallas, spec((32, 64), f32), spec((32, 64), f32),
                spec((32,), f32))
    return (rmsnorm_pallas, spec((B, 960), bf16), spec((960,), bf16))


@pytest.mark.parametrize("name", ["paged_decode_attention",
                                  "decode_attention", "pmf_conv", "rmsnorm"])
def test_kernel_compiles_for_v5e(spec, name):
    fn, *args = _kernel_args(spec, name)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.fixture(scope="module")
def smollm(spec):
    """SmolLM-360M at its published widths, cut to two layers, as shapes."""
    cfg = get_arch("smollm-360m").scaled(n_layers=2, remat=False)
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), shapes)
    return cfg, params


def test_paged_decode_step_compiles_with_kernel(spec, smollm, monkeypatch):
    cfg, params = smollm
    # compiled for the described TPU, the step takes its TPU branch: the
    # Pallas kernel, not the jnp oracle that the CPU backend would pick
    monkeypatch.setattr(decode_ops, "interpret_default", lambda: False)
    arena = spec((cfg.n_layers, NP, HKV, PS, HD), jnp.bfloat16)
    rows = spec((B,), jnp.int32)
    text = _compiled_text(T.paged_decode_fn(cfg), params, arena, arena,
                          spec((B, MP), jnp.int32), rows, rows)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("chunk,prefix", [(256, 0), (128, 0), (64, 64)])
def test_chunk_prefill_step_compiles(spec, smollm, chunk, prefix):
    cfg, params = smollm
    kv = spec((cfg.n_layers, 1, prefix, HKV, HD), jnp.bfloat16)
    _compiled_text(T.chunk_prefill_fn(cfg), params,
                   spec((1, chunk), jnp.int32), kv, kv)

