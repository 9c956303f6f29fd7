"""The benchmark's yardstick on the CPU: operation and byte counts against
counts worked by hand, the trace reduction against hand-made and recorded
traces, the peak table, and the weights' layout against the served
model's."""

import sys
from pathlib import Path

import numpy as np
import pytest

from bench import flops as F
from bench import peaks as P
from bench import trace as T

TINY = {"d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 2,
        "d_ff": 16, "vocab": 32, "n_layers": 3}
RECORDED = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_layer_matmul_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, gate/up 8x16 each, down 16x8
    assert F.layer_matmul_params(TINY) == 64 + 32 + 32 + 64 + 128 * 3


def test_step_flops_by_hand():
    # 5 tokens through 3 layers of 576 weights, 2 rows of 8x32 logits
    assert F.step_flops(TINY, 5, 2) == 2 * (3 * 576 * 5 + 8 * 32 * 2)


def test_paged_attn_cost_by_hand():
    # 10 context tokens: k and v of 2 heads x 2 dims in bf16 = 16 B each;
    # 2 decode tokens: q (4 heads x 2 dims) bf16 in, f32 out = 48 B each
    nbytes, flops = F.paged_attn_cost(TINY, ctx_tokens=10, decode_tokens=2)
    assert nbytes == 3 * (10 * 16 + 2 * 48)
    # q.k and p.v: 2 FLOPs per multiply-add, 4 heads x 2 dims x 10 tokens
    assert flops == 3 * (10 * 4 * 2 * 4)


def test_roofline_picks_the_larger_bound():
    peak = {"hbm_bytes_per_s": 100.0, "bf16_flops_per_s": 1000.0}
    assert F.roofline_seconds(200.0, 1000.0, peak) == (2.0, "memory")
    assert F.roofline_seconds(50.0, 3000.0, peak) == (3.0, "compute")


def test_peak_table():
    pk = P.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        P.peaks("TPU v9 imaginary")


def ev(name, start, dur, plane="/device:TPU:0", line=T.OPS_LINE):
    return T.Event(plane, line, name, float(start), float(dur))


def test_union_busy_and_gaps_by_hand():
    ops = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 50, 20)]
    assert T.union([(0, 10), (5, 15), (30, 35)]) == [(0, 15), (30, 35)]
    assert T.busy_ns(ops, 0, 60) == 15 + 5 + 10
    assert T.idle_gaps(ops, 0, 60) == [(15, 30), (35, 50)]
    assert T.idle_gaps(ops, 0, 80) == [(15, 30), (35, 50), (70, 80)]


def test_module_runs_and_gap_attribution():
    ops = [ev("%x = f32[] op()", 0, 2), ev("%y = f32[] op()", 2, 3),
           ev("%z = f32[] op()", 6, 1), ev("%x = f32[] op()", 8, 2)]
    mods = [ev("jit_f(1)", 0, 5, line=T.MODULES_LINE),
            ev("jit_g(2)", 6, 1, line=T.MODULES_LINE),
            ev("jit_f(1)", 8, 2, line=T.MODULES_LINE)]
    held = T.runs_holding(mods, ops, lambda n: n.startswith("%x"))
    assert [(m.name, m.start_ns) for m in held] == [("jit_f(1)", 0),
                                                   ("jit_f(1)", 8)]
    host = {"bench.step": [(0, 20)], "bench.wait": [(20, 40)]}
    got = T.attribute_gaps([(10, 18), (25, 40), (40, 50)], host)
    assert got == pytest.approx({"bench.step": 8e-9, "bench.wait": 15e-9,
                                 "other": 10e-9})
    by = T.seconds_by_name(ops)
    assert by["%x"] == pytest.approx(4e-9)


def test_busy_matches_a_bitmap_on_a_random_trace():
    rng = np.random.default_rng(0)
    ops = [ev("o", int(s), int(d)) for s, d in
           zip(rng.integers(0, 1000, 200), rng.integers(1, 30, 200))]
    bitmap = np.zeros(1100, bool)
    for e in ops:
        bitmap[int(e.start_ns):int(e.start_ns + e.dur_ns)] = True
    assert T.busy_ns(ops, 100, 900) == bitmap[100:900].sum()


def test_recorded_tpu_trace():
    """A trace recorded on a v5e: three calls of a jitted paged decode
    attention under ``bench.step`` spans inside one ``bench.window``."""
    events = T.load(RECORDED)
    ops = T.device_ops(events)
    assert T.devices(events) == ["/device:TPU:0"]
    kernel = [e for e in ops if T.is_paged_attn(e.name)]
    assert len(kernel) == 3
    mods = T.device_modules(events)
    assert len(T.runs_holding(mods, ops, T.is_paged_attn)) == 3
    (lo, hi), = T.spans(events, "bench.window")
    assert len(T.spans(events, "bench.step")) == 3
    busy = T.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    gaps = T.idle_gaps(ops, lo, hi)
    assert sum(t - s for s, t in gaps) == pytest.approx(hi - lo - busy)


def test_weights_layout_matches_the_served_model():
    import jax

    from bench import harness, weights as W
    from bench.tests import tiny

    harness._import_program()
    from repro.models import transformer as TF

    conf = dict(tiny.CONFIG)
    model = harness.model_block(conf)
    want = TF.init_abstract(harness.model_config({"model": model}))
    got = jax.eval_shape(lambda: W.make_params(model, 1))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_one_layer_rebuilds_bit_for_bit():
    import jax

    from bench import weights as W
    from bench.tests import tiny
    from bench import harness

    model = harness.model_block(dict(tiny.CONFIG))
    seed = 2**33 + 5
    full = W.make_params(model, seed)
    one = W.layer(model, seed, 1)
    for a, b in zip(jax.tree.leaves(one),
                    jax.tree.leaves(jax.tree.map(lambda x: x[1],
                                                 full["layers"]))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    emb, head, norm = W.embed_and_head(model, seed)
    assert np.array_equal(np.asarray(emb), np.asarray(full["embed"]["emb"]))
    assert np.array_equal(np.asarray(head), np.asarray(emb).T)
    assert np.array_equal(np.asarray(norm),
                          np.asarray(full["final_ln"]["scale"]))
    # norm scales are drawn, not ones, so a program that skips them shows
    for scale in (norm, one["ln1"]["scale"], one["ln2"]["scale"]):
        s = np.asarray(scale, np.float32)
        assert 0.02 < s.std() < 0.2 and abs(s.mean() - 1.0) < 0.05
    other = W.make_params(model, seed + 2**32)
    assert not np.array_equal(np.asarray(other["embed"]["emb"]),
                              np.asarray(full["embed"]["emb"]))


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
