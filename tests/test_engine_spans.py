"""Wall-clock spans inside the live engine's step loop (DESIGN.md §2.9).

A toy-width model on the CPU, step batching on: the span tree of every
step, each request's ``queued`` → ``prefill`` → ``decode`` phases and its
``first_token`` event, the K/V bytes the step loop copies between host and
device for a planted prefix hit, the step programs' names on the device,
and ``profiled`` turning into a named scope under a JAX trace.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import Telemetry, chrome_trace, validate_chrome_trace
from repro.serving.batching import StepBatchingConfig
from repro.serving.cluster import Router
from repro.serving.engine import EngineConfig, Request, ServingEngine

PREFILL_KIDS = ["kv.upload", "launch", "sync", "kv.download"]
DECODE_KIDS = ["prepare", "launch", "sync", "readback"]


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs.registry import ARCHS
    from repro.models import transformer as T
    cfg = ARCHS["smollm-360m"].reduced().scaled(
        n_layers=1, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab=128, head_dim=32, remat=False)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, prefix_cache=False, max_batch=4, budget=64):
    cfg, params = model
    return ServingEngine(cfg, params, EngineConfig(
        n_units=1, elasticity=None, merging="none", pruning=None,
        result_cache=False, max_len=96, batch_buckets=(1, 2, 4),
        prefix_cache=prefix_cache,
        batching=StepBatchingConfig(max_batch=max_batch,
                                    step_token_budget=budget)))


def _prompts(n, seed=7, lo=4, hi=40):
    rng = np.random.default_rng(seed)
    return [tuple(int(x) for x in
                  rng.integers(1, 127, size=rng.integers(lo, hi)))
            for _ in range(n)]


@pytest.fixture(scope="module")
def routed_run(tiny_model):
    """Five requests through the front door, traced."""
    eng = _engine(tiny_model)
    tel = Telemetry(wall_clock=lambda: tel.clock())
    router = Router([eng], telemetry=tel)
    reqs = [Request(prompt=p, n_new=3, deadline=1e9) for p in _prompts(5)]
    stats = router.run([(float(i), r) for i, r in enumerate(reqs)])
    return eng, tel, reqs, stats


def _children(tel, parent):
    return [s for s in tel.spans if s["parent"] == parent["id"]]


class TestStepLoopSpans:
    def test_every_step_has_its_span_tree(self, routed_run):
        eng, tel, reqs, stats = routed_run
        steps = tel.spans_of("unit.step")
        assert steps and len(steps) == len(tel.events_of("batch_step"))
        for st in steps:
            kids = _children(tel, st)
            assert [k["name"] for k in kids] == \
                ["unit.prefill"] * st["chunks"] + \
                ["unit.decode"] * (st["decode"] > 0)
            for k in kids:
                names = [g["name"] for g in _children(tel, k)]
                if k["name"] == "unit.decode":
                    assert names == DECODE_KIDS
                else:
                    assert names[:4] == PREFILL_KIDS
                    assert names[4:] in ([], ["readback", "unit.commit"])
                assert st["start"] <= k["start"] <= k["end"] <= st["end"]
        # each step's parent chain reaches the front door's router.step
        by_id = {s["id"]: s for s in tel.spans}
        up = steps[0]
        while up["parent"] is not None:
            up = by_id[up["parent"]]
        assert up["name"] == "router.step"

    def test_batch_step_events_carry_wall_time_and_kv_bytes(self,
                                                            routed_run):
        eng, tel, reqs, stats = routed_run
        evs = tel.events_of("batch_step")
        steps = tel.spans_of("unit.step")
        assert [e["step_s"] for e in evs] == \
            pytest.approx([s["end"] - s["start"] for s in steps])
        assert sum(e["kv_bytes"] for e in evs) == stats["kv_host_bytes"] > 0
        assert tel.metrics.histogram("step_ms").count == len(steps)
        assert tel.metrics.histogram("step_ticks") is None
        m = tel.metrics
        # launches summed per step program as their spans are recorded
        progs = tel.programs
        assert progs["chunk_prefill"]["launches"] == len(reqs)
        assert progs["paged_decode_step"]["launches"] == \
            len(tel.spans_of("unit.decode")) > 0
        for name, e in progs.items():
            launches = [s for s in tel.spans_of("launch")
                        if s["program"] == name]
            assert e["launches"] == len(launches)
            assert e["cold_launches"] == sum(s["cold"] for s in launches)
            assert e["execute_s"] > 0
        assert sum(m.counter_value("kv_host_bytes", dir=d)
                   for d in ("h2d", "d2h")) == stats["kv_host_bytes"]

    def test_request_phases_in_order(self, routed_run):
        eng, tel, reqs, stats = routed_run
        assert tel._open == {}
        phases: dict = {}
        for s in tel.spans:
            if s["name"].startswith("request."):
                phases.setdefault(s["rid"], []).append(s)
        assert sorted(phases) == sorted(r.rid for r in reqs)
        for spans in phases.values():
            spans.sort(key=lambda s: s["start"])
            assert [s["name"] for s in spans] == \
                ["request.queued", "request.prefill", "request.decode"]
            for a, b in zip(spans, spans[1:]):
                assert a["end"] == b["start"]      # one instant per change
            assert spans[-1]["outcome"] == "done"

    def test_first_token_events_split_the_wait(self, routed_run):
        eng, tel, reqs, stats = routed_run
        firsts = {e["rid"]: e for e in tel.events_of("first_token")}
        assert sorted(firsts) == sorted(r.rid for r in reqs)
        for s in tel.spans_of("request.queued"):
            e = firsts[s["rid"]]
            assert e["queued_s"] == pytest.approx(s["end"] - s["start"])
        for s in tel.spans_of("request.decode"):
            assert firsts[s["rid"]]["wall"] == s["start"]

    def test_wall_clock_chrome_trace_puts_steps_on_the_unit_track(
            self, routed_run):
        eng, tel, reqs, stats = routed_run
        mid = eng.units[0].machine.mid
        trace = chrome_trace(tel.events, clock="wall", spans=tel.spans)
        validate_chrome_trace(trace)
        evs = trace["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X" and e["cat"] == "span"]
        assert {e["tid"] for e in xs if e["name"].startswith(
            ("unit.", "launch", "sync", "kv."))} == {mid}
        assert sum(e["ph"] == "b" and e["name"] == "request.queued"
                   for e in evs) == len(reqs)
        assert min(e["ts"] for e in evs if e["ph"] != "M") == 0.0


def test_prefix_hit_kv_bytes_match_the_shapes(tiny_model):
    """A second prompt that shares two 16-token blocks with the first:
    its prefill uploads the 32 cached tokens' K/V, downloads its 8-token
    chunk's, and commits all 40 tokens padded to three pages."""
    cfg, _ = tiny_model
    eng = _engine(tiny_model, prefix_cache=True)
    tel = Telemetry()
    eng.attach_telemetry(tel)
    rng = np.random.default_rng(3)
    shared = tuple(int(x) for x in rng.integers(1, 127, size=32))
    a = shared + tuple(int(x) for x in rng.integers(1, 127, size=8))
    b = shared + tuple(int(x) for x in rng.integers(1, 127, size=8))
    eng.run([(0.0, Request(prompt=a, n_new=2, deadline=1e9))])
    before = eng.collect_stats()
    eng.run([(1000.0, Request(prompt=b, n_new=2, deadline=1e9))])
    after = eng.collect_stats()
    assert after["prefix_tokens_reused"] - before["prefix_tokens_reused"] \
        == 32
    # host K/V keeps the chunk program's output dtype; the arena its own
    from repro.models import transformer as T
    pk = jnp.zeros((cfg.n_layers, 1, 0, cfg.n_kv_heads,
                    cfg.resolved_head_dim), jnp.bfloat16)
    _, kn, _ = jax.eval_shape(T.chunk_prefill_fn(cfg), tiny_model[1],
                              jnp.zeros((1, 8), jnp.int32), pk, pk)
    host = np.dtype(kn.dtype).itemsize
    arena = np.dtype(eng._runners[eng.units[0].machine.mid]
                     .pages["kp"].dtype).itemsize
    per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.resolved_head_dim
    want = per_tok * (32 * host + 8 * host + 48 * arena)
    assert after["kv_host_bytes"] - before["kv_host_bytes"] == want
    # the same bytes, split by direction, in the metrics registry
    m = tel.metrics
    assert m.counter_value("kv_host_bytes", dir="d2h") + \
        m.counter_value("kv_host_bytes", dir="h2d") == after["kv_host_bytes"]


def test_decode_counts_the_paged_kernels_blocks(tiny_model):
    """One decode launch of three sequences in the 4-row bucket: 96-token
    tables of 16-token pages are 6 pages, blocks of 2 pages (32 tokens),
    3 blocks a row.  Contexts 0, 16 and 40 attend over 1, 17 and 41
    tokens (1, 1 and 2 blocks); the padding row over 1 (1 block)."""
    eng = _engine(tiny_model)
    tel = Telemetry()
    eng.attach_telemetry(tel)
    run = eng._runners[eng.units[0].machine.mid]
    assert (run.mp, run.ppb) == (6, 2)
    seqs = [object() for _ in range(3)]
    for i, (s, n) in enumerate(zip(seqs, (0, 16, 40))):
        run.states[id(s)] = {"cur": 1, "len": n, "out": [],
                             "tab": np.arange(1 + 6 * i, 7 + 6 * i)}
    run._decode(seqs, tel)
    m = tel.metrics
    assert m.counter_value("paged_kv_blocks", kind="live") == 5
    assert m.counter_value("paged_kv_blocks", kind="grid") == 4 * 3
    assert [run.states[id(s)]["len"] for s in seqs] == [1, 17, 41]


def test_spans_leave_greedy_tokens_unchanged(tiny_model):
    prompts = _prompts(6, seed=11)
    outs = []
    for tel in (Telemetry(), None):
        eng = _engine(tiny_model, max_batch=4, budget=16)
        if tel is not None:
            eng.attach_telemetry(tel)
        reqs = [Request(prompt=p, n_new=4, deadline=1e9) for p in prompts]
        eng.run([(float(i), r) for i, r in enumerate(reqs)])
        outs.append([r.tokens for r in reqs])
    assert outs[0] == outs[1]


def test_step_programs_carry_stable_names(tiny_model):
    from repro.models import transformer as T
    cfg, params = tiny_model
    pk = jnp.zeros((cfg.n_layers, 1, 0, cfg.n_kv_heads,
                    cfg.resolved_head_dim), jnp.bfloat16)
    chunk = jax.jit(T.chunk_prefill_fn(cfg)).lower(
        params, jnp.zeros((1, 8), jnp.int32), pk, pk)
    assert chunk.as_text().startswith("module @jit_chunk_prefill ")
    pages = T.init_paged_cache(cfg, 9, 16)
    rows = jnp.zeros((2,), jnp.int32)
    dec = jax.jit(T.paged_decode_fn(cfg)).lower(
        params, pages["kp"], pages["vp"], jnp.zeros((2, 4), jnp.int32),
        rows, rows)
    assert dec.as_text().startswith("module @jit_paged_decode_step ")
    # the attention front door, called while the step is traced, names
    # its operations instead of timing the trace
    assert "paged_decode_attention/" in dec.compile().as_text()


def test_profiled_under_trace_times_nothing(tiny_model):
    from repro.obs import profiling
    tel = Telemetry()
    profiling.install(tel)
    try:
        f = jax.jit(lambda x: profiling.profiled("double", lambda y: 2 * y,
                                                 x))
        assert float(f(jnp.float32(2.0))) == 4.0
        assert "double/" in f.lower(jnp.float32(1.0)).compile().as_text()
        assert tel.spans_of("kernel.double") == []
        # eagerly, the same front door is a timed launch
        assert float(profiling.profiled("double", lambda y: 2 * y,
                                        jnp.float32(3.0))) == 6.0
        (k,) = tel.spans_of("kernel.double")
        assert k["cold"] is True
    finally:
        profiling.install(None)
