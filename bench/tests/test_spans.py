"""The program's spans in the benchmark, on the CPU: the device-trace
readers still read the recorded trace as they did, the clock alignment
recovers a planted offset, idle gaps land on the innermost span, the stall
finder and each new reader work on hand-made runs, and a tiny cell's span
report runs end to end."""

from types import SimpleNamespace

import pytest

from bench import harness
from bench import spans as S
from bench import trace as T
from bench.tests import tiny

MODEL = {"d_model": 960, "n_heads": 15, "n_kv_heads": 5, "head_dim": 64,
         "d_ff": 2560, "vocab": 49152, "n_layers": 32}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def ev(name, start, dur, plane="/device:TPU:0", line=T.MODULES_LINE):
    return T.Event(plane, line, name, float(start), float(dur))


def host(name, start, end):
    return T.Event("/host:CPU", "python3", name, float(start),
                   float(end - start))


def test_device_trace_readers_read_the_recorded_trace_as_before():
    """The values every device-trace reader gave on the recorded v5e trace
    before the program had spans of its own."""
    from pathlib import Path
    tr = harness.reduce_trace(Path(__file__).parent / "data")
    run = {"trace": tr, "seconds": 1.0, "model": MODEL, "peaks": PEAKS,
           "events": [{"kind": "batch_step", "decode": 2, "chunks": 0,
                       "tokens": 2}] * 3,
           "records": [{"status": "done", "done": 0.5,
                        "item": SimpleNamespace(prompt=[0] * 100,
                                                n_new=3)}]}
    read = {n: harness.reader(n)(run) for n in (
        "decode_step_ms", "device_idle_pct", "paged_attn_roofline",
        "prefill_step_ms", "host_bound_idle_pct")}
    assert read["decode_step_ms"] == pytest.approx(0.062781, abs=1e-12)
    assert read["device_idle_pct"] == pytest.approx(97.30338797278898,
                                                    rel=1e-12)
    assert read["paged_attn_roofline"] == pytest.approx(51.07725949247905,
                                                        rel=1e-12)
    assert tr["gap_by_host"] == {"bench.step": pytest.approx(0.002264677)}
    # its one program is ``jit__lambda``: the new readers find nothing
    assert read["prefill_step_ms"] is None
    assert read["host_bound_idle_pct"] is None


def test_clock_offset_recovers_a_planted_offset():
    offset = 1_500_000.0                 # host runs 1.5 ms ahead
    spans, mods = [], []
    for i in range(10):
        t0 = 100_000_000.0 * i
        # device: the decode program runs 40 ms, ends at t0 + 50 ms
        mods.append(ev(f"{S.DECODE_PROGRAM}(7)", t0 + 10e6, 40e6))
        mods.append(ev(f"{S.PREFILL_PROGRAM}(8)", t0 + 1e6, 8e6))
        # host: its sync returns 20 us after the device finished
        end = t0 + 50e6 + offset + 20_000.0 + (i % 3) * 1000.0
        spans += [host("unit.decode", t0 + 9e6 + offset, end + 1e6),
                  host("sync", t0 + 12e6 + offset, end),
                  host("sync", t0 + 1e6 + offset, t0 + 9.5e6 + offset)]
    # the trace cut the first step's host side off
    spans = [s for s in spans if s.start_ns > 15e6 + offset]
    got = S.clock_offset_ns(S.program_spans(spans), mods)
    assert got == pytest.approx(offset + 21_000.0, abs=1000.0)
    shifted = S.to_device_clock([("sync", 10.0, 20.0)], 4.0)
    assert shifted == [("sync", 6.0, 16.0)]
    assert S.clock_offset_ns([("sync", 0.0, 1.0)], mods) is None


def test_innermost_attribution_by_hand():
    spans = [("router.step", 0, 100), ("cp.map", 5, 15),
             ("unit.step", 20, 90), ("unit.decode", 30, 80),
             ("prepare", 30, 40), ("launch", 40, 45), ("sync", 45, 75),
             ("readback", 75, 80)]
    assert S.innermost_pieces(spans) == [
        (0, 5, "router.step"), (5, 15, "cp.map"), (15, 20, "router.step"),
        (20, 30, "unit.step"), (30, 40, "prepare"), (40, 45, "launch"),
        (45, 75, "sync"), (75, 80, "readback"), (80, 90, "unit.step"),
        (90, 100, "router.step")]
    gaps = [(10, 25), (38, 42), (85, 110)]
    got = S.attribute_innermost(gaps, spans)
    assert got == pytest.approx({
        "cp.map": 5e-9, "router.step": 15e-9, "unit.step": 10e-9,
        "prepare": 2e-9, "launch": 2e-9, "other": 10e-9})
    # a child that overruns its parent is cut at the parent's end
    assert S.innermost_pieces([("a", 0, 10), ("b", 5, 20)]) == \
        [(0, 5, "a"), (5, 10, "b")]


def test_stall_finder_by_hand():
    spans = [("bench.step", 0, 10), ("router.step", 1, 9),
             ("unit.step", 2, 8), ("bench.wait", 12, 13),
             ("bench.step", 30, 31)]
    assert S.self_time(spans, 0, 40) == pytest.approx(
        {"bench.step": 3.0, "router.step": 2.0, "unit.step": 6.0,
         "bench.wait": 1.0})
    assert S.longest_uncovered(spans, 0, 40) == (13, 17)
    assert S.longest_uncovered(spans, 0, 31) == (13, 17)
    assert S.longest_uncovered(spans, 5, 11) == (10, 1)


def test_wait_causes_by_hand():
    """A request waits 2-12: 2-4 the harness sleeps, 4-5 the router's step
    maps, 5-9 another sequence's decode step runs (its launch and sync
    count as the decode), 9-10 the router's step itself, 10-12 nothing."""
    spans = [("bench.wait", 0, 4), ("bench.step", 4, 10),
             ("router.step", 4, 10), ("cp.map", 4, 5), ("unit.step", 5, 9),
             ("unit.decode", 5, 9), ("launch", 6, 7), ("sync", 7, 9)]
    got = S.wait_causes([(2e9, 12e9)], [(n, a * 1e9, b * 1e9)
                                        for n, a, b in spans])
    assert got == pytest.approx({"bench.wait": 2.0, "cp.map": 1.0,
                                 "unit.decode": 4.0, "router.step": 1.0,
                                 "other": 2.0})


def _run(events, modules=(), busy_s=0.0, window_s=10.0, prefilled=100):
    trace = {"ops": [], "modules": list(modules), "busy_s": busy_s,
             "window_s": window_s, "gaps": [], "gap_by_host": {}}
    return {"events": events, "trace": trace, "seconds": window_s,
            "s0": {"prefill_tokens": 50}, "s1": {"prefill_tokens":
                                                50 + prefilled}}


def test_new_readers_on_hand_made_runs():
    steps = [{"kind": "batch_step", "decode": 1, "chunks": 1, "tokens": 9,
              "step_s": 2.0, "kv_bytes": 40960},
             {"kind": "batch_step", "decode": 1, "chunks": 0, "tokens": 1,
              "step_s": 3.0, "kv_bytes": 0}]
    firsts = [{"kind": "first_token", "queued_s": 0.004},
              {"kind": "first_token", "queued_s": 0.002}]
    mods = [ev(f"{S.PREFILL_PROGRAM}(1)", 0, 8e6),
            ev(f"{S.PREFILL_PROGRAM}(2)", 10e6, 12e6),
            ev(f"{S.DECODE_PROGRAM}(3)", 30e6, 60e6), ev("jit_f(4)", 0, 1e9)]
    run = _run(steps + firsts, mods, busy_s=4.0)
    read = {n: harness.reader(n)(run) for n in (
        "prefill_step_ms", "queue_wait_ms", "kv_host_kib_per_token",
        "host_bound_idle_pct")}
    assert read["prefill_step_ms"] == pytest.approx(10.0)
    assert read["queue_wait_ms"] == pytest.approx(3.0)
    assert read["kv_host_kib_per_token"] == pytest.approx(40.0 / 100)
    # 5 s of steps, 4 s of them busy, over a 10 s window
    assert read["host_bound_idle_pct"] == pytest.approx(10.0)


def test_new_readers_read_nothing_from_a_program_without_spans():
    """The parent program: ``jit_f`` steps, no first-token event, and
    ``batch_step`` events without wall time or K/V bytes."""
    steps = [{"kind": "batch_step", "decode": 1, "chunks": 1, "tokens": 9}]
    run = _run(steps, [ev("jit_f(1)", 0, 8e6)], busy_s=4.0)
    for name in ("prefill_step_ms", "queue_wait_ms",
                 "kv_host_kib_per_token", "host_bound_idle_pct"):
        assert harness.reader(name)(run) is None, name
    untraced = dict(run, trace=None)
    for name in ("prefill_step_ms", "host_bound_idle_pct"):
        assert harness.reader(name)(untraced) is None, name


def test_span_report_on_a_tiny_cell():
    from bench import span_report
    out = span_report.report(tiny.spec(tiny.unshared()), 2**31 + 5, 2.0,
                             False, require_tpu=False)
    assert out["tokens_per_s"] > 0 and out["spans_dropped"] == 0
    names = [n for n, _ in out["self_time_top5"]]
    assert "bench.wait" in names or "unit.step" in names
    assert 0.0 <= out["longest_uncovered"]["length_s"] <= out["window_s"]
    assert out["metrics"]["queue_wait_ms"] >= 0.0
    qw = out["queue_wait"]
    assert qw["requests"] == len(qw["each"]) > 0
    assert sum(qw["mean_ms_by_cause"].values()) == \
        pytest.approx(qw["mean_ms"])
    assert out["metrics"]["kv_host_kib_per_token"] > 0.0
