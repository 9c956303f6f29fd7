"""Where the host's time goes in one run of a benchmark cell, by the
program's own spans.

    python3 bench/span_report.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs the cell as ``bench/run.py`` does up to the window's close (no drain
and no correctness check) with the program's spans recorded in memory, the
harness's ``bench.*`` spans among them.  With ``--trace 1`` the recorder's
annotate hook is on (``jax.profiler.TraceAnnotation``): every nested span
also lands in the profiler's trace, which is then put on the device's
clock (``bench/spans.py``) so that each device idle gap is laid on the
innermost span open over it.  Prints one JSON line: tokens per second, the
new per-layer metrics as their readers read this run, the five spans with
the most time of their own in the window and the longest stretch no span
covers (the stall finder), the wait of each request sent in the window
laid on what ran meanwhile (``queue_wait``), and, traced, the clock offset
and the idle breakdown.  Without the TPU chips the cell asks for it exits 1.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402
from bench import spans as S  # noqa: E402
from bench import trace as T  # noqa: E402
from bench import traffic as TR  # noqa: E402

READERS = ("queue_wait_ms", "kv_host_kib_per_token", "host_bound_idle_pct",
           "prefill_step_ms", "decode_step_ms", "device_idle_pct")


def analyse_trace(tdir: str, mem_spans, run_: dict) -> dict:
    """The traced run's program spans on the device clock, and the idle
    gaps laid on them."""
    tr = run_["trace"]
    events = T.load(T.find_xplane(tdir))
    host = S.program_spans(events)
    window = [(s, t) for n, s, t in host if n == "bench.window"]
    offset = S.clock_offset_ns(host, T.device_modules(events))
    out = {"clock_offset_ms": None if offset is None else offset * 1e-6,
           "spans_in_trace": len(host),
           "spans_in_memory": len(mem_spans)}
    by_program: dict = {}
    for m in tr["modules"]:
        name = S.program_name(m.name)
        by_program[name] = by_program.get(name, 0.0) + m.dur_ns * 1e-9
    out["device_s_by_program"] = sorted(by_program.items(),
                                        key=lambda kv: -kv[1])[:8]
    if offset is None or not window:
        return out
    dev = S.to_device_clock(host, offset)
    gaps = tr["gaps"]
    by_span = S.attribute_innermost(gaps, dev)
    below = sum(v for k, v in by_span.items()
                if k in S.PROGRAM_SPANS and k != "router.step")
    above = sum(by_span.get(k, 0.0) for k in
                ("router.step", "bench.step", "bench.submit"))
    in_steps = S.attribute_innermost(
        gaps, [s for s in dev if s[0] == "unit.step"]).get("unit.step", 0.0)
    out.update(
        idle_s=sum(t - s for s, t in gaps) * 1e-9,
        idle_by_harness_span=T.attribute_gaps(
            gaps, {n: T.spans(events, n) for n in
                   ("bench.step", "bench.submit", "bench.wait")}),
        idle_by_innermost_span=dict(sorted(by_span.items(),
                                           key=lambda kv: -kv[1])),
        idle_below_router_step_s=below,
        idle_on_router_step_and_harness_s=above,
        below_router_step_pct=100.0 * below / (below + above)
        if below + above > 0 else None,
        host_bound_idle_pct_aligned=100.0 * in_steps / tr["window_s"])
    return out


def waits_by_cause(spans, lo: float, hi: float) -> dict:
    """The ``request.queued`` spans of the requests sent in [lo, hi), each
    laid on the spans open meanwhile (``bench/spans.py`` ``wait_causes``):
    their mean and the mean by cause, in ms, and per request its wait and
    its two largest causes."""
    waits = [(s["start"], s["end"]) for s in spans
             if s["name"] == "request.queued" and lo <= s["start"] < hi]
    if not waits:
        return {"requests": 0}
    ns = [(s["name"], s["start"] * 1e9, s["end"] * 1e9) for s in spans
          if not s.get("keyed")]

    def causes(ws):
        return S.wait_causes([(a * 1e9, b * 1e9) for a, b in ws], ns)

    n = len(waits)
    each = []
    for w in waits:
        top = sorted(causes([w]).items(), key=lambda kv: -kv[1])[:2]
        each.append([round(1e3 * (w[1] - w[0]), 3),
                     {k: round(1e3 * v, 3) for k, v in top}])
    return {"requests": n,
            "mean_ms": 1e3 * sum(b - a for a, b in waits) / n,
            "mean_ms_by_cause": {k: 1e3 * v / n for k, v in sorted(
                causes(waits).items(), key=lambda kv: -kv[1])},
            "each": each}


def report(spec: dict, seed: int, seconds: float, trace: bool,
           require_tpu: bool = True, t_start: float | None = None):
    t_start = time.perf_counter() if t_start is None else t_start
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.REPO
                                                  / ".jax_cache")
    import jax

    chips = spec["cell"]["chips"]
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"span_report: needs {chips} TPU chip(s)", file=sys.stderr)
        return None
    harness._import_program()
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import Telemetry
    from repro.serving.engine import TICKS_PER_SEC

    from bench import weights as W

    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf, traffic = spec["config"], spec["traffic"]
    vocab = conf["model"]["vocab"]
    params = W.make_params(conf["model"], seed)
    jax.block_until_ready(params)
    tel = Telemetry(wall_clock=time.perf_counter,
                    annotate=jax.profiler.TraceAnnotation if trace else None)
    router = harness.build(conf, traffic, params, tel)
    # the harness's spans go through the recorder too: in memory, and in
    # the profiler's trace when the hook is on
    driver = harness.Driver(router, vocab, TICKS_PER_SEC, annotate=tel.span)
    harness.warm(driver, traffic, conf["serve"], vocab, seed)
    source = TR.source(traffic, vocab, seconds, seed)
    s0 = harness._window_stats(router)
    n_ev0 = len(tel.events)
    setup_s = time.perf_counter() - t_start
    tdir = tempfile.mkdtemp(prefix="span_report_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        driver.open()
        with driver.annotate("bench.window"):
            close = driver.loop(source, seconds, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    lo, hi = driver.t0, driver.t0 + close
    run_ = {"seconds": close, "s0": s0, "s1": harness._window_stats(router),
            "events": [e for e in tel.events[n_ev0:]
                       if lo <= e.get("wall", -1.0) <= hi],
            "records": driver.records, "model": conf["model"],
            "peaks": None, "trace": None}
    mem = [(s["name"], s["start"], s["end"]) for s in tel.spans
           if not s.get("keyed") and s["end"] > lo and s["start"] < hi]
    own = S.self_time(mem, lo, hi)
    queue_wait = waits_by_cause(tel.spans, lo, hi)
    gap_at, gap_s = S.longest_uncovered(mem, lo, hi)
    steps = [e["step_s"] for e in run_["events"]
             if e["kind"] == "batch_step" and "step_s" in e]
    out = {"setup_s": setup_s, "window_s": close,
           "unit_steps": len(steps),
           "unit_step_ms_mean": 1e3 * sum(steps) / max(len(steps), 1),
           "tokens_per_s": sum(e["decode"] + e["chunks"]
                               for e in run_["events"]
                               if e["kind"] == "batch_step") / close,
           "self_time_top5": sorted(own.items(), key=lambda kv: -kv[1])[:5],
           "longest_uncovered": {"offset_s": gap_at - lo, "length_s": gap_s},
           "queue_wait": queue_wait,
           "spans_dropped": tel.spans_dropped}
    if trace:
        run_["trace"] = harness.reduce_trace(tdir)
        out.update(analyse_trace(tdir, mem, run_))
        shutil.rmtree(tdir, ignore_errors=True)
    out["metrics"] = {}
    for name in READERS:
        val = harness.reader(name)(run_)
        if val is not None:
            out["metrics"][name] = val
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = report(harness.cell_spec(args.workload), args.seed, args.seconds,
                 bool(args.trace), t_start=T_START)
    if out is None:
        return 1
    if "clock_offset_ms" in out:
        print(f"clock_offset_ms={out['clock_offset_ms']!r}", file=sys.stderr)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "trace": args.trace, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
