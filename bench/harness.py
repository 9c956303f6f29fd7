"""The benchmark harness: one cell, one seed, one measured window.

``run`` builds the served system the way ``repro.launch.serve.run`` does
(``Router`` -> one engine plane -> one unit, step batching on, elasticity
off), warms every executable the cell's traffic can reach, then drives the
front door open-loop on the wall clock for ``seconds`` and reads the
metrics the cell names.  Afterwards it frees the program and compares a
sample of the served tokens with the float32 reference.

Only public surfaces of the program are read: ``Router.submit``,
``Router.step``, ``Router.next_event_time``, ``Router.collect_stats``, the
``Request`` fields and the ``obs.Telemetry`` events.  Deadlines go to the
engine in its own unit (``TICKS_PER_SEC``) and are judged on the wall
clock; the engine's modelled clock is never read as a time.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

from . import traffic as TR

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
DRAIN_S = 60.0          # how long a run serves past the close, at most


def _import_program():
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_benchmark() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def model_block(conf: dict) -> dict:
    """A configuration file's published (Hugging Face) keys, in the names
    the served model and the benchmark's own code use."""
    return {"name": conf["name"], "family": "dense",
            "n_layers": conf["num_hidden_layers"],
            "d_model": conf["hidden_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["head_dim"], "d_ff": conf["intermediate_size"],
            "vocab": conf["vocab_size"], "rope_theta": conf["rope_theta"],
            "norm_eps": conf["rms_norm_eps"],
            "tie_embeddings": conf["tie_word_embeddings"]}


def cell_spec(name: str, bench: dict | None = None) -> dict:
    """Everything the harness needs for one cell, found by name."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    conf = json.loads((REPO / entry["file"]).read_text())
    conf["model"] = model_block(conf)
    return {"cell": w, "config": conf,
            "traffic": TR.load(w["traffic"]),
            "end_to_end": [m for m in bench["end_to_end"]
                           if _applies(m, name)],
            "per_layer": [m for m in bench["per_layer"] if _applies(m, name)]}


def reader(name: str):
    """A metric's reader, ``bench/metrics/<name>.py``: ``read(run)`` returns
    the value, or None where the run holds nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def quantile(values, q: float) -> float:
    """Nearest-rank quantile."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


# ---------------------------------------------------------------------------
# building the served system
# ---------------------------------------------------------------------------

def model_config(conf: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(**conf["model"], remat=False)


def step_budget(serve: dict, traffic: dict) -> int:
    """A step token budget under which every prompt is prefilled in one
    chunk, whatever else joins the step: room for a full batch of the
    longest prompts plus one decode per sequence."""
    longest = max(c + p for c, p in TR.chunk_shapes(
        traffic, serve["prefix_max_prompt"]))
    return serve["max_batch"] * (longest + 1)


def build(conf: dict, traffic: dict, params, tel):
    from repro.core.pruning import PruningConfig
    from repro.serving.autoscale import ElasticityConfig
    from repro.serving.batching import StepBatchingConfig
    from repro.serving.cluster import Router, make_engine_planes
    from repro.serving.engine import EngineConfig

    sv, eng = conf["serve"], traffic["engine"]
    ecfg = EngineConfig(
        n_units=1, heuristic=eng["heuristic"], merging=eng["merging"],
        pruning=PruningConfig(**eng["pruning"]) if eng["pruning"] else None,
        elasticity=ElasticityConfig(policy="queue", max_extra=0),
        batching=StepBatchingConfig(max_batch=sv["max_batch"],
                                    step_token_budget=step_budget(sv,
                                                                  traffic)),
        max_len=sv["max_len"], batch_buckets=tuple(sv["batch_buckets"]),
        kv_block_size=sv["kv_block_size"],
        kv_cache_blocks=sv["kv_cache_blocks"],
        prefix_max_prompt=sv["prefix_max_prompt"])
    planes = make_engine_planes(model_config(conf), params, ecfg, 1)
    return Router(planes, policy="least-loaded", telemetry=tel)


# ---------------------------------------------------------------------------
# the open-loop driver
# ---------------------------------------------------------------------------

class Driver:
    """Sends requests at their due wall times through ``Router.submit`` and
    advances the plane one event at a time.

    The engine's clock is virtual.  An arrival is stamped at the later of
    the engine's last event and the wall clock mapped into ticks, but never
    past the next pending event, so that ``submit`` never runs a backlog of
    events on its own; its deadline is the wall-clock slack left, in ticks.
    Completions are stamped on the wall clock as soon as a request's tokens
    or status show them.
    """

    def __init__(self, router, vocab: int, tps: float, annotate=None):
        self.router, self.vocab, self.tps = router, vocab, tps
        self.v = 0.0
        self.v_base = 0.0
        self.t0 = time.perf_counter()
        self.inflight: list[dict] = []
        self.records: list[dict] = []
        self.annotate = annotate or (lambda name: nullcontext())

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def open(self) -> None:
        self.t0 = time.perf_counter()
        self.v_base = self.v

    def submit(self, item: TR.Item, source=None) -> dict:
        from repro.serving.engine import Request

        now = self.now()
        te = self.router.next_event_time()
        w = self.v_base + now * self.tps
        v = max(self.v, min(w, te if te is not None else math.inf))
        left = item.due + item.slack - now
        req = Request(prompt=tuple(int(x) for x in item.prompt),
                      n_new=int(item.n_new), deadline=v + left * self.tps)
        with self.annotate("bench.submit"):
            self.router.submit(req, v)
        rec = {"item": item, "req": req, "due": item.due, "sent": now,
               "deadline": item.due + item.slack, "done": None,
               "status": "unfinished", "ok": False, "source": source}
        self.inflight.append(rec)
        self.records.append(rec)
        return rec

    def step(self) -> bool:
        te = self.router.next_event_time()
        if te is None:
            return False
        with self.annotate("bench.step"):
            self.router.step(float(np.nextafter(te, math.inf)))
        self.v = max(self.v, te)
        self.poll()
        return True

    def poll(self) -> None:
        now, keep = self.now(), []
        for rec in self.inflight:
            r = rec["req"]
            if r.status == "dropped":
                rec.update(done=now, status="dropped")
            elif r.status == "done" or len(r.tokens) >= r.n_new:
                rec.update(done=now, status="done",
                           ok=(len(r.tokens) == r.n_new and all(
                               0 <= t < self.vocab for t in r.tokens)))
            else:
                keep.append(rec)
                continue
            if rec["source"] is not None:
                rec["source"].on_finish(rec["item"], now, rec["ok"])
        self.inflight = keep

    def quiesce(self) -> None:
        while self.step():
            pass

    def loop(self, source, due_before: float, stop: float,
             drain: bool = False) -> float:
        """Send what falls due before ``due_before`` and serve until
        ``stop`` seconds after ``open``; with ``drain``, return as soon as
        every request sent has finished.  Returns the time it stopped."""
        while True:
            now = self.now()
            nxt = source.next_due()
            while nxt is not None and nxt <= now and nxt < due_before:
                item = source.pop()
                if item is not None:
                    self.submit(item, source)
                nxt = source.next_due()
            if now >= stop or (drain and not self.inflight and (
                    nxt is None or nxt >= due_before)):
                return now
            if not self.step():
                wake = min(nxt if nxt is not None and nxt < due_before
                           else stop, stop)
                with self.annotate("bench.wait"):
                    time.sleep(max(0.0, min(wake - self.now(), 0.05)))


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------

def warm_items(traffic: dict, serve: dict, vocab: int, rng):
    """Phases of one-token requests that make the engine run every
    (chunk, prefix) prefill the mix can reach, then a batch whose decodes
    pass through every batch bucket."""
    shapes = TR.chunk_shapes(traffic, serve["prefix_max_prompt"])

    def toks(n):
        return rng.integers(1, vocab, size=n, dtype=np.int64)

    def item(prompt, n_new=1):
        return TR.Item(0.0, prompt, n_new, 1e6)

    bases = {p: toks(p) for p in sorted({p for _, p in shapes if p})}
    cold = sorted({c for c, p in shapes if p == 0} - set(bases))
    first = [item(b) for b in bases.values()] + [item(toks(c)) for c in cold]
    second = [item(np.concatenate([bases[p], toks(c)]))
              for c, p in shapes if p]
    short = min(c for c, p in shapes if p == 0)
    decode = [item(toks(short), n_new=2 + i)
              for i in range(serve["max_batch"])]
    return [first, second, decode]


def warm(driver: Driver, traffic: dict, serve: dict, vocab: int,
         seed: int) -> int:
    rng = np.random.default_rng([seed, 99])
    n = 0
    for phase in warm_items(traffic, serve, vocab, rng):
        for it in phase:
            driver.submit(it)
        driver.quiesce()
        n += len(phase)
    bad = [r for r in driver.records if not r["ok"]]
    if bad:
        raise RuntimeError(f"{len(bad)} warm-up requests did not finish")
    driver.records.clear()
    return n


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def shares_cached_prefix(rec: dict, records, block: int,
                         max_prompt: int) -> bool:
    """Whether the request's prompt begins with a block that a request
    finished before it was sent had in its prompt, both within the prefix
    cache's prompt limit: a prefill the cache most likely seeded."""
    p = rec["item"].prompt
    if len(p) > max_prompt:
        return False
    return any(o is not rec and o["done"] is not None
               and o["done"] <= rec["sent"]
               and len(o["item"].prompt) <= max_prompt
               and np.array_equal(o["item"].prompt[:block], p[:block])
               for o in records)


def sample_for_check(records, n: int, seed: int) -> list[dict]:
    """``n`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in records if r["status"] == "done" and r["ok"]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["item"].prompt)
                  + r["item"].n_new)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 5])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def _verdict(checks: dict) -> dict:
    return {"correct": all(c["value"] <= c["limit"]
                           for c in checks.values()), "checks": checks}


def check(conf: dict, traffic: dict, records, seed: int,
          control: bool = False) -> dict:
    """Numbers compared, each with its limit; ``correct`` when every one
    is within it.  With ``control`` the float8 control is judged too, in
    the program's place over the same sample and by the same limits
    (``control``), for setting the limit; the benchmark's runs never do."""
    from . import reference

    lim, sv = traffic["check"], conf["serve"]
    finished = [r for r in records if r["status"] == "done"]
    bad = sum(not r["ok"] for r in finished)
    samples = sample_for_check(records, lim["samples"], seed)
    checks = {"bad_answers": {"value": bad, "limit": 0}}
    info = {"tokens_compared": 0}
    ctrl = None
    if samples:
        res = reference.served_gaps(
            conf["model"], seed,
            [(r["item"].prompt, list(r["req"].tokens)) for r in samples],
            pad_to=sv["max_len"], fp8_control=control)
        checks["max_logit_gap"] = {"value": res["max_gap"],
                                   "limit": lim["max_logit_gap"]}
        info = {k: v for k, v in res.items()
                if k not in ("max_gap", "control_max_gap")}
        info["sampled"] = len(samples)
        info["sampled_cache_seeded"] = sum(
            shares_cached_prefix(r, records, sv["kv_block_size"],
                                 sv["prefix_max_prompt"]) for r in samples)
        if control:
            ctrl = _verdict({"max_logit_gap": {
                "value": res["control_max_gap"],
                "limit": lim["max_logit_gap"]}})
    else:
        checks["no_request_finished"] = {"value": 1, "limit": 0}
    out = {**_verdict(checks), "info": info}
    if control:
        out["control"] = ctrl or {"correct": False, "checks": {}}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def _window_stats(router) -> dict:
    s = router.collect_stats()
    return {k: s.get(k, 0) for k in (
        "mapping_wall_s", "prefix_tokens_reused", "prefill_tokens")}


def reduce_trace(trace_dir: str) -> dict:
    from . import trace as T

    events = T.load(T.find_xplane(trace_dir))
    ops = T.device_ops(events)
    span = T.spans(events, "bench.window")
    if span:
        lo, hi = span[0]
    else:
        lo = min(e.start_ns for e in ops)
        hi = max(e.start_ns + e.dur_ns for e in ops)
    devs = T.devices(events)
    busy = [T.busy_ns(T.device_ops(events, d), lo, hi) for d in devs]
    ops = T.in_window(ops, lo, hi)
    modules = T.in_window(T.device_modules(events), lo, hi)
    gaps = T.idle_gaps(T.device_ops(events, devs[0]), lo, hi) if devs else []
    host = {n: T.spans(events, n)
            for n in ("bench.step", "bench.submit", "bench.wait")}
    return {"ops": ops, "modules": modules,
            "busy_s": sum(busy) / max(len(busy), 1) * 1e-9,
            "window_s": (hi - lo) * 1e-9, "gaps": gaps,
            "gap_by_host": T.attribute_gaps(gaps, host)}


def run(spec: dict, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, t_start: float | None = None,
        log=print, control: bool = False) -> dict | None:
    """Run one cell once; returns the result object, or None (after saying
    why on stderr) when the chips the cell asks for are not there."""
    t_start = time.perf_counter() if t_start is None else t_start
    # the compile cache lives inside the checkout, whatever the environment
    # names: the program's ``enable_compile_cache`` takes it from here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    import jax

    chips = spec["cell"]["chips"]
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"bench: needs {chips} TPU chip(s); JAX finds "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return None
    _import_program()
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import Telemetry
    from repro.serving.engine import TICKS_PER_SEC

    from . import weights as W
    from .compile_counter import CompileCounter
    from .peaks import peaks

    cache_dir = enable_compile_cache()
    # JAX read the variable when it was first imported, which may have been
    # before it was set here
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    conf, traffic = spec["config"], spec["traffic"]
    vocab = conf["model"]["vocab"]
    pk = peaks(devs[0].device_kind) if require_tpu else None

    params = W.make_params(conf["model"], seed)
    jax.block_until_ready(params)
    tel = Telemetry(wall_clock=time.perf_counter)
    router = build(conf, traffic, params, tel)
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: nullcontext()))
    driver = Driver(router, vocab, TICKS_PER_SEC, annotate=annotate)
    n_warm = warm(driver, traffic, conf["serve"], vocab, seed)
    source = TR.source(traffic, vocab, seconds, seed)
    compiles0 = counter.mark()
    s0 = _window_stats(router)
    n_ev0 = len(tel.events)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f} warm_requests={n_warm} cache={cache_dir} "
        f"compiles={compiles0[0]} compile_s={compiles0[1]:.3f} "
        f"cache_hits={compiles0[2]}", file=sys.stderr)

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
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
    compiles1 = counter.mark()
    s1 = _window_stats(router)
    driver.loop(source, seconds, seconds + DRAIN_S, drain=True)
    wall_open = driver.t0
    wall_close = wall_open + close
    events = [e for e in tel.events[n_ev0:]
              if wall_open <= e.get("wall", -1.0) <= wall_close]
    device = _device_info(jax, chips)
    records = driver.records
    made = sum(e["decode"] + e["chunks"] for e in tel.events[n_ev0:]
               if e["kind"] == "batch_step")
    served = sum(r["item"].n_new for r in records if r["status"] == "done")

    run_ = {"seconds": close, "setup_s": setup_s, "records": records,
            "s0": s0, "s1": s1, "events": events, "model": conf["model"],
            "compiles": compiles1[0] - compiles0[0], "peaks": pk,
            "trace": None}
    router = driver = source = None
    del params
    gc.collect()

    if tdir is not None:
        run_["trace"] = reduce_trace(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = run_["trace"]["busy_s"]
        device["window_s"] = run_["trace"]["window_s"]

    metrics = {}
    for m in (spec["per_layer"] if trace else spec["end_to_end"]):
        val = reader(m["name"])(run_)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    due_in = [r for r in records if r["due"] < seconds]
    late = [r["sent"] - r["due"] for r in due_in]
    fin = [r for r in due_in if r["status"] == "done" and r["ok"]
           and r["done"] <= close]
    lat = [r["done"] - r["due"] for r in due_in if r["status"] == "done"]
    log(f"window_s={close:.3f} sent={len(due_in)} finished_in_window="
        f"{len(fin)} dropped={sum(r['status'] == 'dropped' for r in due_in)} "
        f"unfinished={sum(r['status'] == 'unfinished' for r in due_in)} "
        f"latency_p50_s={quantile(lat, 0.5) if lat else None} "
        f"generator_late_p50_s={quantile(late, 0.5) if late else None} "
        f"generator_late_max_s={max(late) if late else None} "
        f"window_compiles={run_['compiles']} "
        f"tokens_made_after_open={made} tokens_served_after_open={served} "
        f"peak_bytes_in_use={device['memory_peak_bytes']}", file=sys.stderr)

    t_ref = time.perf_counter()
    verdict = check(conf, traffic, records, seed, control=control)
    verdict["info"]["check_s"] = time.perf_counter() - t_ref
    for k, c in verdict["checks"].items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}",
            file=sys.stderr)
    out = {"correct": verdict["correct"], "attempted": len(due_in),
           "failed": sum(r["status"] != "done" or not r["ok"]
                         for r in due_in),
           "metrics": metrics, "device": device}
    if run_["trace"] is not None:
        tr = run_["trace"]
        from .trace import seconds_by_name
        top = sorted(seconds_by_name(tr["ops"]).items(),
                     key=lambda kv: -kv[1])[:10]
        gaps = sorted(tr["gap_by_host"].items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [[n, s] for n, s in top],
                            "idle_gaps": [[n, s] for n, s in gaps]}
    out["info"] = verdict["info"]
    if control:
        out["control"] = verdict["control"]
    out["checks"] = verdict["checks"]
    return out
