"""Subprocess smokes for the public CLIs (train / serve / dryrun --help)."""

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
ENV = dict(os.environ, PYTHONPATH=SRC)
# the CPU suite serves the toy-width model
SERVE = ["repro.launch.serve", "--reduced"]


def _run(args, timeout=900, env=ENV):
    out = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    return out.stdout


def test_train_cli_reduced(tmp_path):
    out = _run(["repro.launch.train", "--arch", "smollm-360m", "--reduced",
                "--steps", "6", "--batch", "2", "--seq", "64",
                "--ckpt-dir", str(tmp_path)])
    assert "finished at step 6" in out
    # resume: same command continues (and is a no-op at the target step)
    out2 = _run(["repro.launch.train", "--arch", "smollm-360m", "--reduced",
                 "--steps", "6", "--batch", "2", "--seq", "64",
                 "--ckpt-dir", str(tmp_path)])
    assert "finished at step 6" in out2


def test_serve_cli(tmp_path):
    out = _run(SERVE + ["--requests", "12", "--units", "1",
                        "--merging", "adaptive", "--pruning", "--rate", "0.5"])
    assert '"completed"' in out


def test_serve_cli_batching():
    out = _run(SERVE + ["--requests", "10", "--units", "1",
                        "--rate", "0.5", "--max-batch", "4",
                        "--step-token-budget", "32"])
    # the batching knobs are echoed back in the JSON summary
    assert '"max_batch": 4' in out and '"step_token_budget": 32' in out
    assert '"completed"' in out


def test_serve_cli_autoscale():
    out = _run(SERVE + ["--requests", "10", "--units", "1",
                        "--rate", "0.5", "--autoscale", "success-chance",
                        "--max-extra-units", "1"])
    # the autoscale decision counters ride in the JSON summary
    assert '"scale_ups"' in out and '"machine_seconds"' in out
    assert '"warmup_ticks"' in out


def test_serve_cli_fleet():
    out = _run(SERVE + ["--requests", "8", "--rate", "0.5",
                        "--fleet", "tpu:1:1.0:1.0,cpu:1:0.5:0.25",
                        "--heuristic", "MCMD", "--max-extra-units", "0"])
    # the fleet spec and the per-mtype cost counters ride in the summary
    assert '"fleet": "tpu:1:1:1:auto:4,cpu:1:0.5:0.25:auto:4"' in out
    assert '"cost"' in out and '"pool_cost"' in out


def test_serve_cli_multiplane():
    out = _run(SERVE + ["--requests", "10", "--units", "1", "--planes", "2",
                        "--router", "affinity", "--rate", "0.5"])
    assert '"completed"' in out
    # per-plane stats + routing counters ride in the JSON summary
    assert '"planes"' in out and '"router"' in out
    assert '"deadlock_breaks"' in out


def test_serve_cli_telemetry_out(tmp_path):
    """--trace-out/--metrics-out/--events-out artifacts validate, and the
    JSON summary carries the consolidated ``telemetry`` key while the
    legacy top-level counters stay (back-compat, kept for one release)."""
    from repro.obs import (SCHEMA_VERSION, validate_chrome_trace,
                           validate_metrics_snapshot)

    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.json"
    events = tmp_path / "events.jsonl"
    out = _run(SERVE + ["--requests", "10", "--units", "1",
                        "--merging", "adaptive", "--pruning", "--rate", "0.5",
                        "--trace-out", str(trace),
                        "--metrics-out", str(metrics),
                        "--events-out", str(events)])
    stats = json.loads(out)
    tel = stats["telemetry"]
    assert tel["schema"] == SCHEMA_VERSION
    # every consolidated counter mirrors its legacy top-level twin
    for k, v in tel["counters"].items():
        assert stats.get(k, 0) == v, k
    assert tel["wall"]["mapping_wall_s"] == stats["mapping_wall_s"]
    assert tel["wall"]["pruning_wall_s"] == stats["pruning_wall_s"]
    validate_metrics_snapshot(tel["metrics"])
    # the emitted artifacts exist and pass the schema checks
    validate_chrome_trace(json.loads(trace.read_text()))
    validate_metrics_snapshot(json.loads(metrics.read_text()))
    ev = [json.loads(line) for line in events.read_text().splitlines()]
    assert ev and all("t" in e and "kind" in e for e in ev)


def test_serve_cli_closed_loop():
    """--workload closed_loop drives the cluster with multi-turn sessions;
    the JSON summary carries per-turn and per-tenant counters and the
    consolidated telemetry validates against the current schema."""
    from repro.obs import validate_telemetry_summary

    out = _run(SERVE + ["--workload", "closed_loop:6:2",
                        "--turns", "3", "--tenants", "gold:1:0.5:1,free:3",
                        "--units", "1", "--rate", "0.5"])
    stats = json.loads(out)
    wl = stats["workload"]
    assert wl["mode"] == "closed_loop"
    assert wl["sessions_done"] == 6
    turns = wl["per_turn"]
    assert [r["turn"] for r in turns] == [0, 1, 2]
    assert all(r["submitted"] == 6 for r in turns)
    assert sum(r["completed"] for r in turns) == stats["completed"]
    tenants = wl["tenants"]
    assert set(tenants) == {"gold", "free"}
    assert sum(t["submitted"] for t in tenants.values()) == 18
    for t in tenants.values():
        assert 0.0 <= t["on_time_rate"] <= 1.0
    # the same summary rides inside telemetry and passes the schema check
    assert stats["telemetry"]["workload"] == wl
    validate_telemetry_summary(stats["telemetry"])
    # tenant labels reach the exported metrics
    counters = stats["telemetry"]["metrics"]["counters"]
    assert any(k.startswith("tenant_completed{") for k in counters)


def test_serve_smse_example_trace_out(tmp_path):
    """Acceptance run: one serve_smse invocation with --trace-out yields a
    Perfetto-loadable Chrome trace (one track per machine, lifecycle spans,
    drop/defer attribution) and a quantile-bearing metrics snapshot."""
    from repro.obs import validate_chrome_trace, validate_metrics_snapshot

    trace_p = tmp_path / "trace.json"
    metrics_p = tmp_path / "metrics.json"
    script = os.path.join(ROOT, "examples", "serve_smse.py")
    out = subprocess.run(
        [sys.executable, script, "--requests", "16", "--planes", "1",
         "--trace-out", str(trace_p), "--metrics-out", str(metrics_p)],
        capture_output=True, text=True, env=ENV, timeout=900, cwd=ROOT)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])

    trace = json.loads(trace_p.read_text())
    validate_chrome_trace(trace)
    evs = trace["traceEvents"]
    machine_tracks = {e["args"]["name"] for e in evs
                      if e["ph"] == "M" and e["name"] == "thread_name"
                      and e["args"]["name"].startswith("machine")}
    assert machine_tracks                       # one track per machine used
    assert [e for e in evs if e["ph"] == "X"]   # execution spans
    opens = sorted(e["id"] for e in evs if e["ph"] == "b")
    closes = sorted(e["id"] for e in evs if e["ph"] == "e")
    assert opens and opens == closes            # every lifecycle span closes

    snap = json.loads(metrics_p.read_text())
    validate_metrics_snapshot(snap)
    for name in ("latency", "queue_wait", "slack"):
        h = snap["histograms"][name]
        assert h["count"] > 0
        assert h["p50"] <= h["p95"] <= h["p99"]
    assert snap["gauges"]["pruning_wall_s"] >= 0.0
    if snap["counters"].get("merges{level=\"task\"}", 0):
        assert snap["histograms"]["merge_saving"]["count"] > 0


def test_dryrun_cli_tiny_decode():
    env = dict(ENV, DRYRUN_DEVICES="8", DRYRUN_MESH="4,2")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-125m",
         "--shape", "decode_32k"],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "roofline" in out.stdout


def test_serve_config_widths():
    """The serve entry point runs the published widths unless --reduced."""
    from repro.configs.registry import get_arch
    from repro.launch.serve import parse_args, serve_config

    full = serve_config(parse_args(["--arch", "smollm-360m"]))
    published = get_arch("smollm-360m")
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.d_ff, full.vocab) == (published.n_layers, published.d_model,
                                       published.n_heads, published.n_kv_heads,
                                       published.d_ff, published.vocab)
    assert not full.remat
    toy = serve_config(parse_args(["--arch", "smollm-360m", "--reduced"]))
    assert toy.n_layers == 2 and toy.d_model < published.d_model
    args = parse_args(["--max-len", "2048"])
    assert args.max_len == 2048 and parse_args([]).max_len == 64


def test_compile_cache_dir(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache goes to a fixed, git-ignored directory inside the checkout."""
    import jax
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
