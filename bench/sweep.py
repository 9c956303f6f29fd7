"""Find a cell's knee once, and fit its model of unloaded latency.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --scales 0.5,1,1.5,2

One process, one chip.  After the cell's set-up it serves requests of the
mix one at a time and fits ``a + b * prompt + c * answer`` seconds to their
latencies (the mix's ``latency_model``).  Then it offers the mix open-loop
at each scale of its rate for ``seconds`` and prints, per rate, what was
offered and what finished: the knee is the highest rate whose completions
keep up with its arrivals.  The benchmark's own runs never call this; the
rates in ``bench/traffic/*.json`` were set from its output.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402
from bench import traffic as TR  # noqa: E402

RATE_KEY = {"open": "rate_per_s", "sessions": "session_rate_per_s"}


def unloaded(driver, spec: dict, vocab: int, seed: int, n: int) -> dict:
    src = TR.OpenSource(dict(spec, rate_per_s=1.0, kind="open",
                             prompt=spec.get("prompt", {
                                 "median": 512, "sigma": 0.6, "lo": 64,
                                 "hi": spec["max_total"] // 2})),
                        vocab, n, seed)
    rows = []
    for it in src.items:
        it.slack = 1e6
        driver.open()
        rec = driver.submit(it)
        while rec["status"] == "unfinished":
            driver.step()
        rows.append((len(it.prompt), it.n_new, rec["done"]))
    a = np.array([[1.0, p, m] for p, m, _ in rows])
    y = np.array([t for _, _, t in rows])
    coef, *_ = np.linalg.lstsq(a, y, rcond=None)
    return {"a_s": float(coef[0]), "b_s_per_prompt_token": float(coef[1]),
            "c_s_per_answer_token": float(coef[2]),
            "residual_rms_s": float(np.sqrt(np.mean((a @ coef - y) ** 2))),
            "n": len(rows)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--scales", default="0.5,1,1.5,2")
    ap.add_argument("--fit", type=int, default=24)
    ap.add_argument("--drain", type=float, default=60.0)
    args = ap.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    spec = harness.cell_spec(args.workload)
    conf, traffic = spec["config"], spec["traffic"]
    harness._import_program()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.REPO / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import Telemetry
    from repro.serving.engine import TICKS_PER_SEC

    from bench import weights as W

    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    vocab = conf["model"]["vocab"]
    params = W.make_params(conf["model"], args.seed)
    tel = Telemetry(wall_clock=time.perf_counter)
    router = harness.build(conf, traffic, params, tel)
    driver = harness.Driver(router, vocab, TICKS_PER_SEC)
    harness.warm(driver, traffic, conf["serve"], vocab, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)

    fit = unloaded(driver, traffic, vocab, args.seed, args.fit)
    print(json.dumps({"latency_model": fit}), flush=True)
    traffic = dict(traffic, latency_model={k: fit[k] for k in (
        "a_s", "b_s_per_prompt_token", "c_s_per_answer_token")})
    key = RATE_KEY[traffic["kind"]]
    base = traffic[key]
    for s in (float(x) for x in args.scales.split(",")):
        mix = dict(traffic, **{key: base * s})
        src = TR.source(mix, vocab, args.seconds, args.seed + int(s * 100))
        driver.records.clear()
        driver.open()
        close = driver.loop(src, args.seconds, args.seconds)
        due = [r for r in driver.records if r["due"] < args.seconds]
        fin = [r for r in due if r["status"] == "done"
               and r["done"] <= close]
        lat = sorted(r["done"] - r["due"] for r in fin)
        row = {key: base * s, "sent": len(due), "finished": len(fin),
               "on_time": sum(r["done"] <= r["deadline"] for r in fin),
               "in_flight_at_close": sum(r["status"] == "unfinished"
                                         for r in due),
               "tokens_per_s": sum(r["item"].n_new for r in fin) / close,
               "latency_p50_s": harness.quantile(lat, 0.5) if lat else None,
               "latency_p95_s": harness.quantile(lat, 0.95) if lat else None}
        print(json.dumps(row), flush=True)
        driver.loop(src, args.seconds, args.seconds + args.drain, drain=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
