#!/usr/bin/env python3
"""Bring-up smoke run on one TPU chip: SmolLM-360M served at full width.

    python chip_smoke.py

One process, one chip.  It fails (exit 1, no result line) when JAX finds
no TPU, and when any phase raises or any check fails:

1. serve   — ``repro.launch.serve.run`` builds the cluster at the model's
             published widths (32 layers, d_model 960, 15/5 heads of 64,
             seeded random weights) with one unit, step batching over 8
             sequences and a 2048-token arena, and serves 8 greedy
             requests of 16 new tokens in four waves.  Three requests share
             a 64-token prefix with the first, so the prefix cache is hit;
             every prompt fits one prefill chunk.  Checks: all requests
             complete with 16 in-vocabulary tokens, and the cache was hit.
2. decode  — one ``paged_decode_fn`` step over a seeded random arena with
             ragged contexts up to 2047 tokens, once through the Pallas
             kernel (its compiled program must hold a ``tpu_custom_call``)
             and once through the jnp oracle; the logits must agree within
             ``LOGIT_TOL``.
3. pmf     — ``batched_success`` (the ``pmf_conv`` kernel, compiled
             natively) against ``core.pmf.chance_of_success``.

Earlier lines name what they report; the last line is one JSON object
with ``ok`` and the device as JAX reports it.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SERVE_ARGV = ["--arch", "smollm-360m", "--units", "1",
              "--max-extra-units", "0", "--max-batch", "8",
              "--step-token-budget", "256", "--max-len", "2048"]
N_NEW = 16
# Kernel vs oracle, both over the same bf16 arena and weights, compared by
# the relative L2 distance of their logits.  They differ only in where
# attention rounds: the oracle rounds scores and probabilities to bf16
# (relative step 2**-8) and the kernel keeps them in f32.  With random
# weights each layer amplifies that difference: the interpreted kernel
# against the oracle at these shapes measured 1.7% after 4 layers, 2.4%
# after 8 and 5.0% after 32 (CPU).  The bound leaves twice that; a wrong
# page, slot, mask or head mapping gives unrelated logits, about 140%.
LOGIT_TOL = 0.10
PMF_TOL = 1e-5          # f32 convolution vs the float64 NumPy reference


class CompileCounter:
    """Counts XLA compiles and their seconds through JAX's monitoring
    events; executables loaded from the persistent cache count as hits."""

    def __init__(self):
        import jax

        self.compiles, self.seconds, self.hits = 0, 0.0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return self.compiles, self.seconds, self.hits

    def since(self, mark: tuple) -> str:
        c, s, h = mark
        return (f"compiles={self.compiles - c} "
                f"compile_s={self.seconds - s:.3f} cache_hits={self.hits - h}")


def smoke_trace(vocab: int, seed: int = 0):
    """Eight greedy requests in four waves, 1000 ticks apart: A0; A1-A3
    (A0's 64-token prefix, new 64-token tails); B0-B1; B2-B3 (120 fresh
    tokens each).  A wave's prompts fit one 256-token step together."""
    import numpy as np
    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)

    def toks(n):
        return tuple(int(x) for x in rng.integers(1, vocab, size=n))

    prefix = toks(64)
    waves = [[prefix + toks(64)],
             [prefix + toks(64) for _ in range(3)],
             [toks(120) for _ in range(2)],
             [toks(120) for _ in range(2)]]
    return [(1000.0 * w, Request(prompt=p, n_new=N_NEW, deadline=1e9))
            for w, wave in enumerate(waves) for p in wave]


def serve_phase(argv) -> dict:
    from repro.launch import serve

    cfg = serve.serve_config(serve.parse_args(argv))
    trace = smoke_trace(cfg.vocab)
    t0 = time.perf_counter()
    stats = serve.run(argv, trace=trace)
    wall = time.perf_counter() - t0
    reqs = [r for _, r in trace]
    done = sum(r.status == "done" and len(r.tokens) == N_NEW
               and all(0 <= t < cfg.vocab for t in r.tokens) for r in reqs)
    return {"wall_s": wall, "requests": len(reqs), "completed_ok": done,
            "completed": stats["completed"],
            "prefix_hits": stats.get("prefix_hits", 0),
            "prefix_tokens_reused": stats.get("prefix_tokens_reused", 0),
            "ok": done == len(reqs) and stats.get("prefix_hits", 0) > 0}


def decode_phase(cfg, batch: int = 8, page_size: int = 16,
                 max_len: int = 2048, seed: int = 0) -> dict:
    """One batched paged-decode step through the kernel and the oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T

    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    mp = max_len // page_size
    n_pages = batch * mp + 1
    rng = np.random.default_rng(seed)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed + 1))
    shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size,
             cfg.resolved_head_dim)
    kp = jax.random.normal(kk, shape, jnp.bfloat16)
    vp = jax.random.normal(kv, shape, jnp.bfloat16)
    tables = jnp.asarray(
        (rng.permutation(n_pages - 1) + 1)[:batch * mp].reshape(batch, mp),
        jnp.int32)
    # ragged contexts from one token to the arena's last slot
    lens = jnp.asarray(np.linspace(1, max_len - 1, batch).astype(np.int32))
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=batch), jnp.int32)
    args = (params, kp, vp, tables, lens, toks)

    kernel = jax.jit(T.paged_decode_fn(cfg)).lower(*args).compile()
    has_kernel = "tpu_custom_call" in kernel.as_text()
    got = kernel(*args)[0]
    want = jax.jit(T.paged_decode_fn(cfg, use_kernel=False))(*args)[0]
    got, want = np.asarray(got), np.asarray(want)
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    rel_l2 = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    finite = bool(np.isfinite(got).all())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    return {"tpu_custom_call": has_kernel, "finite": finite,
            "max_rel_err": rel, "rel_l2_err": rel_l2, "tol": LOGIT_TOL,
            "argmax_agree": agree,
            "ok": has_kernel and finite and rel_l2 <= LOGIT_TOL}


def pmf_phase(n: int = 32, length: int = 64, seed: int = 7) -> dict:
    import numpy as np
    from repro.core.pmf import PMF, chance_of_success
    from repro.kernels.pmf_conv.ops import batched_success

    rng = np.random.default_rng(seed)
    pets, pcts, dls = [], [], []
    for _ in range(n):
        e = PMF.from_normal(rng.uniform(4, 20), rng.uniform(1, 4))
        c = PMF.from_normal(rng.uniform(5, 30), rng.uniform(1, 5))
        pets.append(e)
        pcts.append(c)
        dls.append(int(e.mean() + c.mean() + rng.integers(-8, 12)))
    got = np.asarray(batched_success(pets, pcts, dls, length=length))
    want = np.asarray([chance_of_success(e, c, d, droppable_prev=True)
                       for e, c, d in zip(pets, pcts, dls)])
    err = float(np.abs(got - want).max())
    return {"n": n, "max_abs_err": err, "tol": PMF_TOL, "ok": err <= PMF_TOL}


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX runs on {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device_kind: {dev.device_kind}", flush=True)
    print(f"compile_cache: {enable_compile_cache()}", flush=True)
    counter = CompileCounter()
    from repro.launch import serve

    cfg = serve.serve_config(serve.parse_args(SERVE_ARGV))
    ok = True
    for name, phase in (("serve", lambda: serve_phase(SERVE_ARGV)),
                        ("decode", lambda: decode_phase(cfg)),
                        ("pmf", pmf_phase)):
        mark, t0 = counter.mark(), time.perf_counter()
        res = phase()
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"{name}: {json.dumps(res)}", flush=True)
        print(f"{name}: wall_s={time.perf_counter() - t0:.3f} "
              f"{counter.since(mark)} peak_bytes_in_use={peak}", flush=True)
        ok = ok and res["ok"]
    if not ok:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
