"""Ch. 6 (Figs. 6.4-6.9) — the SMSE prototype on real model executions,
plus the event-driven scheduler-overhead benchmark on a bursty trace and
the front-door router-scaling sweep.

Validation targets:
  * warm-started units start much faster than cold (Fig 6.4's thread-vs-
    container-vs-VM ladder, mapped to executable-compile vs cache reuse);
  * deadline-aware policies (EDF/MU) beat FCFS on miss rate (Fig 6.7);
  * merging+pruning cut executions (cost) while preserving QoS;
  * the control plane's event-driven loop costs O(events) on sparse bursty
    traces (no idle-tick polling) with bounded per-mapping-event overhead;
  * the front door: a 1-plane Router matches the bare engine's QoS exactly,
    and the shared cross-plane detector steers duplicate / prefix-
    overlapping traffic to the plane holding the merge target or cached KV
    (DESIGN.md §2.6) — all emitted to ``BENCH_serving.json`` for
    results/render_experiments.py.
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.configs.registry import ARCHS
from repro.core.fleet import FleetSpec
from repro.core.pruning import PruningConfig
from repro.core.simulation import PETOracle, SimConfig, Simulator
from repro.core.tasks import Machine, PETMatrix, Task
from repro.models import transformer as T
from repro.serving.autoscale import ElasticityConfig
from repro.serving.batching import SeqState, StepBatchingConfig, UnitBatch
from repro.serving.cluster import Plane, Router, make_engine_planes
from repro.serving.workload import (SessionConfig, SessionPool,
                                    StagedConfig, StagedPool, TenantSpec,
                                    WorkloadDriver)
from repro.serving.engine import (TICKS_PER_SEC, EngineConfig,
                                  ProcessingUnit, Request, ServingEngine)

from .common import Csv

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "BENCH_serving.json")


def _model():
    cfg = ARCHS["smollm-360m"].reduced().scaled(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab=256, head_dim=32, remat=False)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _trace(cfg, n=60, rate=0.25, deadline=250.0, seed=0, n_prompts=5):
    rng = np.random.default_rng(seed)
    prompts = [tuple(rng.integers(1, cfg.vocab, size=10).tolist())
               for _ in range(n_prompts)]
    out, t = [], 0.0
    for _ in range(n):
        out.append((t, Request(
            prompt=prompts[int(rng.integers(0, n_prompts))], n_new=3,
            seed=int(rng.integers(0, 2)), deadline=t + deadline)))
        t += float(rng.exponential(1.0 / rate))
    return out


def _bursty_trace(n_bursts: int, burst: int, gap: float, deadline: float,
                  seed: int = 0, n_prompts: int = 6):
    """Bursts of simultaneous arrivals separated by long idle gaps — the
    worst case for a tick-polling loop, the cheap case for event-driven."""
    rng = np.random.default_rng(seed)
    prompts = [tuple(rng.integers(1, 1000, size=8).tolist())
               for _ in range(n_prompts)]
    out = []
    for b in range(n_bursts):
        t = b * gap
        for _ in range(burst):
            out.append((t, Request(
                prompt=prompts[int(rng.integers(0, n_prompts))],
                op="generate", n_new=int(rng.integers(1, 4)),
                seed=int(rng.integers(0, 2)), deadline=t + deadline)))
    return out


def scheduler_overhead(n_requests: int, csv: Csv, checks: dict) -> list[dict]:
    """Event-driven control-plane overhead on a bursty trace.

    Stub-execution mode (oracle-timed, no JAX) isolates scheduler cost:
    the wall clock measures admission + merge appropriateness + pruning +
    mapping, not model math."""
    burst = 8
    n_bursts = max(4, n_requests // burst)
    n = n_bursts * burst
    rng = np.random.default_rng(5)
    pet = PETMatrix.generate(["generate"], ["m0"], rng, mean_range=(10, 25))
    rows = []
    for tag, merging, prune in (
            ("plain", "none", None),
            ("merge", "adaptive", None),
            ("merge+prune", "adaptive",
             PruningConfig(initial_defer_threshold=0.1,
                           base_drop_threshold=0.05))):
        eng = ServingEngine(None, None, EngineConfig(
            n_units=2, elasticity=None, merging=merging,
            heuristic="EDF", pruning=prune, result_cache=False,
            prefix_cache=False), stub_oracle=PETOracle(pet, seed=7))
        trace = _bursty_trace(n_bursts, burst, gap=500.0, deadline=120.0)
        t0 = time.perf_counter()
        stats = eng.run(trace)
        wall = time.perf_counter() - t0
        total = stats["completed"] + stats["dropped"]
        row = {
            "config": tag,
            "requests": n,
            "mapping_events": stats["mapping_events"],
            "us_per_mapping_event": 1e6 * stats["mapping_wall_s"]
            / max(stats["mapping_events"], 1),
            "wall_s": wall,
            "on_time": stats["on_time"],
            "missed": stats["missed"],
            "dropped": stats["dropped"],
            "miss_rate": 1.0 - stats["on_time"] / max(total, 1),
            "merges": stats["merges"],
            "merge_rejected": stats["merge_rejected"],
            "deferred": stats["deferred"],
            "deadlock_breaks": stats["deadlock_breaks"],
        }
        rows.append(row)
        csv.add(f"sched_overhead_{tag}",
                us_per_call=row["us_per_mapping_event"],
                mapping_events=row["mapping_events"],
                miss_rate=round(row["miss_rate"], 3),
                merges=row["merges"], dropped=row["dropped"])
        checks[f"accounted_{tag}"] = total == n
        checks[f"no_deadlock_{tag}"] = stats["deadlock_breaks"] == 0
        # event-driven: mapping events scale with events (arrivals coalesce
        # per burst + one per completion + warm/wake), never with idle time
        checks[f"event_bound_{tag}"] = \
            stats["mapping_events"] <= 3 * n + 2 * n_bursts + 8
    return rows


def _dup_heavy_trace(n: int, seed: int = 1, n_prompts: int = 4,
                     deadline: float = 400.0, gap: float = 0.5):
    """Arrivals dense enough that duplicates of a hot prompt are usually
    still queued somewhere — the regime where routing on the shared
    detector can co-locate them with their merge target."""
    rng = np.random.default_rng(seed)
    prompts = [tuple(rng.integers(1, 1000, size=8).tolist())
               for _ in range(n_prompts)]
    out, t = [], 0.0
    for _ in range(n):
        out.append((t, Request(
            prompt=prompts[int(rng.integers(0, n_prompts))], op="generate",
            n_new=int(rng.integers(1, 4)), seed=int(rng.integers(0, 2)),
            deadline=t + deadline)))
        t += float(rng.exponential(gap))
    return out


def _router_row(n_planes: int, detector: str, stats: dict,
                wall: float) -> dict:
    """One BENCH_serving.json router row (schema shared with
    results/render_experiments.py::router_scaling_table)."""
    routed = stats["router"]["routed"].values()
    total = stats["n_requests"]
    return {
        "planes": n_planes,
        "detector": detector,
        "requests": total,
        "on_time": stats["on_time"],
        "miss_rate": 1.0 - stats["on_time"] / max(total, 1),
        "merges": stats["merges"],
        "affinity_routed": stats["router"]["affinity_hits"],
        "prefix_routed": stats["router"]["prefix_affinity"],
        "routed_spread": f"{min(routed)}-{max(routed)}",
        "deadlock_breaks": stats["deadlock_breaks"],
        "wall_s": wall,
    }


def router_scaling(n_requests: int, csv: Csv, checks: dict) -> list[dict]:
    """Front-door scaling: 1/2/4 stub-engine planes under the affinity
    policy, shared vs per-plane detector, plus a 2-plane simulator row
    showing prefix-affinity routing against the paged KV cache."""
    rng = np.random.default_rng(3)
    pet = PETMatrix.generate(["generate"], ["m0"], rng, mean_range=(8, 16))
    ekw = dict(n_units=1, elasticity=None, result_cache=False,
               prefix_cache=False, heuristic="EDF", merging="adaptive")

    bare = ServingEngine(None, None, EngineConfig(**ekw),
                         stub_oracle=PETOracle(pet, seed=11))
    bare_stats = bare.run(_dup_heavy_trace(n_requests))

    rows = []
    for n_planes in (1, 2, 4):
        for shared in (True, False):
            planes = make_engine_planes(
                None, None, EngineConfig(**ekw), n_planes,
                stub_oracles=[PETOracle(pet, seed=11)
                              for _ in range(n_planes)])
            router = Router(planes, policy="affinity",
                            shared_detector=shared)
            t0 = time.perf_counter()
            stats = router.run(_dup_heavy_trace(n_requests))
            wall = time.perf_counter() - t0
            total = stats["n_requests"]
            row = _router_row(n_planes, "shared" if shared else "per-plane",
                              stats, wall)
            rows.append(row)
            csv.add(f"router_{n_planes}p_{row['detector']}",
                    merges=row["merges"],
                    affinity_routed=row["affinity_routed"],
                    miss_rate=round(row["miss_rate"], 3))
            checks[f"router_accounted_{n_planes}p_{row['detector']}"] = \
                total == n_requests
            if n_planes == 1 and shared:
                # 1-plane front door == bare engine (the oracle property the
                # equivalence tests assert in full decision-trace detail)
                checks["router_1p_matches_bare"] = (
                    (stats["on_time"], stats["missed"], stats["dropped"],
                     stats["merges"])
                    == (bare_stats["on_time"], bare_stats["missed"],
                        bare_stats["dropped"], bare_stats["merges"]))
            if n_planes > 1 and shared:
                checks[f"cross_plane_affinity_{n_planes}p"] = \
                    row["affinity_routed"] > 0

    # -- prefix-affinity row: simulator planes, payload-free KV cache -------
    def sim_plane(pid: int) -> Plane:
        sim = Simulator([], [Machine(mid=1, mtype="m0", queue_size=4)],
                        PETOracle(pet, seed=5 + pid),
                        SimConfig(heuristic="EDF", prefix_cache_blocks=64,
                                  kv_block_size=16))
        return Plane(sim, pid=pid)

    router = Router([sim_plane(0), sim_plane(1)], policy="affinity")
    srng = np.random.default_rng(7)
    sys_prompts = [tuple(srng.integers(1, 1000, size=32).tolist())
                   for _ in range(2)]
    t, n_sim = 0.0, min(n_requests, 48)
    t0 = time.perf_counter()
    for i in range(n_sim):
        toks = sys_prompts[i % 2] + \
            tuple(srng.integers(1000, 2000, size=8).tolist())
        router.submit(Task(ttype="generate", data_id=f"s{i}", op="generate",
                           params=(), arrival=t, deadline=t + 500.0,
                           tokens=toks), t)
        t += 30.0
    stats = router.drain()
    wall = time.perf_counter() - t0
    row = _router_row(2, "shared+prefix", stats, wall)
    rows.append(row)
    csv.add("router_2p_prefix_sim", prefix_routed=row["prefix_routed"],
            prefix_hits=stats["prefix_hits"])
    checks["prefix_affinity_routes"] = row["prefix_routed"] > 0
    checks["prefix_affinity_hits"] = stats["prefix_hits"] > 0
    return rows


def _elastic_trace(n_phases: int = 4, surge: int = 24, burst: int = 8,
                   gap: float = 260.0, seed: int = 0):
    """Alternating load shapes that separate the two elasticity signals.

    A *loose surge* piles up a deep batch queue of slack-deadline work
    (everything finishes on time on the base pool — depth-triggered
    scale-up is pure spend) and a *tight burst* brings a shallow queue of
    urgent work (the depth trigger never fires, but most of it misses
    without extra capacity).  Success-chance scaling tells the two apart;
    queue depth cannot."""
    rng = np.random.default_rng(seed)

    def req(t, deadline):
        return Request(prompt=tuple(rng.integers(1, 5000, size=8).tolist()),
                       op="generate", n_new=2, deadline=t + deadline)

    out, t = [], 0.0
    for _ in range(n_phases):
        for _ in range(surge):              # deep queue, slack deadlines
            out.append((t, req(t, 1200.0)))
            t += 1.0
        t += gap
        for _ in range(burst):              # shallow queue, tight deadlines
            out.append((t, req(t, 45.0)))
            t += 2.0
        t += gap
    return out


def _autoscale_elasticity(policy: str) -> ElasticityConfig:
    return ElasticityConfig(
        policy=policy, max_extra=3, cooldown=10.0,
        scale_up_queue=12, scale_down_queue=2,
        low_chance=0.55, high_chance=0.9,
        budget_machine_seconds=900.0)


def _mirror_tasks(trace):
    """Simulator tasks via the engine's own similarity-key builder, so both
    substrates see one workload by construction."""
    return [r.to_task(t, i) for i, (t, r) in enumerate(trace)]


def autoscale_policies(csv: Csv, checks: dict, n_phases: int = 4,
                       strict: bool = True) -> list[dict]:
    """Cost/QoS elasticity ladder (DESIGN.md §2.7): the legacy queue
    hysteresis vs the Ch. 5 success-chance scaler vs the budgeted
    cost-aware scaler, on the mixed loose-surge/tight-burst trace — one
    row per (policy x substrate), stub-execution engine and simulator.

    Claim under test: reacting to degrading success probability buys
    >= QoS at <= machine-seconds versus reacting to queue depth."""
    rng = np.random.default_rng(17)
    pet = PETMatrix.generate(["generate"], ["m0"], rng, mean_range=(10, 22))
    trace = _elastic_trace(n_phases=n_phases)
    n = len(trace)
    rows, by_key = [], {}
    for policy in ("fixed", "queue", "success-chance", "cost-aware"):
        elasticity = (None if policy == "fixed"
                      else _autoscale_elasticity(policy))
        for substrate in ("engine", "simulator"):
            if substrate == "engine":
                sub = ServingEngine(None, None, EngineConfig(
                    n_units=1, heuristic="EDF", merging="none",
                    result_cache=False, prefix_cache=False,
                    elasticity=elasticity), stub_oracle=PETOracle(pet, seed=7))
                t0 = time.perf_counter()
                stats = sub.run(trace)
                wall = time.perf_counter() - t0
            else:
                sub = Simulator(
                    _mirror_tasks(trace),
                    [Machine(mid=1, mtype="m0", queue_size=4)],
                    PETOracle(pet, seed=7),
                    SimConfig(heuristic="EDF", merging="none",
                              elasticity=elasticity))
                t0 = time.perf_counter()
                st = sub.run()
                wall = time.perf_counter() - t0
                stats = {
                    "on_time": st.on_time, "missed": st.missed,
                    "dropped": st.dropped, "scale_ups": st.scale_ups,
                    "scale_downs": st.scale_downs,
                    "machine_seconds": st.machine_seconds,
                    "extra_machine_seconds": st.extra_machine_seconds,
                    "warmup_ticks": st.warmup_ticks,
                }
            ms = stats["machine_seconds"]
            row = {
                "policy": policy, "substrate": substrate, "requests": n,
                "on_time": stats["on_time"], "missed": stats["missed"],
                "dropped": stats["dropped"],
                "miss_rate": 1.0 - stats["on_time"] / max(n, 1),
                "scale_ups": stats["scale_ups"],
                "scale_downs": stats["scale_downs"],
                "machine_seconds": ms,
                "extra_machine_seconds": stats["extra_machine_seconds"],
                "warmup_ticks": stats["warmup_ticks"],
                "wall_s": wall,
            }
            rows.append(row)
            by_key[(policy, substrate)] = row
            csv.add(f"autoscale_{policy}_{substrate}",
                    on_time=row["on_time"],
                    scale_ups=row["scale_ups"],
                    machine_seconds=round(ms, 1))
            checks[f"autoscale_accounted_{policy}_{substrate}"] = \
                stats["on_time"] + stats["missed"] + stats["dropped"] == n
    if strict:
        for substrate in ("engine", "simulator"):
            q = by_key[("queue", substrate)]
            s = by_key[("success-chance", substrate)]
            c = by_key[("cost-aware", substrate)]
            # the acceptance claim: >= QoS at <= machine-seconds
            checks[f"autoscale_qos_{substrate}"] = \
                s["on_time"] >= q["on_time"]
            checks[f"autoscale_cost_{substrate}"] = \
                s["machine_seconds"] <= q["machine_seconds"] * 1.001
            # the budget gates *scale-up decisions*; busy extras keep
            # accruing while they drain (one retire per cooldown), so the
            # guarantee is budget + a bounded in-flight overshoot
            checks[f"autoscale_budget_{substrate}"] = \
                c["extra_machine_seconds"] <= 900.0 + 3 * 60.0
    return rows


def _tight_trace(n=40, seed=1, n_prompts=5, deadline=20.0, rate=2.0):
    """Deadlines tight enough that the pruner's drop pass engages — the
    regime where per-drop attribution actually has something to say."""
    rng = np.random.default_rng(seed)
    prompts = [tuple(rng.integers(1, 1000, size=8).tolist())
               for _ in range(n_prompts)]
    out, t = [], 0.0
    for _ in range(n):
        out.append((t, Request(
            prompt=prompts[int(rng.integers(0, n_prompts))], op="generate",
            n_new=int(rng.integers(1, 4)), seed=int(rng.integers(0, 2)),
            deadline=t + deadline)))
        t += float(rng.exponential(1.0 / rate))
    return out


def qos_attribution(csv: Csv, checks: dict, n_requests: int = 40,
                    strict: bool = True, emit: tuple | None = None
                    ) -> list[dict]:
    """QoS attribution by policy (DESIGN.md §2.9): every drop carries its
    reason (and, for pruner drops, the chance-of-success at decision time),
    every defer its chance vs threshold — aggregated into one row per
    policy for results/render_experiments.py.  Stub-execution engines with
    a repro.obs.Telemetry attached; each run is re-checked against a
    telemetry-off twin (zero perturbation, the recorder's core contract).

    ``emit=(trace_path, metrics_path)`` additionally exports the last
    policy's Chrome trace + metrics snapshot and schema-validates both
    (the CI bench-smoke artifact)."""
    from collections import Counter

    from repro.obs import (Telemetry, chrome_trace, validate_chrome_trace,
                           validate_metrics_snapshot, write_chrome_trace,
                           write_metrics)

    pet = PETMatrix.generate(["generate"], ["m0"],
                             np.random.default_rng(3), mean_range=(8, 16))
    trace = _tight_trace(n=n_requests)
    rows = []
    tel = None
    for tag, cfg_kw in (
            ("edf-merge", dict(heuristic="EDF", merging="adaptive",
                               pruning=None)),
            ("edf-pruned", dict(heuristic="EDF", merging="adaptive",
                                pruning=PruningConfig(
                                    initial_defer_threshold=0.1,
                                    base_drop_threshold=0.3,
                                    dynamic_defer=True))),
            ("msd-pruned", dict(heuristic="MSD", merging="conservative",
                                pruning=PruningConfig(
                                    initial_defer_threshold=0.1,
                                    base_drop_threshold=0.3,
                                    dynamic_defer=True)))):
        def build():
            return ServingEngine(None, None, EngineConfig(
                n_units=2, elasticity=None, result_cache=False,
                prefix_cache=False, position_finder=None, **cfg_kw),
                stub_oracle=PETOracle(pet, seed=11))
        tel = Telemetry()
        eng = build()
        eng.attach_telemetry(tel)
        eng.cp.trace = []
        stats = eng.run(trace)
        off = build()
        off.cp.trace = []
        off.run(trace)
        checks[f"qos_zero_perturbation_{tag}"] = \
            off.cp.trace == eng.cp.trace
        reasons = Counter(e["reason"] for e in tel.events_of("drop"))
        row = {
            "policy": tag,
            "requests": len(trace),
            "on_time": stats["on_time"],
            "missed": stats["missed"],
            "dropped": stats["dropped"],
            "drop_reasons": dict(sorted(reasons.items())),
            "defers": len(tel.events_of("defer")),
            "merge_saving": round(sum(e["saving"] for e in
                                      tel.events_of("merge_saving")), 3),
            "pruning_wall_s": stats["pruning_wall_s"],
        }
        rows.append(row)
        csv.add(f"qos_attribution_{tag}", on_time=row["on_time"],
                dropped=row["dropped"], defers=row["defers"],
                reasons="/".join(f"{k}:{v}"
                                 for k, v in row["drop_reasons"].items()))
        # attribution must be complete: reasons partition the drop count
        checks[f"qos_drops_attributed_{tag}"] = \
            sum(reasons.values()) == stats["dropped"]
        if strict and cfg_kw["pruning"] is not None:
            checks[f"qos_pruner_engaged_{tag}"] = reasons.get("pruned", 0) > 0
    if emit is not None:
        trace_path, metrics_path = emit
        validate_chrome_trace(chrome_trace(tel.events))
        validate_metrics_snapshot(tel.metrics.snapshot())
        write_chrome_trace(tel.events, trace_path)
        write_metrics(tel.metrics, metrics_path)
        checks["qos_obs_schema_valid"] = True
    return rows


def _hetero_trace(n=80, rate=0.2, deadline=300.0, seed=5):
    """Moderate load, slack deadlines: the regime where a cost-aware
    mapper can drain work onto slow-but-cheap machines without missing."""
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for _ in range(n):
        out.append((t, Request(
            prompt=tuple(rng.integers(1, 1000, size=8).tolist()),
            op="generate", n_new=2, deadline=t + deadline)))
        t += float(rng.exponential(1.0 / rate))
    return out


def hetero_fleet(csv: Csv, checks: dict, n_requests: int = 80,
                 strict: bool = True) -> list[dict]:
    """Heterogeneous-fleet cost ladder (DESIGN.md §2.8, Fig. 5.19's cost
    axis): a homogeneous all-fast pool vs a mixed fast-expensive /
    slow-cheap fleet under the speed-blind EDF baseline vs the cost-aware
    MCMD mapper, on both substrates — one FleetSpec builds the engine's
    units and the simulator's machines, so the rows are bitwise-comparable.

    Claims under test: (1) on the *same* mixed fleet, cost-aware mapping
    buys a lower execution-cost total at equal-or-better on-time
    completions than speed-blind mapping; (2) with elasticity on, the
    per-mtype billing integral charges cheap extras at their own rate
    (extra_pool_cost ~= cheap_rate x extra_machine_seconds), not at the
    homogeneous machine-seconds rate."""
    rng = np.random.default_rng(23)
    # inconsistent=False: one base PET per task type, machine speed is the
    # only time axis — the clean consistent-heterogeneity setting
    pet = PETMatrix.generate(["generate"], ["fast", "slow"], rng,
                             mean_range=(10, 18), inconsistent=False)
    fleet_mixed = FleetSpec.parse("fast:2:1.0:1.0,slow:2:0.5:0.25")
    fleet_homo = FleetSpec.parse("fast:4:1.0:1.0")

    rows, by_key = [], {}
    for label, fleet, heur in (("homogeneous", fleet_homo, "EDF"),
                               ("hetero-speed-blind", fleet_mixed, "EDF"),
                               ("hetero-cost-aware", fleet_mixed, "MCMD")):
        for substrate in ("engine", "simulator"):
            trace = _hetero_trace(n=n_requests)
            if substrate == "engine":
                sub = ServingEngine(None, None, EngineConfig(
                    fleet=fleet, heuristic=heur, merging="none",
                    elasticity=None, result_cache=False,
                    prefix_cache=False), stub_oracle=PETOracle(pet, seed=7))
                t0 = time.perf_counter()
                stats = sub.run(trace)
                wall = time.perf_counter() - t0
                stats = {k: stats[k] for k in
                         ("on_time", "missed", "dropped", "cost",
                          "pool_cost", "machine_seconds")}
            else:
                sim = Simulator(_mirror_tasks(trace), fleet,
                                PETOracle(pet, seed=7),
                                SimConfig(heuristic=heur, merging="none"))
                t0 = time.perf_counter()
                st = sim.run()
                wall = time.perf_counter() - t0
                stats = {"on_time": st.on_time, "missed": st.missed,
                         "dropped": st.dropped, "cost": st.cost,
                         "pool_cost": st.pool_cost,
                         "machine_seconds": st.machine_seconds}
            row = {"fleet": label, "spec": fleet.serialize(),
                   "heuristic": heur, "substrate": substrate,
                   "requests": n_requests, **stats, "wall_s": wall}
            rows.append(row)
            by_key[(label, substrate)] = row
            csv.add(f"hetero_{label}_{substrate}",
                    on_time=row["on_time"], cost=round(row["cost"], 1),
                    pool_cost=round(row["pool_cost"], 1))
            checks[f"hetero_accounted_{label}_{substrate}"] = \
                row["on_time"] + row["missed"] + row["dropped"] == n_requests
    if strict:
        for substrate in ("engine", "simulator"):
            blind = by_key[("hetero-speed-blind", substrate)]
            aware = by_key[("hetero-cost-aware", substrate)]
            # the acceptance claim: lower total cost at >= on-time
            checks[f"hetero_cost_{substrate}"] = aware["cost"] < blind["cost"]
            checks[f"hetero_qos_{substrate}"] = \
                aware["on_time"] >= blind["on_time"]
    # one spec, two substrates: the decision parity the control plane
    # guarantees shows up as identical cost/QoS numbers per row
    for label in ("homogeneous", "hetero-speed-blind", "hetero-cost-aware"):
        eng, sim_ = by_key[(label, "engine")], by_key[(label, "simulator")]
        checks[f"hetero_parity_{label}"] = \
            (eng["on_time"], round(eng["cost"], 6)) == \
            (sim_["on_time"], round(sim_["cost"], 6))

    # -- per-mtype autoscale billing: cheap extras bill at the cheap rate --
    el = ElasticityConfig(policy="queue", max_extra=3, cooldown=10.0,
                          scale_up_queue=6, scale_down_queue=1)
    small = FleetSpec.parse("fast:1:1.0:1.0,slow:1:0.5:0.25")
    sim = Simulator(
        _mirror_tasks(_hetero_trace(n=n_requests, rate=0.5, deadline=200.0)),
        small, PETOracle(pet, seed=7),
        SimConfig(heuristic="EDF", merging="none", elasticity=el))
    st = sim.run()
    row = {"fleet": "hetero-autoscale", "spec": small.serialize(),
           "heuristic": "EDF", "substrate": "simulator",
           "requests": n_requests, "on_time": st.on_time,
           "missed": st.missed, "dropped": st.dropped, "cost": st.cost,
           "pool_cost": st.pool_cost, "machine_seconds": st.machine_seconds,
           "extra_machine_seconds": st.extra_machine_seconds,
           "extra_pool_cost": st.extra_pool_cost, "scale_ups": st.scale_ups,
           "wall_s": 0.0}
    rows.append(row)
    csv.add("hetero_autoscale_billing", scale_ups=st.scale_ups,
            extra_ms=round(st.extra_machine_seconds, 1),
            extra_pool_cost=round(st.extra_pool_cost, 1))
    checks["hetero_billing_scales"] = st.scale_ups > 0
    # extras are the cheapest row (0.25/tick): per-mtype billing must charge
    # well under the homogeneous machine-seconds rate (1.0/tick)
    checks["hetero_billing_per_mtype"] = \
        st.extra_pool_cost <= 0.2501 * st.extra_machine_seconds + 1e-6
    return rows


def _batch_trace(n: int, n_new: int = 24, plen: int = 8, seed: int = 9):
    """``n`` decode-heavy generations arriving at once on one unit — the
    concurrency regime continuous batching exists for."""
    rng = np.random.default_rng(seed)
    return [(0.0, Request(
        prompt=tuple(rng.integers(1, 1000, size=plen).tolist()),
        op="generate", n_new=n_new, deadline=1e9)) for _ in range(n)]


def continuous_batching(csv: Csv, checks: dict,
                        concurrencies=(8, 16, 32, 64), n_new: int = 24,
                        strict: bool = True) -> list[dict]:
    """Step-level continuous batching (DESIGN.md §2.10): tokens/sec per
    unit, sequential (run-to-completion) vs batched, at concurrency 8-64
    on both analytic substrates (stub-execution engine and simulator, one
    oracle — makespans must agree bitwise), plus the p95 decode-step
    latency a 4096-token prefill inflicts on co-resident decodes when it
    is chunked into the step budget instead of monopolizing the unit.

    Acceptance claims: >= 2x tokens/sec per unit at concurrency >= 16,
    and p95 decode latency under the concurrent long prefill <= 1.5x the
    idle-decode baseline (vs a ~200x head-of-line stall without
    chunking)."""
    rng = np.random.default_rng(29)
    pet = PETMatrix.generate(["generate"], ["m0"], rng, mean_range=(8, 16))
    # decode-heavy split: long generations put 3/4 of the oracle-sampled
    # work into decode steps, where the batch economics live
    bat = StepBatchingConfig(max_batch=8, step_token_budget=64,
                             prefill_fraction=0.25)
    ekw = dict(n_units=1, elasticity=None, heuristic="EDF", merging="none",
               pruning=None, result_cache=False, prefix_cache=False)
    rows, tps = [], {}
    for conc in concurrencies:
        trace = _batch_trace(conc, n_new=n_new)
        tokens = sum(len(r.prompt) + r.n_new for _, r in trace)
        for mode, cfg_b in (("sequential", None), ("batched", bat)):
            eng = ServingEngine(None, None,
                                EngineConfig(batching=cfg_b, **ekw),
                                stub_oracle=PETOracle(pet, seed=11))
            t0 = time.perf_counter()
            stats = eng.run(trace)
            wall = time.perf_counter() - t0
            mk = eng.cp.stats["last_completion"]
            sim = Simulator(_mirror_tasks(trace), FleetSpec.homogeneous(1),
                            PETOracle(pet, seed=11),
                            SimConfig(heuristic="EDF", merging="none",
                                      batching=cfg_b))
            st = sim.run()
            tps[(conc, mode)] = tokens / max(mk / TICKS_PER_SEC, 1e-9)
            row = {
                "mode": mode, "concurrency": conc, "requests": conc,
                "n_new": n_new, "tokens": tokens,
                "makespan_ticks": round(mk, 6),
                "tokens_per_sec_per_unit": round(tps[(conc, mode)], 3),
                "on_time": stats["on_time"], "missed": stats["missed"],
                "dropped": stats["dropped"],
                "max_batch": bat.max_batch if cfg_b else 1,
                "step_token_budget":
                    bat.step_token_budget if cfg_b else None,
                "wall_s": wall,
            }
            rows.append(row)
            # one oracle, two substrates: batch-dependent step costs must
            # keep the analytic twins in lockstep (the §2.10 contract)
            checks[f"batching_parity_{mode}_{conc}"] = (
                round(mk, 6) == round(st.makespan, 6)
                and stats["on_time"] == st.on_time)
            checks[f"batching_accounted_{mode}_{conc}"] = \
                stats["on_time"] + stats["missed"] + stats["dropped"] == conc
        speedup = tps[(conc, "batched")] / max(tps[(conc, "sequential")],
                                               1e-9)
        csv.add(f"batching_c{conc}",
                seq_tps=round(tps[(conc, "sequential")], 1),
                bat_tps=round(tps[(conc, "batched")], 1),
                speedup=round(speedup, 2))
        if strict and conc >= 16:
            checks[f"batching_speedup_{conc}"] = speedup >= 2.0

    # -- p95 decode-step latency under a concurrent 4096-token prefill ------
    # walker-level (substrate-independent): 8 steady decoders, then the
    # same 8 with a 4k prefill chunked into the residual step budget
    lat_cfg = StepBatchingConfig(max_batch=9, step_token_budget=64)
    rp, rd, plen_long = 0.05, 2.0, 4096

    def _p95_decode_dt(with_prefill: bool) -> float:
        dts: list[float] = []
        ub = UnitBatch(lat_cfg, on_step=lambda t, dt, plan:
                       dts.append(dt) if plan.decode else None)
        for i in range(8):
            t = Task(ttype="generate", data_id=f"dec{i}", op="generate",
                     params=(4096,))
            ub.join(SeqState(task=t, plen=1, n_new=4096, prefill_done=1,
                             decoded=1, prefill_rate=rp, decode_step=rd),
                    0.0)
        if with_prefill:
            t = Task(ttype="generate", data_id="long", op="generate",
                     params=(1,))
            ub.join(SeqState(task=t, plen=plen_long, n_new=1,
                             prefill_rate=rp, decode_step=rd), 0.0)
        for _ in range(40):                 # 40 quanta x 8 steps
            t_end, done = ub.run_quantum(ub.clock)
            if t_end is None or (with_prefill and done):
                break                       # stop when the prefill finishes
        return float(np.percentile(dts, 95))

    p95_idle = _p95_decode_dt(False)
    p95_load = _p95_decode_dt(True)
    stall_serial = plen_long * rp           # run-to-completion HoL stall
    rows.append({
        "mode": "decode_latency", "concurrency": 8, "requests": 8,
        "p95_decode_ticks_idle": round(p95_idle, 6),
        "p95_decode_ticks_with_4k_prefill": round(p95_load, 6),
        "latency_ratio": round(p95_load / max(p95_idle, 1e-9), 3),
        "serial_hol_stall_ticks": round(stall_serial, 3),
        "prefill_tokens": plen_long,
        "step_token_budget": lat_cfg.step_token_budget,
    })
    csv.add("batching_decode_p95", idle=round(p95_idle, 3),
            with_prefill=round(p95_load, 3),
            ratio=round(p95_load / max(p95_idle, 1e-9), 2),
            serial_stall=round(stall_serial, 1))
    checks["batching_p95_bounded"] = p95_load <= 1.5 * p95_idle
    checks["batching_p95_vs_serial"] = p95_load < stall_serial
    # schema guard for render_experiments.py / CI smoke: every throughput
    # row carries the keys the table builder reads
    checks["batching_rows_schema"] = all(
        {"mode", "concurrency", "tokens_per_sec_per_unit",
         "makespan_ticks"} <= set(r) for r in rows if "tokens" in r)
    return rows


def _disagg_trace(n: int, seed: int = 17, rate: float = 0.25,
                  n_new: int = 24, plen: int = 48, deadline: float = 600.0):
    """Decode-heavy open-loop arrivals with multi-block prompts (48 tokens
    = 3 KV blocks), so prefill→decode handoffs carry non-zero migration
    cost and the phase split has real work on both sides."""
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for _ in range(n):
        out.append((t, Request(
            prompt=tuple(rng.integers(1, 1000, size=plen).tolist()),
            op="generate", n_new=n_new, deadline=t + deadline)))
        t += float(rng.exponential(1.0 / rate))
    return out


def disaggregation(csv: Csv, checks: dict, n_requests: int = 48,
                   strict: bool = True) -> list[dict]:
    """Prefill/decode disaggregation (DESIGN.md §2.13): a unified
    mixed-phase fleet vs a phase-specialized one at matched catalog cost
    — one fast prefill unit feeding two slow-cheap decode units, with KV
    blocks migrated at the phase boundary — on both analytic substrates
    (stub engine and simulator must stay trace-parity-equal with
    disaggregation ON).

    Acceptance claims: (1) the disaggregated fleet's p95 decode-step
    latency under a concurrent 4096-token prefill is <= 1.10x its idle
    baseline (vs ~1.24x for the unified fleet, where the chunked prefill
    shares the decode units' step budget — PR 7's bound); (2) at
    equal-or-lower fleet cost rate, the disaggregated fleet's execution
    cost is <= the unified fleet's on the same trace."""
    rng = np.random.default_rng(31)
    pet = PETMatrix.generate(["generate"], ["m0"], rng, mean_range=(8, 16))
    bat = StepBatchingConfig(max_batch=8, step_token_budget=64,
                             prefill_fraction=0.25)
    # matched catalog cost: 2x(speed 1.0 @ 1.0/tick) = 2.0/tick unified vs
    # 1x(1.5 @ 1.25) + 2x(0.5 @ 0.35) = 1.95/tick disaggregated
    fleets = (("unified", FleetSpec.parse("m0:2:1.0:1.0")),
              ("disaggregated", FleetSpec.parse(
                  "m0@prefill:1:1.5:1.25,m0@decode:2:0.5:0.35")))

    # -- p95 decode-step latency under a concurrent 4k prefill -------------
    # walker-level (substrate-independent), same methodology as the
    # continuous-batching section: 8 steady decoders on one unit, then the
    # same 8 under the long-prompt request.  Unified: the 4k prefill chunks
    # inline into the decode unit's step budget.  Disaggregated: the
    # prefill ran on the prefill plane, so the decode unit only ever sees
    # the handed-off sequence as one more decode-only batch member.
    lat_cfg = StepBatchingConfig(max_batch=9, step_token_budget=64)
    rp, rd, plen_long, n_new_long = 0.05, 2.0, 4096, 16

    def _p95_decode_dt(load) -> float:
        dts: list[float] = []
        ub = UnitBatch(lat_cfg, on_step=lambda t, dt, plan:
                       dts.append(dt) if plan.decode else None)
        for i in range(8):
            t = Task(ttype="generate", data_id=f"dec{i}", op="generate",
                     params=(4096,))
            ub.join(SeqState(task=t, plen=1, n_new=4096, prefill_done=1,
                             decoded=1, prefill_rate=rp, decode_step=rd),
                    0.0)
        if load is not None:
            t = Task(ttype="generate", data_id="long", op="generate",
                     params=(n_new_long,))
            if load == "unified":
                seq = SeqState(task=t, plen=plen_long, n_new=n_new_long,
                               prefill_rate=rp, decode_step=rd)
            else:       # post-handoff continuation, as join_batch builds it
                seq = SeqState(task=t, plen=plen_long, n_new=n_new_long,
                               prefill_done=plen_long, decoded=1,
                               prefill_rate=rp, decode_step=rd)
            ub.join(seq, 0.0)
        for _ in range(80):
            t_end, done = ub.run_quantum(ub.clock)
            if t_end is None or (load is not None and done):
                break               # stop when the long request finishes
        return float(np.percentile(dts, 95))

    p95_idle = _p95_decode_dt(None)
    p95 = {m: _p95_decode_dt(m) for m, _ in fleets}
    ratio = {m: p95[m] / max(p95_idle, 1e-9) for m in p95}

    # -- end-to-end: same trace, matched-cost fleets, both substrates ------
    trace = _disagg_trace(n_requests)
    tokens = sum(len(r.prompt) + r.n_new for _, r in trace)
    rows, by_key = [], {}
    for mode, fleet in fleets:
        rate_total = sum(s.count * s.cost_rate for s in fleet.specs)
        for substrate in ("engine", "simulator"):
            if substrate == "engine":
                sub = ServingEngine(None, None, EngineConfig(
                    fleet=fleet, heuristic="EDF", merging="none",
                    elasticity=None, result_cache=False,
                    prefix_cache=False, batching=bat),
                    stub_oracle=PETOracle(pet, seed=13))
                sub.cp.trace = []
                t0 = time.perf_counter()
                stats = sub.run(trace)
                wall = time.perf_counter() - t0
                mk, cost, cp = (sub.cp.stats["last_completion"],
                                stats["cost"], sub.cp)
                qos = (stats["on_time"], stats["missed"], stats["dropped"])
            else:
                sim = Simulator(_mirror_tasks(trace), fleet,
                                PETOracle(pet, seed=13),
                                SimConfig(heuristic="EDF", merging="none",
                                          batching=bat))
                sim.cp.trace = []
                t0 = time.perf_counter()
                st = sim.run()
                wall = time.perf_counter() - t0
                mk, cost, cp = st.makespan, st.cost, sim.cp
                qos = (st.on_time, st.missed, st.dropped)
            handoffs = sum(1 for e in cp.trace if e[0] == "handoff")
            row = {
                "mode": mode, "spec": fleet.serialize(),
                "substrate": substrate, "fleet_cost_rate": rate_total,
                "requests": n_requests, "tokens": tokens,
                "makespan_ticks": round(mk, 6),
                "tokens_per_sec": round(
                    tokens / max(mk / TICKS_PER_SEC, 1e-9), 3),
                "on_time": qos[0], "missed": qos[1], "dropped": qos[2],
                "cost": round(cost, 6), "handoffs": handoffs,
                "p95_decode_ticks_idle": round(p95_idle, 6),
                "p95_decode_ticks_with_4k_prefill": round(p95[mode], 6),
                "latency_ratio_4k_prefill": round(ratio[mode], 3),
                "wall_s": wall,
            }
            rows.append(row)
            by_key[(mode, substrate)] = row
            checks[f"disagg_accounted_{mode}_{substrate}"] = \
                qos[0] + qos[1] + qos[2] == n_requests
        # one FleetSpec, two substrates: the §2.13 contract — handoff
        # destination picks and migration prices must agree bitwise
        eng_r, sim_r = by_key[(mode, "engine")], by_key[(mode, "simulator")]
        checks[f"disagg_parity_{mode}"] = (
            eng_r["makespan_ticks"] == sim_r["makespan_ticks"]
            and eng_r["on_time"] == sim_r["on_time"]
            and eng_r["cost"] == sim_r["cost"]
            and eng_r["handoffs"] == sim_r["handoffs"])
        csv.add(f"disagg_{mode}", on_time=eng_r["on_time"],
                cost=round(eng_r["cost"], 1), handoffs=eng_r["handoffs"],
                tps=round(eng_r["tokens_per_sec"], 1),
                p95_ratio=round(ratio[mode], 3))
    checks["disagg_handoffs"] = \
        by_key[("disaggregated", "engine")]["handoffs"] > 0
    checks["disagg_unified_no_handoffs"] = \
        by_key[("unified", "engine")]["handoffs"] == 0
    if strict:
        # the §2.13 acceptance gate: phase isolation bounds decode p95
        # under the 4k prefill to <= 1.10x idle, beating the unified
        # fleet's chunked-prefill bound, at equal-or-lower exec cost on an
        # equal-or-cheaper fleet
        checks["disagg_p95_bounded"] = ratio["disaggregated"] <= 1.10
        checks["disagg_p95_beats_unified"] = \
            ratio["disaggregated"] < ratio["unified"]
        checks["disagg_cost"] = (
            by_key[("disaggregated", "engine")]["cost"]
            <= by_key[("unified", "engine")]["cost"])
        checks["disagg_fleet_rate"] = (
            by_key[("disaggregated", "engine")]["fleet_cost_rate"]
            <= by_key[("unified", "engine")]["fleet_cost_rate"])
    # schema guard for render_experiments.py / CI smoke
    checks["disagg_rows_schema"] = all(
        {"mode", "substrate", "tokens_per_sec", "cost", "handoffs",
         "latency_ratio_4k_prefill"} <= set(r) for r in rows)
    return rows


def _session_tenants():
    return [TenantSpec("gold", share=0.3, slack=0.6, priority=1),
            TenantSpec("free", share=0.7, slack=1.2)]


def _session_row(mode: str, substrate: str, stats: dict, summary: dict,
                 wall: float) -> dict:
    per = summary.get("per_turn") or summary["per_stage"]
    submitted = sum(r["submitted"] for r in per)
    on_time = sum(r["on_time"] for r in per)
    execs = max(stats.get("executions", 0), 1)
    return {
        "mode": mode, "substrate": substrate,
        "users": summary.get("users", summary.get("dags")),
        "turns": summary.get("turns", summary.get("stages")),
        "submitted": submitted,
        "completed": sum(r["completed"] for r in per),
        "on_time": on_time,
        "dropped": sum(r["dropped"] for r in per),
        "on_time_rate": round(on_time / max(submitted, 1), 4),
        "sessions_done": summary.get("sessions_done",
                                     summary.get("dags_done")),
        "peak_active": summary.get("peak_active_sessions",
                                   summary.get("peak_active_dags")),
        "prefix_hit_rate": round(stats.get("prefix_hits", 0) / execs, 4),
        "tenant_on_time": {
            name: {"submitted": t["submitted"], "on_time": t["on_time"],
                   "on_time_rate": round(t["on_time_rate"], 4)}
            for name, t in summary["tenants"].items()},
        "wall_s": round(wall, 3),
    }


def closed_loop_sessions(csv: Csv, checks: dict,
                         users_sim: int = 1_000_000,
                         users_engine: int = 1_000,
                         strict: bool = True) -> list[dict]:
    """Closed-loop session workload (DESIGN.md §2.11): open-loop vs
    closed-loop vs staged-DAG traffic with gold/free SLO tiers on the stub
    engine (per-tenant on-time split per row), one ``users_sim``-user
    4-turn closed-loop row on the simulator (streaming generator — the
    ``peak_active`` column is the bounded-memory evidence: per-session
    state exists only in flight or thinking, never O(users)), and the same
    generator at 1/1000 scale on the live engine, where multi-turn
    sessions re-arrive with grown prefixes and must beat the single-shot
    baseline's prefix hit rate strictly."""
    tenants = _session_tenants()
    pet = PETMatrix.generate(["generate"], ["m0"],
                             np.random.default_rng(31), mean_range=(8, 16))
    rows = []

    def stub_router():
        eng = ServingEngine(None, None, EngineConfig(
            n_units=2, elasticity=None, result_cache=False,
            prefix_cache=False, heuristic="EDF", merging="adaptive"),
            stub_oracle=PETOracle(pet, seed=11))
        return Router([Plane(eng, pid=0)], policy="round-robin",
                      shared_detector=False)

    # -- open vs closed vs staged on the stub engine (same tenant tiers) ----
    trio = (
        ("open_loop", SessionPool(SessionConfig(
            users=48, turns=1, arrival_rate=0.4, deadline=150.0, seed=7),
            tenants)),
        ("closed_loop", SessionPool(SessionConfig(
            users=12, turns=4, arrival_rate=0.4,
            think=("uniform", 2.0, 6.0), deadline=150.0, seed=7), tenants)),
        ("staged_dag", StagedPool(StagedConfig(
            dags=12, arrival_rate=0.3, slack=3.0, seed=7), tenants)),
    )
    for mode, pool in trio:
        t0 = time.perf_counter()
        stats = WorkloadDriver(stub_router(), pool).run()
        row = _session_row(mode, "stub-engine", stats, pool.summary(),
                           time.perf_counter() - t0)
        rows.append(row)
        csv.add(f"sessions_{mode}", submitted=row["submitted"],
                on_time_rate=row["on_time_rate"],
                gold=row["tenant_on_time"]["gold"]["on_time_rate"],
                free=row["tenant_on_time"]["free"]["on_time_rate"])
        checks[f"sessions_accounted_{mode}"] = \
            stats["completed"] + stats["dropped"] == row["submitted"]
    checks["sessions_tenant_split"] = all(
        set(r["tenant_on_time"]) == {"gold", "free"} for r in rows)

    # -- million-user closed loop on the simulator (streaming, emit=task) ---
    fast_pet = PETMatrix.generate(["generate"], ["m0"],
                                  np.random.default_rng(3),
                                  mean_range=(0.05, 0.1))
    sim = Simulator([], [Machine(mid=i, queue_size=64) for i in range(8)],
                    PETOracle(fast_pet, seed=11),
                    SimConfig(heuristic="EDF", merging="none"))
    router = Router([Plane(sim, pid=0)], policy="round-robin",
                    shared_detector=False)
    pool = SessionPool(SessionConfig(
        users=users_sim, turns=4, arrival_rate=20.0, think=("const", 0.5),
        deadline=100.0, emit="task", n_new=1, seed=1))
    t0 = time.perf_counter()
    stats = WorkloadDriver(router, pool).run()
    wall = time.perf_counter() - t0
    summary = pool.summary()
    row = _session_row("closed_loop_at_scale", "simulator", stats, summary,
                       wall)
    rows.append(row)
    csv.add("sessions_at_scale", users=users_sim,
            tasks=row["submitted"], peak_active=row["peak_active"],
            tasks_per_sec=round(row["submitted"] / max(wall, 1e-9)),
            on_time_rate=row["on_time_rate"])
    checks["sessions_scale_all_finished"] = \
        summary["sessions_done"] == users_sim
    # the streaming bound: concurrently-active sessions, not user count
    checks["sessions_scale_memory_bounded"] = \
        row["peak_active"] < users_sim / 10
    if strict:
        checks["sessions_scale_million"] = users_sim >= 1_000_000
        checks["sessions_scale_memory_tight"] = \
            row["peak_active"] < users_sim / 1000

    # -- same generator, 1/1000 scale, live engine: prefix-reuse gain -------
    cfg, params = _model()

    def live_router():
        eng = ServingEngine(cfg, params, EngineConfig(
            n_units=1, elasticity=None, result_cache=False,
            prefix_cache=True, heuristic="EDF", merging="none",
            max_len=64, kv_block_size=4))
        return Router([Plane(eng, pid=0)], policy="round-robin",
                      shared_detector=False)

    hit_rate = {}
    for mode, users, turns in (
            ("engine_closed_loop", users_engine, 4),
            ("engine_single_shot", users_engine * 4, 1)):
        pool = SessionPool(SessionConfig(
            users=users, turns=turns, arrival_rate=0.02,
            think=("uniform", 5.0, 10.0), deadline=500.0, vocab=250,
            seed=7))
        t0 = time.perf_counter()
        stats = WorkloadDriver(live_router(), pool,
                               record_hit_depth=True).run()
        row = _session_row(mode, "engine", stats, pool.summary(),
                           time.perf_counter() - t0)
        row["per_turn_hit_depth"] = [
            round(r["mean_hit_depth"], 3) for r in pool.summary()["per_turn"]]
        rows.append(row)
        hit_rate[mode] = row["prefix_hit_rate"]
        csv.add(f"sessions_{mode}", requests=row["submitted"],
                prefix_hit_rate=row["prefix_hit_rate"],
                on_time_rate=row["on_time_rate"])
        if turns > 1:
            depths = row["per_turn_hit_depth"]
            # turn k's hit depth never regresses below turn k-1's
            checks["sessions_turn_depth_monotone"] = all(
                b >= a for a, b in zip(depths, depths[1:]))
            checks["sessions_turn_depth_positive"] = depths[-1] > 0.0
    # the acceptance criterion: multi-turn beats single-shot strictly
    checks["sessions_prefix_gain"] = \
        hit_rate["engine_closed_loop"] > hit_rate["engine_single_shot"]

    # schema guard for render_experiments.py / CI smoke
    checks["sessions_rows_schema"] = all(
        {"mode", "substrate", "users", "turns", "submitted", "on_time",
         "on_time_rate", "prefix_hit_rate", "peak_active",
         "tenant_on_time"} <= set(r) for r in rows)
    return rows


# ---------------------------------------------------------------------------
# §Calibration: record -> fit -> replay drift audit (DESIGN.md §2.12)
# ---------------------------------------------------------------------------

def _recorded_engine_run(trace, engine, capacity: int = 1 << 15):
    """Run ``engine`` over ``trace`` with a flight recorder attached and
    every side channel filled — the serve-CLI ``--record-out`` wiring in
    miniature."""
    from repro.obs import FlightRecorder
    rec = FlightRecorder(capacity=capacity)
    for t, item in trace:
        rec.note_arrival(t, item)
    engine.attach_telemetry(rec)
    stats = engine.run(trace)
    rec.snapshot_estimator(0.0, engine.estimator)
    rec.note_machines(engine.machines)
    rec.note_engine_config(engine.cfg)
    rec.note_stats(stats)
    return rec, stats


def _calibration_rows(tag: str, report: dict) -> list[dict]:
    rows = [{"source": tag, "stage": name, **row}
            for name, row in report["stages"].items()]
    rows.append({"source": tag, "stage": "summary",
                 "max_stage_drift_pct": report["max_stage_drift_pct"],
                 "decisions_match": report["decisions"]["match"],
                 "completed_gap": report["counters"]["completed"]["gap"],
                 "dropped_gap": report["counters"]["dropped"]["gap"]})
    return rows


def calibration(csv: Csv, checks: dict, n_requests: int = 60,
                strict: bool = True, emit: tuple | None = None) -> list[dict]:
    """Close the observability loop as a number (DESIGN.md §2.12): record
    a run, fit a PET oracle from its telemetry, re-drive the recorded
    arrivals through the simulator, and report per-stage drift.

    Two experiments share the artifact:

      * **control** — replay under the recording's own stub oracle; trace
        equivalence demands an *exact* decision match (pins the recorder's
        serialization fidelity end to end);
      * **fitted** — replay under the telemetry-fitted oracle; every
        scored per-stage latency divergence must stay within 15%.

    ``strict`` adds a live-engine row (tiny compiled model): the same
    record->fit->replay pipeline over real kernel timings, same 15% bound.
    ``emit=(record_path, drift_path)`` writes the smoke artifacts the CI
    job schema-validates and uploads.
    """
    import json as _json
    from repro.obs import drift_report
    pet = PETMatrix.generate(["generate"], ["m0"],
                             np.random.default_rng(3), mean_range=(8, 16))
    # low utilization on purpose: queueing noise stays sub-tick, so the
    # drift number measures the oracle fit, not scheduling jitter
    trace = _tight_trace(n=n_requests, seed=2, deadline=250.0, rate=0.08)
    eng = ServingEngine(None, None, EngineConfig(
        n_units=2, elasticity=None, heuristic="EDF", merging="none",
        pruning=None, result_cache=False, prefix_cache=False),
        stub_oracle=PETOracle(pet, seed=11))
    rec, stats = _recorded_engine_run(trace, eng)
    record = _json.loads(_json.dumps(rec.to_artifact()))

    ctrl = drift_report(record, oracle=PETOracle(pet, seed=11),
                        control=True)
    checks["calibration_control_exact"] = ctrl["decisions"]["match"] and \
        ctrl["max_stage_drift_pct"] == 0.0
    fitted = drift_report(record)
    checks["calibration_drift_bounded"] = \
        fitted["max_stage_drift_pct"] <= 15.0
    rows = _calibration_rows("stub-control", ctrl) + \
        _calibration_rows("stub-fitted", fitted)
    csv.add("calibration_stub",
            control_match=ctrl["decisions"]["match"],
            fitted_drift_pct=fitted["max_stage_drift_pct"],
            decisions=ctrl["decisions"]["recorded"])

    if emit is not None:
        record_path, drift_path = emit
        rec.save(record_path)
        with open(drift_path, "w") as f:
            _json.dump(fitted, f, indent=1)

    if strict:
        # live engine: real compiled-kernel timings through the same loop
        cfg, params = _model()
        live = ServingEngine(cfg, params, EngineConfig(
            n_units=1, elasticity=None, heuristic="EDF", merging="none",
            pruning=None, result_cache=False, prefix_cache=False,
            max_len=48, batch_buckets=(1,)))
        # steady-state measurement: pre-compile the exact prompt shape so
        # the first recorded span is a warm launch, not an XLA compile (the
        # simulator deliberately does not model cold starts — warm pools
        # are Fig 6.4's subject); long decodes keep warm spans above the
        # 1-tick stage-scoring floor
        plen, rng = 10, np.random.default_rng(4)
        for u in live.units:
            u.warmup(prompt_len=plen, buckets=(1,))
        prompts = [tuple(rng.integers(1, cfg.vocab, size=plen).tolist())
                   for _ in range(4)]
        live_trace, t = [], 0.0
        for _ in range(min(n_requests, 24)):
            live_trace.append((t, Request(
                prompt=prompts[int(rng.integers(0, 4))], n_new=24,
                seed=int(rng.integers(0, 2)), deadline=t + 500.0)))
            # arrivals far apart relative to the ~3-tick spans: queueing
            # collisions are rare on both sides, so the latency drift
            # measures the oracle fit, not small-sample collision luck
            t += float(rng.exponential(40.0))
        live_rec, live_stats = _recorded_engine_run(live_trace, live)
        live_record = _json.loads(_json.dumps(live_rec.to_artifact()))
        live_report = drift_report(live_record)
        checks["calibration_live_drift_bounded"] = \
            live_report["max_stage_drift_pct"] <= 15.0
        rows += _calibration_rows("engine-fitted", live_report)
        csv.add("calibration_live",
                fitted_drift_pct=live_report["max_stage_drift_pct"],
                completed=live_report["counters"]["completed"]["recorded"])
    checks["calibration_rows_schema"] = all(
        "source" in r and "stage" in r for r in rows)
    return rows


def run(csv: Csv, n_requests: int = 60) -> dict:
    checks = {}
    cfg, params = _model()

    # --- Fig 6.4: cold vs warm unit start-up -------------------------------
    u0 = ProcessingUnit(0, cfg, params, max_len=48)
    cold = u0.warmup(buckets=(1, 2, 4))
    u1 = ProcessingUnit(1, cfg, params, max_len=48, shared_fns=u0.fns)
    warm = u1.warmup(buckets=(1, 2, 4))
    csv.add("fig6.4_startup", cold_s=round(cold, 2), warm_s=round(warm, 3),
            speedup=round(cold / max(warm, 1e-6), 1))
    checks["warm_faster"] = warm < cold / 3

    # --- Fig 6.7: scheduling policies under load ---------------------------
    miss = {}
    for heur in ("FCFS-RR", "EDF", "MU"):
        ecfg = EngineConfig(n_units=2, elasticity=None,
                            heuristic=heur, merging="none", pruning=None,
                            result_cache=False, max_len=48,
                            batch_buckets=(1,))
        eng = ServingEngine(cfg, params, ecfg)
        stats = eng.run(_trace(cfg, n=n_requests, deadline=150.0))
        total = stats["completed"] + stats["dropped"]
        miss[heur] = 1.0 - stats["on_time"] / max(total, 1)
        csv.add(f"fig6.7_{heur}", miss_rate=round(miss[heur], 3))
    checks["edf_at_least_fcfs"] = miss["EDF"] <= miss["FCFS-RR"] + 0.05

    # --- merging + pruning cost/QoS ----------------------------------------
    res = {}
    for tag, merging, prune in (
            ("full", "adaptive",
             PruningConfig(initial_defer_threshold=0.1,
                           base_drop_threshold=0.05)),
            ("none", "none", None)):
        ecfg = EngineConfig(n_units=2, elasticity=None,
                            heuristic="EDF", merging=merging, pruning=prune,
                            result_cache=(tag == "full"), max_len=48,
                            batch_buckets=(1, 2, 4))
        eng = ServingEngine(cfg, params, ecfg)
        t0 = time.perf_counter()
        stats = eng.run(_trace(cfg, n=n_requests, deadline=200.0, seed=2))
        res[tag] = stats
        csv.add(f"smse_{tag}", us_per_call=(time.perf_counter() - t0) * 1e6,
                on_time=stats["on_time"], executions=stats["executions"],
                merges=stats["merges"], cache_hits=stats["cache_hits"],
                dropped=stats["dropped"])
    checks["reuse_cuts_executions"] = (res["full"]["executions"]
                                       < res["none"]["executions"])
    checks["qos_not_sacrificed"] = (res["full"]["on_time"]
                                    >= res["none"]["on_time"] - 5)

    # --- event-driven scheduler overhead on a bursty trace -----------------
    rows = scheduler_overhead(max(n_requests * 4, 160), csv, checks)
    # --- front-door router scaling (1/2/4 planes, shared vs per-plane) -----
    router_rows = router_scaling(max(n_requests, 40), csv, checks)
    # --- autoscale policy ladder (queue vs success-chance vs cost-aware) ---
    autoscale_rows = autoscale_policies(csv, checks)
    # --- heterogeneous fleet: cost-aware mapping + per-mtype billing -------
    hetero_rows = hetero_fleet(csv, checks)
    # --- QoS attribution: drop/defer reasons x policy via telemetry --------
    qos_rows = qos_attribution(csv, checks)
    # --- continuous batching: tokens/sec per unit + p95 decode latency -----
    batching_rows = continuous_batching(csv, checks)
    # --- prefill/decode disaggregation: phase planes + KV migration --------
    disagg_rows = disaggregation(csv, checks)
    # --- closed-loop sessions: multi-turn users, DAGs, SLO tiers, 1M scale -
    sessions_rows = closed_loop_sessions(csv, checks)
    # --- calibration: record -> fit -> replay drift audit ------------------
    calibration_rows = calibration(csv, checks)
    with open(OUT_PATH, "w") as f:
        json.dump({"bench": "serving_control_plane", "rows": rows,
                   "router_rows": router_rows,
                   "autoscale_rows": autoscale_rows,
                   "hetero_rows": hetero_rows,
                   "qos_rows": qos_rows,
                   "batching_rows": batching_rows,
                   "disagg_rows": disagg_rows,
                   "sessions_rows": sessions_rows,
                   "calibration_rows": calibration_rows}, f, indent=1)
    return checks


if __name__ == "__main__":
    # CI smoke entry: the autoscale + heterogeneous-fleet sections alone,
    # tiny traces, loose checks (exercises the SCALER_POLICIES registry,
    # both substrates, the Pallas pmf_conv signal path, the
    # FleetSpec plumbing and the cost-aware heuristics without the model
    # benchmarks)
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="autoscale + hetero-fleet sections only, tiny "
                         "traces, registry/path/parity checks (no "
                         "QoS-vs-cost assertions)")
    args = ap.parse_args()
    csv = Csv("autoscale+hetero (smoke)" if args.smoke else "serving")
    checks: dict = {}
    if args.smoke:
        autoscale_rows = autoscale_policies(csv, checks, n_phases=1,
                                            strict=False)
        hetero_rows = hetero_fleet(csv, checks, n_requests=32, strict=False)
        # observability smoke: attribution rows + the Perfetto trace and
        # metrics snapshot CI schema-validates and uploads as artifacts
        here = os.path.dirname(OUT_PATH)
        qos_rows = qos_attribution(
            csv, checks, strict=False,
            emit=(os.path.join(here, "BENCH_smoke_trace.json"),
                  os.path.join(here, "BENCH_smoke_metrics.json")))
        # continuous-batching smoke: small concurrencies, substrate-parity
        # and row-schema checks stay on (strict only drops the 2x claim)
        batching_rows = continuous_batching(csv, checks,
                                            concurrencies=(8, 16),
                                            n_new=12, strict=False)
        # disaggregation smoke: small trace, substrate-parity + handoff +
        # row-schema checks stay on (strict only drops the p95/cost claims)
        disagg_rows = disaggregation(csv, checks, n_requests=24,
                                     strict=False)
        # closed-loop smoke: scaled-down populations (2000 simulated
        # users, 24 engine sessions), schema + accounting + prefix-gain
        # checks stay on (strict only drops the million-user claims)
        sessions_rows = closed_loop_sessions(csv, checks, users_sim=2000,
                                             users_engine=24, strict=False)
        # calibration smoke: stub record -> fit -> replay with the exact
        # control-match and 15% drift checks on; emits the flight record
        # and drift report CI schema-validates and uploads
        calibration_rows = calibration(
            csv, checks, n_requests=40, strict=False,
            emit=(os.path.join(here, "BENCH_smoke_record.json"),
                  os.path.join(here, "BENCH_smoke_drift.json")))
        payload = {"bench": "serving_autoscale_smoke",
                   "autoscale_rows": autoscale_rows,
                   "hetero_rows": hetero_rows,
                   "qos_rows": qos_rows,
                   "batching_rows": batching_rows,
                   "disagg_rows": disagg_rows,
                   "sessions_rows": sessions_rows,
                   "calibration_rows": calibration_rows}
        # own artifact: never clobber the full run's BENCH_serving.json
        smoke_path = OUT_PATH.replace("BENCH_serving",
                                      "BENCH_autoscale_smoke")
        with open(smoke_path, "w") as f:
            json.dump(payload, f, indent=1)
    else:
        checks = run(csv)
    csv.emit()
    failed = [k for k, ok in checks.items() if not ok]
    print("checks:", "PASS" if not failed else f"FAIL {failed}")
    raise SystemExit(1 if failed else 0)
