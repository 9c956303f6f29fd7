"""Continuous batching + chunked prefill (DESIGN.md §2.10).

Covers every layer of the step-level scheduler: the substrate-independent
``UnitBatch`` walker, the paged flash-decode kernel against its oracle
(ragged lengths, masked-block edges), the live engine's token-identity
acceptance criterion (batched greedy output == sequential, bitwise, for
any token budget / batch size), simulator <-> stub-engine decision-trace
equivalence with batching on, and the recalibrated cold-start estimator.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # minimal install: keep unit tests, skip property tests
    from conftest import given, settings, st  # noqa: F401

from repro.core.fleet import FleetSpec
from repro.core.pruning import PruningConfig
from repro.core.simulation import PETOracle, SimConfig, Simulator
from repro.core.tasks import Machine, PETMatrix, Task
from repro.serving.batching import (SeqState, StepBatchingConfig, StepPlan,
                                    UnitBatch, analytic_cost_fn, step_cost,
                                    task_dims)
from repro.serving.engine import (EngineConfig, Request, ServingEngine,
                                  TimeEstimator)


def _seq(tid=0, plen=32, n_new=4, rate=0.5, dstep=2.0, **kw):
    task = Task(ttype="generate", data_id=f"d{tid}", op="generate",
                params=(n_new,))
    return SeqState(task=task, plen=plen, n_new=n_new, prefill_rate=rate,
                    decode_step=dstep, **kw)


# ---------------------------------------------------------------------------
# the step walker
# ---------------------------------------------------------------------------

class TestUnitBatch:
    def _ub(self, **kw):
        kw.setdefault("max_batch", 4)
        kw.setdefault("step_token_budget", 16)
        return UnitBatch(StepBatchingConfig(**kw))

    def test_decode_first_then_chunks_within_budget(self):
        ub = self._ub()
        decoding = _seq(0, plen=8, n_new=4)
        decoding.prefill_done = 8       # mid-decode
        decoding.decoded = 1
        prefilling = _seq(1, plen=40, n_new=2)
        ub.join(decoding, 0.0)
        ub.join(prefilling, 0.0)
        ub.seqs.extend(ub.pending)
        ub.pending.clear()
        plan = ub.plan_step()
        assert plan.decode == [decoding]
        # the remaining budget (16 - 1) goes to the prefill chunk
        assert plan.chunks == [(prefilling, 15)]
        assert plan.tokens == 16

    def test_chunks_in_join_order_and_split_across_steps(self):
        ub = self._ub(step_token_budget=24)
        a, b = _seq(0, plen=20, n_new=1), _seq(1, plen=20, n_new=1)
        ub.join(a, 0.0)
        ub.join(b, 0.0)
        t_end, done = ub.run_quantum(0.0)
        # step 1: a (older) gets its full 20-token prefill, b the remaining
        # 4; a's final-chunk logits are its single new token, so a completes
        # and the quantum ends early with b still mid-prefill
        assert [s.task.tid for s in done] == [a.task.tid]
        assert (a.prefill_done, b.prefill_done) == (20, 4)
        t_end2, done2 = ub.run_quantum(t_end)
        assert [s.task.tid for s in done2] == [b.task.tid]
        assert t_end2 > t_end > 0.0

    def test_quantum_stops_at_first_completion(self):
        ub = self._ub(quantum_steps=64)
        fast = _seq(0, plen=4, n_new=1)
        slow = _seq(1, plen=4, n_new=50)
        ub.join(fast, 0.0)
        ub.join(slow, 0.0)
        t_end, done = ub.run_quantum(0.0)
        assert [s.task.tid for s in done] == [fast.task.tid]
        assert slow.decoded < slow.n_new        # still in flight
        assert slow in ub.seqs

    def test_fused_step_cost_overlap(self):
        assert step_cost(10.0, 4.0, 0.35) == pytest.approx(10.0 + 0.35 * 4.0)
        assert step_cost(4.0, 10.0, 0.35) == pytest.approx(10.0 + 0.35 * 4.0)
        cfg = StepBatchingConfig(batch_marginal_cost=0.2,
                                 fused_step_overlap=0.0)
        cost = analytic_cost_fn(cfg)
        d1, d2 = _seq(0, plen=1, n_new=8, dstep=2.0), \
            _seq(1, plen=1, n_new=8, dstep=4.0)
        for s in (d1, d2):
            s.prefill_done = s.plen
        # batch economics: 2 decodes cost (1 + 0.2) * mean(2, 4), not 2 + 4
        assert cost(StepPlan(decode=[d1, d2])) == pytest.approx(1.2 * 3.0)

    def test_eviction_leaves_corunners_untouched(self):
        ub = self._ub(quantum_steps=2)
        a, b = _seq(0, plen=4, n_new=40), _seq(1, plen=4, n_new=40)
        ub.join(a, 0.0)
        ub.join(b, 0.0)
        ub.run_quantum(0.0)
        ub.evict(a.task)
        t_end, done = ub.run_quantum(ub.clock)
        assert a not in ub.seqs
        assert b in ub.seqs and not b.dead

    def test_empty_quantum_returns_none(self):
        ub = self._ub()
        assert ub.run_quantum(5.0) == (None, [])

    def test_task_dims_fallbacks(self):
        cfg = StepBatchingConfig(default_prompt=64, default_n_new=8)
        bare = Task(ttype="t0", data_id="d", op="op")
        assert task_dims(bare, cfg) == (64, 8)
        rich = Task(ttype="generate", data_id="d", op="generate",
                    params=(3, 0.0, 0), tokens=tuple(range(17)))
        assert task_dims(rich, cfg) == (17, 3)


# ---------------------------------------------------------------------------
# paged flash-decode kernel vs oracle
# ---------------------------------------------------------------------------

class TestPagedDecodeKernel:
    def _data(self, b, mp, ps, h, hkv, hd, seed=0, n_pages=None):
        import jax
        import jax.numpy as jnp
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        n_pages = n_pages or (b * mp + 1)
        q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
        kp = jax.random.normal(ks[1], (n_pages, hkv, ps, hd), jnp.float32)
        vp = jax.random.normal(ks[2], (n_pages, hkv, ps, hd), jnp.float32)
        # disjoint per-sequence tables over a shuffled page pool
        perm = np.asarray(
            jax.random.permutation(ks[3], n_pages - 1)) + 1
        tables = jnp.asarray(perm[:b * mp].reshape(b, mp), jnp.int32)
        return q, kp, vp, tables

    def test_kernel_matches_ref_ragged_lengths(self):
        import jax.numpy as jnp
        from repro.kernels.decode_attention.ops import paged_decode_attention
        from repro.kernels.decode_attention.ref import \
            paged_decode_attention_ref
        b, mp, ps = 4, 3, 8
        q, kp, vp, tables = self._data(b, mp, ps, 4, 2, 16)
        lengths = jnp.asarray([1, 7, 13, 24], jnp.int32)   # ragged, max full
        out = paged_decode_attention(q, kp, vp, tables, lengths,
                                     interpret=True, use_kernel=True)
        ref = paged_decode_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_single_masked_block_edge(self):
        """A sequence whose length leaves every page but the first fully
        masked — the per-page online-softmax init/normalize edge."""
        import jax.numpy as jnp
        from repro.kernels.decode_attention.ops import paged_decode_attention
        from repro.kernels.decode_attention.ref import \
            paged_decode_attention_ref
        b, mp, ps = 2, 4, 8
        q, kp, vp, tables = self._data(b, mp, ps, 4, 4, 16, seed=3)
        lengths = jnp.asarray([1, ps], jnp.int32)  # 1 token; exact boundary
        out = paged_decode_attention(q, kp, vp, tables, lengths,
                                     interpret=True, use_kernel=True)
        ref = paged_decode_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_unused_pages_never_leak(self):
        """Garbage in pages past ``length`` (including other sequences'
        pages) must not change the output at all."""
        import jax.numpy as jnp
        from repro.kernels.decode_attention.ops import paged_decode_attention
        b, mp, ps = 2, 3, 8
        q, kp, vp, tables = self._data(b, mp, ps, 4, 2, 16, seed=5)
        lengths = jnp.asarray([5, 11], jnp.int32)
        out1 = paged_decode_attention(q, kp, vp, tables, lengths,
                                      interpret=True, use_kernel=True)
        # poison every page beyond each sequence's last valid one
        kp2 = kp.at[tables[0, 1:]].set(99.0).at[tables[1, 2:]].set(99.0)
        vp2 = vp.at[tables[0, 1:]].set(-99.0).at[tables[1, 2:]].set(-99.0)
        # ... and the in-page tail of the last valid page
        kp2 = kp2.at[tables[0, 0], :, 5:].set(99.0)
        vp2 = vp2.at[tables[0, 0], :, 5:].set(-99.0)
        out2 = paged_decode_attention(q, kp2, vp2, tables, lengths,
                                      interpret=True, use_kernel=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 4), st.sampled_from([(2, 8), (3, 16)]),
           st.integers(0, 10_000))
    def test_prop_kernel_equals_ref(self, b, geom, seed):
        import jax
        import jax.numpy as jnp
        from repro.kernels.decode_attention.ops import paged_decode_attention
        from repro.kernels.decode_attention.ref import \
            paged_decode_attention_ref
        mp, ps = geom
        q, kp, vp, tables = self._data(b, mp, ps, 4, 2, 16, seed=seed)
        lengths = jax.random.randint(jax.random.PRNGKey(seed + 1), (b,),
                                     1, mp * ps + 1)
        out = paged_decode_attention(q, kp, vp, tables,
                                     jnp.asarray(lengths, jnp.int32),
                                     interpret=True, use_kernel=True)
        ref = paged_decode_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


def test_paged_decode_step_kernel_matches_oracle(tiny_model):
    """One whole ``paged_decode_fn`` step over a head-major arena: the
    interpreted kernel and the jnp oracle write the same new KV into the
    same page slots and give the same logits."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    cfg, params = tiny_model
    b, mp, ps = 3, 4, 8
    arena = T.init_paged_cache(cfg, b * mp + 1, ps)
    assert arena["kp"].shape == (cfg.n_layers, b * mp + 1, cfg.n_kv_heads,
                                 ps, cfg.resolved_head_dim)
    # prefill KV (L, S, Hkv, hd) lands token s at page s // ps, slot s % ps
    kv = np.arange(cfg.n_layers * 11 * cfg.n_kv_heads * 2,
                   dtype=np.float32).reshape(cfg.n_layers, 11,
                                             cfg.n_kv_heads, 2)
    pages = T.kv_to_pages(kv, ps)
    assert pages.shape == (cfg.n_layers, 2, cfg.n_kv_heads, ps, 2)
    np.testing.assert_array_equal(pages[:, 1, :, 10 - ps], kv[:, 10])
    assert not pages[:, 1, :, 11 - ps:].any()
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    kp = jax.random.normal(ks[0], arena["kp"].shape, jnp.bfloat16)
    vp = jax.random.normal(ks[1], arena["vp"].shape, jnp.bfloat16)
    tables = jnp.arange(1, b * mp + 1, dtype=jnp.int32).reshape(b, mp)
    lens = jnp.asarray([0, 9, mp * ps - 1], jnp.int32)
    toks = jnp.asarray([5, 17, 99], jnp.int32)
    outs = [jax.jit(T.paged_decode_fn(cfg, use_kernel=k))(
        params, kp, vp, tables, lens, toks) for k in (True, False)]
    (lk, kk, vk), (lr, kr, vr) = outs
    np.testing.assert_array_equal(np.asarray(kk, np.float32),
                                  np.asarray(kr, np.float32))
    np.testing.assert_array_equal(np.asarray(vk, np.float32),
                                  np.asarray(vr, np.float32))
    # the new token's KV landed in page tables[b, len // ps], slot len % ps
    page, slot = tables[1, 9 // ps], 9 % ps
    assert not np.array_equal(np.asarray(kk[0, page, :, slot], np.float32),
                              np.asarray(kp[0, page, :, slot], np.float32))
    # the kernel keeps scores and probabilities in f32 where the oracle
    # rounds them to bf16 (relative step 2**-8): logits differ by about 1%
    # of their range; a wrong page, slot or head moves them by all of it
    lk, lr = np.asarray(lk), np.asarray(lr)
    assert np.abs(lk - lr).max() <= 3e-2 * np.abs(lr).max()


class TestBlockTuning:
    def test_tune_block_s_clamps_and_minimizes_padding(self):
        from repro.kernels.decode_attention.ops import tune_block_s
        assert tune_block_s(64, block_s=512) == 64       # clamp to s
        assert tune_block_s(512, block_s=512) == 512     # exact: keep
        # 520 @ 512 pads 504 masked positions; shrinking to 128 pads 120
        assert tune_block_s(520, block_s=512) == 128
        for s in (1, 3, 96, 130, 500, 1000, 4096):
            bs = tune_block_s(s, block_s=512)
            assert 1 <= bs <= max(s, 1)
            # the pad never reaches a whole block: no masked-only launches
            assert (-s) % bs < bs

    def test_interpret_defaults_off_accelerator(self):
        import jax
        from repro.kernels.decode_attention.ops import interpret_default
        assert interpret_default() == \
            (jax.default_backend() not in ("tpu", "gpu"))


# ---------------------------------------------------------------------------
# live engine: batched == sequential, token for token
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    import jax
    from repro.configs.registry import ARCHS
    from repro.models import transformer as T
    cfg = ARCHS["smollm-360m"].reduced().scaled(
        n_layers=1, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab=128, head_dim=32, remat=False)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(n, seed=7, lo=4, hi=60):
    rng = np.random.default_rng(seed)
    return [tuple(int(x) for x in
                  rng.integers(1, 127, size=rng.integers(lo, hi)))
            for _ in range(n)]


def _run_engine(model, reqs, batching=None):
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(
        n_units=1, elasticity=None, merging="none", pruning=None,
        result_cache=False, max_len=96, batch_buckets=(1, 2, 4),
        batching=batching))
    stats = eng.run([(float(i), r) for i, r in enumerate(reqs)])
    return eng, stats


class TestTokenIdentity:
    @pytest.mark.parametrize("budget,max_batch", [(16, 4), (7, 8)])
    def test_batched_equals_sequential_greedy(self, tiny_model, budget,
                                              max_batch):
        """The tentpole acceptance criterion: any chunk/decode interleaving
        under any token budget yields bitwise-identical greedy outputs."""
        prompts = _prompts(8)
        seq_reqs = [Request(prompt=p, n_new=4, deadline=1e9)
                    for p in prompts]
        bat_reqs = [Request(prompt=p, n_new=4, deadline=1e9)
                    for p in prompts]
        _, s0 = _run_engine(tiny_model, seq_reqs)
        _, s1 = _run_engine(tiny_model, bat_reqs,
                            StepBatchingConfig(max_batch=max_batch,
                                               step_token_budget=budget))
        assert s0["completed"] == s1["completed"] == len(prompts)
        for a, b in zip(seq_reqs, bat_reqs):
            assert a.tokens == b.tokens
            assert len(b.tokens) == 4

    def test_batching_compresses_virtual_time(self, tiny_model):
        """Same workload, same per-token rates: the batched engine's
        makespan must beat run-to-completion (the whole point)."""
        prompts = _prompts(8, seed=11)
        a = [Request(prompt=p, n_new=4, deadline=1e9) for p in prompts]
        b = [Request(prompt=p, n_new=4, deadline=1e9) for p in prompts]
        eng_a, _ = _run_engine(tiny_model, a)
        eng_b, _ = _run_engine(tiny_model, b,
                               StepBatchingConfig(max_batch=8,
                                                  step_token_budget=32))
        assert eng_b.cp.stats["last_completion"] < \
            eng_a.cp.stats["last_completion"]

    def test_sampled_requests_fall_back_to_exclusive(self, tiny_model):
        """Non-greedy requests run the legacy path (exclusive step) and
        still complete with their own sampled trajectories."""
        prompts = _prompts(3, seed=3)
        reqs = [Request(prompt=p, n_new=3, temperature=0.8, seed=i)
                for i, p in enumerate(prompts)]
        _, stats = _run_engine(tiny_model, reqs,
                               StepBatchingConfig(max_batch=4,
                                                  step_token_budget=16))
        assert stats["completed"] == 3
        assert all(len(r.tokens) == 3 for r in reqs)

    @settings(max_examples=5, deadline=None)
    @given(st.integers(2, 64), st.integers(2, 8), st.integers(0, 10_000))
    def test_prop_any_interleaving_token_identical(self, tiny_model, budget,
                                                   max_batch, seed):
        prompts = _prompts(6, seed=seed)
        seq_reqs = [Request(prompt=p, n_new=3, deadline=1e9)
                    for p in prompts]
        bat_reqs = [Request(prompt=p, n_new=3, deadline=1e9)
                    for p in prompts]
        _, _ = _run_engine(tiny_model, seq_reqs)
        _, _ = _run_engine(tiny_model, bat_reqs,
                           StepBatchingConfig(max_batch=max_batch,
                                              step_token_budget=budget))
        for a, b in zip(seq_reqs, bat_reqs):
            assert a.tokens == b.tokens


# ---------------------------------------------------------------------------
# simulator <-> stub-engine decision equivalence under batching
# ---------------------------------------------------------------------------

def _pet(seed=3):
    rng = np.random.default_rng(seed)
    return PETMatrix.generate(["generate"], ["m0"], rng, mean_range=(8, 16))


def _request_trace(n=40, seed=1, n_prompts=5, deadline=80.0, rate=0.5):
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


def _mirror_tasks(trace):
    return [Task(ttype=req.op, data_id=str(hash(req.prompt)), op=req.op,
                 params=req.params_sig, arrival=t, deadline=req.deadline,
                 user=f"u{i % 8}", tokens=req.prompt)
            for i, (t, req) in enumerate(trace)]


BATCHED_EQUIV = [
    dict(heuristic="EDF", merging="adaptive", position_finder=None,
         pruning=None),
    dict(heuristic="MSD", merging="conservative", position_finder=None,
         pruning=PruningConfig(initial_defer_threshold=0.1,
                               base_drop_threshold=0.05,
                               dynamic_defer=True)),
]


class TestBatchedDecisionEquivalence:
    @pytest.mark.parametrize("cfg_kw", BATCHED_EQUIV,
                             ids=["edf-adaptive", "msd-pruned"])
    def test_same_trace_same_decisions_batched(self, cfg_kw):
        """The batch-dependent step cost model runs identically on both
        analytic substrates: decision traces stay bit-equal with
        continuous batching turned on."""
        pet = _pet()
        trace = _request_trace()
        bat = StepBatchingConfig(max_batch=4, step_token_budget=32)

        eng = ServingEngine(None, None, EngineConfig(
            n_units=2, elasticity=None, result_cache=False,
            prefix_cache=False, batching=bat, **cfg_kw),
            stub_oracle=PETOracle(pet, seed=11))
        eng.cp.trace = []
        stats = eng.run(trace)

        sim = Simulator(
            _mirror_tasks(trace), FleetSpec.homogeneous(2),
            PETOracle(pet, seed=11),
            SimConfig(hard_deadlines=cfg_kw["pruning"] is not None,
                      batching=bat, **cfg_kw))
        sim.cp.trace = []
        st = sim.run()

        assert sim.cp.trace == eng.cp.trace
        assert (st.on_time, st.missed, st.dropped) == \
            (stats["on_time"], stats["missed"], stats["dropped"])
        assert stats["deadlock_breaks"] == 0 == st.deadlock_breaks
        kinds = {e[0] for e in sim.cp.trace}
        assert "start" in kinds and "finish" in kinds

    def test_batched_machines_complete_everything(self):
        """Analytic batching end to end: no task stranded, makespan beats
        run-to-completion on the same oracle draw distribution."""
        pet = _pet()
        n = 30
        tasks = [Task(ttype="generate", data_id=f"d{i}", op="generate",
                      params=(4,), arrival=float(i), deadline=1e9)
                 for i in range(n)]
        seq = Simulator(
            [Task(ttype=t.ttype, data_id=t.data_id, op=t.op,
                  params=t.params, arrival=t.arrival, deadline=t.deadline)
             for t in tasks],
            [Machine(mid=0)], PETOracle(pet, seed=5), SimConfig()).run()
        bat = Simulator(
            tasks, [Machine(mid=0)], PETOracle(pet, seed=5),
            SimConfig(batching=StepBatchingConfig(max_batch=8))).run()
        assert bat.on_time + bat.missed + bat.dropped == n
        assert bat.makespan < seq.makespan


# ---------------------------------------------------------------------------
# recalibrated cold-start estimator (satellite)
# ---------------------------------------------------------------------------

class TestColdEstimate:
    def test_default_rates_reproduce_legacy_formula(self):
        est = TimeEstimator()
        for plen, n_new in ((16, 1), (64, 8), (300, 32), (4096, 128)):
            mu, _ = est.mean_std("generate", plen, n_new)
            legacy = max(5.0 * (plen + n_new * 4) / 64.0, 1.0)
            assert mu == legacy

    def test_calibrate_reprices_cold_estimates(self):
        est = TimeEstimator()
        est.calibrate(prefill_rate=0.01, decode_rate=2.0)
        mu, _ = est.mean_std("generate", 1000, 2)
        # decode-dominated now: the old blob formula would say ~85 ticks
        assert mu == pytest.approx(1000 * 0.01 + 2 * 2.0)

    def test_live_engine_calibrates_on_warmup(self, tiny_model):
        cfg, params = tiny_model
        eng = ServingEngine(cfg, params, EngineConfig(
            n_units=1, elasticity=None, merging="none", pruning=None,
            result_cache=False, max_len=96, batch_buckets=(1, 2),
            batching=StepBatchingConfig(max_batch=2)))
        est = eng.estimator
        assert (est.prefill_rate, est.decode_rate) != (5.0 / 64, 20.0 / 64)
        assert est.prefill_rate > 0 and est.decode_rate > 0
