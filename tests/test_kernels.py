"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp ref oracles.  Off an accelerator the front
doors resolve ``interpret=None`` to the Pallas interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # minimal install: keep unit tests, skip property tests
    from conftest import given, settings, st  # noqa: F401

from repro.core.pmf import PMF, chance_of_success
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.pmf_conv.ops import batched_success, pmf_conv
from repro.kernels.pmf_conv.pmf_conv import pmf_conv_pallas
from repro.kernels.pmf_conv.ref import pmf_conv_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# pmf_conv
# ---------------------------------------------------------------------------

class TestPmfConv:
    def _data(self, n, le, lc, seed=0):
        rng = np.random.default_rng(seed)
        pet = rng.random((n, le)).astype(np.float32)
        pet /= pet.sum(axis=1, keepdims=True)
        pct = rng.random((n, lc)).astype(np.float32)
        pct /= pct.sum(axis=1, keepdims=True)
        dl = rng.integers(0, le + lc, size=n).astype(np.float32)
        return jnp.asarray(pet), jnp.asarray(pct), jnp.asarray(dl)

    @pytest.mark.parametrize("n,le,lc", [(4, 8, 16), (16, 32, 32),
                                         (3, 5, 64), (9, 64, 128)])
    def test_matches_ref(self, n, le, lc):
        pet, pct, dl = self._data(n, le, lc)
        out_k, suc_k = pmf_conv(pet, pct, dl, use_kernel=True)
        out_r, suc_r = pmf_conv_ref(pet, pct, dl)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=1e-6, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(suc_k), np.asarray(suc_r),
                                   atol=1e-6, rtol=1e-5)

    def test_mass_conservation(self):
        pet, pct, dl = self._data(8, 16, 24, seed=3)
        out, _ = pmf_conv(pet, pct, dl)
        np.testing.assert_allclose(np.asarray(out).sum(axis=1), 1.0,
                                   atol=1e-5)

    def test_success_against_core_pmf(self):
        """End-to-end: kernel success == core.pmf.chance_of_success."""
        rng = np.random.default_rng(7)
        pets, pcts, dls = [], [], []
        for _ in range(12):
            e = PMF.from_normal(rng.uniform(8, 30), rng.uniform(1, 5))
            c = PMF.from_normal(rng.uniform(10, 60), rng.uniform(2, 8))
            pets.append(e)
            pcts.append(c)
            dls.append(int(e.mean() + c.mean() + rng.integers(-10, 15)))
        got = batched_success(pets, pcts, dls, length=128)
        want = [chance_of_success(e, c, dl, droppable_prev=True)
                for e, c, dl in zip(pets, pcts, dls)]
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_success_exact_when_first_pet_impulse_has_mass(self):
        """PETs clamped at t=1 carry real mass on their first impulse; the
        previous task may then free the machine exactly at dl - 1 and the
        kernel path must count it, as ``chance_of_success`` does."""
        rng = np.random.default_rng(7)
        pets = [PMF.from_normal(rng.uniform(3, 6), rng.uniform(1, 2))
                for _ in range(32)]
        pcts = [PMF.from_normal(rng.uniform(5, 30), rng.uniform(1, 5))
                for _ in range(32)]
        dls = [int(e.mean() + c.mean() + rng.integers(-8, 12))
               for e, c in zip(pets, pcts)]
        assert all(p.offset == 1 for p in pets)
        got = batched_success(pets, pcts, dls, length=64)
        want = [chance_of_success(e, c, dl, droppable_prev=True)
                for e, c, dl in zip(pets, pcts, dls)]
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_rolled_kernel_matches_ref_at_autoscaler_grid(self):
        """The lane-rotate formulation at the success-chance autoscaler's
        (32, 64) grid, with deadlines before, inside and past the grid —
        every shift 0..63 runs, so a wrong rotate direction cannot pass."""
        pet, pct, _ = self._data(32, 64, 64, seed=11)
        dl = jnp.asarray(np.linspace(-1, 140, 32).round(), jnp.float32)
        out_k, suc_k = pmf_conv_pallas(pet, pct, dl, interpret=True)
        out_r, suc_r = pmf_conv_ref(pet, pct, dl)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=1e-6, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(suc_k), np.asarray(suc_r),
                                   atol=1e-6, rtol=1e-5)

    def test_interpret_none_resolves_through_default(self, monkeypatch):
        from repro.kernels.pmf_conv import ops as pmf_ops
        calls = []

        def default():
            calls.append(1)
            return True         # no accelerator here: interpret

        monkeypatch.setattr(pmf_ops, "interpret_default", default)
        pet, pct, dl = self._data(4, 8, 16)
        out, suc = pmf_conv(pet, pct, dl)
        assert calls == [1]
        np.testing.assert_allclose(np.asarray(suc),
                                   np.asarray(pmf_conv_ref(pet, pct, dl)[1]),
                                   atol=1e-6, rtol=1e-5)
        batched_success([PMF.impulse(3)], [PMF.impulse(2)], [9], length=16)
        assert calls == [1, 1]

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 12), st.integers(2, 24), st.integers(2, 48),
           st.integers(0, 10_000))
    def test_prop_kernel_equals_ref(self, n, le, lc, seed):
        pet, pct, dl = self._data(n, le, lc, seed=seed)
        out_k, suc_k = pmf_conv(pet, pct, dl, use_kernel=True)
        out_r, suc_r = pmf_conv_ref(pet, pct, dl)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=1e-6, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(suc_k), np.asarray(suc_r),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

class TestDecodeAttention:
    def _data(self, b, s, h, hkv, hd, dtype, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (b, h, hd), dtype)
        k = jax.random.normal(ks[1], (b, s, hkv, hd), dtype)
        v = jax.random.normal(ks[2], (b, s, hkv, hd), dtype)
        lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
        return q, k, v, lengths

    @pytest.mark.parametrize("b,s,h,hkv,hd,bs", [
        (2, 128, 8, 4, 32, 64), (1, 256, 4, 1, 64, 128),
        (3, 96, 6, 2, 16, 32), (2, 512, 16, 16, 64, 512),
    ])
    def test_matches_ref_shapes(self, b, s, h, hkv, hd, bs):
        q, k, v, lengths = self._data(b, s, h, hkv, hd, jnp.float32)
        out = decode_attention(q, k, v, lengths, block_s=bs)
        ref = decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q, k, v, lengths = self._data(2, 64, 4, 2, 32, dtype)
        out = decode_attention(q, k, v, lengths, block_s=32)
        ref = decode_attention_ref(q, k, v, lengths)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    def test_masking_exact(self):
        """Entries beyond `length` must not affect the output at all."""
        q, k, v, lengths = self._data(2, 64, 4, 2, 32, jnp.float32)
        lengths = jnp.array([10, 30])
        out1 = decode_attention(q, k, v, lengths, block_s=16)
        k2 = k.at[:, 40:].set(99.0)
        v2 = v.at[:, 40:].set(-99.0)
        out2 = decode_attention(q, k2, v2, lengths, block_s=16)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 3), st.sampled_from([32, 48, 96]),
           st.sampled_from([(4, 2), (4, 4), (8, 2)]),
           st.integers(0, 10_000))
    def test_prop_kernel_equals_ref(self, b, s, heads, seed):
        h, hkv = heads
        q, k, v, lengths = self._data(b, s, h, hkv, 16, jnp.float32, seed)
        out = decode_attention(q, k, v, lengths, block_s=32)
        ref = decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

class TestRmsnorm:
    @pytest.mark.parametrize("shape,dtype", [
        ((4, 128), jnp.float32), ((2, 16, 256), jnp.bfloat16),
        ((1, 960), jnp.float32), ((5, 7, 64), jnp.bfloat16),
    ])
    def test_matches_ref(self, shape, dtype):
        x = jax.random.normal(KEY, shape, dtype)
        scale = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], dtype)
        out = rmsnorm(x, scale)
        ref = rmsnorm_ref(x, scale)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-2, rtol=2e-2)

    def test_unit_variance(self):
        x = 37.0 * jax.random.normal(KEY, (8, 512), jnp.float32)
        out = rmsnorm(x, jnp.ones((512,)))
        rms = np.asarray(jnp.sqrt(jnp.mean(out * out, axis=-1)))
        np.testing.assert_allclose(rms, 1.0, atol=1e-3)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 64), st.sampled_from([64, 128, 384]),
           st.integers(0, 10_000))
    def test_prop_kernel_equals_ref(self, rows, d, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (rows, d),
                              jnp.float32)
        scale = jnp.ones((d,))
        np.testing.assert_allclose(np.asarray(rmsnorm(x, scale)),
                                   np.asarray(rmsnorm_ref(x, scale)),
                                   atol=1e-5, rtol=1e-5)
