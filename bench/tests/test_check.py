"""The comparison that decides ``correct``, at toy widths on the CPU.

A whole run of a tiny cell through the served path must come out correct;
the float8 control, judged in the program's place by the same limits over
the same sample, must come out not correct; and a run whose timed path is
broken underneath (the chip check skipped, everything else as in a
benchmark run) must come out not correct, once for each fault a served cell
can have: a token altered where it is produced, a decode step that leaves
the KV state unchanged, and a norm scale left out.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import reference
from bench.tests import tiny

SEED = 2**31 + 77
SECONDS = 2.0


def run(spec, **kw):
    return harness.run(spec, SEED, SECONDS, False, require_tpu=False,
                       log=lambda *a, **k: None, **kw)


@pytest.fixture(scope="module")
def runner_cls():
    harness._import_program()
    from repro.serving.engine import _UnitRunner
    return _UnitRunner


def test_tiny_run_is_correct():
    out = run(tiny.spec(tiny.sessions()))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["max_logit_gap"]["value"] <= \
        out["checks"]["max_logit_gap"]["limit"]


def test_fp8_control_reads_over_the_limit():
    """The control: the reference computed in float8, put in the program's
    place over the run's own sample, puts other tokens first, and is judged
    not correct by the limit that the program stays under."""
    out = run(tiny.spec(tiny.sessions()), control=True)
    assert out["correct"], out["checks"]
    assert not out["control"]["correct"], out["control"]
    gap = out["control"]["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] > \
        out["checks"]["max_logit_gap"]["value"]
    assert out["info"]["sampled_cache_seeded"] > 0


@pytest.mark.parametrize("norm", ["final_ln", "ln1", "ln2"])
def test_dropped_norm_scale_is_not_correct(norm, monkeypatch):
    """The program serves with one norm's scales left at 1 (as if it
    skipped them); the reference reads the seeded scales."""
    from bench import weights as W

    make = W.make_params

    def dropped(cfg, seed):
        p = make(cfg, seed)
        tree = p if norm == "final_ln" else p["layers"]
        tree[norm] = {"scale": jnp.ones_like(tree[norm]["scale"])}
        return p

    monkeypatch.setattr(W, "make_params", dropped)
    out = run(tiny.spec(tiny.sessions()))
    assert not out["correct"], out["checks"]


def test_altered_token_is_not_correct(runner_cls, monkeypatch):
    complete = runner_cls.complete

    def altered(self, s):
        st = self.states.get(id(s))
        if st is not None and st["out"]:
            st["out"][-1] = (st["out"][-1] + 1) % self.eng.model_cfg.vocab
        return complete(self, s)

    monkeypatch.setattr(runner_cls, "complete", altered)
    out = run(tiny.spec(tiny.sessions()))
    assert not out["correct"]


def test_unchanged_kv_state_is_not_correct(runner_cls, monkeypatch):
    init = runner_cls.__init__

    def stale(self, *a, **k):
        init(self, *a, **k)
        pdec = self._pdec

        def step(params, kp, vp, *rest):
            logits, _, _ = pdec(params, kp, vp, *rest)
            return logits, kp, vp

        self._pdec = step

    monkeypatch.setattr(runner_cls, "__init__", stale)
    out = run(tiny.spec(tiny.sessions()))
    assert not out["correct"]


def test_reference_matches_itself_under_jit_caching():
    model = harness.model_block(copy.deepcopy(tiny.CONFIG))
    seqs = np.ones((2, 256), np.int32)
    a = reference.logits_at(model, 3, seqs, np.array([0, 1]),
                            np.array([5, 9]))
    b = reference.logits_at(model, 3, seqs, np.array([0, 1]),
                            np.array([5, 9]))
    assert np.array_equal(a, b)
    assert jax.devices()[0].platform == "cpu"
