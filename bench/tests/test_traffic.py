"""The traffic generator: seeded, on the grid, within the context, and
covered by the shapes the warm-up compiles.  CPU only, no JAX."""

import numpy as np
import pytest

from bench import traffic as TR
from bench.tests import tiny

MIXES = ["sessions", "chat_unshared"]
SEED = 2**31 + 12345


def drain(src, finish_at_due: bool = True):
    """Pop every request of a source, finishing each at its due time."""
    out = []
    while src.next_due() is not None:
        it = src.pop()
        if it is None:
            continue
        out.append(it)
        src.on_finish(it, it.due, True)
    return out


def schedule(items):
    return [(round(it.due, 9), len(it.prompt), it.n_new,
             tuple(it.prompt[:8].tolist())) for it in items]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    spec = TR.load(mix)
    a = drain(TR.source(spec, 1000, 30.0, SEED))
    b = drain(TR.source(spec, 1000, 30.0, SEED))
    c = drain(TR.source(spec, 1000, 30.0, SEED + 1))
    assert schedule(a) == schedule(b)
    assert schedule(a) != schedule(c)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_on_grid_within_context(mix):
    spec = TR.load(mix)
    items = drain(TR.source(spec, 1000, 30.0, SEED))
    assert items
    for it in items:
        assert len(it.prompt) % spec["grid"] == 0
        assert 1 <= it.n_new and len(it.prompt) + it.n_new <= spec["max_total"]
        assert it.prompt.min() >= 1 and it.prompt.max() < 1000
        assert it.slack > 0


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_schedule(mix):
    """Seeds draw the token ids; the schedule of sizes and due times is
    the same for every seed."""
    spec = TR.load(mix)
    work = [[(round(it.due, 9), len(it.prompt), it.n_new)
             for it in drain(TR.source(spec, 1000, 30.0, s))]
            for s in (1, 2, 2**33 + 3)]
    assert work[0] == work[1] == work[2]


def _prefix_len(prompt, cached, block: int = 16):
    """What the engine's block-granular radix cache reuses: the longest
    common prefix with any cached prompt, in whole blocks, short of the
    whole prompt."""
    best = 0
    for c in cached:
        n = min(len(c), len(prompt) - 1)
        diff = np.nonzero(prompt[:n] != c[:n])[0]
        lcp = int(diff[0]) if diff.size else n
        best = max(best, lcp // block * block)
    return best


@pytest.mark.parametrize("mix,pmax", [("sessions", 1024),
                                      ("chat_unshared", 1024)])
def test_shapes_cover_every_request(mix, pmax):
    """Replays the prefix cache as the engine keeps it (whole prompts of at
    most ``pmax`` tokens, looked up only for prompts of at most ``pmax``)
    and checks that every (chunk, prefix) the requests need is warmed."""
    spec = TR.load(mix)
    shapes = set(TR.chunk_shapes(spec, pmax))
    for seed in (SEED, 3, 4):
        cached = []
        for it in drain(TR.source(spec, 1000, 60.0, seed)):
            plen = len(it.prompt)
            p = _prefix_len(it.prompt, cached) if plen <= pmax else 0
            assert (plen - p, p) in shapes, (plen, p)
            if plen <= pmax:
                cached.append(it.prompt)


def test_sessions_share_prefixes():
    spec = TR.load("sessions")
    items = drain(TR.source(spec, 1000, 60.0, SEED))
    later = [it for it in items if it.turn > 0]
    assert later and all(it.session is not None for it in later)


def test_tiny_mixes_are_covered_too():
    for spec in (tiny.sessions(), tiny.unshared()):
        pmax = tiny.CONFIG["serve"]["prefix_max_prompt"]
        shapes = set(TR.chunk_shapes(spec, pmax))
        cached = []
        for it in drain(TR.source(spec, 256, 10.0, 5)):
            plen = len(it.prompt)
            p = _prefix_len(it.prompt, cached) if plen <= pmax else 0
            assert (plen - p, p) in shapes
            if plen <= pmax:
                cached.append(it.prompt)


def test_stratified_draws_are_quantiles():
    rng = np.random.default_rng(0)
    d = {"median": 100, "sigma": 0.5, "lo": 10, "hi": 1000}
    v = np.sort(TR.lognormal(d, 101, rng))
    assert v[50] == pytest.approx(100.0)
    assert TR.zipf_counts(4, 1.0, 25).sum() == 25
