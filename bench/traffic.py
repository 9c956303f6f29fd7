"""One general traffic generator over the mixes in ``bench/traffic/*.json``.

A mix is data: its kind (``open`` single-turn requests, or ``sessions`` of
turns that share a tenant's system prompt and their own history), its
rates, its length distributions and its deadline model.  Everything is
drawn from ``--seed``.

Every seed gets the same schedule.  Lengths, gaps between arrivals, think
times, turn counts and tenant choices are the quantiles (i + 1/2)/n of
their distributions, paired and ordered by a fixed shuffle (``SCHEDULE``);
the seed draws the token ids, as it draws the weights.  A window holds a
few dozen requests at this system's speed, so a seed that reordered them
would change how much work falls inside it: runs of different seeds read
as far apart as 50% that way (TPU v5e, smollm-360m.sessions at 0.1
sessions/s), and two runs of one seed within 1%.

Lengths are rounded up to ``grid`` tokens, so that the prefill executables
a mix can reach form a small set, which ``chunk_shapes`` enumerates for the
warm-up.
"""

from __future__ import annotations

import heapq
import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

DIR = Path(__file__).resolve().parent / "traffic"
_Z = NormalDist()


def load(name: str) -> dict:
    return json.loads((DIR / f"{name}.json").read_text())


def grid_ceil(n: float, grid: int) -> int:
    return int(-(-int(math.ceil(n)) // grid) * grid)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


#: the shuffle that pairs and orders the schedule, the same for every seed
SCHEDULE = 20240601


def lognormal(d: dict, n: int, rng) -> np.ndarray:
    """``n`` stratified draws of a lognormal given by its median and sigma,
    clipped to [lo, hi], in ``rng``'s order."""
    z = np.array([_Z.inv_cdf(q) for q in _quantiles(n)])
    v = np.clip(d["median"] * np.exp(d["sigma"] * z), d["lo"], d["hi"])
    return rng.permutation(v)


def exponential(mean: float, n: int, rng) -> np.ndarray:
    return rng.permutation(-mean * np.log1p(-_quantiles(n)))


def uniform_int(lo: int, hi: int, n: int, rng) -> np.ndarray:
    return rng.permutation(lo + np.floor(_quantiles(n) * (hi - lo + 1))
                           .astype(int))


def zipf_counts(n_items: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` draws fall on each of ``n_items`` ranks under
    Zipf(s) popularity, by largest remainder."""
    w = 1.0 / np.arange(1, n_items + 1) ** s
    exact = n * w / w.sum()
    cnt = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - cnt))[:n - cnt.sum()]:
        cnt[i] += 1
    return cnt


@dataclass
class Item:
    """One request of the schedule: due (seconds from the window's open),
    prompt ids, tokens to generate, and its deadline (seconds after due)."""

    due: float
    prompt: np.ndarray
    n_new: int
    slack: float
    session: int | None = None
    turn: int = 0


def slack(spec: dict, plen: int, n_new: int) -> float:
    """beta times the mix's model of unloaded latency."""
    m = spec["latency_model"]
    return spec["beta"] * (m["a_s"] + m["b_s_per_prompt_token"] * plen
                           + m["c_s_per_answer_token"] * n_new)


class OpenSource:
    """Single-turn requests at fixed due times."""

    def __init__(self, spec: dict, vocab: int, seconds: float, seed: int):
        rng = np.random.default_rng([seed, 1])
        fixed = np.random.default_rng(SCHEDULE)
        n = max(1, int(round(spec["rate_per_s"] * seconds)))
        gaps = exponential(1.0 / spec["rate_per_s"], n, fixed)
        due = np.cumsum(gaps) - gaps[0]
        plens = [grid_ceil(x, spec["grid"])
                 for x in lognormal(spec["prompt"], n, fixed)]
        answers = np.rint(lognormal(spec["answer"], n, fixed)).astype(int)
        self.items = []
        for i in range(n):
            n_new = int(min(answers[i], spec["max_total"] - plens[i]))
            self.items.append(Item(
                float(due[i]), rng.integers(1, vocab, size=plens[i],
                                            dtype=np.int64),
                n_new, slack(spec, plens[i], n_new)))
        self._next = 0

    def next_due(self) -> float | None:
        return (self.items[self._next].due if self._next < len(self.items)
                else None)

    def pop(self) -> Item:
        self._next += 1
        return self.items[self._next - 1]

    def on_finish(self, item: Item, wall: float, ok: bool) -> None:
        pass


class SessionSource:
    """Sessions of turns: a tenant's system prompt, then user turns and
    answers that grow the history.  The first turn is due at the session's
    start; each later one a think time after the previous turn finished
    (closed loop inside a session, open loop across sessions).  The answer
    re-enters the next prompt as seed-drawn ids of the served length.
    Every session's lengths and think times are fixed by the schedule and
    its ids by the seed, so only the due times of later turns depend on the
    run."""

    def __init__(self, spec: dict, vocab: int, seconds: float, seed: int):
        rng = np.random.default_rng([seed, 2])
        fixed = np.random.default_rng(SCHEDULE)
        self.spec, self.vocab, self.seed = spec, vocab, seed
        self.grid = spec["grid"]
        n = max(1, int(round(spec["session_rate_per_s"] * seconds)))
        t = spec["tenants"]
        self.sys = [rng.integers(1, vocab, size=L, dtype=np.int64)
                    for L in t["system_prompt_tokens"]]
        tenant_of = fixed.permutation(np.repeat(
            np.arange(len(self.sys)),
            zipf_counts(len(self.sys), t["zipf_s"], n)))
        turns = uniform_int(spec["turns"][0], spec["turns"][1], n, fixed)
        k = spec["turns"][1]
        user = lognormal(spec["user"], n * k, fixed).reshape(n, k)
        answer = np.rint(lognormal(spec["answer"], n * k, fixed)) \
            .astype(int).reshape(n, k)
        think = exponential(spec["think_mean_s"], n * k, fixed).reshape(n, k)
        gaps = exponential(1.0 / spec["session_rate_per_s"], n, fixed)
        starts = np.cumsum(gaps) - gaps[0]
        self.sessions = [{"tenant": int(tenant_of[i]), "turns": int(turns[i]),
                          "user": user[i], "answer": answer[i],
                          "think": think[i], "done": 0,
                          "history": self.sys[int(tenant_of[i])],
                          "rng": np.random.default_rng([seed, 3, i])}
                         for i in range(n)]
        self._heap = [(float(starts[i]), i) for i in range(n)]
        heapq.heapify(self._heap)

    def _turn(self, sid: int, due: float) -> Item | None:
        s = self.sessions[sid]
        j, hist = s["done"], s["history"]
        plen = grid_ceil(len(hist) + s["user"][j], self.grid)
        n_new = int(s["answer"][j])
        if plen + n_new > self.spec["max_total"]:
            return None
        user = s["rng"].integers(1, self.vocab, size=plen - len(hist),
                                 dtype=np.int64)
        return Item(due, np.concatenate([hist, user]), n_new,
                    slack(self.spec, plen, n_new), session=sid, turn=j)

    def next_due(self) -> float | None:
        return self._heap[0][0] if self._heap else None

    def pop(self) -> Item | None:
        due, sid = heapq.heappop(self._heap)
        return self._turn(sid, due)

    def on_finish(self, item: Item, wall: float, ok: bool) -> None:
        """Schedule the session's next turn a think time after ``wall``."""
        s = self.sessions[item.session]
        j = s["done"]
        s["done"] += 1
        if not ok or s["done"] >= s["turns"]:
            return
        answer = s["rng"].integers(1, self.vocab, size=item.n_new,
                                   dtype=np.int64)
        s["history"] = np.concatenate([item.prompt, answer])
        heapq.heappush(self._heap, (wall + float(s["think"][j]),
                                    item.session))


def source(spec: dict, vocab: int, seconds: float, seed: int):
    kinds = {"open": OpenSource, "sessions": SessionSource}
    return kinds[spec["kind"]](spec, vocab, seconds, seed)


def chunk_shapes(spec: dict, prefix_max_prompt: int) -> list[tuple[int, int]]:
    """Every (chunk, cached prefix) pair a mix can give a one-chunk prefill.

    Open mixes share nothing, so the prefix is 0 and the chunk is a prompt
    length on the grid.  In a session the cache holds whole earlier prompts
    (the tenant's system prompt, or an earlier turn) of at most
    ``prefix_max_prompt`` tokens, and a prompt longer than that is neither
    looked up nor stored; so a prompt of P + C tokens with P > 0 has
    P >= the shortest system prompt and P + C <= ``prefix_max_prompt``.
    """
    g = spec["grid"]
    if spec["kind"] == "open":
        lo = grid_ceil(spec["prompt"]["lo"], g)
        hi = grid_ceil(spec["prompt"]["hi"], g)
        return [(c, 0) for c in range(lo, hi + 1, g)]
    sys_lo = min(spec["tenants"]["system_prompt_tokens"])
    first = grid_ceil(sys_lo + spec["user"]["lo"], g)
    hi = spec["max_total"] - int(spec["answer"]["lo"])
    shapes = [(c, 0) for c in range(first, hi + 1, g)]
    for p in range(grid_ceil(sys_lo, g), prefix_max_prompt, g):
        shapes += [(c, p) for c in range(g, prefix_max_prompt - p + 1, g)]
    return shapes
