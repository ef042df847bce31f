"""The traffic generator: the same seed gives the same plan, seeds differ
only in order, sizes stay in their ranges and context caps, and turns
are played as a base answer followed by evaluations over history and
answer."""
from __future__ import annotations

import enum
import json
import sys
from collections import Counter, deque
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench import driver, traffic  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
BIG = 2**31 + 12345          # seeds are any whole number, some > 32 bits


def mix(name, seed, n_inv=3):
    return traffic.Mix.build(traffic.load_mix(BENCH / "traffic"
                                              / f"{name}.json"), seed, n_inv)


def plan(name, seed, n=60):
    m = mix(name, seed)
    out = []
    for s in m.sessions():
        out.append(s)
        if len(out) == n:
            return m, out


def sizes(sessions):
    return sorted((t.message, t.answer, tuple(sorted(t.evals)), t.direct)
                  for s in sessions for t in s.turns)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan(name):
    _, a = plan(name, BIG)
    _, b = plan(name, BIG)
    assert a == b


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ_in_order_not_in_sizes(name):
    m, a = plan(name, 7, n=64)
    _, b = plan(name, 8, n=64)
    assert a != b
    # one whole cycle of the first-message pool: the same multiset
    assert Counter(s.turns[0].message for s in a) \
        == Counter(s.turns[0].message for s in b)
    if a[0].arrival is not None:
        assert a[-1].arrival == pytest.approx(b[-1].arrival)


@pytest.mark.parametrize("name", MIXES)
def test_sizes_in_range_and_contexts_capped(name):
    m, ss = plan(name, 3, n=200)
    p = m.params
    for s in ss:
        assert 1 <= len(s.turns)
        assert len(s.turns) <= max(p["turns"]["values"])
        ctx = 0
        for k, t in enumerate(s.turns):
            spec = p["first_message"] if k == 0 else p["message"]
            assert spec["lo"] <= t.message <= spec["hi"]
            assert p["answer"]["lo"] <= t.answer <= p["answer"]["hi"]
            for a, n in t.evals:
                ev = p["evaluations"]
                assert 0 <= a < ev["of"]
                assert ev["len"]["lo"] <= n <= ev["len"]["hi"]
            assert len({a for a, _ in t.evals}) == len(t.evals)
            ctx += t.message + t.answer
        lo, hi = traffic.context_bounds([s], 3)
        assert hi <= p["max_context"]


def test_context_caps_of_the_cells():
    caps = {n: traffic.load_mix(BENCH / "traffic" / f"{n}.json")
            ["max_context"] for n in MIXES}
    assert caps["pipeline"] == 2048
    assert caps["short-batch"] == 512


@pytest.mark.parametrize("name", MIXES)
def test_length_support_covers_every_seed(name):
    sup = set(traffic.length_support(mix(name, 1)))
    for seed in (1, 2, BIG):
        m = mix(name, seed)
        used = traffic.prompt_lengths(m.planned(120, 300), 3)
        assert set(used) <= sup


def test_direct_share_exact_per_cycle():
    m, ss = plan("short-batch", 11, n=64)
    assert sum(t.direct is not None for s in ss for t in s.turns) == 32


# ---------------------------------------------------------------------------
# the client plays turns in order, against a stand-in engine
# ---------------------------------------------------------------------------
class State(enum.Enum):
    QUEUED = "queued"
    DECODE = "decode"
    DONE = "done"


class FakeReq:
    def __init__(self, rid, prompt, max_new, adapter):
        self.req_id, self.prompt, self.max_new = rid, prompt, max_new
        self.adapter = adapter
        self.inv_start = len(prompt)
        self.output_tokens = []
        self.state = State.QUEUED
        self.n_computed = self.n_cache_hit_tokens = 0


class FakeEngine:
    """One token per request per step; answers count 1000, 1001, ..."""

    def __init__(self):
        self.waiting, self.running, self.log = deque(), [], []

    def submit(self, prompt, max_new, adapter_name=None):
        r = FakeReq(len(self.log), list(prompt), max_new, adapter_name)
        self.log.append(r)
        self.waiting.append(r)
        return r.req_id

    def step(self):
        while self.waiting:
            r = self.waiting.popleft()
            r.state = State.DECODE
            r.n_computed = len(r.prompt)
            self.running.append(r)
        for r in self.running:
            r.output_tokens.append(1000 + len(r.output_tokens))
            r.n_computed += 1
            if len(r.output_tokens) == r.max_new:
                r.state = State.DONE
        self.running = [r for r in self.running if r.state != State.DONE]


def test_turns_are_base_then_evaluations_over_history_and_answer():
    params = json.loads((BENCH / "traffic" / "pipeline.json").read_text())
    params["arrival"]["rate_per_s"] = 1000.0
    params["think_s"] = {"values": [0.0]}
    m = traffic.Mix.build(params, 5, 3)
    sessions = m.planned(0.01, 0)
    eng = FakeEngine()
    names = [f"a{i}" for i in range(4)]
    t = [0.0]
    c = driver.Client(eng, m, names, [3, 4, 5], 49155,
                      clock=lambda: t[0])
    c.window, c.stop_at = (0.0, 1e9), 1e9
    c.start(0.0, sessions)
    for _ in range(5000):
        t[0] += 1e-3
        c._release_due(t[0])
        if c.busy():
            c.step()
    assert sessions and all(tr.done is not None for tr in c.turns)
    for s in sessions:
        turns = [tr for tr in c.turns if tr.session is s]
        assert len(turns) == len(s.turns)
        history = []
        for k, tr in enumerate(turns):
            base, *evals = tr.recs
            assert base.kind == "base" and base.adapter is None
            assert base.req.prompt[:len(history)] == history
            assert len(base.req.prompt) == len(history) + s.turns[k].message
            answer = base.req.output_tokens
            assert len(answer) == s.turns[k].answer
            assert [e.kind for e in evals] == ["eval"] * 2
            assert sorted(e.adapter for e in evals) == sorted(
                names[a] for a, _ in s.turns[k].evals)
            for e in evals:
                assert e.req.prompt == base.req.prompt + answer + [3, 4, 5]
                assert e.due >= base.done
            history = base.req.prompt + answer
