"""The one traffic generator: reads a mix's parameters (``bench/traffic/
<mix>.json``) and plans sessions from ``--seed``.

A mix describes sessions of turns.  A turn is a base request (the
session's context so far plus a new message, answered by the base
model), then ``evaluations.per_turn`` aLoRA requests over the base
prompt, its answer and the adapter's invocation tokens, submitted
together once the answer is complete.  A turn may instead go straight to
one adapter (``direct_adapter_share``): its prompt ends in the
invocation tokens and nothing follows.  The next turn is due a think
time after the turn's last token.

A mix may share documents among its sessions (``documents``: ``count``
documents, their lengths ``len``, popularity Zipf(``zipf``)): a session's
first message is then one document followed by the ``first_message``
tokens.  Document ``d`` has the ``d``-th of ``count`` quantiles of
``len`` (of its ``values``, in their order) for every seed, and the same
token ids in every session of a seed, so the prefix cache can serve it;
``d = 0`` is the most popular.

Sessions arrive open loop (``arrival.kind = "poisson"``, a rate per
second) or come from a fixed number of closed-loop clients, each
starting its next session when the last one has ended.

Every seed draws the same multiset of sizes, gaps and choices, in an
order of its own: each distribution is a fixed pool of ``pool`` values
(quantiles of the stated distribution, rounded to ``quantum`` tokens),
shuffled by the seed and drawn without replacement, cycle after cycle.
Token ids are drawn from the seed in ``[TOKEN_LO, vocab)``, so no
prompt contains an invocation sequence by chance.
"""
from __future__ import annotations

import itertools
import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

TOKEN_LO = 10


def load_mix(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any whole number)."""
    return np.random.default_rng([abs(int(seed)) % (1 << 64), *stream])


# ---------------------------------------------------------------------------
# fixed pools, shuffled per seed
# ---------------------------------------------------------------------------
def quantile_pool(spec: dict, n: int, quantum: int = 1) -> List[float]:
    """``n`` quantiles of a distribution spec, at (i + 0.5) / n.

    ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` (clipped),
    ``{"dist": "exponential", "mean"}``, ``{"dist": "uniform", "lo", "hi"}``
    or ``{"values": [...]}`` (each value equally often).  A spec's own
    ``quantum`` overrides the mix's."""
    if "values" in spec:
        vals = list(spec["values"])
        return [vals[i % len(vals)] for i in range(n)]
    qs = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "lognormal":
        nd = NormalDist()
        out = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
               for q in qs]
    elif dist == "exponential":
        out = [-spec["mean"] * math.log(1.0 - q) for q in qs]
    elif dist == "uniform":
        out = [spec["lo"] + q * (spec["hi"] - spec["lo"]) for q in qs]
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "lo" in spec:
        out = [min(max(v, spec["lo"]), spec["hi"]) for v in out]
    quantum = spec.get("quantum", quantum)
    if quantum:
        out = [int(round(v / quantum) * quantum) for v in out]
        if "lo" in spec:
            lo = int(math.ceil(spec["lo"] / quantum) * quantum)
            hi = int(math.floor(spec["hi"] / quantum) * quantum)
            out = [min(max(v, lo), hi) for v in out]
    return out


def zipf_picks(count: int, a: float, n: int) -> List[int]:
    """``n`` picks among ``count`` items, item ``i`` as often as Zipf(``a``)
    gives it, (i + 1)^-a / sum: the counts apportioned by largest
    remainder, so each share is within 1 / n of Zipf's."""
    w = [(i + 1) ** -a for i in range(count)]
    exact = [n * x / sum(w) for x in w]
    k = [int(e) for e in exact]
    for i in sorted(range(count), key=lambda i: k[i] - exact[i])[
            :n - sum(k)]:
        k[i] += 1
    return [i for i in range(count) for _ in range(k[i])]


class Pool:
    """Draws a fixed multiset in a seeded order, reshuffled each cycle."""

    def __init__(self, values: Sequence, rng: np.random.Generator):
        self.values = list(values)
        self.rng = rng
        self._order: List[int] = []

    def draw(self):
        if not self._order:
            self._order = list(self.rng.permutation(len(self.values)))
        return self.values[self._order.pop()]


# ---------------------------------------------------------------------------
# the plan: sessions, turns and their sizes, fixed by the seed
# ---------------------------------------------------------------------------
@dataclass
class Turn:
    message: int                     # new prompt tokens of this turn
    answer: int                      # tokens the base (or adapter) answers
    evals: List[Tuple[int, int]]     # (adapter index, tokens) per evaluation
    direct: Optional[int] = None     # adapter index of a direct turn
    doc: Optional[int] = None        # the shared document the message
                                     # starts with (counted in ``message``)


@dataclass
class Session:
    sid: int
    arrival: Optional[float]         # open loop: seconds after the start
    turns: List[Turn]
    think: List[float]               # seconds after each turn


@dataclass
class Mix:
    """A traffic mix's parameters with the pools of one seed."""
    params: dict
    seed: int
    n_inv: int                       # invocation tokens per adapter prompt
    pools: Dict[str, Pool] = field(default_factory=dict)
    doc_lens: List[int] = field(default_factory=list)
    _doc_ids: Dict[Tuple[int, int], List[int]] = field(default_factory=dict)

    @classmethod
    def build(cls, params: dict, seed: int, n_inv: int) -> "Mix":
        m = cls(params, seed, n_inv)
        n, q = params.get("pool", 64), params.get("quantum", 1)
        specs = {"first": params["first_message"],
                 "message": params.get("message", params["first_message"]),
                 "answer": params["answer"],
                 "turns": params.get("turns", {"values": [1]}),
                 "think": params.get("think_s", {"values": [0.0]})}
        ev = params.get("evaluations")
        if ev:
            specs["eval_len"] = ev["len"]
        for i, (name, spec) in enumerate(sorted(specs.items())):
            quantum = 0 if name == "think" or "values" in spec else q
            m.pools[name] = Pool(quantile_pool(spec, n, quantum),
                                 rng_for(seed, 1, i))
        if ev:
            combos = list(itertools.combinations(range(ev["of"]),
                                                 ev["per_turn"]))
            m.pools["eval_pick"] = Pool(combos, rng_for(seed, 2))
        share = params.get("direct_adapter_share", 0.0)
        n_adapters = params.get("adapters", 0)
        if share:
            k = int(round(share * n))
            m.pools["direct"] = Pool([True] * k + [False] * (n - k),
                                     rng_for(seed, 3))
            m.pools["direct_pick"] = Pool(
                [i % n_adapters for i in range(n)], rng_for(seed, 4))
        arr = params["arrival"]
        if arr["kind"] == "poisson":
            m.pools["gap"] = Pool(
                quantile_pool({"dist": "exponential",
                               "mean": 1.0 / arr["rate_per_s"]}, n, 0),
                rng_for(seed, 6))
        docs = params.get("documents")
        if docs:
            # after the pools above, on streams of their own, so that a
            # mix without documents draws what it always drew
            m.doc_lens = quantile_pool(docs["len"], docs["count"], q)
            m.pools["doc"] = Pool(zipf_picks(docs["count"], docs["zipf"], n),
                                  rng_for(seed, 10))
        return m

    def document(self, d: int, vocab: int) -> List[int]:
        """Document ``d``'s token ids: the same in every session of the
        seed."""
        key = (d, vocab)
        if key not in self._doc_ids:
            g = rng_for(self.seed, 11, d)
            self._doc_ids[key] = list(map(int, g.integers(
                TOKEN_LO, vocab, self.doc_lens[d])))
        return self._doc_ids[key]

    def sessions(self) -> Iterator[Session]:
        """The seed's sessions in order of arrival (or of start, closed
        loop), each cut so its context never exceeds ``max_context``."""
        p = self.params
        cap = p["max_context"]
        t = 0.0
        for sid in itertools.count():
            arrival = None
            if p["arrival"]["kind"] == "poisson":
                t += self.pools["gap"].draw()
                arrival = t
            ctx = 0
            n_turns = int(self.pools["turns"].draw())
            turns, think = [], []
            for k in range(n_turns):
                msg = int(self.pools["first" if k == 0 else "message"]
                          .draw())
                doc = None
                if k == 0 and "doc" in self.pools:
                    doc = int(self.pools["doc"].draw())
                    msg += self.doc_lens[doc]
                ans = int(self.pools["answer"].draw())
                direct = None
                evals: List[Tuple[int, int]] = []
                if "direct" in self.pools and self.pools["direct"].draw():
                    direct = int(self.pools["direct_pick"].draw())
                    need = ctx + msg + self.n_inv + ans
                elif "eval_pick" in self.pools:
                    pick = self.pools["eval_pick"].draw()
                    evals = [(a, int(self.pools["eval_len"].draw()))
                             for a in pick]
                    need = ctx + msg + ans + self.n_inv + max(
                        e for _, e in evals)
                else:
                    need = ctx + msg + ans
                gap = float(self.pools["think"].draw())
                if need > cap:
                    if k == 0:
                        raise ValueError(
                            f"mix: a first turn needs {need} tokens, over "
                            f"max_context {cap}")
                    break
                turns.append(Turn(msg, ans, evals, direct, doc))
                think.append(gap)
                ctx += msg + ans
            yield Session(sid, arrival, turns, think)

    def planned(self, horizon_s: float, n_closed: int) -> List[Session]:
        """Every session that can start in a run: open loop, those that
        arrive before ``horizon_s``; closed loop, the first ``n_closed``."""
        out = []
        for s in self.sessions():
            if s.arrival is not None and s.arrival >= horizon_s:
                break
            if s.arrival is None and len(out) >= n_closed:
                break
            out.append(s)
        return out


def prompt_lengths(sessions: Sequence[Session], n_inv: int) -> List[int]:
    """Every prompt length these sessions submit (base and adapter)."""
    out = set()
    for s in sessions:
        ctx = 0
        for t in s.turns:
            if t.direct is not None:
                out.add(ctx + t.message + n_inv)
            else:
                out.add(ctx + t.message)
                if t.evals:
                    out.add(ctx + t.message + t.answer + n_inv)
            ctx += t.message + t.answer
    return sorted(out)


def length_support(mix: "Mix") -> List[int]:
    """Every prompt length the mix can submit under any seed: the pools
    hold the same values for every seed, so the contexts a session can
    reach are sums of them, turn by turn, under ``max_context``.  A run
    that makes each of these lengths once in set-up does the same work
    whatever its seed."""
    p, n_inv = mix.params, mix.n_inv
    vals = {k: set(pool.values) for k, pool in mix.pools.items()}
    cap = p["max_context"]
    ev = max(vals.get("eval_len", {0}))
    direct = "direct" in mix.pools
    out = set()
    ctx = {0}
    for k in range(int(max(vals["turns"]))):
        msgs = vals["first" if k == 0 else "message"]
        if k == 0 and mix.doc_lens:
            msgs = {d + m for d in mix.doc_lens for m in msgs}
        nxt = set()
        for c in ctx:
            for m in msgs:
                for a in vals["answer"]:
                    if c + m + a + n_inv + ev > cap and not (
                            direct and c + m + n_inv + a <= cap):
                        continue
                    if direct:
                        out.add(c + m + n_inv)
                    out.add(c + m)
                    if "eval_pick" in mix.pools:
                        out.add(c + m + a + n_inv)
                    nxt.add(c + m + a)
        ctx = nxt
    return sorted(out)


def context_bounds(sessions: Sequence[Session],
                   n_inv: int) -> Tuple[int, int]:
    """(shortest prompt, longest prompt + output) over these sessions."""
    lo, hi = 1 << 30, 0
    for s in sessions:
        ctx = 0
        for t in s.turns:
            lo = min(lo, ctx + t.message + (n_inv if t.direct is not None
                                            else 0))
            end = ctx + t.message + t.answer
            if t.direct is not None:
                end += n_inv
            for _, e in t.evals:
                end = max(end, ctx + t.message + t.answer + n_inv + e)
            hi = max(hi, end)
            ctx += t.message + t.answer
    return lo, hi
