"""The wall-clock client: submits a mix's requests to ``Engine`` when they
are due, drives ``Engine.step``, and stamps every output token when the
step that made it visible on the host returns.

All times are ``time.perf_counter()`` seconds on the client's side.  A
request's clock starts when it was due, not when the loop got round to
submitting it; the gap between the two is the generator's lateness.
"""
from __future__ import annotations

import contextlib
import heapq
import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from bench.traffic import TOKEN_LO, Mix, Session, rng_for

PENDING = -1          # the engine's placeholder for a token still on device


@dataclass
class Rec:
    """One request as the client sees it."""
    kind: str                        # "base" | "eval" | "direct"
    adapter: Optional[str]
    due: float
    measured: bool
    req: object                      # the engine's Request
    prompt_len: int
    max_new: int
    turn: Optional["TurnRec"] = None
    stamps: List[float] = field(default_factory=list)
    done: Optional[float] = None


@dataclass
class TurnRec:
    due: float
    measured: bool
    prompt: List[int]                # the base prompt: history + message
    session: Session
    index: int
    recs: List[Rec] = field(default_factory=list)
    done: Optional[float] = None


@dataclass
class StepRec:
    """What one ``Engine.step`` call computed: per request, the token
    positions ``[lo, hi)`` whose K/V it wrote, and from which position an
    adapter applies (``None``: none)."""
    t0: float
    t1: float
    spans: List[tuple]               # (lo, hi, adapter_from or None)


def _annotate(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class Client:
    """Plays one mix against one engine.

    ``adapter_names[i]`` is the engine's name of the mix's adapter ``i``;
    ``inv`` the invocation tokens every adapter prompt ends in."""

    def __init__(self, eng, mix: Mix, adapter_names: List[str],
                 inv: List[int], vocab: int, *,
                 annotate: bool = False,
                 clock: Callable[[], float] = time.perf_counter):
        self.eng = eng
        self.mix = mix
        self.names = adapter_names
        self.inv = list(inv)
        self.vocab = vocab
        self.clock = clock
        self.ann = _annotate(annotate)
        self.heap: list = []
        self._seq = itertools.count()
        self.active: List[Rec] = []
        self.recs: List[Rec] = []
        self.turns: List[TurnRec] = []
        self.steps: List[StepRec] = []
        self.lateness: List[float] = []
        self.window = (float("inf"), float("inf"))
        self.stop_at = float("inf")        # no new turn is due from here

    # -- schedule ---------------------------------------------------------
    def _at(self, due: float, fn: Callable[[float], None]) -> None:
        """Run ``fn(due)`` once the clock passes ``due``."""
        heapq.heappush(self.heap, (due, next(self._seq), fn))

    def start(self, t_start: float, sessions: List[Session]) -> None:
        """Schedule the planned sessions from ``t_start``: open loop at
        their arrivals, closed loop one per client, the rest as clients
        free up."""
        arr = self.mix.params["arrival"]
        if arr["kind"] == "poisson":
            for s in sessions:
                self._at(t_start + s.arrival,
                         lambda due, s=s: self._turn(s, 0, None, due))
        else:
            self._closed = iter(sessions)
            for _ in range(arr["clients"]):
                self._next_closed(t_start)

    def _next_closed(self, due: float) -> None:
        s = next(self._closed, None)
        if s is not None:
            self._at(due, lambda due, s=s: self._turn(s, 0, None, due))

    def _in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    # -- requests ---------------------------------------------------------
    def _tokens(self, s: Session, k: int, n: int) -> List[int]:
        g = rng_for(self.mix.seed, 8, s.sid, k)
        return list(map(int, g.integers(TOKEN_LO, self.vocab, n)))

    def _submit(self, kind, prompt, max_new, adapter, due, turn) -> Rec:
        now = self.clock()
        name = None if adapter is None else self.names[adapter]
        rid = self.eng.submit(prompt, max_new, adapter_name=name)
        req = self.eng.waiting[-1]
        assert req.req_id == rid
        rec = Rec(kind, name, due, self._in_window(due), req, len(prompt),
                  max_new, turn)
        self.lateness.append(now - due)
        self.active.append(rec)
        self.recs.append(rec)
        turn.recs.append(rec)
        return rec

    def _turn(self, s: Session, k: int, ctx: Optional[List[int]],
              due: float) -> None:
        if due >= self.stop_at:
            return
        t = s.turns[k]
        prompt = (ctx or []) + self._tokens(s, k, t.message)
        tr = TurnRec(due, self._in_window(due), prompt, s, k)
        self.turns.append(tr)
        if t.direct is not None:
            self._submit("direct", prompt + self.inv, t.answer, t.direct,
                         due, tr)
        else:
            self._submit("base", prompt, t.answer, None, due, tr)

    def _on_done(self, rec: Rec, now: float) -> None:
        tr = rec.turn
        s, k = tr.session, tr.index
        t = s.turns[k]
        if rec.kind == "base" and t.evals:
            if not tr.measured and now >= self.stop_at:
                return                     # an unmeasured turn: generator off
            answer = list(rec.req.output_tokens)
            for a, n in t.evals:
                self._submit("eval", tr.prompt + answer + self.inv, n, a,
                             now, tr)
            return
        if any(r.done is None for r in tr.recs):
            return
        tr.done = now
        answer = [r for r in tr.recs if r.kind == "base"]
        nxt = now + s.think[k]
        if k + 1 < len(s.turns):
            ctx = tr.prompt + list(answer[0].req.output_tokens) \
                if answer else tr.prompt
            self._at(nxt, lambda due, s=s, k=k, ctx=ctx:
                     self._turn(s, k + 1, ctx, due))
        elif self.mix.params["arrival"]["kind"] == "closed":
            self._next_closed(nxt)

    def _release_due(self, now: float) -> None:
        while self.heap and self.heap[0][0] <= now:
            due, _, fn = heapq.heappop(self.heap)
            fn(due)

    # -- the loop ---------------------------------------------------------
    def _stamp(self, now: float) -> None:
        still = []
        for rec in self.active:
            r = rec.req
            out = r.output_tokens
            i = len(rec.stamps)
            while i < len(out) and out[i] != PENDING:
                rec.stamps.append(now)
                i += 1
            if r.state.value == "done":
                rec.done = now
                r.input_embeds = None      # host copy of the prompt's rows
                self._on_done(rec, now)
            else:
                still.append(rec)
        self.active = still

    def step(self) -> None:
        eng = self.eng
        before = [(r, r.n_computed) for r in eng.running]
        t0 = self.clock()
        with self.ann("bench.step"):
            eng.step()
        t1 = self.clock()
        seen = {id(r) for r, _ in before}
        spans = []
        for r, lo in before + [(r, r.n_cache_hit_tokens)
                               for r in eng.running if id(r) not in seen]:
            if r.n_computed > lo:
                frm = r.inv_start if r.adapter is not None else None
                spans.append((lo, r.n_computed, frm))
        self.steps.append(StepRec(t0, t1, spans))
        with self.ann("bench.client"):
            self._stamp(t1)

    def busy(self) -> bool:
        e = self.eng
        return bool(e.waiting or e.running)

    def run(self, until: float, *, measured_only: bool = False) -> None:
        """Release due work and step the engine until ``until``; with
        ``measured_only`` stop early once every measured turn is done."""
        while True:
            now = self.clock()
            if now >= until:
                return
            if measured_only and self.measured_done():
                return
            with self.ann("bench.client"):
                self._release_due(now)
            if self.busy():
                self.step()
                continue
            nxt = self.heap[0][0] if self.heap else until
            with self.ann("bench.wait"):
                time.sleep(max(0.0, min(nxt, until) - self.clock()) * 0.5
                           if nxt - now > 2e-3 else 0.0)

    def measured_done(self) -> bool:
        return all(t.done is not None for t in self.turns if t.measured) \
            and all(r.done is not None for r in self.recs if r.measured) \
            and not any(self._in_window(d) for d, _, _ in self.heap)

    # -- results ----------------------------------------------------------
    def measured(self) -> List[Rec]:
        return [r for r in self.recs if r.measured]

    def measured_turns(self) -> List[TurnRec]:
        return [t for t in self.turns if t.measured]


def lateness_line(lat: List[float]) -> str:
    if not lat:
        return "generator lateness: no submissions"
    a = np.asarray(lat) * 1e3
    return (f"generator lateness over {len(a)} submissions: median "
            f"{np.median(a):.3f} ms, p99 {np.percentile(a, 99):.3f} ms, max "
            f"{a.max():.3f} ms")


def gaps_ms(rec: Rec) -> List[float]:
    s = rec.stamps
    return [(b - a) * 1e3 for a, b in zip(s, s[1:])]
