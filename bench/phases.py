"""Reduce a traced run's ``.xplane.pb`` by the names the program wrote
into it: the mixed step's device time by named scope, and the device's
idle time by the host span open over it.

The program names two things in the profiler trace:

* engine phases, host annotations ``engine.<phase>`` (``Tracer.phase``
  in ``repro.obs``), nested as the work nests: ``engine.step`` holds
  ``prefetch``, ``schedule`` (holding ``admit``), ``submit`` (holding
  ``assemble`` and ``dispatch``) and ``retire`` (holding ``fetch`` and
  ``finish``);
* the mixed step's named scopes (``SCOPES``), in the ``tf_op`` path of
  each device op's metadata.

Scopes: inside each traced ``_mixed_impl`` execution, every instant goes
to the innermost device op running then (an op nested in another, as a
``while`` holds its body, is the inner one), and the op's time to the
innermost known scope of its ``tf_op`` path, else to ``other``; instants
inside an execution when no op ran go to ``gaps``.  So the scopes sum to
the executions' device time.  A fusion of ops from several scopes
carries its root op's path, and counts under that op's scope.

Phase gaps: every idle instant of the first device in the traced window
goes to the innermost host span open then: an ``engine.*`` span first,
else the benchmark's own ``bench.*`` annotation, else ``none``.  So the
phases sum to the device's idle time in the window.

The window and the step executions are ``bench/trace.py``'s.  The
``tf_op`` stat sits on the plane's event metadata, which
``jax.profiler.ProfileData`` does not expose; the protobuf module
``tensorflow.tsl.profiler.protobuf.xplane_pb2`` does.  Without that
module, or on a program that writes no phases or scopes, the readers
get ``None``.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from types import SimpleNamespace as NS
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace

PHASE = "engine."                 # the program's phase annotations
SCOPES = ("embed", "qkv", "lora", "kv_write", "attention", "out_proj",
          "mlp", "logits", "ssd")
DEVICE = re.compile(r"/device:TPU:\d+")


@dataclass
class Phases:
    window_s: float
    idle_s: float                     # the first device's, in the window
    step_count: int                   # traced mixed-step executions
    step_s: float                     # their device time, summed
    scopes: Dict[str, float] = field(default_factory=dict)
    idle_by_phase: Dict[str, float] = field(default_factory=dict)
    engine_spans: int = 0             # engine.* spans in the window
    scoped_ops: int = 0               # step ops under a known scope

    @property
    def engine_idle_s(self) -> float:
        return sum(v for k, v in self.idle_by_phase.items()
                   if k.startswith(PHASE))

    def breakdown(self) -> dict:
        """Both lists, longest first, in seconds."""
        def ranked(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv:
                                              -kv[1])]
        return {"scopes": ranked(self.scopes),
                "idle_by_phase": ranked(self.idle_by_phase)}


def scope_of(tf_op: str) -> str:
    """The innermost known scope of an op's ``tf_op`` path, e.g.
    ``jit(_mixed_impl)/qkv/lora/while/body/dot_general:`` -> ``lora``."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return "other"


def split_time(spans: Iterable[Tuple[float, float, object, int]],
               windows: List[Tuple[float, float]]) -> Dict[object, float]:
    """The time of ``windows`` (sorted, disjoint ``(a, b)``) by the
    innermost of ``spans`` (``(a, b, key, rank)``) open at each instant:
    the highest rank, then the latest start, then the earliest end.
    Time no span covers goes to ``None``."""
    pts = [(a, 2, None) for a, b in windows] + \
        [(b, 2, None) for a, b in windows]
    spans = [s for s in spans if s[1] > s[0]]
    for i, (a, b, _, _) in enumerate(spans):
        pts.append((a, 1, i))
        pts.append((b, 0, i))
    pts.sort(key=lambda p: (p[0], p[1]))
    out: Dict[object, float] = defaultdict(float)
    active: Dict[int, tuple] = {}
    wi, prev = 0, None
    for t, kind, i in pts:
        if prev is not None and t > prev:
            while wi < len(windows) and windows[wi][1] <= prev:
                wi += 1
            cover, j = 0.0, wi
            while j < len(windows) and windows[j][0] < t:
                cover += min(t, windows[j][1]) - max(prev, windows[j][0])
                j += 1
            if cover > 0:
                key = max(active.values())[3] if active else None
                out[key] += cover
        if kind == 1:
            a, b, key, rank = spans[i]
            active[i] = (rank, a, -b, key)
        elif kind == 0:
            active.pop(i, None)
        prev = t
    return dict(out)


def _events(plane, name: str):
    for ln in plane.lines:
        if ln.name == name:
            return list(ln.events)
    return []


def reduce_planes(planes) -> Optional[Phases]:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events``: ``name``, ``start_ns``, ``duration_ns``, and on device
    ops ``tf_op``); host events need only be the ``engine.*`` and
    ``bench.*`` ones."""
    planes = list(planes)
    devices = [p for p in planes if DEVICE.fullmatch(p.name)]
    host = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for p in planes if p.name.startswith("/host")
            for ln in p.lines for e in ln.events
            if e.name.startswith((trace.ANNOTATION, PHASE))]
    bench_ev = [h for h in host if h[2].startswith(trace.ANNOTATION)]
    if not devices or not bench_ev:
        return None
    w0 = min(a for a, _, _ in bench_ev)
    w1 = max(b for _, b, _ in bench_ev)
    scopes: Dict[str, float] = defaultdict(float)
    step_n, step_ns, scoped, idle = 0, 0.0, 0, {}
    for i, p in enumerate(devices):
        ops = [(e.start_ns, e.start_ns + e.duration_ns,
                scope_of(getattr(e, "tf_op", "") or ""), 0)
               for e in _events(p, "XLA Ops")]
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for e in _events(p, "XLA Modules")
                      if trace.STEP_MODULE in e.name
                      and e.start_ns >= w0
                      and e.start_ns + e.duration_ns <= w1)
        step_n += len(mods)
        step_ns += sum(b - a for a, b in mods)
        in_steps = [o for o in ops if any_overlap(o, mods)]
        scoped += sum(1 for o in in_steps if o[2] != "other")
        for k, v in split_time(in_steps, mods).items():
            scopes["gaps" if k is None else k] += v
        if i == 0:
            busy = trace.union([(max(a, w0), min(b, w1))
                                for a, b, _, _ in ops if b > w0 and a < w1])
            gaps, prev = [], w0
            for a, b in busy + [(w1, w1)]:
                if a > prev:
                    gaps.append((prev, a))
                prev = max(prev, b)
            spans = [(a, b, n, 2 if n.startswith(PHASE) else 1)
                     for a, b, n in host]
            idle = {("none" if k is None else k): v * 1e-9
                    for k, v in split_time(spans, gaps).items()}
    return Phases(window_s=(w1 - w0) * 1e-9,
                  idle_s=sum(idle.values()), step_count=step_n,
                  step_s=step_ns * 1e-9,
                  scopes={k: v * 1e-9 for k, v in scopes.items()},
                  idle_by_phase=idle,
                  engine_spans=sum(1 for a, b, n in host
                                   if n.startswith(PHASE)
                                   and b > w0 and a < w1),
                  scoped_ops=scoped)


def any_overlap(op, mods: List[Tuple[float, float]]) -> bool:
    """Whether the op ``(a, b, ...)`` overlaps one of the sorted,
    disjoint ``mods``."""
    a, b = op[0], op[1]
    j = bisect.bisect_right(mods, (b, b)) - 1
    return j >= 0 and mods[j][0] < b and mods[j][1] > a


# ---------------------------------------------------------------------------
# reading the file
# ---------------------------------------------------------------------------
def xplane_module():
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return None
    return xplane_pb2


def _stat_str(plane, stat) -> str:
    """A string stat's value; a ``ref_value`` names a stat metadata."""
    if stat.WhichOneof("value") == "ref_value":
        return plane.stat_metadata[stat.ref_value].name
    return stat.str_value


def load_planes(path: str) -> Optional[list]:
    """The device and host planes of an ``.xplane.pb``, in the shape
    ``reduce_planes`` reads; ``None`` without the protobuf module."""
    xp = xplane_module()
    if xp is None:
        return None
    space = xp.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for p in space.planes:
        dev = DEVICE.fullmatch(p.name) is not None
        if not dev and not p.name.startswith("/host"):
            continue
        tf_op = {}
        if dev:
            stat = next((k for k, m in p.stat_metadata.items()
                         if m.name == "tf_op"), None)
            for k, md in p.event_metadata.items():
                for st in md.stats:
                    if st.metadata_id == stat:
                        tf_op[k] = _stat_str(p, st)
        lines = []
        for ln in p.lines:
            if dev and ln.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs = []
            for e in ln.events:
                name = p.event_metadata[e.metadata_id].name
                if not dev and not name.startswith((trace.ANNOTATION,
                                                    PHASE)):
                    continue
                evs.append(NS(name=name,
                              start_ns=ln.timestamp_ns + e.offset_ps / 1e3,
                              duration_ns=e.duration_ps / 1e3,
                              tf_op=tf_op.get(e.metadata_id)))
            lines.append(NS(name=ln.name, events=evs))
        out.append(NS(name=p.name, lines=lines))
    return out


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime: float) -> Optional[Phases]:
    planes = load_planes(path)
    r = None if planes is None else reduce_planes(planes)
    if r is not None:
        print("phases: " + json.dumps(r.breakdown()), file=sys.stderr,
              flush=True)
    return r


def reduce_file(path: str) -> Optional[Phases]:
    return _reduce_file(path, os.path.getmtime(path))


def of_run(run) -> Optional[Phases]:
    """The phases of a traced run: its ``trace_path`` where the run
    keeps one, else the newest trace the harness wrote."""
    if getattr(run, "trace", None) is None:
        return None
    path = getattr(run, "trace_path", None)
    if path is None:
        from bench import harness
        path = trace.latest_xplane(str(harness.OUT / "trace"))
    return None if path is None else reduce_file(path)


def scope_ms(run, scope: str) -> Optional[float]:
    """Device time of ``scope`` per traced mixed step, in ms; ``None``
    where the trace names no scope."""
    p = of_run(run)
    if p is None or not p.scoped_ops or not p.step_count:
        return None
    return p.scopes.get(scope, 0.0) / p.step_count * 1e3
