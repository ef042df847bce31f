"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, the mixed step's executions, the device
operations that took most time, and the longest idle gaps of the device,
each named by what the host was doing in it.

Device time comes from the device planes (``/device:TPU:<n>``): busy is
the union of the intervals of the events on their ``XLA Ops`` line,
averaged over the chips; module executions are the events of their
``XLA Modules`` line.  The traced window is the span of the benchmark's
own host annotations (``bench.*``), on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

STEP_MODULE = "_mixed_impl"       # the jitted mixed step's name
ANNOTATION = "bench."             # the harness's host annotations


@dataclass
class Reduced:
    window_s: float
    busy_s: float                         # mean over device planes
    n_devices: int
    step_count: int                       # executions of the mixed step
    step_s: float                         # their device time, summed
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


def latest_xplane(root: str) -> Optional[str]:
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def op_family(name: str) -> str:
    """An op by its kind and result type, without its instance number or
    layouts: ``%fusion.40 = bf16[32768,16,8,128]{...} fusion(...)`` ->
    ``fusion bf16[32768,16,8,128]``, so one op of every layer and of
    every step program adds up under one name."""
    head, eq, rest = name.partition(" = ")
    m = re.search(r" ([a-z][\w\-]*)\(", rest) if eq else None
    if m is None:
        return re.sub(r"[.\-_]\d+$", "", head.lstrip("%"))
    typ = re.sub(r"\{[^}]*\}", "", rest[:m.start()])
    return f"{m.group(1)} {typ}"[:120]


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def _line(lines: dict, name: str):
    return _events(lines[name]) if name in lines else ()


def reduce_planes(planes, top: int = 10) -> Optional[Reduced]:
    """``planes``: objects with ``name`` and ``lines`` (each with
    ``name`` and ``events``: ``name``, ``start_ns``, ``duration_ns``)."""
    planes = list(planes)
    devices = [p for p in planes if re.fullmatch(r"/device:TPU:\d+",
                                                 p.name)]
    host = [e for p in planes if p.name.startswith("/host")
            for ln in p.lines for e in _events(ln)
            if e[0].startswith(ANNOTATION)]
    if not devices or not host:
        return None
    w0 = min(a for _, a, _ in host)
    w1 = max(b for _, _, b in host)
    busy_total, step_n, step_ns = 0, 0, 0
    ops: Dict[str, int] = defaultdict(int)
    first_busy: List[Tuple[int, int]] = []
    for i, p in enumerate(devices):
        lines = {ln.name: ln for ln in p.lines}
        iv = []
        for name, a, b in _line(lines, "XLA Ops"):
            a, b = max(a, w0), min(b, w1)
            if b > a:
                iv.append((a, b))
                ops[op_family(name)] += b - a
        merged = union(iv)
        busy_total += sum(b - a for a, b in merged)
        if i == 0:
            first_busy = merged
        for name, a, b in _line(lines, "XLA Modules"):
            if STEP_MODULE in name and a >= w0 and b <= w1:
                step_n += 1
                step_ns += b - a
    # idle gaps of the first device, named by the host annotation that
    # overlaps each most
    gaps, prev = [], w0
    for a, b in first_busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "none", 0
        for name, ha, hb in host:
            ov = min(b, hb) - max(a, ha)
            if ov > cover:
                best, cover = name, ov
        named.append((best, (b - a) * 1e-9))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total / len(devices) * 1e-9,
                   n_devices=len(devices), step_count=step_n,
                   step_s=step_ns * 1e-9,
                   top_ops=[(n, t * 1e-9) for n, t in top_ops],
                   idle_gaps=named)


def reduce_file(path: str, top: int = 10) -> Optional[Reduced]:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, top)
