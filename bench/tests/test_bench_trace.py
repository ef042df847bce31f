"""The trace reduction (``bench/trace.py``): on planes made by hand, and
on a small trace recorded on a TPU v5e (``data/trace_tiny.xplane.pb``,
made by ``record_trace.py``: the fixture cell, a 2-layer model, one
second traced, the host planes cut to the benchmark's annotations)."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import trace  # noqa: E402

RECORDED = HERE / "data" / "trace_tiny.xplane.pb"


def ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def planes(ops, modules, host):
    return [NS(name="/device:TPU:0", lines=[
                NS(name="XLA Ops", events=[ev(*e) for e in ops]),
                NS(name="XLA Modules", events=[ev(*e) for e in modules])]),
            NS(name="/host:CPU", lines=[
                NS(name="python3", events=[ev(*e) for e in host])])]


def test_busy_union_and_step_time_by_hand():
    # window 0..1000 ns from the host annotations; ops overlap 100-300
    # and 250-400 (union 300 ns), and 700-800
    r = trace.reduce_planes(planes(
        ops=[("fusion.1", 100, 200), ("fusion.2", 250, 150),
             ("copy.7", 700, 100)],
        modules=[("jit__mixed_impl(3)", 100, 300),
                 ("jit__mixed_impl(3)", 700, 100),
                 ("jit_other(1)", 500, 10)],
        host=[("bench.step", 0, 600), ("bench.client", 600, 400)]))
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(400e-9)
    assert r.step_count == 2
    assert r.step_s == pytest.approx(400e-9)
    assert dict(r.top_ops) == pytest.approx({"fusion": 350e-9,
                                             "copy": 100e-9})


def test_idle_gaps_named_by_the_host_annotation_over_them():
    r = trace.reduce_planes(planes(
        ops=[("fusion.1", 100, 200), ("fusion.2", 700, 100)],
        modules=[],
        host=[("bench.step", 0, 350), ("bench.client", 350, 500),
              ("bench.wait", 850, 150)]))
    # gaps: 0-100 (step), 300-700 (client over 350-700), 800-1000
    # (wait over 850-1000)
    assert r.idle_gaps == [("bench.client", pytest.approx(400e-9)),
                           ("bench.wait", pytest.approx(200e-9)),
                           ("bench.step", pytest.approx(100e-9))]


def test_no_device_plane_reads_nothing():
    assert trace.reduce_planes([NS(name="/host:CPU", lines=[])]) is None


# ---------------------------------------------------------------------------
# the recorded trace
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(RECORDED))
    return pd, trace.reduce_file(str(RECORDED))


def test_recorded_trace_has_a_device_and_steps(recorded):
    _, r = recorded
    assert r.n_devices == 1
    assert r.step_count > 0
    assert 0 < r.busy_s <= r.window_s
    assert 0 < r.step_s <= r.window_s


def test_recorded_busy_matches_a_plain_count(recorded):
    pd, r = recorded
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    host = [(e.start_ns, e.start_ns + e.duration_ns) for p in pd.planes
            if p.name.startswith("/host") for ln in p.lines
            for e in ln.events if e.name.startswith("bench.")]
    w0, w1 = min(a for a, _ in host), max(b for _, b in host)
    # mark every busy 10 ns of the window; the union at that resolution
    busy = np.zeros((int(w1) - int(w0)) // 10 + 1, bool)
    for e in ops.events:
        a = max(int(e.start_ns), int(w0)) - int(w0)
        b = min(int(e.start_ns + e.duration_ns), int(w1)) - int(w0)
        if b > a:
            busy[a // 10:-(-b // 10)] = True
    assert r.busy_s == pytest.approx(busy.sum() * 1e-8, rel=0.02)


def test_recorded_steps_are_the_mixed_step_modules(recorded):
    pd, r = recorded
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    mods = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    names = {e.name for e in mods.events}
    assert any("_mixed_impl" in n for n in names)
    assert r.step_count <= sum(1 for e in mods.events
                               if "_mixed_impl" in e.name)


def test_recorded_gaps_are_named_and_ordered(recorded):
    _, r = recorded
    assert r.idle_gaps
    lengths = [s for _, s in r.idle_gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert all(n.startswith("bench.") for n, _ in r.idle_gaps)
    assert sum(lengths) <= r.window_s - r.busy_s + 1e-9
