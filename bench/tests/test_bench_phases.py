"""The reduction by the program's own names (``bench/phases.py``) and the
four readers that use it: on planes made by hand, on the small trace of
a program without phases or scopes (``data/trace_tiny.xplane.pb``), and
on one recorded on a TPU v5e with both (``data/trace_phases.xplane.pb``,
made by ``record_phases.py``: the fixture cell, a 2-layer model, a
quarter second kept, the host planes cut to the benchmark's annotations
and the engine's phases)."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import harness, phases, trace  # noqa: E402

RECORDED = HERE / "data" / "trace_phases.xplane.pb"
WITHOUT = HERE / "data" / "trace_tiny.xplane.pb"
READERS = ("host_idle_share", "attn_ms", "kv_write_ms", "lora_ms")
STEP = "jit(_mixed_impl)"


def ev(name, start_ns, dur_ns, tf_op=None):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns, tf_op=tf_op)


def planes(ops, modules, host):
    return [NS(name="/device:TPU:0", lines=[
                NS(name="XLA Ops", events=[ev(*e) for e in ops]),
                NS(name="XLA Modules", events=[ev(*e) for e in modules])]),
            NS(name="/host:CPU", lines=[
                NS(name="python3", events=[ev(*e) for e in host])])]


def read_all(path):
    run = NS(trace=object(), trace_path=str(path))
    return {m: harness.load_reader(m)(run) for m in READERS}


@pytest.mark.parametrize("tf_op,scope", [
    (f"{STEP}/qkv/lora/while/body/closed_call/dot_general:", "lora"),
    (f"{STEP}/qkv/dot_general:", "qkv"),
    (f"{STEP}/kv_write/scatter:", "kv_write"),
    (f"{STEP}/attention/jit(_where)/select_n:", "attention"),
    (f"{STEP}/mlp/jit(silu)/logistic:", "mlp"),
    (f"{STEP}/ssd/lora/dot_general:", "lora"),
    (f"{STEP}/gather:", "other"),
    ("k_pool:", "other"),
    ("", "other"),
])
def test_an_op_goes_to_the_innermost_known_scope(tf_op, scope):
    assert phases.scope_of(tf_op) == scope


def test_idle_splits_over_nested_engine_spans_and_the_wait(monkeypatch):
    # device busy 200-300 and 500-520 of a 0-1000 window; the engine's
    # phases nest inside bench.step, then the client waits
    r = phases.reduce_planes(planes(
        ops=[("fusion.1", 200, 100), ("fusion.2", 500, 20)],
        modules=[],
        host=[("bench.step", 0, 600), ("engine.step", 10, 580),
              ("engine.schedule", 20, 180), ("engine.admit", 50, 100),
              ("engine.retire", 300, 280), ("engine.fetch", 320, 180),
              ("bench.wait", 650, 350)]))
    assert r.window_s == pytest.approx(1000e-9)
    want = {"bench.step": 20, "engine.step": 20, "engine.schedule": 80,
            "engine.admit": 100, "engine.retire": 80, "engine.fetch": 180,
            "none": 50, "bench.wait": 350}
    assert r.idle_by_phase == pytest.approx({k: v * 1e-9
                                             for k, v in want.items()})
    assert r.idle_s == pytest.approx(880e-9)
    assert r.engine_spans == 5
    monkeypatch.setattr(phases, "of_run", lambda run: r)
    share = harness.load_reader("host_idle_share")(NS(trace=object()))
    assert share == pytest.approx(46.0)


def test_step_ops_go_to_their_scopes_and_the_rest_to_other():
    r = phases.reduce_planes(planes(
        ops=[("while.3", 100, 200, f"{STEP}/qkv/lora/while:"),
             ("fusion.7", 120, 80,
              f"{STEP}/qkv/lora/while/body/closed_call/dot_general:"),
             ("fusion.9", 300, 50, f"{STEP}/attention/gather:"),
             ("copy.1", 360, 40, "k_pool:"),
             ("fusion.4", 400, 80, f"{STEP}/jit(_where)/select_n:"),
             ("fusion.9", 600, 100, f"{STEP}/attention/gather:")],
        modules=[("jit__mixed_impl(1)", 100, 400),
                 ("jit_other(2)", 600, 100)],
        host=[("bench.step", 0, 1000)]))
    assert r.step_count == 1
    assert r.step_s == pytest.approx(400e-9)
    # the while's own time and its body are both the delta; the op
    # outside the step counts nowhere
    assert r.scopes == pytest.approx({"lora": 200e-9, "attention": 50e-9,
                                      "other": 120e-9, "gaps": 30e-9})
    assert sum(r.scopes.values()) == pytest.approx(r.step_s)
    assert r.scoped_ops == 3


def test_a_trace_without_phases_or_scopes_reads_nothing():
    r = phases.reduce_planes(planes(
        ops=[("fusion.1", 100, 200, f"{STEP}/dot_general:")],
        modules=[("jit__mixed_impl(1)", 100, 200)],
        host=[("bench.step", 0, 1000)]))
    assert r.scopes == pytest.approx({"other": 200e-9})
    assert r.idle_by_phase == pytest.approx({"bench.step": 800e-9})
    assert r.engine_spans == 0 and r.scoped_ops == 0
    assert phases.reduce_planes([NS(name="/host:CPU", lines=[])]) is None


def test_split_time_prefers_rank_then_the_latest_start():
    spans = [(0, 100, "outer", 1), (10, 90, "inner", 1),
             (20, 30, "high", 2), (25, 80, "low", 0)]
    got = phases.split_time(spans, [(0, 50), (95, 120)])
    assert got == {"outer": 15, "inner": 30, "high": 10, None: 20}


# ---------------------------------------------------------------------------
# recorded traces
# ---------------------------------------------------------------------------
def test_the_old_trace_reads_none():
    """A program that writes no phases and no scopes (the older trace,
    recorded before either existed): every reader of them returns None,
    and raises nothing."""
    assert read_all(WITHOUT) == dict.fromkeys(READERS)


@pytest.fixture(scope="module")
def recorded():
    return phases.reduce_file(str(RECORDED)), trace.reduce_file(
        str(RECORDED))


def test_recorded_trace_reads_all_four(recorded):
    vals = read_all(RECORDED)
    assert all(v is not None and v > 0 for v in vals.values()), vals
    r, _ = recorded
    assert vals["host_idle_share"] <= 100.0 * r.idle_s / r.window_s


def test_recorded_scopes_sum_to_the_step_time(recorded):
    r, t = recorded
    assert r.step_count == t.step_count > 0
    assert sum(r.scopes.values()) == pytest.approx(t.step_s, rel=1e-4)
    assert "other" in r.scopes
    assert {"qkv", "lora", "kv_write", "attention", "mlp", "logits"} \
        <= set(r.scopes)


def test_recorded_idle_splits_by_phase(recorded):
    r, t = recorded
    assert sum(r.idle_by_phase.values()) == pytest.approx(
        t.window_s - t.busy_s, rel=0.01)
    # every idle instant of an engine step lies in one of its phases
    engine = r.engine_idle_s
    assert engine > 0
    assert r.idle_by_phase.get("bench.step", 0.0) < 0.05 * (
        engine + r.idle_by_phase.get("bench.step", 0.0))
