"""Observability layer (repro.obs + the serving-stack instrumentation).

Covers the contracts ``docs/observability.md`` promises:

* the Tracer's bounded rings trim in bulk and count what they dropped;
* engine phases nest as the work nests, each opening one profiler
  annotation ``engine.<phase>``; a disabled tracer never calls the
  annotation factory;
* the mixed step's named scopes reach the compiled module's metadata
  and change nothing else in it;
* ``REPRO_TRACE=0`` and ``EngineConfig.trace`` kill recording entirely
  (no events, no ledger, no counters — the hot path stays untouched);
* the Perfetto export is a deterministic function of the ring contents
  (goldened on a hand-built tracer with fixed timestamps);
* a real 2-replica multi-adapter fleet run produces a structurally
  valid trace: phase spans per step, placement events per submission,
  lifecycle summaries per finished request, and a Perfetto JSON whose
  request timelines expand to queue/prefill/decode spans;
* the cache-reuse ledger reconciles EXACTLY with the prefix cache's
  hit counters on attention-only archs (the paper's central quantity
  is accounted, not sampled).
"""
import contextlib
import json
import re

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.alora import AdapterSpec, init_adapter_weights
from repro.models import init_params
from repro.obs import (TRACE_RING_KEEP, TRACE_RING_MAX, Tracer,
                       d2h_summary, prometheus_text, reuse_by_adapter,
                       to_perfetto)
from repro.obs.tracer import NO_PHASE, trace_enabled_default
from repro.serving import Engine, EngineConfig
from repro.serving.router import Router
from repro.serving.runner import STEP_SCOPES

KEY = jax.random.key(0)
INV = (7, 8, 9)
ARCH = "granite-3.2-8b"


@pytest.fixture(scope="module")
def zoo():
    """(cfg, params, adapters) for the attention-only arch, built once."""
    cfg = get_reduced(ARCH)
    params = init_params(KEY, cfg)
    ads = [(AdapterSpec(f"ad{i}", rank=8,
                        invocation_tokens=INV if i % 2 else None),
            init_adapter_weights(jax.random.key(100 + i), cfg, 8))
           for i in range(2)]
    return cfg, params, ads


def mk_engine(zoo, **ecfg_kw):
    cfg, params, ads = zoo
    kw = dict(max_running=4, max_batched_tokens=64, adapter_slots=2)
    kw.update(ecfg_kw)
    return Engine(cfg, params, adapters=ads,
                  engine_cfg=EngineConfig(**kw))


def run_multiturn(target, cfg, *, sessions=3, turns=2, gen=4, seed=3):
    """Sequential multi-turn trace: each round runs to idle before the
    next extends its prompts, so later turns' admission probes actually
    find the earlier turns' blocks registered (nonzero reuse)."""
    rng = np.random.RandomState(seed)
    hi = min(400, cfg.vocab_size)
    convo = [list(rng.randint(10, hi, 24 + 4 * (s % 3)))
             for s in range(sessions)]
    ids = []
    for t in range(turns):
        round_ids = []
        for s in range(sessions):
            adapter = f"ad{s % 2}" if t % 2 else None
            round_ids.append(target.submit(convo[s], gen,
                                           adapter_name=adapter))
        target.run_until_idle()
        for s, rid in enumerate(round_ids):
            out = target.request(rid).output_tokens
            assert len(out) == gen
            convo[s] = convo[s] + list(out) + list(rng.randint(10, hi, 12))
        ids.extend(round_ids)
    return ids


# ---------------------------------------------------------------------------
# ring bounds + kill switch (no engine needed)
# ---------------------------------------------------------------------------
def test_ring_overflow_trims_in_bulk_and_counts_dropped():
    tr = Tracer(enabled=True)
    extra = 10
    for i in range(TRACE_RING_MAX + extra):
        tr.span("schedule", "s", 0.0, 1.0, None)
    # at the threshold the OLDEST half goes in one bulk del, then
    # appends resume — never a per-append pop
    assert len(tr.events) == TRACE_RING_KEEP + extra
    assert tr.dropped == TRACE_RING_MAX - TRACE_RING_KEEP
    # the most recent records survive, in order
    assert tr.events[-1] == ("span", "schedule", "s", 0.0, 1.0, None, None)
    # the dropped count is surfaced by the Perfetto export
    names = [e["args"]["name"] for e in to_perfetto([tr])["traceEvents"]
             if e.get("name") == "process_name"]
    assert names[0] == (f"replica 0 · step phases · {tr.dropped} oldest "
                        "records dropped")


def test_env_kill_switch(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert not trace_enabled_default()
    tr = Tracer()                        # inherits the env default
    tr.span("schedule", "s", 0.0, 1.0, None)
    tr.event("pool", "prefetch", None)
    tr.count("x")
    tr.ledger_entry(0, None, 8, 8, False, 0.0)
    tr.request_summary(0, None, 0.0, 1.0, 2.0, 3.0, 16, 4, 0)
    assert not tr.events and not tr.ledger and not tr.counters
    # an explicit enabled=True overrides the environment (the A/B the
    # overhead benchmark runs)
    assert Tracer(enabled=True).enabled


def test_engine_trace_off_is_silent(zoo):
    """EngineConfig.trace=False: the whole stack (engine, runner, pool)
    records nothing — rings stay empty, counters stay empty."""
    cfg, _, _ = zoo
    eng = mk_engine(zoo, trace=False)
    run_multiturn(eng, cfg, sessions=2, turns=1)
    assert not eng.tracer.events
    assert not eng.tracer.ledger
    assert not eng.tracer.counters
    assert not eng.adapter_pool.tracer.enabled


# ---------------------------------------------------------------------------
# Perfetto export golden (hand-built rings, fixed timestamps)
# ---------------------------------------------------------------------------
def test_perfetto_export_golden():
    """to_perfetto is a pure function of the ring contents: a hand-built
    tracer with fixed timestamps produces exactly this JSON.  (Only
    ``Tracer.event`` stamps its own wall clock, so the golden uses
    spans, a ledger row and a request summary — all caller-timed.)"""
    tr = Tracer(enabled=True, replica=0)
    tr.span("schedule", "schedule", 1.0, 1.5, 5.0, {"n": 2})
    tr.ledger_entry(0, "ad0#v1", 32, 16, False, 5.0)
    tr.request_summary(0, "ad0#v1", arrival=0.0, t_prefill_start=1.0,
                       t_decode_start=2.0, t_done=3.0, prompt_len=48,
                       output_len=8, cache_hit_tokens=32)
    got = to_perfetto([tr])
    life_args = {"req_id": 0, "adapter_uid": "ad0#v1", "arrival": 0.0,
                 "t_prefill_start": 1.0, "t_decode_start": 2.0,
                 "t_done": 3.0, "prompt_len": 48, "output_len": 8,
                 "cache_hit_tokens": 32}
    want = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": "replica 0 · step phases"}},
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
         "args": {"name": "schedule"}},
        {"name": "schedule", "pid": 1, "tid": 1, "ts": 1.0e6,
         "args": {"n": 2, "vclock": 5.0}, "ph": "X", "dur": 0.5e6},
        {"name": "process_name", "ph": "M", "pid": 1001, "tid": 0,
         "args": {"name": "replica 0 · requests (virtual clock)"}},
        {"name": "thread_name", "ph": "M", "pid": 1001, "tid": 1,
         "args": {"name": "req 0 [ad0#v1]"}},
        {"name": "queue", "ph": "X", "pid": 1001, "tid": 1, "ts": 0.0,
         "dur": 1.0e6, "args": life_args},
        {"name": "prefill", "ph": "X", "pid": 1001, "tid": 1,
         "ts": 1.0e6, "dur": 1.0e6, "args": life_args},
        {"name": "decode", "ph": "X", "pid": 1001, "tid": 1, "ts": 2.0e6,
         "dur": 1.0e6, "args": life_args},
        {"name": "admit", "ph": "i", "s": "t", "pid": 1001, "tid": 1,
         "ts": 5.0e6,
         "args": {"adapter_uid": "ad0#v1", "reused": 32,
                  "recomputed": 16, "state_reused": False}},
    ], "displayTimeUnit": "ms"}
    assert got == want
    json.dumps(got)                      # serializable as-is


def test_prometheus_text_format():
    a = Tracer(enabled=True, replica=0)
    b = Tracer(enabled=True, replica=-1)
    a.count("steps_total", 3)
    b.count("placements_total", 2)
    text = prometheus_text([a, b])
    assert text == ("# TYPE repro_placements_total counter\n"
                    'repro_placements_total{replica="router"} 2\n'
                    "# TYPE repro_steps_total counter\n"
                    'repro_steps_total{replica="0"} 3\n')


def test_d2h_summary_aggregates_per_tag():
    out = d2h_summary([(3, "int32", "step"), (2, "int32", "step"),
                       (128, "float32", "admit")])
    assert out["step"] == {"count": 2.0, "elems": 5.0, "bytes": 20.0}
    assert out["admit"]["bytes"] == 128 * 4


# ---------------------------------------------------------------------------
# ledger ↔ prefix-cache reconciliation (the paper's central quantity)
# ---------------------------------------------------------------------------
def test_ledger_reconciles_with_prefix_cache_hits(zoo):
    """Over a run without admission failures on an attention-only arch,
    Σ ledger.reused == BlockManager.hits × block_size EXACTLY — the
    per-request ledger is an accounting of the same block-level probes
    the cache counts, not an estimate."""
    cfg, _, _ = zoo
    eng = mk_engine(zoo, max_running=8, max_batched_tokens=128)
    ids = run_multiturn(eng, cfg, sessions=3, turns=2)
    led = eng.tracer.ledger
    assert len(led) == len(ids)          # one row per admission
    reused = sum(r[2] for r in led)
    recomputed = sum(r[3] for r in led)
    bs = eng.ecfg.block_size
    assert reused == eng.kv_mgr.hits * bs
    assert reused > 0                    # turn 2 actually hit turn 1
    # counters mirror the ledger totals
    assert eng.tracer.counters["tokens_reused_total"] == reused
    assert eng.tracer.counters["tokens_recomputed_total"] == recomputed
    assert eng.tracer.counters["admissions_total"] == len(ids)
    # per-adapter roll-up is consistent and the aLoRA rows reuse
    # base-model blocks (cross-model reuse, the paper's mechanism)
    table = reuse_by_adapter([eng.tracer])
    assert sum(r["reused"] for r in table.values()) == reused
    assert any(uid != "base" and r["reused"] > 0
               for uid, r in table.items())


# ---------------------------------------------------------------------------
# fleet run: structural trace golden over 2 replicas
# ---------------------------------------------------------------------------
def test_fleet_trace_structure(zoo):
    cfg, params, ads = zoo
    kw = dict(max_running=4, max_batched_tokens=64, adapter_slots=2)
    router = Router([Engine(cfg, params, adapters=ads,
                            engine_cfg=EngineConfig(**kw))
                     for _ in range(2)])
    ids = run_multiturn(router, cfg, sessions=4, turns=2)

    # the router stamped fleet positions and logged every placement
    assert [e.tracer.replica for e in router.replicas] == [0, 1]
    assert router.tracer.replica == -1
    placements = [e for e in router.tracer.events if e[2] == "placement"]
    assert len(placements) == len(ids)
    assert router.tracer.counters["placements_total"] == len(ids)

    tracers = [e.tracer for e in router.replicas] + [router.tracer]
    for eng in router.replicas:
        tr = eng.tracer
        names = {(e[0], e[1], e[2]) for e in tr.events}
        # every work step leaves one span per phase
        for phase in ("schedule", "submit", "retire"):
            assert ("span", phase, phase) in names, phase
        spans = [e for e in tr.events
                 if e[0] == "span" and e[2] == "schedule"]
        assert len(spans) == tr.counters["steps_total"]
        # lifecycle: one arrival event + one finish summary per request
        arrivals = [e for e in tr.events if e[2] == "arrival"]
        summaries = [e for e in tr.events if e[0] == "request"]
        assert len(arrivals) == len(summaries)
        assert tr.counters["requests_finished_total"] == len(summaries)
        # schema: every record is a 7-tuple on a known track
        for e in tr.events:
            assert len(e) == 7
            assert e[1] in ("step", "schedule", "submit", "retire", "pool",
                            "router", "lifecycle")
    # both replicas actually served work (affinity spread the sessions)
    assert all(e.tracer.counters.get("steps_total", 0) > 0
               for e in router.replicas)

    # Perfetto export: loads, and every finished request expands into
    # queue/prefill/decode spans on its replica's request process
    doc = json.loads(json.dumps(to_perfetto(tracers)))
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert {1, 2, 1001, 1002, 2001} <= pids
    life = [e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["pid"] in (1001, 1002)]
    by_name = {}
    for e in life:
        by_name.setdefault(e["name"], []).append(e)
        assert e["dur"] >= 0.0
    n_fin = sum(e.tracer.counters["requests_finished_total"]
                for e in router.replicas)
    assert len(by_name["prefill"]) == len(by_name["decode"]) == n_fin
    # phase spans land on the wall-clock phase processes
    phase = [e for e in doc["traceEvents"]
             if e.get("ph") == "X" and e["pid"] in (1, 2)]
    assert {e["name"] for e in phase} >= {"schedule", "submit", "retire"}

    # the ledger rows are the replicas' admissions; prometheus text
    # parses per family
    assert sum(len(e.tracer.ledger) for e in router.replicas) == \
        sum(e.tracer.counters["admissions_total"] for e in router.replicas)
    text = prometheus_text(tracers)
    for line in text.splitlines():
        assert line.startswith("# TYPE repro_") or \
            line.startswith("repro_"), line

    # fleet-level reconciliation: summed ledger reuse == summed
    # prefix-cache hits × block_size across the fleet
    reused = sum(r[2] for t in tracers for r in t.ledger)
    bs = router.replicas[0].ecfg.block_size
    assert reused == sum(e.kv_mgr.hits for e in router.replicas) * bs
    assert reused > 0


def test_async_engine_trace_has_overlapping_phases(zoo):
    """Async submission: the submit span of step N and the retire span
    of step N's previous in-flight work both exist; each retire holds
    one ``fetch`` span, the step's one sync, and the runner logged each
    fetch as int32 ids (the ids-only invariant)."""
    cfg, _, _ = zoo
    eng = mk_engine(zoo, max_running=8, max_batched_tokens=128)
    run_multiturn(eng, cfg, sessions=3, turns=1)
    spans = [e for e in eng.tracer.events if e[0] == "span"]
    fetches = [e for e in spans if e[1:3] == ("retire", "fetch")]
    retires = [e for e in spans if e[1:3] == ("retire", "retire")]
    assert fetches and len(fetches) == len(retires)
    assert all(r[3] <= f[3] and f[4] <= r[4]
               for f, r in zip(fetches, retires))
    step_fetches = [f for f in eng.runner.d2h_fetches if f[2] == "step"]
    assert all(dtype == "int32" for _, dtype, _ in step_fetches)
    assert eng.tracer.counters["d2h_step_transfers_total"] == \
        len(step_fetches) == len(fetches)
    assert eng.async_overlap_steps > 0


# ---------------------------------------------------------------------------
# engine phases: profiler annotations that nest as the work nests
# ---------------------------------------------------------------------------
class FakeAnnotations:
    """An annotation factory that logs each annotation's name with the
    name of the one open around it (its parent)."""

    def __init__(self):
        self.open, self.log = [], []

    def __call__(self, name):
        return self._ctx(name)

    @contextlib.contextmanager
    def _ctx(self, name):
        self.log.append((name, self.open[-1] if self.open else None))
        self.open.append(name)
        try:
            yield
        finally:
            self.open.pop()


PARENT = {"engine.prefetch": "engine.step", "engine.schedule": "engine.step",
          "engine.admit": "engine.schedule", "engine.submit": "engine.step",
          "engine.assemble": "engine.submit",
          "engine.dispatch": "engine.submit", "engine.retire": "engine.step",
          "engine.fetch": "engine.retire", "engine.finish": "engine.retire",
          "engine.step": None}


def test_phases_nest_as_the_work_nests(zoo):
    cfg, _, _ = zoo
    eng = mk_engine(zoo, trace=True)
    ann = FakeAnnotations()
    eng.tracer.annotate = ann
    run_multiturn(eng, cfg, sessions=2, turns=2)
    assert not ann.open
    names = {n for n, _ in ann.log}
    assert names == set(PARENT)
    for name, parent in ann.log:
        assert parent == PARENT[name], (name, parent)
    # the ring holds the same phases, one span per annotation, each
    # inside its parent's span and stamped with the virtual clock
    spans = [e for e in eng.tracer.events if e[0] == "span"]
    assert len(spans) == len(ann.log)
    steps = [e for e in spans if e[2] == "step"]
    for e in spans:
        assert e[5] is not None
        parent = PARENT["engine." + e[2]]
        if parent is None:
            continue
        assert any(p[3] <= e[3] and e[4] <= p[4] for p in spans
                   if p[2] == parent[len("engine."):]), e
    assert len(steps) == eng.tracer.counters["steps_total"]
    # the engine's own factory is the profiler's
    assert mk_engine(zoo).tracer.annotate is jax.profiler.TraceAnnotation


def test_phases_off_call_no_factory_and_record_nothing(zoo):
    cfg, _, _ = zoo
    eng = mk_engine(zoo, trace=False)
    ann = FakeAnnotations()
    eng.tracer.annotate = ann
    run_multiturn(eng, cfg, sessions=2, turns=1)
    assert ann.log == []
    assert not eng.tracer.events and not eng.tracer.counters
    assert eng.tracer.phase("step", "step") is NO_PHASE
    with eng.tracer.phase("step", "step") as ph:
        assert ph is NO_PHASE


# ---------------------------------------------------------------------------
# named scopes in the mixed step
# ---------------------------------------------------------------------------
def mixed_step_lowering(zoo, scopes=True, monkeypatch=None):
    """The mixed step of a captured serving batch, lowered fresh (with
    ``scopes=False``, every ``jax.named_scope`` a no-op)."""
    from repro.analysis.step_audit import capture_batch
    eng = mk_engine(zoo, trace=False)
    mb = capture_batch(eng)
    if not scopes:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    jax.clear_caches()
    return eng.runner.lower_mixed(mb)


def test_mixed_step_carries_every_scope(zoo):
    low = mixed_step_lowering(zoo)
    text = low.as_text(debug_info=True)
    hlo = low.compile().as_text()
    for scope in STEP_SCOPES:
        if scope == "ssd":            # no SSM layer in this model
            continue
        assert f"/{scope}/" in text, scope
        assert re.search(rf'op_name="[^"]*/{scope}/', hlo), scope
    assert re.search(r'op_name="[^"]*/qkv/lora/', hlo)


def test_compile_cache_keys_carry_the_scopes(monkeypatch):
    """JAX's cache key strips op metadata, where the scopes live: the
    program adds the scope vocabulary to every key instead."""
    from jax._src import cache_key

    from repro.launch.compile_cache import key_scopes
    monkeypatch.setattr(cache_key, "custom_hook", cache_key.custom_hook)
    assert cache_key.custom_hook() == ""
    tag = key_scopes()
    assert cache_key.custom_hook() == tag
    assert all(scope in tag for scope in STEP_SCOPES)


def test_engine_is_freed_without_the_cycle_collector(zoo):
    """The tracer's clock reads the engine through a weak reference, so
    dropping an engine frees its device pools at once."""
    import gc
    import weakref
    eng = mk_engine(zoo, trace=True)
    ref = weakref.ref(eng)
    gc.disable()
    try:
        del eng
        assert ref() is None
    finally:
        gc.enable()


def test_scopes_change_only_metadata(zoo, monkeypatch):
    def body(low):
        return [re.sub(r", metadata=\{[^}]*\}", "", ln)
                for ln in low.compile().as_text().splitlines()
                if " = " in ln]
    scoped = body(mixed_step_lowering(zoo))
    plain = body(mixed_step_lowering(zoo, False, monkeypatch))
    # leave no unscoped trace in the caches for later tests
    monkeypatch.undo()
    jax.clear_caches()
    assert scoped == plain
