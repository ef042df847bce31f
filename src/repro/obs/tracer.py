"""Hot-path-safe trace recording: the ``Tracer`` every serving-stack
component stamps spans, events, counters and cache-reuse ledger entries
into.

Recording is APPEND-ONLY PLAIN PYTHON — no jax calls, no numpy syncs,
no device work of any kind.  Tracer methods run inside the engine's
schedule/submit phases (admission, placement probes, batch assembly),
where a single hidden device sync would stall the async pipeline once
per step — so the hot-path lint (``repro.analysis.hotpath_lint``)
checks every function in this module wholesale and rejects ANY
``jax.*``/``jnp.*`` call or blocking construct, with no annotation
escape hatch (rule ``obs-jax``/``obs-sync``).  Anything that needs
real work — byte accounting, JSON, aggregation — belongs in
``repro.obs.export``, which only ever runs off the step path.

Engine phases (``Tracer.phase``) are the one place recording meets the
profiler: each opens a host annotation named ``engine.<name>`` from the
factory the tracer was constructed with (the engine passes
``jax.profiler.TraceAnnotation``, so this module itself calls no jax),
which lands on the profiler's clock beside the device planes, and on
exit records the same interval as a ring span.  A disabled tracer hands
out one shared no-op context instead.

Two timestamps ride every record:

* ``t0``/``t1`` — host wall time (``time.perf_counter()`` seconds):
  the honest timebase for per-step phase spans and cross-replica
  overlap (the async pipeline's submit/retire concurrency is a
  wall-clock fact);
* ``vclock`` — the engine's virtual clock at record time (``None``
  where no clock exists, e.g. runner/pool internals): the timebase of
  the discrete-event simulation request lifecycles live on.

Ring bounds: like the runner's ``d2h_fetches`` log, the event and
ledger rings trim their OLDEST half in bulk at ``TRACE_RING_MAX`` so a
long-lived engine never accumulates one record per step forever;
``Tracer.dropped`` counts what the trim discarded (exporters surface
it so a truncated trace is never mistaken for a complete one).

The kill switch: ``REPRO_TRACE=0`` disables recording at construction
(every method early-returns on ``self.enabled``); ``EngineConfig.trace``
overrides the environment per engine (the benchmark A/B measuring the
overhead budget documented in ``docs/observability.md``).
"""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

# bulk-trim bounds for the event + ledger rings (oldest half dropped at
# the threshold, mirroring runner.D2H_LOG_MAX/KEEP)
TRACE_RING_MAX = 65536
TRACE_RING_KEEP = 32768

# track vocabulary (Perfetto thread per track, see docs/observability.md)
TRACKS = ("schedule", "submit", "retire", "pool", "router", "lifecycle",
          "step")
# profiler annotation prefix of an engine phase (``Tracer.phase``)
PHASE_PREFIX = "engine."

# a plain tuple per record — the stable schema ``repro.obs.export``
# renders; tests golden it: (kind, track, name, t0, t1, vclock, args)
EventRec = Tuple[str, str, str, float, float, Optional[float],
                 Optional[Dict[str, Any]]]
# (req_id, adapter_uid, reused, recomputed, state_reused, vclock)
LedgerRec = Tuple[int, Optional[str], int, int, bool, Optional[float]]


def trace_enabled_default() -> bool:
    """Tracing is ON by default; ``REPRO_TRACE=0`` is the kill switch."""
    return os.environ.get("REPRO_TRACE", "1") != "0"


class _NoPhase:
    """What a disabled tracer's ``phase`` returns: one shared context
    that enters and exits and records nothing."""
    __slots__ = ()

    def __enter__(self) -> "_NoPhase":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NO_PHASE = _NoPhase()


class Phase:
    """One open engine phase: a profiler annotation ``engine.<name>``
    (when the tracer has an annotation factory) around the body, and a
    ring span on ``track`` recorded on exit, stamped with the tracer's
    virtual clock at that moment.  ``args`` may be set inside the body;
    they ride the ring span only."""
    __slots__ = ("tracer", "track", "name", "args", "_ann", "_t0")

    def __init__(self, tracer: "Tracer", track: str, name: str):
        self.tracer, self.track, self.name = tracer, track, name
        self.args: Optional[Dict[str, Any]] = None
        self._ann: Any = None
        self._t0 = 0.0

    def __enter__(self) -> "Phase":
        annotate = self.tracer.annotate
        if annotate is not None:
            self._ann = annotate(PHASE_PREFIX + self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self.tracer
        tr.span(self.track, self.name, self._t0, t1,
                None if tr.clock is None else tr.clock(), self.args)
        return False


class Tracer:
    """Bounded-ring trace recorder (one per engine / router).

    All recording methods are O(1) plain-python appends and early-return
    when disabled — safe to call from schedule/submit-phase code.

    ``annotate`` (a context-manager factory taking a name) opens the
    profiler annotation of each ``phase``; ``clock`` returns the virtual
    clock a phase's span is stamped with.  Both come from whoever
    constructs the tracer: the engine passes
    ``jax.profiler.TraceAnnotation`` and its own clock.
    """

    def __init__(self, enabled: Optional[bool] = None, replica: int = 0,
                 annotate: Optional[Callable[[str], Any]] = None,
                 clock: Optional[Callable[[], float]] = None):
        self.enabled = trace_enabled_default() if enabled is None \
            else bool(enabled)
        self.replica = replica
        self.annotate = annotate
        self.clock = clock
        self.events: List[EventRec] = []
        self.ledger: List[LedgerRec] = []
        self.counters: Dict[str, float] = {}
        self.dropped = 0            # records the ring trim discarded

    # ------------------------------------------------------------------
    def set_replica(self, replica: int) -> None:
        """Stamp this tracer's fleet position (the router assigns these
        so per-replica Perfetto tracks line up with placement events)."""
        self.replica = replica

    # ------------------------------------------------------------------
    def _append(self, ring: List[Any], rec: Any) -> None:
        if len(ring) >= TRACE_RING_MAX:
            drop = len(ring) - TRACE_RING_KEEP
            del ring[:drop]
            self.dropped += drop
        ring.append(rec)

    # ------------------------------------------------------------------
    def span(self, track: str, name: str, t0: float, t1: float,
             vclock: Optional[float],
             args: Optional[Dict[str, Any]] = None) -> None:
        """A completed interval [t0, t1] (wall seconds) on ``track``."""
        if not self.enabled:
            return
        self._append(self.events, ("span", track, name, t0, t1, vclock,
                                   args))

    def phase(self, track: str, name: str):
        """Context manager for one engine phase (see :class:`Phase`); a
        disabled tracer returns the shared ``NO_PHASE``, so the off path
        allocates nothing and the profiler sees nothing."""
        if not self.enabled:
            return NO_PHASE
        return Phase(self, track, name)

    def event(self, track: str, name: str, vclock: Optional[float],
              args: Optional[Dict[str, Any]] = None) -> None:
        """An instant event, wall-stamped here at record time."""
        if not self.enabled:
            return
        t = time.perf_counter()
        self._append(self.events, ("event", track, name, t, t, vclock,
                                   args))

    def count(self, name: str, delta: float = 1.0) -> None:
        """Bump a monotonic counter (Prometheus-counter semantics)."""
        if not self.enabled:
            return
        self.counters[name] = self.counters.get(name, 0.0) + delta

    # ------------------------------------------------------------------
    def ledger_entry(self, req_id: int, adapter_uid: Optional[str],
                     reused: int, recomputed: int, state_reused: bool,
                     vclock: Optional[float]) -> None:
        """One cache-reuse ledger row, recorded at a successful
        admission — the aLoRA switch boundary: ``adapter_uid`` is the
        model the request runs under, ``reused`` the prefix tokens the
        cache served (KV blocks prefilled by the base model or sibling
        adapters included — the paper's central quantity), ``recomputed``
        the prompt remainder prefill must execute.  Failed admissions
        (``_try_admit`` bail paths) return their acquired blocks and
        record nothing, so over a run without admission failures the
        ledger's reused-token total reconciles exactly with
        ``BlockManager.hits * block_size`` on attention-only archs."""
        if not self.enabled:
            return
        self._append(self.ledger, (req_id, adapter_uid, int(reused),
                                   int(recomputed), bool(state_reused),
                                   vclock))
        self.counters["tokens_reused_total"] = \
            self.counters.get("tokens_reused_total", 0.0) + reused
        self.counters["tokens_recomputed_total"] = \
            self.counters.get("tokens_recomputed_total", 0.0) + recomputed
        self.counters["admissions_total"] = \
            self.counters.get("admissions_total", 0.0) + 1.0

    # ------------------------------------------------------------------
    def request_summary(self, req_id: int, adapter_uid: Optional[str],
                        arrival: float, t_prefill_start: Optional[float],
                        t_decode_start: Optional[float], t_done: float,
                        prompt_len: int, output_len: int,
                        cache_hit_tokens: int) -> None:
        """The full lifecycle of a finished request, in VIRTUAL-clock
        seconds (the engine's discrete-event timebase).  Recorded once
        at finish (retire phase); the exporter expands it into
        queue/prefill/decode spans on the request timeline."""
        if not self.enabled:
            return
        t = time.perf_counter()
        self._append(self.events, (
            "request", "lifecycle", "request", t, t, t_done,
            {"req_id": req_id, "adapter_uid": adapter_uid,
             "arrival": arrival, "t_prefill_start": t_prefill_start,
             "t_decode_start": t_decode_start, "t_done": t_done,
             "prompt_len": prompt_len, "output_len": output_len,
             "cache_hit_tokens": cache_hit_tokens}))
        self.counters["requests_finished_total"] = \
            self.counters.get("requests_finished_total", 0.0) + 1.0
