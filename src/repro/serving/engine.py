"""The serving engine: scheduler + continuous batching + chunked prefill +
cross-model prefix caching (the paper's system, §3).

Request flow (paper Fig. 5):

  submit → [queue] → admission (prefix-cache match: base-aligned block
  hashes + SSM state snapshots) → chunked prefill (budgeted per step,
  interleaved with decodes) → decode (continuous batching) → done

The engine runs a discrete-event loop with a **virtual clock**: arrivals
follow the benchmark-provided schedule; each ``step()`` executes real
jitted model work and advances the clock by its measured wall time.  This
reproduces queue-buildup dynamics (paper §4.2.1/4.3) honestly on CPU with
reduced-scale models — the code path is identical to a real deployment,
only the device differs.

Cross-model reuse appears in two places:

* admission calls ``PrefixCache.match_and_acquire`` with the request's
  ``AdapterKey`` — aLoRA requests transparently hit blocks prefilled by
  the base model or sibling adapters (and vice versa);
* every block filled — during prefill OR decode (generated tokens are
  cached too, paper §4.4) — is registered under its base-aligned hash.

Adapters are a dynamic, paged resource (``serving/adapter_pool.py``):
the registry can hold far more adapters than fit on device, and the
scheduler is adapter-aware — waiting requests trigger async weight
prefetch, admission pins a device slot (or queues behind eviction), and
finish/preemption unpin it.  Block hashes salt on the registration uid,
so slot recycling never aliases the prefix cache.

Each iteration is an explicit **schedule → submit → retire** pipeline
(see ``Engine.step``): sampling runs on device inside the mixed step,
so with ``EngineConfig.async_submission`` (the default) step N+1 is
scheduled, assembled and dispatched BEFORE step N's sampled token ids
are synced to host — all host-side work hides under device compute, and
the per-step device→host payload is a handful of int32 ids instead of
``(R, vocab)`` logits.
"""
from __future__ import annotations

import time
import weakref
from collections import deque
from dataclasses import dataclass
from itertools import islice
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.activation_mask import adapter_index_for_positions, find_invocation_start
from repro.core.alora import AdapterSpec
from repro.core.block_hash import (AdapterKey, block_extra, hash_block,
                                   request_block_hashes)
from repro.core.kv_manager import BlockManager, OutOfBlocks
from repro.core.prefix_cache import PrefixCache
from repro.models.model import Runtime
from repro.obs.tracer import Tracer
from repro.serving.adapter_pool import AdapterPool, AdapterRegistration, rank_bucket
from repro.serving.metrics import AdapterPoolStats, MetricsAggregate, aggregate
from repro.serving.request import Request, State
from repro.serving.runner import MixedBatch, ModelRunner, RunnerConfig, StepHandle

# placeholder a submitted-but-unretired step leaves in output_tokens:
# the token's VALUE is still on device (patched at retire); its position
# already counts for scheduling.  Never a valid vocab id.
PENDING = -1


@dataclass
class _InflightStep:
    """A submitted mixed step awaiting retirement: the device handle
    plus, per request row, the bookkeeping that must wait for the
    sampled token ids — ``(request, epoch-at-submit, sampled-row index,
    output_tokens patch index | None, decode block-boundary position |
    None, eagerly-claimed state-snapshot slot | None)``."""
    handle: StepHandle
    retires: List[Tuple[Request, int, int, Optional[int], Optional[int],
                        Optional[int]]]


@dataclass(frozen=True)
class EngineConfig:
    block_size: int = 16
    num_blocks: int = 512
    max_running: int = 8
    num_state_slots: int = 64
    max_batched_tokens: int = 128     # chunked-prefill budget per step
    enable_prefix_cache: bool = True
    # "mixed": one jitted device call per step over a single ragged batch
    # of all decode tokens + prefill chunks (vLLM v1-style) — the default
    # for EVERY architecture family: attention-only, SSM/hybrid (ragged
    # SSD scan with per-token live-state gather/scatter) and
    # encoder-decoder (per-row cross-attention KV).
    # "sequential": the v0-style separate decode_batch/prefill_chunk path,
    # kept as an explicit config choice (equivalence oracle + debugging).
    execution_mode: str = "mixed"
    # attention impl for the mixed step: "ref" (jnp gather, runs
    # everywhere) | "pallas" (TPU kernel) | "pallas_interpret" (tests)
    mixed_attn_impl: str = "ref"
    # ragged-SSD impl for the mixed step, same choices as above
    mixed_ssd_impl: str = "ref"
    # grouped-LoRA delta for the mixed step: "ref" (ragged jnp over the
    # step's active slots) | "pallas"/"pallas_interpret" (SGMV kernel) |
    # "dense" (pre-pool full stacked scan; equivalence oracle)
    mixed_lora_impl: str = "ref"
    # ---- dynamic adapter pool (serving/adapter_pool.py) --------------
    # device-resident adapter slots.  None -> one slot per adapter given
    # at construction (everything resident, the pre-pool behavior);
    # smaller values make admission cycle adapters through the slots
    # (LRU eviction + async prefetch).
    adapter_slots: Optional[int] = None
    # rank bucket every adapter zero-pads into.  None -> pow2 bucket of
    # the largest construction-time adapter rank (min 8).  Must be set
    # explicitly if later registrations need a higher rank.
    adapter_slot_rank: Optional[int] = None
    # ---- adapter-aware admission (docs/scheduling.md) ----------------
    # "affinity" (default): scan a bounded window of the waiting queue,
    # skip requests blocked on slots/blocks, and admit base-model /
    # resident-adapter / staged-adapter requests first (same-adapter
    # admissions batched), under the starvation-age cap below.  "fcfs":
    # strict queue order with head-of-line break — the equivalence
    # oracle (and the pre-scheduler behaviour).  Admission order never
    # changes any request's tokens (greedy decoding is per-request
    # deterministic; the mixed≡sequential suites prove batch-composition
    # independence) — only queueing latency.
    admission_policy: str = "affinity"
    # how deep into `waiting` the affinity scan and the prefetch pass
    # look each step
    admission_window: int = 32
    # starvation-age cap K: once a scanned-but-bypassed request has been
    # overtaken by younger admissions in K scans, it becomes a barrier —
    # nothing behind it in the queue admits before it does
    admission_starvation_cap: int = 8
    # ---- adapter staging tier (AdapterPool) --------------------------
    # max registrations holding a device staging copy at once (prefetch
    # past it is deferred, not dropped).  None -> one per adapter slot.
    adapter_staging_budget: Optional[int] = None
    # scheduler ticks until a staged-but-never-claimed copy expires —
    # the bound on the prefetch-leak window
    adapter_staging_ttl: int = 64
    # slot eviction-policy hook forwarded to AdapterPool: given the
    # unpinned resident uids (least-recently-acquired first), returns
    # the victim uid.  None = LRU.
    adapter_evict_policy: Optional[Callable[[Sequence[str]], str]] = None
    # ---- async step pipeline (schedule → submit → retire) ------------
    # True (default): one-step-lookahead submission.  Sampling runs on
    # device inside the mixed step, only the (R,) int32 sampled ids ever
    # cross to host, and step N's host sync happens AFTER step N+1 has
    # been scheduled, assembled and dispatched — host work overlaps
    # device compute.  False retires every step before the next one is
    # scheduled: the synchronous oracle the async path must match
    # token for token.  Mixed-mode only; "sequential" execution is
    # always synchronous.
    async_submission: bool = True
    # execution-time model: clock advances by measured wall time of each
    # step, scaled by this factor (1.0 = honest CPU timing)
    time_scale: float = 1.0
    # ---- TP-sharded execution (distributed/sharding.py §Sharded serving)
    # Shard the one jitted mixed step over this mesh: params tensor-
    # parallel, the paged K/V pool on its KV-head dim, SSM pools on
    # head/channel dims, adapter slot B stacks on their output dim, and
    # per-token scheduler metadata replicated.  The host-side scheduler,
    # block manager and adapter registry stay single-process.  None (the
    # default) keeps the single-device path exactly as before.  Requires
    # execution_mode="mixed" and the jnp "ref" kernel impls (GSPMD
    # partitions them; Pallas kernels are single-device).
    mesh: Optional[jax.sharding.Mesh] = None
    # With a mesh whose "data" axis has size > 1, additionally shard the
    # PACKED TOKEN AXIS of the mixed step over that axis: per-token
    # metadata rows and input embeds split across the data devices, so
    # max_batched_tokens scales with the data-axis size instead of every
    # device redundantly computing the full packed batch.  Per-request
    # arrays and the sampled ids stay replicated (retirement and the next
    # step's from_buf gathers read them whole).  False keeps the
    # replicate-everything TP layout (the sharded≡unsharded A/B leg).
    data_shard_tokens: bool = True
    # ---- tracing (repro.obs) -----------------------------------------
    # Record request-lifecycle spans, step-phase spans, the cache-reuse
    # ledger and pool events into this engine's Tracer.  None (default)
    # follows the environment: on unless REPRO_TRACE=0.  Recording is
    # append-only plain python (hot-path safe, lint-enforced); the
    # overhead budget is bench-asserted (<2% mean step latency,
    # benchmarks/bench_mixed_batch.py --trace-check).
    trace: Optional[bool] = None


class Engine:
    def __init__(self, cfg: ModelConfig, params, *,
                 engine_cfg: EngineConfig = EngineConfig(),
                 adapters: Optional[List[Tuple[AdapterSpec, dict]]] = None,
                 rt: Runtime = Runtime()):
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.rt = rt
        if engine_cfg.mesh is not None \
                and engine_cfg.execution_mode != "mixed":
            raise ValueError(
                "sharded execution (EngineConfig.mesh) is built on the "
                "one-call-per-step mixed path; execution_mode="
                f"{engine_cfg.execution_mode!r} is single-device only")
        adapters = adapters or []
        # trace recorder (repro.obs): created FIRST so the adapter pool
        # and runner can stamp events into the same per-replica rings;
        # the router re-stamps replica ids after construction.  Its
        # phases open profiler annotations (``engine.<phase>``), on the
        # device trace's clock
        me = weakref.ref(self)     # the tracer must not keep us alive
        self.tracer = Tracer(enabled=engine_cfg.trace,
                             annotate=jax.profiler.TraceAnnotation,
                             clock=lambda: me().clock)
        # dynamic adapter pool: construction-time adapters are ordinary
        # registrations; more can be registered/unregistered at any time
        # and cycle through the fixed device slots (heterogeneous ranks
        # zero-pad into the slot bucket — no equal-rank requirement)
        self.adapter_pool: Optional[AdapterPool] = None
        if adapters or engine_cfg.adapter_slots is not None:
            n_slots = engine_cfg.adapter_slots \
                if engine_cfg.adapter_slots is not None \
                else max(len(adapters), 1)
            slot_rank = engine_cfg.adapter_slot_rank \
                if engine_cfg.adapter_slot_rank is not None \
                else rank_bucket(max((s.rank for s, _ in adapters),
                                     default=1))
            self.adapter_pool = AdapterPool(
                cfg, num_slots=n_slots, slot_rank=slot_rank,
                mesh=engine_cfg.mesh, tracer=self.tracer,
                staging_budget=engine_cfg.adapter_staging_budget,
                staging_ttl=engine_cfg.adapter_staging_ttl,
                evict_policy=engine_cfg.adapter_evict_policy)
            for spec, w in adapters:
                self.adapter_pool.register(spec, w)

        rcfg = RunnerConfig(
            block_size=engine_cfg.block_size,
            num_blocks=engine_cfg.num_blocks + 1,
            max_running=engine_cfg.max_running + 1,
            num_state_slots=engine_cfg.num_state_slots + 1,
            mixed_attn_impl=engine_cfg.mixed_attn_impl,
            mixed_ssd_impl=engine_cfg.mixed_ssd_impl,
            mixed_lora_impl=engine_cfg.mixed_lora_impl,
            data_shard_tokens=engine_cfg.data_shard_tokens,
        )
        self.runner = ModelRunner(
            cfg, params, rcfg,
            self.adapter_pool.layers if self.adapter_pool else None, rt,
            mesh=engine_cfg.mesh, tracer=self.tracer)

        has_attn = self.runner.La > 0
        has_ssm = self.runner.Ls > 0
        kv_mgr = BlockManager(engine_cfg.num_blocks,
                              engine_cfg.block_size) if has_attn else None
        st_mgr = BlockManager(engine_cfg.num_state_slots,
                              engine_cfg.block_size) if has_ssm else None
        self.kv_mgr = kv_mgr
        self.st_mgr = st_mgr
        self.cache = PrefixCache(block_size=engine_cfg.block_size,
                                 kv_manager=kv_mgr, state_manager=st_mgr) \
            if engine_cfg.enable_prefix_cache else None

        self.clock = 0.0
        self._next_id = 0
        # deques: arrivals pop from the left every step and preemption
        # pushes to the front — with the admission-window scan these
        # queues are hot at depth, and list.pop(0) is O(n)
        self.pending: "deque[Request]" = deque()   # future arrivals (sorted)
        self.waiting: "deque[Request]" = deque()   # arrived, not admitted
        self.running: List[Request] = []      # prefill/decode in flight
        self.done: List[Request] = []
        self._free_slots = list(range(engine_cfg.max_running))
        self._xkv: Dict[int, tuple] = {}      # req_id -> encoder KV
        self._budget_debt = 0                 # min-progress overdraft
        self.preemptions = 0
        self.last_step_tokens = (0, 0)        # (n_decode, n_prefill)
        self.t_assembly = 0.0                 # host-side batch-pack time
        if engine_cfg.execution_mode not in ("mixed", "sequential"):
            raise ValueError(
                f"unknown execution_mode {engine_cfg.execution_mode!r}: "
                "expected 'mixed' or 'sequential'")
        if engine_cfg.admission_policy not in ("affinity", "fcfs"):
            raise ValueError(
                f"unknown admission_policy "
                f"{engine_cfg.admission_policy!r}: "
                "expected 'affinity' or 'fcfs'")
        if engine_cfg.admission_window < 1 \
                or engine_cfg.admission_starvation_cap < 1:
            raise ValueError("admission_window and "
                             "admission_starvation_cap must be >= 1")
        self.use_mixed = engine_cfg.execution_mode == "mixed"
        self.use_async = self.use_mixed and engine_cfg.async_submission
        self._inflight: Optional[_InflightStep] = None
        # steps whose schedule/assembly ran while the previous step was
        # still executing on device (the overlap the pipeline exists for)
        self.async_overlap_steps = 0

    # ------------------------------------------------------------------
    # adapter lifecycle (delegates to the AdapterPool)
    # ------------------------------------------------------------------
    @property
    def adapters(self) -> Dict[str, AdapterRegistration]:
        """Currently-registered adapters, by name."""
        pool = self.adapter_pool
        if pool is None:
            return {}
        return {name: pool.get(pool.uid_of(name))
                for name in pool.registered}

    def register_adapter(self, spec: AdapterSpec, weights) -> str:
        """Register an adapter at any time; returns its registry uid.
        The engine may hold many more registrations than device slots —
        residency is managed per admission."""
        if self.adapter_pool is None:
            raise RuntimeError(
                "engine was built without an adapter pool; pass "
                "adapters=... at construction or set "
                "EngineConfig.adapter_slots")
        return self.adapter_pool.register(spec, weights)

    def unregister_adapter(self, name: str) -> None:
        """Drop a registration.  Refuses while any live request (queued,
        waiting or running) still references it."""
        if self.adapter_pool is None:
            raise KeyError(name)
        uid = self.adapter_pool.uid_of(name)
        for group in (self.running, self.waiting, self.pending):
            if any(r.adapter_uid == uid for r in group):
                raise RuntimeError(
                    f"adapter {name!r} still referenced by live requests")
        self.adapter_pool.unregister(name)

    def adapter_pool_stats(self) -> AdapterPoolStats:
        if self.adapter_pool is None:
            return AdapterPoolStats()
        return self.adapter_pool.stats()

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               adapter_name: Optional[str] = None,
               arrival_time: Optional[float] = None,
               prefix_embeds: Optional[np.ndarray] = None,
               frame_embeds: Optional[np.ndarray] = None,
               salt: Tuple = ()) -> int:
        req = Request(
            req_id=self._next_id,
            prompt=list(map(int, prompt)),
            max_new_tokens=max_new_tokens,
            arrival_time=self.clock if arrival_time is None
            else arrival_time,
            prefix_embeds=prefix_embeds,
            frame_embeds=frame_embeds,
            salt=salt,
        )
        self._next_id += 1
        if adapter_name is not None:
            pool = self.adapter_pool
            if pool is None:
                raise KeyError(adapter_name)
            uid = pool.uid_of(adapter_name)
            ra = pool.get(uid)
            req.adapter = ra.spec
            req.adapter_uid = uid       # stable cache identity; the
            req.adapter_slot = 0        # device slot is pinned at admission
            if ra.spec.kind == "alora":
                inv = find_invocation_start(req.prompt,
                                            ra.spec.invocation_tokens)
                # invocation sequence absent -> activate at end of prompt
                req.inv_start = len(req.prompt) if inv is None else inv
        if req.arrival_time <= self.clock:
            self.waiting.append(req)
        else:
            self.pending.append(req)
            if len(self.pending) > 1 \
                    and req.arrival_time < self.pending[-2].arrival_time:
                self.pending = deque(sorted(
                    self.pending, key=lambda r: r.arrival_time))
        if self.tracer.enabled:
            self.tracer.event("lifecycle", "arrival", req.arrival_time,
                              {"req_id": req.req_id,
                               "prompt_len": len(req.prompt),
                               "adapter_uid": req.adapter_uid})
        return req.req_id

    # ------------------------------------------------------------------
    # admission: prefix-cache match + block allocation
    # ------------------------------------------------------------------
    def _try_admit(self, req: Request) -> bool:
        ecfg = self.ecfg
        bs = ecfg.block_size
        n_prompt = len(req.prompt)
        # every request pins a run slot: SSM archs keep live state there,
        # and ALL archs address the runner's per-slot last-sampled-token
        # buffer through it (async decode rows read the previous token on
        # device, so the slot is the token's stable identity)
        if not self._free_slots:
            return False

        adapter_pinned = False

        # prefix-cache match.  We match against prompt[:-1]: the last
        # prompt token must always be recomputed to produce first-token
        # logits, so the reuse boundary (KV blocks AND the SSM state
        # snapshot, which must sit at the SAME boundary) never covers it.
        n_reuse, kv_blocks, state_slot = 0, [], None
        req.hashes = request_block_hashes(req.prompt, bs,
                                          req.adapter_key(), req.salt)
        if self.cache is not None:
            m = self.cache.match_and_acquire(req.prompt[:-1],
                                             req.adapter_key(), req.salt)
            n_reuse, kv_blocks, state_slot = (m.n_tokens, m.kv_blocks,
                                              m.state_slot)

        # allocate blocks for the uncached remainder of the prompt
        n_total_blocks = (n_prompt + bs - 1) // bs
        n_new = n_total_blocks - len(kv_blocks)
        new_blocks: List[int] = []

        def bail() -> bool:
            # single cleanup for every failure path: return everything
            # acquired so far — cache-matched blocks, partially
            # allocated fresh blocks, the state-snapshot ref, and the
            # adapter-slot pin (the slot stays resident/warm for retry)
            if self.kv_mgr is not None:
                self.kv_mgr.release_all(kv_blocks + new_blocks)
            if state_slot is not None:
                self.st_mgr.release(state_slot)
            if adapter_pinned:
                self.adapter_pool.release(req.adapter_uid)
                req.adapter_slot = 0
            return False

        mgr = self.kv_mgr
        if mgr is not None:
            if mgr.num_free() < n_new:
                return bail()
            try:
                for _ in range(n_new):
                    new_blocks.append(mgr.allocate())
            except OutOfBlocks:
                return bail()
            req.block_ids = kv_blocks + new_blocks

        # adapter admission charge, AFTER blocks so a block-side failure
        # never pays an eviction+install for nothing: pin the adapter's
        # device slot (installing it, evicting an LRU-unpinned slot if
        # needed).  When every slot is pinned by running requests the
        # admission fails — the request queues behind eviction, never
        # behind a device sync.
        if req.adapter_uid is not None:
            slot = self.adapter_pool.acquire(req.adapter_uid)
            if slot is None:
                req.block_ids = []
                return bail()
            req.adapter_slot = slot
            adapter_pinned = True

        req.n_computed = n_reuse
        req.n_cache_hit_tokens = n_reuse
        req.run_slot = self._free_slots.pop()
        if self.runner.Ls > 0:
            if state_slot is not None:
                self.runner.restore_state(state_slot, req.run_slot)
                req.state_reused = True
                self.st_mgr.release(state_slot)   # copied into live state
            else:
                self.runner.reset_live(req.run_slot)

        # cache-reuse ledger: one row per SUCCESSFUL admission (the
        # aLoRA switch boundary) — tokens the cache served vs the
        # remainder prefill recomputes, under the adapter the request
        # runs as.  Bail paths above returned their blocks and record
        # nothing.
        if self.tracer.enabled:
            self.tracer.ledger_entry(req.req_id, req.adapter_uid, n_reuse,
                                     n_prompt - n_reuse, req.state_reused,
                                     self.clock)

        # embeddings + (whisper) encoder KV.  Kept host-side (numpy) so
        # the mixed-batch assembly packs rows without device round-trips
        # (the one admission-time sync happens inside build_input_embeds,
        # annotated and logged there).
        req.input_embeds = self.runner.build_input_embeds(
            req.prompt, req.prefix_embeds)
        if self.cfg.is_encoder_decoder:
            assert req.frame_embeds is not None
            self._xkv[req.req_id] = self.runner.encode(req.frame_embeds)

        req.state = State.PREFILL
        self.running.append(req)
        return True

    # ------------------------------------------------------------------
    # adapter-aware admission (EngineConfig.admission_policy="affinity")
    # ------------------------------------------------------------------
    def _affinity_class(self, r: Request) -> int:
        """Admission-readiness class: 2 = no install needed (base-model
        request, or adapter already resident in a slot), 1 = weights
        staged on device (install is a local scatter), 0 = host-only
        (install stalls on the H2D copy)."""
        if r.adapter_uid is None:
            return 2
        return self.adapter_pool.affinity_of(r.adapter_uid)

    def _admit_affinity(self) -> None:
        """Windowed adapter-affinity admission (docs/scheduling.md).

        Strict FCFS breaks on the first inadmissible request, so a head
        blocked on a pinned adapter slot starves everything behind it —
        including base-model requests and requests whose adapter is
        already resident.  This scan looks at the first
        ``admission_window`` waiting requests, tries them in affinity
        order (no-install first, staged next, host-only last; equal
        classes keep queue order, same-adapter requests adjacent so
        their admissions batch), and skips — rather than breaks on —
        any that fail on slots/blocks.

        Starvation-age cap: a scanned request bypassed by a younger
        admission bumps ``admission_skips``; once that reaches
        ``admission_starvation_cap`` the request is a *barrier* — the
        candidate set is truncated at the oldest capped request, so
        nothing behind it in the queue can be admitted before it.  The
        capped request's counter can then never advance again: the cap
        is the exact bound on how often any request is bypassed.
        Admission order never alters decoded tokens (greedy decoding is
        per-request deterministic; batch-composition independence is
        proven by the mixed≡sequential suites) — only queue latency.
        """
        ecfg = self.ecfg
        if not self.waiting or len(self.running) >= ecfg.max_running:
            return
        window = list(islice(self.waiting, ecfg.admission_window))
        barrier = len(window) - 1
        for i, r in enumerate(window):
            if r.admission_skips >= ecfg.admission_starvation_cap:
                barrier = i
                break
        candidates = window[:barrier + 1]
        # affinity class desc; within a class, group by adapter uid
        # (base model first) then queue order — stable and deterministic
        order = sorted(
            range(len(candidates)),
            key=lambda i: (-self._affinity_class(candidates[i]),
                           candidates[i].adapter_uid or "", i))
        admitted: List[int] = []
        for i in order:
            if len(self.running) >= ecfg.max_running:
                break
            r = candidates[i]
            # a candidate that needs a slot install is skipped outright
            # while no slot is free or evictable — unlike the FCFS
            # oracle, the scan never issues an acquire it can already
            # see failing (this is most of the acquire_fails win)
            if r.adapter_uid is not None and self._affinity_class(r) < 2 \
                    and not self.adapter_pool.can_take_slot():
                continue
            if self._try_admit(r):
                admitted.append(i)
        if not admitted:
            return                # nothing admitted -> nobody bypassed
        admitted_ids = {id(candidates[i]) for i in admitted}
        # a request is bypassed when a YOUNGER (deeper-queued) request
        # admitted this scan; an older one admitting does not count
        youngest = max(admitted)
        n_skips = 0
        for i, r in enumerate(candidates):
            if i < youngest and id(r) not in admitted_ids:
                r.admission_skips += 1
                n_skips += 1
        # (admissions_total itself is stamped per ledger row in
        # _try_admit — only the skip accounting is scan-level)
        if self.tracer.enabled and n_skips:
            self.tracer.count("admission_skips_total", n_skips)
        self.waiting = deque(r for r in self.waiting
                             if id(r) not in admitted_ids)

    # ------------------------------------------------------------------
    # one scheduler step
    # ------------------------------------------------------------------
    def step(self) -> float:
        """Run one engine iteration; returns the step's execution time.

        The iteration is three explicit phases.  With
        ``async_submission=True`` (default) they form a one-step-
        lookahead pipeline; with ``False`` every step retires before the
        next is scheduled — the synchronous oracle::

                      ┌─ schedule ─┐┌─ submit ──┐┌──── retire ─────┐
            host,     │ decodes,   ││ assemble  ││ sync step N-1's │
            step N    │ admission, ││ batch,    ││ sampled ids,    │
                      │ prefills   ││ dispatch  ││ patch tokens,   │
                      └────────────┘└───────────┘│ hash/register   │
                                                 │ blocks, finish  │
                                                 └─────────────────┘
            device    ──[ step N-1 executing ]───[ step N ]─────────

        Schedule and submit of step N never wait for step N-1's tokens:
        the mixed step samples on device, decode rows read the previous
        token straight from the device ``tok_buf`` (``from_buf``), and
        host bookkeeping that needs the values (``PENDING`` placeholder
        patching, decode block-boundary hashing, request finishing) is
        deferred to the retire phase — which runs AFTER step N is
        already in flight, so the only blocking device→host transfer
        per iteration is the previous step's (R,) int32 sampled array.
        """
        # move due arrivals into the waiting queue
        while self.pending and self.pending[0].arrival_time <= self.clock:
            self.waiting.append(self.pending.popleft())
        # idle: expire adapter stages, then jump to the next arrival
        if not self.waiting and not self.running:
            if self.adapter_pool is not None:
                self.adapter_pool.tick()
            if self.pending:
                self.clock = self.pending[0].arrival_time
            return 0.0
        with self.tracer.phase("step", "step"):
            return self._step()

    def _step(self) -> float:
        """``step`` with work to do, inside its ``step`` phase."""
        tr = self.tracer
        # scheduler-driven adapter prefetch: issue the async host→device
        # transfer for every adapter an admission-window request will
        # need, so the weights are staged (or already in flight) by the
        # time admission pins a slot below.  The window is the admission
        # window, NOT spare running capacity: a full engine is exactly
        # when slots are about to free, and prefetching for the queue
        # head there is the whole point of the queue-time head start
        # (the old `max_running - len(running)` window collapsed to zero
        # under load).  Device cost is bounded by the pool's staging
        # budget, not the window; tick() first so expired stages free
        # budget for this step's prefetches.
        if self.adapter_pool is not None:
            with tr.phase("step", "prefetch"):
                self.adapter_pool.tick()
                for r in islice(self.waiting, self.ecfg.admission_window):
                    if r.adapter_uid is not None:
                        self.adapter_pool.prefetch(r.adapter_uid)

        t_before = self.clock
        prev = self._inflight
        self._inflight = None

        # ---- schedule ------------------------------------------------
        with tr.phase("schedule", "schedule") as ph:
            # decode first: running requests claim their next block
            # BEFORE admission can hand freed blocks to new/preempted
            # requests — this (plus recompute-preemption below)
            # guarantees progress under block starvation (vLLM's
            # decode-priority scheduling)
            decodes = self._schedule_decodes()
            n_decode = len(decodes)

            # admission: adapter-aware windowed scan (default) or the
            # strict FCFS-with-break oracle
            # (EngineConfig.admission_policy="fcfs")
            with tr.phase("schedule", "admit"):
                if self.ecfg.admission_policy == "fcfs":
                    while self.waiting \
                            and len(self.running) < self.ecfg.max_running:
                        if not self._try_admit(self.waiting[0]):
                            break
                        self.waiting.popleft()
                else:
                    self._admit_affinity()

            # chunked-prefill budget: whatever the decodes left of
            # max_batched_tokens, minus last step's minimum-progress
            # overdraft.  Only when NO decode ran may prefill overdraw by
            # one block (minimum progress); the overdraft is charged to
            # the next step instead of silently violating the cap.
            avail = self.ecfg.max_batched_tokens - n_decode \
                - self._budget_debt
            budget = avail
            if n_decode == 0 and budget < self.ecfg.block_size:
                budget = self.ecfg.block_size
            prefills = self._schedule_prefills(budget)
            n_prefill = sum(hi - lo for _, lo, hi in prefills)
            # everything spent this step (decodes are non-deferrable)
            # plus inherited debt beyond the cap carries forward — debt
            # is paid down by under-cap steps, never silently forgiven
            self._budget_debt = max(0, n_decode + n_prefill
                                    + self._budget_debt
                                    - self.ecfg.max_batched_tokens)
            self.last_step_tokens = (n_decode, n_prefill)
            if tr.enabled:
                ph.args = {"n_decode": n_decode, "n_prefill": n_prefill,
                           "running": len(self.running),
                           "waiting": len(self.waiting)}
                tr.count("steps_total")
                tr.count("decode_tokens_total", n_decode)
                tr.count("prefill_tokens_total", n_prefill)

        # ---- submit --------------------------------------------------
        if self.use_mixed:
            with tr.phase("submit", "submit") as ph:
                asm0 = self.t_assembly + self.runner.t_assembly
                inflight = self._submit_mixed(decodes, prefills)
                if tr.enabled:
                    # covers host-side batch assembly (HostBufferPool
                    # take + pack, runner _dev_meta staging) AND the
                    # jitted dispatch
                    ph.args = {"n_decode": n_decode, "n_prefill": n_prefill,
                               "t_assembly": self.t_assembly
                               + self.runner.t_assembly - asm0}
            if inflight is not None and prev is not None:
                self.async_overlap_steps += 1
            if not self.use_async and inflight is not None:
                # synchronous oracle: retire the step we just submitted
                self._retire_traced(inflight)
                inflight = None
            # ---- retire (async: AFTER step N+1 is in flight) --------
            self._retire_traced(prev)
            self._inflight = inflight
        else:
            self._execute_decodes(decodes)
            self._execute_prefills(prefills)
            # sequential oracle: the step above ran to completion, so
            # every token value is already host-known
            # phase: retire-ok (sequential oracle path)
            self._finish_requests()
        # block starvation with zero progress: preempt the most recent
        # running request (vLLM recompute-preemption) so the others can
        # allocate; it re-enters the queue and re-prefills via the
        # prefix cache.  In async mode a just-retired step may have
        # freed blocks/slots — only preempt once the pipeline is fully
        # drained (prev is None) and the scheduler STILL found no work,
        # so preemption never races an in-flight step.
        if n_decode == 0 and n_prefill == 0 and prev is None \
                and self.running:
            # drain-guarded: prev is None means no unretired step is in
            # flight, so no PENDING value can race the rollback
            # phase: retire-ok (pipeline drained)
            self._preempt(self.running[-1])
        return self.clock - t_before

    # ------------------------------------------------------------------
    def _preempt(self, r: Request) -> None:
        # step() itself only preempts with the pipeline fully drained
        # (no unretired step), but _preempt is also callable out of band
        # (tests, future scheduler policies) while rows of r still ride
        # an unretired step: bumping the epoch makes the retire phase
        # drop those rows (their schedule-time bookkeeping is rolled
        # back right here)
        r.epoch += 1
        # drop trailing PENDING placeholders — their producing step will
        # never patch them (epoch mismatch), and recompute-after-
        # readmission must only ever replay host-known token values
        while r.output_tokens and r.output_tokens[-1] == PENDING:
            r.output_tokens.pop()
        if self.kv_mgr is not None and r.block_ids:
            self.kv_mgr.release_all(r.block_ids)
        r.block_ids = []
        if r.run_slot >= 0:
            self._free_slots.append(r.run_slot)
            r.run_slot = -1
        if r.adapter_uid is not None and r.adapter_slot > 0:
            self.adapter_pool.release(r.adapter_uid)
            r.adapter_slot = 0
        r.n_computed = 0
        r.state_reused = False
        r.state = State.QUEUED
        # drop the encoder KV now: re-admission re-encodes, and a
        # preempted-then-never-readmitted request must not pin its
        # cross-attention tensors for the engine's lifetime
        self._xkv.pop(r.req_id, None)
        self.running.remove(r)
        self.waiting.appendleft(r)
        self.preemptions += 1
        if self.tracer.enabled:
            self.tracer.event("schedule", "preempt", self.clock,
                              {"req_id": r.req_id})
            self.tracer.count("preemptions_total")
        if self.preemptions > 1000:
            raise RuntimeError("preemption livelock: pool too small for "
                               "a single request")

    # ------------------------------------------------------------------
    # scheduling: pick this step's work (and claim blocks) WITHOUT
    # executing — both execution paths consume the same schedule
    # ------------------------------------------------------------------
    def _schedule_decodes(self) -> List[Request]:
        # finished-pending requests (async: final token still riding an
        # unretired step) never take another decode row; in sync modes
        # finish always runs before the next schedule, so this filter is
        # a no-op there
        decodes = [r for r in self.running
                   if r.state == State.DECODE and not r.is_finished()]
        bs = self.ecfg.block_size
        # ensure each request has a block for the position it writes
        ok: List[Request] = []
        for r in decodes:
            pos = r.n_computed
            if self.kv_mgr is not None:
                n_before = len(r.block_ids)
                while len(r.block_ids) <= pos // bs:
                    try:
                        r.block_ids.append(self.kv_mgr.allocate())
                    except OutOfBlocks:
                        break
                if len(r.block_ids) <= pos // bs:
                    # starved: return the partial speculative claim — a
                    # skipped request must not sit on blocks it cannot
                    # use this step while admission and the other
                    # decodes starve behind it (needless recompute-
                    # preemptions otherwise); it retries next step
                    while len(r.block_ids) > n_before:
                        self.kv_mgr.release(r.block_ids.pop())
                    continue
            ok.append(r)
        return ok

    def _schedule_prefills(self, budget: int
                           ) -> List[Tuple[Request, int, int]]:
        bs = self.ecfg.block_size
        spans: List[Tuple[Request, int, int]] = []
        for r in self.running:
            if budget <= 0:
                break
            if r.state != State.PREFILL:
                continue
            n_prompt = len(r.prompt)
            lo = r.n_computed
            hi = min(n_prompt, lo + min(budget,
                                        self.runner.rcfg.chunk_tokens))
            # keep chunk boundaries block-aligned except the final chunk
            if hi < n_prompt:
                hi = lo + ((hi - lo) // bs) * bs
                if hi <= lo:
                    continue
            if r.t_prefill_start is None:
                r.t_prefill_start = self.clock
            budget -= hi - lo
            spans.append((r, lo, hi))
        return spans

    # ------------------------------------------------------------------
    # post-execution bookkeeping shared by both execution paths, split
    # into the token-value-free half (``_advance_*`` — runs at submit
    # time, BEFORE the step's sampled ids exist on host) and the
    # deferred half that patches values / hashes generated blocks once
    # the retire phase has synced them
    # ------------------------------------------------------------------
    def _advance_decode(self, r: Request
                        ) -> Tuple[Optional[int], Optional[int],
                                   Optional[int]]:
        """Advance ``r`` past one decode token whose value may still be
        on device.  Returns ``(patch_idx, boundary_pos, snap_slot)`` for
        the retire phase: the output_tokens index holding a PENDING
        placeholder (frontier rows only), the position that completed a
        block (hash + register deferred until its tokens are host-known)
        and the state-snapshot slot claimed for it — snapshotting the
        live SSM state must happen NOW, while the pools still hold this
        step's output (the next submit advances them)."""
        r.n_computed += 1
        bs = self.ecfg.block_size
        pos = r.n_computed
        boundary_pos = snap_slot = None
        if self.cache is not None and pos % bs == 0:
            boundary_pos = pos
            if self.st_mgr is not None:
                b = pos // bs - 1
                # when every token of block b is already host-known (the
                # sync paths always; async only for replayed boundaries
                # — recompute after preemption), the hash is computable
                # NOW: skip the slot claim + device copies for a state
                # the cache already holds, exactly like the pre-split
                # lookup-first path.  Otherwise (async frontier: the fed
                # token may still be PENDING) snapshot speculatively and
                # let the retire phase register or drop it.
                toks = r.all_tokens
                known = all(t != PENDING
                            for t in toks[len(r.hashes) * bs:pos])
                cached = False
                if known and not self.use_async:
                    # sync only: retire follows immediately, so a lookup
                    # hit here is exactly the pre-split lookup-first
                    # behavior.  Async must NOT take the shortcut — the
                    # cached entry could be evicted before this step
                    # retires, and by then the live pools have advanced
                    # past the state, so the speculative snapshot is the
                    # only way to re-register it.
                    # guarded by `known and not use_async`: every token
                    # through block b is host-known on this branch
                    # phase: retire-ok (sync path, tokens host-known)
                    self._extend_hash_chain(r, b)
                    cached = self.st_mgr.lookup(r.hashes[b]) is not None
                if not cached:
                    try:
                        snap_slot = self.st_mgr.allocate()
                    except OutOfBlocks:
                        snap_slot = None  # pool pressure: skip snapshot
                    else:
                        self.runner.snapshot_live(max(r.run_slot, 0),
                                                  snap_slot)
        patch_idx = None
        # extend only at the sampling frontier (after a preemption the
        # decode path RECOMPUTES known tokens first)
        if pos == len(r.all_tokens) and not r.is_finished():
            patch_idx = len(r.output_tokens)
            r.output_tokens.append(PENDING)
        return patch_idx, boundary_pos, snap_slot

    def _advance_prefill(self, r: Request, lo: int, hi: int,
                         boundary) -> Optional[int]:
        """Token-value-free half of prefill postprocessing: block/state
        registration only needs the PROMPT hashes (known at admission),
        so it runs at submit time.  Returns the output_tokens index of
        the first-token PENDING placeholder, or None."""
        r.n_computed = hi
        # register every block completed by this chunk (+ snapshots)
        self._register_prefill_blocks(r, lo, hi, boundary)
        patch_idx = None
        if hi == len(r.prompt):                     # prefill complete
            r.state = State.DECODE
            # t_decode_start is stamped when the first token's VALUE
            # arrives (retire / sync postprocess), not here at submit —
            # TTFT must include the prefill step's device time
            if not r.output_tokens:                 # not a re-prefill
                patch_idx = 0
                r.output_tokens.append(PENDING)
        return patch_idx

    def _postprocess_decode(self, r: Request, tok: int) -> None:
        """Synchronous decode postprocessing (sequential oracle path):
        advance + retire back to back with the host-known token."""
        patch_idx, boundary_pos, snap_slot = self._advance_decode(r)
        if patch_idx is not None:
            r.output_tokens[patch_idx] = tok
        if boundary_pos is not None:
            self._register_decode_block(r, boundary_pos, snap_slot)

    def _postprocess_prefill(self, r: Request, lo: int, hi: int,
                             logits_row: np.ndarray, boundary) -> None:
        patch_idx = self._advance_prefill(r, lo, hi, boundary)
        if r.state == State.DECODE and r.t_decode_start is None:
            r.t_decode_start = self.clock
        if patch_idx is not None:
            r.output_tokens[patch_idx] = int(np.argmax(logits_row))

    def _adapter_idx(self, r: Request, positions: np.ndarray) -> np.ndarray:
        return adapter_index_for_positions(
            positions, r.adapter_slot,
            r.adapter.kind if r.adapter else None, r.inv_start)

    # ------------------------------------------------------------------
    # sequential execution (v0-style: one decode batch + one device call
    # per prefill chunk; kept as an explicit execution_mode choice — the
    # mixed path's equivalence oracle and a debugging aid)
    # ------------------------------------------------------------------
    def _execute_decodes(self, ok: List[Request]) -> None:
        if not ok:
            return
        tokens = np.array([r.all_tokens[r.n_computed] for r in ok],
                          np.int32)
        positions = np.array([r.n_computed for r in ok], np.int32)
        lengths = positions + 1
        adapter_idx = np.array([
            self._adapter_idx(r, np.array([r.n_computed]))[0]
            for r in ok], np.int32)
        run_slots = np.array([max(r.run_slot, 0) for r in ok], np.int32)
        block_tables = [r.block_ids for r in ok]
        xkv_list = None
        if self.cfg.is_encoder_decoder:
            xkv_list = [self._xkv[r.req_id] for r in ok]
        t0 = time.perf_counter()
        logits = self.runner.decode_batch(
            tokens=tokens, positions=positions, block_tables=block_tables,
            lengths=lengths, adapter_idx=adapter_idx, run_slots=run_slots,
            xkv_list=xkv_list)
        logits = np.asarray(logits)               # sync
        self.clock += (time.perf_counter() - t0) * self.ecfg.time_scale
        nxt = np.argmax(logits, axis=-1)
        for r, t in zip(ok, nxt):
            self._postprocess_decode(r, int(t))

    def _execute_prefills(self,
                          spans: List[Tuple[Request, int, int]]) -> None:
        for r, lo, hi in spans:
            aidx = self._adapter_idx(r, np.arange(lo, hi))
            t0 = time.perf_counter()
            logits, boundary = self.runner.prefill_chunk(
                input_embeds=r.input_embeds, lo=lo, hi=hi,
                block_ids=r.block_ids if self.kv_mgr is not None else [],
                adapter_idx_row=aidx, run_slot=max(r.run_slot, 0),
                xkv=self._xkv.get(r.req_id))
            logits = np.asarray(logits)           # sync
            self.clock += (time.perf_counter() - t0) * self.ecfg.time_scale
            self._postprocess_prefill(r, lo, hi, logits, boundary)

    # ------------------------------------------------------------------
    # unified mixed-batch execution: ALL decode tokens and prefill chunks
    # of the step packed into one ragged batch → one jitted device call.
    # Serves every architecture family: attention-only, SSM/hybrid
    # (ragged SSD scan over the packed axis) and encoder-decoder
    # (per-row cross-attention KV indexed by req_rows).  ``_submit_mixed``
    # only DISPATCHES the call and applies the token-value-free
    # bookkeeping; ``_retire`` later syncs the step's sampled ids and
    # applies everything that needed them.
    # ------------------------------------------------------------------
    def _submit_mixed(self, decodes: List[Request],
                      prefills: List[Tuple[Request, int, int]]
                      ) -> Optional[_InflightStep]:
        if not decodes and not prefills:
            return None
        tr = self.tracer
        with tr.phase("submit", "assemble"):
            mb, span_snaps = self._pack_mixed(decodes, prefills)
            t0 = time.perf_counter()
            args = self.runner._assemble_mixed(mb)
        with tr.phase("submit", "dispatch"):
            # one jitted call, no sync
            handle = self.runner.dispatch_mixed(args, len(mb.block_tables))
            self.clock += (time.perf_counter() - t0) * self.ecfg.time_scale
        # eager (token-value-free) bookkeeping; the retire list records
        # what must wait for the sampled ids.  Decode rows first, then
        # prefill — the same order the sequential path registers blocks
        retires: List[Tuple] = []
        for i, r in enumerate(decodes):
            patch_idx, bpos, slot = self._advance_decode(r)
            retires.append((r, r.epoch, i, patch_idx, bpos, slot))
        for j, (r, lo, hi) in enumerate(prefills):
            bnd = None
            if handle.boundary is not None:
                off, cnt = span_snaps[j]
                bnd = (handle.boundary[0][:, off:off + cnt],
                       handle.boundary[1][:, off:off + cnt])
            patch_idx = self._advance_prefill(r, lo, hi, bnd)
            retires.append((r, r.epoch, len(decodes) + j, patch_idx,
                            None, None))
        return _InflightStep(handle=handle, retires=retires)

    def _pack_mixed(self, decodes: List[Request],
                    prefills: List[Tuple[Request, int, int]]
                    ) -> Tuple[MixedBatch, List[Tuple[int, int]]]:
        """Pack the step's decode tokens and prefill chunks into one
        ragged :class:`MixedBatch`; also returns each prefill span's
        (offset, count) into the batch's ``snap_rows``."""
        t_host = time.perf_counter()
        bs = self.ecfg.block_size
        reqs = decodes + [r for r, _, _ in prefills]
        R = len(reqs)
        T = len(decodes) + sum(hi - lo for _, lo, hi in prefills)

        # host-side assembly into the runner's persistent capacity-
        # doubling buffers (no per-step reallocation)
        take = self.runner.host_bufs.take
        tok_ids = take("e_tok", T, np.int32)
        embeds = take("e_emb", T, np.float32,
                      trailing=(self.cfg.d_model,))
        use_embeds = take("e_use", T, bool)
        from_buf = take("e_fb", T, bool)
        positions = take("e_pos", T, np.int32)
        adapter_idx = take("e_ad", T, np.int32)
        req_rows = take("e_rows", T, np.int32)
        row_cols = take("e_cols", T, np.int32)
        write_bids = take("e_wb", T, np.int32)
        write_offs = take("e_wo", T, np.int32)
        out_rows = take("e_out", R, np.int32)
        run_slots = take("e_slots", R, np.int32)
        block_tables = [list(r.block_ids) for r in reqs]
        # packed indices of prefill block-boundary tokens (SSM snapshot
        # emission points) + each span's (offset, count) into that list
        snap_rows: List[int] = []
        span_snaps: List[Tuple[int, int]] = []

        t = 0
        for i, r in enumerate(decodes):
            pos = r.n_computed
            tok = r.all_tokens[pos]
            # PENDING: the token is last step's sample, not yet on host —
            # the device reads it from tok_buf at this request's run slot
            from_buf[t] = tok == PENDING
            tok_ids[t] = max(tok, 0)
            positions[t] = pos
            adapter_idx[t] = self._adapter_idx(r, np.array([pos]))[0]
            req_rows[t] = i
            if self.kv_mgr is not None:
                write_bids[t] = r.block_ids[pos // bs]
                write_offs[t] = pos % bs
            out_rows[i] = t
            run_slots[i] = max(r.run_slot, 0)
            t += 1
        for j, (r, lo, hi) in enumerate(prefills):
            row = len(decodes) + j
            n = hi - lo
            sl = slice(t, t + n)
            pr = np.arange(lo, hi)
            embeds[sl] = r.input_embeds[lo:hi]
            use_embeds[sl] = True
            positions[sl] = pr
            adapter_idx[sl] = self._adapter_idx(r, pr)
            req_rows[sl] = row
            row_cols[sl] = pr - lo
            if self.kv_mgr is not None:
                bids = np.array(r.block_ids, np.int32)
                write_bids[sl] = bids[pr // bs]
                write_offs[sl] = pr % bs
            out_rows[row] = t + n - 1
            run_slots[row] = max(r.run_slot, 0)
            off = len(snap_rows)
            if self.st_mgr is not None:
                # every b in range(lo//bs, hi//bs) is a FULL block:
                # (b+1)*bs <= hi by construction
                for b in range(lo // bs, hi // bs):
                    snap_rows.append(t + (b + 1) * bs - 1 - lo)
            span_snaps.append((off, len(snap_rows) - off))
            t += n

        xkv_list = None
        if self.cfg.is_encoder_decoder:
            xkv_list = [(r.req_id, self._xkv[r.req_id]) for r in reqs]

        # the step's active adapter slots (ascending, for the grouped-
        # LoRA delta): every token's adapter_idx is either 0 or its
        # request's pinned slot, so the per-request set covers the batch
        active = sorted({r.adapter_slot for r in reqs
                         if r.adapter_slot > 0})

        mb = MixedBatch(tok_ids=tok_ids, embeds=embeds,
                        use_embeds=use_embeds, from_buf=from_buf,
                        positions=positions,
                        adapter_idx=adapter_idx, req_rows=req_rows,
                        row_cols=row_cols, write_bids=write_bids,
                        write_offs=write_offs, block_tables=block_tables,
                        out_rows=out_rows, run_slots=run_slots,
                        snap_rows=np.array(snap_rows, np.int32),
                        xkv_list=xkv_list,
                        active_slots=np.array(active, np.int32))
        self.t_assembly += time.perf_counter() - t_host
        return mb, span_snaps

    # ------------------------------------------------------------------
    def _retire_traced(self, inf: Optional[_InflightStep]) -> None:
        """``_retire`` inside the ``retire`` phase (covers the one
        sanctioned D2H sync + the deferred bookkeeping)."""
        if inf is None:
            return
        with self.tracer.phase("retire", "retire") as ph:
            self._retire(inf)
            if self.tracer.enabled:
                ph.args = {"rows": len(inf.retires)}

    # ------------------------------------------------------------------
    def _retire(self, inf: Optional[_InflightStep]) -> None:
        """Retire a submitted step: the one blocking device→host sync
        per iteration (the (R,) int32 sampled ids), then the deferred
        bookkeeping — patch PENDING tokens, hash + register decode-
        completed blocks, finish requests.  Rows whose request was
        preempted after submit (epoch mismatch) are dropped; only their
        state-snapshot claim needs returning."""
        if inf is None:
            return
        tr = self.tracer
        with tr.phase("retire", "fetch"):
            t0 = time.perf_counter()
            sampled = self.runner.fetch_sampled(inf.handle)
            self.clock += (time.perf_counter() - t0) * self.ecfg.time_scale
        with tr.phase("retire", "finish"):
            for r, epoch, row, patch_idx, bpos, slot in inf.retires:
                if r.epoch != epoch:
                    if slot is not None:
                        self.st_mgr.release(slot)
                    continue
                # first-token arrival defines decode start: the clock
                # above just absorbed this step's device time, so
                # TTFT/prefill keep including the prefill step's
                # execution (stamping at submit would shift it into the
                # decode stage)
                if r.state == State.DECODE and r.t_decode_start is None:
                    r.t_decode_start = self.clock
                if patch_idx is not None:
                    r.output_tokens[patch_idx] = int(sampled[row])
                if bpos is not None:
                    self._register_decode_block(r, bpos, slot)
            self._finish_requests()

    # ------------------------------------------------------------------
    def _adopt_canonical(self, r: Request, b: int, h) -> None:
        """Register block ``b`` of ``r`` under hash ``h``.  When another
        live block already owns the hash (concurrent identical prefixes),
        remap the request onto the canonical block and release the
        duplicate back to the pool instead of keeping both allocated."""
        bid = r.block_ids[b]
        canon = self.cache.register_kv_block(h, bid)
        if canon != bid:
            self.kv_mgr.acquire(canon)
            self.kv_mgr.release(bid)
            r.block_ids[b] = canon

    # ------------------------------------------------------------------
    def _register_prefill_blocks(self, r: Request, lo: int, hi: int,
                                 boundary) -> None:
        if self.cache is None:
            return
        bs = self.ecfg.block_size
        for b in range(lo // bs, hi // bs):
            if (b + 1) * bs > hi:
                break
            h = r.hashes[b]
            if self.kv_mgr is not None and b < len(r.block_ids):
                self._adopt_canonical(r, b, h)
            if self.st_mgr is not None:
                # boundary states are per chunk of size bs within [lo, hi)
                c_idx = b - lo // bs
                if self.st_mgr.lookup(h) is None:
                    try:
                        slot = self.st_mgr.allocate()
                    except OutOfBlocks:
                        continue
                    self.runner.snapshot_boundary(boundary, c_idx, slot)
                    self.cache.register_state(h, slot)
                    self.st_mgr.release(slot)       # cached, not owned

    # ------------------------------------------------------------------
    def _extend_hash_chain(self, r: Request, b: int) -> None:
        """Extend the block-hash chain INCREMENTALLY from the last
        cached parent through block ``b`` (one hash_block per new block;
        recomputing the whole chain from token 0 made long decodes O(n²)
        in hashing work).  Idempotent; every token through block ``b``
        must be host-known."""
        bs = self.ecfg.block_size
        toks = r.all_tokens
        while len(r.hashes) <= b:
            i = len(r.hashes)
            lo, hi = i * bs, (i + 1) * bs
            parent = r.hashes[-1] if r.hashes else None
            extra = r.salt + block_extra(r.adapter_key(), lo, hi)
            r.hashes.append(hash_block(parent, toks[lo:hi], extra))

    # ------------------------------------------------------------------
    def _register_decode_block(self, r: Request, pos: int,
                               snap_slot: Optional[int]) -> None:
        """A decode step that reached ``pos`` completed a block: hash +
        register it (generated tokens are cached too — paper §4.4).
        Runs at RETIRE time — the block's token values must be host-
        known; ``snap_slot`` holds the live-state snapshot
        ``_advance_decode`` took while the pools still held that step's
        output."""
        b = pos // self.ecfg.block_size - 1
        self._extend_hash_chain(r, b)
        h = r.hashes[b]
        if self.kv_mgr is not None and b < len(r.block_ids):
            self._adopt_canonical(r, b, h)
        if snap_slot is not None:
            if self.st_mgr.lookup(h) is None:
                self.cache.register_state(h, snap_slot)
            self.st_mgr.release(snap_slot)

    # ------------------------------------------------------------------
    def _finish_requests(self) -> None:
        still = []
        for r in self.running:
            # a request only finishes once its final token VALUE is on
            # host (async: the last output may still be a PENDING
            # placeholder riding the just-submitted step — it finishes
            # at that step's retire, right after the patch)
            if r.state == State.DECODE and r.is_finished() \
                    and (not r.output_tokens
                         or r.output_tokens[-1] != PENDING):
                r.state = State.DONE
                r.t_done = self.clock
                if self.tracer.enabled:
                    self.tracer.request_summary(
                        r.req_id, r.adapter_uid, r.arrival_time,
                        r.t_prefill_start, r.t_decode_start, r.t_done,
                        len(r.prompt), len(r.output_tokens),
                        r.n_cache_hit_tokens)
                if self.kv_mgr is not None:
                    self.kv_mgr.release_all(r.block_ids)
                if r.run_slot >= 0:
                    self._free_slots.append(r.run_slot)
                if r.adapter_uid is not None and r.adapter_slot > 0:
                    self.adapter_pool.release(r.adapter_uid)
                    r.adapter_slot = 0
                self._xkv.pop(r.req_id, None)
                self.done.append(r)
            else:
                still.append(r)
        self.running = still

    # ------------------------------------------------------------------
    def run_until_idle(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not (self.pending or self.waiting or self.running):
                return
            self.step()
        raise RuntimeError("engine did not drain")

    # ------------------------------------------------------------------
    def metrics_for(self, req_ids: Sequence[int]) -> MetricsAggregate:
        ids = set(req_ids)
        return aggregate([r.metrics() for r in self.done
                          if r.req_id in ids])

    def request(self, req_id: int) -> Request:
        for pool in (self.done, self.running, self.waiting, self.pending):
            for r in pool:
                if r.req_id == req_id:
                    return r
        raise KeyError(req_id)

    def kv_hit_rate(self) -> float:
        mgr = self.kv_mgr or self.st_mgr
        return mgr.hit_rate()

    # ------------------------------------------------------------------
    # replica surface (serving/router.py): read-only placement probes a
    # multi-replica router scores admissions with.  All host-side python
    # over scheduler state — no device work, no cache/statistics mutation.
    # ------------------------------------------------------------------
    @property
    def idle(self) -> bool:
        """No live work anywhere in the pipeline (queued or admitted)."""
        return not (self.pending or self.waiting or self.running)

    def cached_prefix_tokens(self, prompt: Sequence[int],
                             adapter_name: Optional[str] = None,
                             salt: Tuple = ()) -> int:
        """How many leading prompt tokens THIS replica's prefix cache
        could serve, were the request admitted here — the same chained
        base-aligned block hashes admission matches on (so aLoRA probes
        transparently score blocks prefilled by the base model or sibling
        adapters), walked with non-acquiring lookups: refcounts and the
        hit/miss counters are untouched.
        """
        if self.cache is None:
            return 0
        prompt = list(map(int, prompt))
        key: Optional[AdapterKey] = None
        if adapter_name is not None:
            pool = self.adapter_pool
            if pool is None:
                raise KeyError(adapter_name)
            uid = pool.uid_of(adapter_name)
            spec = pool.get(uid).spec
            inv = 0
            if spec.kind == "alora":
                i = find_invocation_start(prompt, spec.invocation_tokens)
                inv = len(prompt) if i is None else i
            key = AdapterKey(uid, spec.kind, inv)
        # match boundary mirrors admission: the last prompt token is
        # always recomputed, so it can never be part of the reuse prefix
        return self.cache.probe(prompt[:-1], key, salt)

    def outstanding_tokens(self) -> int:
        """Remaining work on this replica, in tokens: uncomputed prompt
        plus ungenerated output over every queued + admitted request.
        The router's least-loaded tiebreak."""
        n = 0
        for r in self.pending:
            n += len(r.prompt) + r.max_new_tokens
        for r in self.waiting:
            n += len(r.prompt) + r.max_new_tokens
        for r in self.running:
            n += max(len(r.prompt) - r.n_computed, 0)
            n += max(r.max_new_tokens - len(r.output_tokens), 0)
        return n

    def adapter_residency(self) -> Dict[str, bool]:
        """Adapter name → device-resident (slot installed) snapshot."""
        pool = self.adapter_pool
        return {} if pool is None else pool.residency()

    def adapter_affinity(self, name: str) -> int:
        """Adapter-affinity class of ``name`` on this replica: 2 slot-
        resident (admission is a pin), 1 staged (weights on device
        awaiting install), 0 host-only or unknown.  The graded version
        of :meth:`adapter_residency` the router scores placements with —
        a replica that already staged the weights beats one that must
        start the H2D copy from scratch."""
        pool = self.adapter_pool
        return 0 if pool is None else pool.affinity(name)
