"""Dynamic adapter lifecycle: the paged adapter-slot pool.

Covers the subsystem's contract end to end:
 1. registry semantics — register/unregister at any time, versioned
    uids, heterogeneous ranks padded into the slot bucket (exactly);
 2. pool mechanics — pin-while-scheduled ref counts, LRU eviction of
    unpinned slots only, acquire failure when everything is pinned,
    prefetch/install/stall counters; the bounded staging tier (budget
    deferral, TTL expiry of unclaimed stages, evict-policy hook) and
    the adapter-aware admission scheduler (blocked-head skip,
    starvation-age cap, churn + preemption under reordering);
 3. engine equivalence under churn — more adapters registered than
    device slots, interleaved admissions/evictions/readmissions, output
    token-identical to the all-resident sequential oracle;
 4. grouped-LoRA impls (dense oracle / ragged ref / Pallas interpret)
    agree through the mixed step;
 5. cache-identity regressions — slot reuse and name re-registration
    can never alias prefix-cache entries across adapters.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.alora import (AdapterSpec, init_adapter_weights,
                              pad_adapter_rank, stack_adapters)
from repro.models import init_params
from repro.models.layers import lora_delta
from repro.serving import Engine, EngineConfig
from repro.serving.adapter_pool import AdapterPool, rank_bucket

KEY = jax.random.key(0)
INV = (7, 8, 9)


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("granite-3.2-8b")
    params = init_params(KEY, cfg)
    return cfg, params


def mk_weights(cfg, seed, rank=8, scale=1.0):
    w = init_adapter_weights(jax.random.key(seed), cfg, rank)
    if scale != 1.0:
        w = jax.tree.map(lambda x: x * scale, w)
    return w


def prompt_of(n, seed=0, vocab=500):
    return list(np.random.RandomState(seed).randint(10, vocab, n))


# ---------------------------------------------------------------------------
# 1. registry + rank padding
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_register_unregister_versioned_uids(self, setup):
        cfg, _ = setup
        pool = AdapterPool(cfg, num_slots=2, slot_rank=8)
        u1 = pool.register(AdapterSpec("a", rank=8), mk_weights(cfg, 1))
        assert u1 == "a#v1"
        with pytest.raises(ValueError):
            pool.register(AdapterSpec("a", rank=8), mk_weights(cfg, 2))
        pool.unregister("a")
        u2 = pool.register(AdapterSpec("a", rank=8), mk_weights(cfg, 2))
        assert u2 == "a#v2" and u2 != u1       # identity never recycled
        with pytest.raises(KeyError):
            pool.unregister("nope")

    def test_rank_over_bucket_rejected(self, setup):
        cfg, _ = setup
        pool = AdapterPool(cfg, num_slots=1, slot_rank=8)
        with pytest.raises(ValueError):
            pool.register(AdapterSpec("big", rank=16),
                          mk_weights(cfg, 1, rank=16))

    def test_rank_padding_is_exact(self, setup):
        """x @ [A|0] @ [B;0] == x @ A @ B — the zero-block invariant the
        bucketed slot shapes rely on.  The padded product equals the
        unpadded one up to float rounding: the zero blocks add exact
        zeros, but XLA may split or reorder a contraction of length 32
        differently from one of length 8, so bit-equality is not
        promised.  What must stay exact is that index-0 tokens (base and
        pre-activation) get a zero delta."""
        cfg, _ = setup
        w = mk_weights(cfg, 3, rank=8)
        padded = pad_adapter_rank(w, 32)
        seg, seg_p = w["seg0"], padded["seg0"]
        assert seg_p["aq"].shape[-1] == 32 and seg_p["bq"].shape[-2] == 32
        x = jax.random.normal(jax.random.key(9), (6, cfg.d_model))
        idx = np.array([1, 1, 1, 0, 1, 0], np.int32)
        for a_k, b_k in (("aq", "bq"), ("ak", "bk"), ("av", "bv")):
            d0 = lora_delta(x, jax.numpy.stack(
                [jax.numpy.zeros_like(seg[a_k][0, 0]), seg[a_k][0, 0]]),
                jax.numpy.stack([jax.numpy.zeros_like(seg[b_k][0, 0]),
                                 seg[b_k][0, 0]]), idx)
            d1 = lora_delta(x, jax.numpy.stack(
                [jax.numpy.zeros_like(seg_p[a_k][0, 0]),
                 seg_p[a_k][0, 0]]),
                jax.numpy.stack([jax.numpy.zeros_like(seg_p[b_k][0, 0]),
                                 seg_p[b_k][0, 0]]), idx)
            d0, d1 = np.asarray(d0), np.asarray(d1)
            # float32 products of O(1) terms: a reordered sum of 8 of
            # them differs by a few ulps
            np.testing.assert_allclose(d1, d0, rtol=1e-5, atol=1e-6)
            assert np.abs(d0[idx == 1]).max() > 0
            np.testing.assert_array_equal(d1[idx == 0], 0.0)

    def test_stack_adapters_mixes_ranks(self, setup):
        """The old `equal-rank` assertion is gone: heterogeneous ranks
        stack into one bucketed tensor."""
        cfg, _ = setup
        stacked = stack_adapters(
            cfg, [mk_weights(cfg, 1, rank=4), mk_weights(cfg, 2, rank=16)],
            16)
        assert stacked["seg0"]["aq"].shape[2] == 3      # zero + 2
        assert stacked["seg0"]["aq"].shape[-1] == 16

    def test_rank_bucket(self):
        assert [rank_bucket(r) for r in (1, 8, 9, 32, 33)] == \
            [8, 8, 16, 32, 64]


# ---------------------------------------------------------------------------
# 2. pool mechanics: pins, LRU eviction, prefetch counters
# ---------------------------------------------------------------------------
class TestPoolMechanics:
    def mk_pool(self, cfg, n_regs=3, num_slots=2):
        pool = AdapterPool(cfg, num_slots=num_slots, slot_rank=8)
        uids = [pool.register(AdapterSpec(f"a{i}", rank=8),
                              mk_weights(cfg, i)) for i in range(n_regs)]
        return pool, uids

    def test_pin_blocks_eviction(self, setup):
        cfg, _ = setup
        pool, (u0, u1, u2) = self.mk_pool(cfg)
        s0, s1 = pool.acquire(u0), pool.acquire(u1)
        assert {s0, s1} == {1, 2} and pool.occupancy == 2
        assert pool.acquire(u2) is None          # everything pinned
        assert pool.acquire_fails == 1
        pool.release(u0)
        s2 = pool.acquire(u2)                    # evicts u0 (unpinned LRU)
        assert s2 == s0 and pool.evictions == 1
        assert pool.get(u0).slot is None
        assert pool.get(u1).slot == s1           # pinned survivor intact

    def test_lru_prefers_least_recently_acquired(self, setup):
        cfg, _ = setup
        pool, (u0, u1, u2) = self.mk_pool(cfg)
        pool.acquire(u0)
        pool.acquire(u1)
        pool.release(u0)
        pool.release(u1)
        pool.acquire(u0)                         # refresh u0's recency
        pool.release(u0)
        pool.acquire(u2)                         # must evict u1, not u0
        assert pool.get(u1).slot is None
        assert pool.get(u0).slot is not None

    def test_release_underflow_asserts(self, setup):
        cfg, _ = setup
        pool, uids = self.mk_pool(cfg)
        pool.acquire(uids[0])
        pool.release(uids[0])
        with pytest.raises(AssertionError):
            pool.release(uids[0])

    def test_unregister_pinned_refuses(self, setup):
        cfg, _ = setup
        pool, uids = self.mk_pool(cfg)
        pool.acquire(uids[0])
        with pytest.raises(RuntimeError):
            pool.unregister("a0")
        pool.release(uids[0])
        pool.unregister("a0")                    # frees the slot
        assert pool.occupancy == 0

    def test_prefetch_then_acquire_never_stalls(self, setup):
        cfg, _ = setup
        pool, uids = self.mk_pool(cfg)
        pool.prefetch(uids[0])
        assert pool.prefetch_issued == 1
        pool.prefetch(uids[0])                   # already staged: no-op
        assert pool.prefetch_issued == 1
        pool.acquire(uids[0])                    # install hit the staging
        assert pool.prefetch_hits == 1
        assert pool.stalled_installs == 0
        pool.acquire(uids[1])                    # no prefetch first
        assert pool.stalled_installs == 1
        assert pool.prefetch_hits == 1
        # re-acquiring a resident slot is a warm hit
        pool.release(uids[0])
        pool.acquire(uids[0])
        assert pool.resident_hits == 1

    def test_residency_costs_one_weight_copy(self, setup):
        """Installing scatters the staged weights into the slot stack
        and frees the staging copy; eviction leaves none behind."""
        cfg, _ = setup
        pool, (u0, u1, u2) = self.mk_pool(cfg)
        pool.prefetch(u0)
        assert pool.get(u0).device_layers is not None
        pool.acquire(u0)
        assert pool.get(u0).device_layers is None    # staging freed
        pool.release(u0)
        pool.acquire(u1)
        pool.acquire(u2)                             # evicts u0
        assert pool.get(u0).slot is None
        assert pool.get(u0).device_layers is None

    def test_installed_weights_land_in_slot(self, setup):
        """The slot row of the layer stack must equal the (padded)
        registered weights; slot 0 stays exactly zero."""
        cfg, _ = setup
        pool, uids = self.mk_pool(cfg)
        slot = pool.acquire(uids[1])
        reg = pool.get(uids[1])
        got = np.asarray(pool.layers[0]["aq"][slot])
        want = np.asarray(reg.host_layers[0]["aq"])
        np.testing.assert_array_equal(got, want)
        assert not np.asarray(pool.layers[0]["aq"][0]).any()


# ---------------------------------------------------------------------------
# 2b. staging tier: bounded prefetch, TTL expiry, evict-policy hook
# ---------------------------------------------------------------------------
class TestStagingTier:
    def mk_pool(self, cfg, n_regs=3, num_slots=2, **kw):
        pool = AdapterPool(cfg, num_slots=num_slots, slot_rank=8, **kw)
        uids = [pool.register(AdapterSpec(f"a{i}", rank=8),
                              mk_weights(cfg, i)) for i in range(n_regs)]
        return pool, uids

    def test_unclaimed_stage_expires(self, setup):
        """Regression for the prefetch leak: a stage no admission ever
        claims (cancelled / drained / routed-away request) is dropped
        after ``staging_ttl`` ticks and its device copy freed."""
        cfg, _ = setup
        pool, (u0, *_) = self.mk_pool(cfg, staging_ttl=3)
        assert pool.prefetch(u0)
        assert pool.staged_now == 1
        assert pool.get(u0).device_layers is not None
        for _ in range(3):
            pool.tick()
        assert pool.staged_now == 1          # within TTL: still staged
        pool.tick()                          # age > ttl: expired
        assert pool.staged_now == 0
        assert pool.staged_dropped == 1
        assert pool.get(u0).device_layers is None
        # the registration is intact: a later prefetch restages
        assert pool.prefetch(u0)
        assert pool.staged_now == 1

    def test_refresh_resets_stage_age(self, setup):
        """The scheduler re-prefetches queued requests every step; each
        call refreshes the stage's age (no new H2D) so a stage a live
        request still wants never expires under it."""
        cfg, _ = setup
        pool, (u0, *_) = self.mk_pool(cfg, staging_ttl=2)
        pool.prefetch(u0)
        for _ in range(6):                   # re-prefetch every tick
            pool.tick()
            assert pool.prefetch(u0)
        assert pool.staged_now == 1
        assert pool.prefetch_issued == 1     # one transfer, many refreshes
        for _ in range(3):                   # stop refreshing
            pool.tick()
        assert pool.staged_now == 0
        assert pool.staged_dropped == 1

    def test_staging_budget_defers_prefetch(self, setup):
        """The staging tier is BOUNDED: a prefetch past the budget is
        deferred (returns False) instead of stacking device copies."""
        cfg, _ = setup
        pool, (u0, u1, u2) = self.mk_pool(cfg, staging_budget=1)
        assert pool.prefetch(u0)
        assert not pool.prefetch(u1)
        assert pool.prefetch_deferred == 1
        assert pool.staged_now == 1
        assert pool.get(u1).device_layers is None
        pool.acquire(u0)                     # install claims the stage
        assert pool.staged_now == 0
        assert pool.prefetch(u1)             # budget freed: staged now
        assert pool.staged_now == 1

    def test_install_claims_stage_not_counted_dropped(self, setup):
        cfg, _ = setup
        pool, (u0, *_) = self.mk_pool(cfg)
        pool.prefetch(u0)
        pool.acquire(u0)
        assert pool.staged_now == 0
        assert pool.staged_dropped == 0      # claimed, not leaked
        assert pool.prefetch_hits == 1

    def test_acquire_stall_bypasses_budget(self, setup):
        """An admission-path stall stages directly even at budget — the
        install claims the copy in the same call, nothing lingers."""
        cfg, _ = setup
        pool, (u0, u1, u2) = self.mk_pool(cfg, staging_budget=1)
        pool.prefetch(u0)                    # budget now full
        slot = pool.acquire(u1)              # never prefetched: stall path
        assert slot is not None
        assert pool.stalled_installs == 1
        assert pool.get(u1).slot == slot
        assert pool.staged_now == 1          # only u0's stage remains

    def test_drop_unclaimed_stages_frees_all_now(self, setup):
        """Regression for the drained-replica stage pin: a stopped
        replica never ticks again, so TTL expiry can't run — the drain
        path drops every unclaimed stage eagerly instead."""
        cfg, _ = setup
        pool, (u0, u1, _) = self.mk_pool(cfg, staging_ttl=100)
        assert pool.prefetch(u0) and pool.prefetch(u1)
        assert pool.staged_now == 2
        assert pool.drop_unclaimed_stages() == 2
        assert pool.staged_now == 0
        assert pool.staged_dropped == 2
        assert pool.get(u0).device_layers is None
        assert pool.get(u1).device_layers is None
        # registrations intact: a later prefetch restages on demand
        assert pool.prefetch(u0)
        assert pool.staged_now == 1
        # idempotent once drained
        assert pool.drop_unclaimed_stages() == 1
        assert pool.drop_unclaimed_stages() == 0

    def test_unregister_drops_stage(self, setup):
        cfg, _ = setup
        pool, (u0, *_) = self.mk_pool(cfg)
        pool.prefetch(u0)
        pool.unregister("a0")
        assert pool.staged_now == 0
        assert pool.staged_dropped == 1

    def test_evict_policy_hook_picks_victim(self, setup):
        """The eviction-policy hook sees the unpinned residents in
        least-recently-acquired-first order and overrides the default
        LRU choice."""
        cfg, _ = setup
        pool, (u0, u1, u2) = self.mk_pool(
            cfg, evict_policy=lambda cands: cands[-1])   # MRU victim
        pool.acquire(u0)
        pool.release(u0)
        pool.acquire(u1)
        pool.release(u1)
        pool.acquire(u2)                     # default LRU would evict u0
        assert pool.get(u1).slot is None     # hook evicted the MRU
        assert pool.get(u0).slot is not None

    def test_evict_policy_must_return_candidate(self, setup):
        cfg, _ = setup
        pool, (u0, u1, u2) = self.mk_pool(
            cfg, evict_policy=lambda cands: "nope#v1")
        pool.acquire(u0)
        pool.release(u0)
        pool.acquire(u1)
        pool.release(u1)
        with pytest.raises(AssertionError):
            pool.acquire(u2)

    def test_affinity_classes_and_slot_gate(self, setup):
        """host-only -> staged -> resident is 0 -> 1 -> 2 (the admission
        ordering key); can_take_slot flips with pins (the scan's
        doomed-acquire gate)."""
        cfg, _ = setup
        pool, (u0, u1, _) = self.mk_pool(cfg, num_slots=1)
        assert pool.affinity_of(u0) == 0 and pool.affinity("a0") == 0
        pool.prefetch(u0)
        assert pool.affinity_of(u0) == 1
        assert pool.can_take_slot()          # a free slot exists
        pool.acquire(u0)
        assert pool.affinity_of(u0) == 2 and pool.affinity("a0") == 2
        assert not pool.can_take_slot()      # sole slot pinned
        pool.release(u0)
        assert pool.can_take_slot()          # unpinned resident victim
        assert pool.affinity("unknown") == 0


# ---------------------------------------------------------------------------
# 3. engine-level: churn equivalence + heterogeneous ranks
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def eng_setup(setup):
    cfg, params = setup
    specs = [AdapterSpec(f"ad{i}", rank=(4 if i % 2 else 8),
                         invocation_tokens=tuple(t + i for t in INV))
             for i in range(4)]
    weights = [mk_weights(cfg, 100 + i, rank=s.rank, scale=4.0)
               for i, s in enumerate(specs)]
    return cfg, params, specs, weights


def churn_workload(eng, specs, reps=2, gen=4):
    rids = []
    k = 0
    for rep in range(reps):
        for i, s in enumerate(specs):
            p = prompt_of(28, seed=rep * 10 + i) + list(s.invocation_tokens)
            rids.append(eng.submit(p, gen, adapter_name=s.name,
                                   arrival_time=1e-9 * k))
            k += 1
    eng.run_until_idle()
    return [eng.request(r).output_tokens for r in rids]


def test_churn_matches_all_resident_oracle(eng_setup):
    """N registered > S resident slots, admissions interleaved with
    decode so slots cycle; outputs must be token-identical to the
    all-resident sequential oracle, and accounting must drain clean."""
    cfg, params, specs, weights = eng_setup
    ads = list(zip(specs, weights))
    eng_o = Engine(cfg, params, adapters=ads,
                   engine_cfg=EngineConfig(execution_mode="sequential",
                                           max_running=3))
    oracle = churn_workload(eng_o, specs)
    assert eng_o.adapter_pool.evictions == 0     # oracle: all resident

    eng = Engine(cfg, params, adapters=ads,
                 engine_cfg=EngineConfig(adapter_slots=2, max_running=3))
    out = churn_workload(eng, specs)
    assert out == oracle
    st = eng.adapter_pool_stats()
    assert st.evictions > 0                      # slots actually cycled
    assert st.num_registered == 4 and st.num_slots == 2
    # pin accounting drains to zero; KV pool fully released
    assert eng.adapter_pool.pinned_slots() == 0
    assert all(eng.adapter_pool.get(eng.adapter_pool.uid_of(s.name)).pins
               == 0 for s in specs)
    assert eng.kv_mgr.num_free() == eng.ecfg.num_blocks


def test_register_evict_readmit_interleaved_with_decode(eng_setup):
    """Registration happens mid-serving (while other requests decode);
    a previously-evicted adapter is readmitted and must produce the same
    tokens as its first run."""
    cfg, params, specs, weights = eng_setup
    eng = Engine(cfg, params, adapters=[(specs[0], weights[0])],
                 engine_cfg=EngineConfig(adapter_slots=2, max_running=3))
    p0 = prompt_of(28, seed=1) + list(specs[0].invocation_tokens)
    r0 = eng.submit(p0, 8, adapter_name="ad0")
    eng.step()                                   # ad0 admitted + running
    for i in (1, 2):                             # register mid-decode
        eng.register_adapter(specs[i], weights[i])
    r1 = eng.submit(prompt_of(28, seed=2)
                    + list(specs[1].invocation_tokens), 4,
                    adapter_name="ad1")
    r2 = eng.submit(prompt_of(28, seed=3)
                    + list(specs[2].invocation_tokens), 4,
                    adapter_name="ad2")
    eng.run_until_idle()
    first = eng.request(r0).output_tokens
    # readmit ad0 after it may have been evicted: identical continuation
    r3 = eng.submit(p0, 8, adapter_name="ad0")
    eng.run_until_idle()
    assert eng.request(r3).output_tokens == first
    assert len(eng.request(r1).output_tokens) == 4
    assert len(eng.request(r2).output_tokens) == 4


def test_heterogeneous_ranks_match_equal_rank_oracle(eng_setup):
    """An engine mixing rank-4 and rank-8 adapters must emit exactly the
    tokens of per-adapter equal-rank engines (padding is exact)."""
    cfg, params, specs, weights = eng_setup
    eng = Engine(cfg, params, adapters=list(zip(specs[:2], weights[:2])),
                 engine_cfg=EngineConfig())
    assert eng.adapter_pool.slot_rank == 8       # bucket of max rank
    prompts = [prompt_of(24, seed=i) + list(specs[i].invocation_tokens)
               for i in range(2)]
    rids = [eng.submit(p, 4, adapter_name=f"ad{i}")
            for i, p in enumerate(prompts)]
    eng.run_until_idle()
    for i in range(2):
        solo = Engine(cfg, params, adapters=[(specs[i], weights[i])],
                      engine_cfg=EngineConfig())
        r = solo.submit(prompts[i], 4, adapter_name=f"ad{i}")
        solo.run_until_idle()
        assert solo.request(r).output_tokens == \
            eng.request(rids[i]).output_tokens


def _impl_tokens(eng_setup, impl):
    cfg, params, specs, weights = eng_setup
    eng = Engine(cfg, params, adapters=list(zip(specs[:3], weights[:3])),
                 engine_cfg=EngineConfig(mixed_lora_impl=impl,
                                         adapter_slots=2))
    return churn_workload(eng, specs[:3], reps=1)


@pytest.fixture(scope="module")
def dense_lora_tokens(eng_setup):
    return _impl_tokens(eng_setup, "dense")


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_mixed_lora_impls_agree(eng_setup, dense_lora_tokens, impl):
    """The grouped ragged-LoRA path (jnp ref and Pallas kernel) must
    emit the same tokens as the dense stacked-scan oracle, through the
    mixed step and under slot churn."""
    assert _impl_tokens(eng_setup, impl) == dense_lora_tokens


# ---------------------------------------------------------------------------
# 4. cache-identity regressions (uid keying, never slot / bare name)
# ---------------------------------------------------------------------------
def test_slot_reuse_never_aliases_prefix_cache(setup):
    """Adapter B inherits adapter A's just-evicted slot; with slot-index
    (or unstable) cache keys B would hit A's cached blocks.  It must
    miss them."""
    cfg, params = setup
    wa = mk_weights(cfg, 50, scale=4.0)
    wb = mk_weights(cfg, 51, scale=4.0)
    sa = AdapterSpec("A", rank=8)                # vanilla lora: every
    sb = AdapterSpec("B", rank=8)                # block adapter-salted
    eng = Engine(cfg, params, adapters=[(sa, wa), (sb, wb)],
                 engine_cfg=EngineConfig(adapter_slots=1, max_running=1))
    p = prompt_of(48, seed=5)
    ra = eng.submit(p, 2, adapter_name="A")
    eng.run_until_idle()
    rb = eng.submit(p, 2, adapter_name="B")      # evicts A, reuses slot 1
    eng.run_until_idle()
    assert eng.request(ra).adapter_slot == 0     # released
    assert eng.adapter_pool.evictions == 1
    assert eng.request(rb).n_cache_hit_tokens == 0
    # positive control: A again — ITS blocks are still hash-reachable
    ra2 = eng.submit(p, 2, adapter_name="A")
    eng.run_until_idle()
    assert eng.request(ra2).n_cache_hit_tokens > 0
    assert eng.request(ra2).output_tokens == eng.request(ra).output_tokens


def test_reregistered_name_never_reuses_old_cache(setup):
    """Unregister 'ad', register different weights under the same name:
    the new registration (new uid) must not hit the old blocks, while
    identical resubmission under the old registration did."""
    cfg, params = setup
    s = AdapterSpec("ad", rank=8)
    eng = Engine(cfg, params,
                 adapters=[(s, mk_weights(cfg, 60, scale=4.0))],
                 engine_cfg=EngineConfig())
    p = prompt_of(48, seed=6)
    r1 = eng.submit(p, 2, adapter_name="ad")
    eng.run_until_idle()
    r2 = eng.submit(p, 2, adapter_name="ad")     # same uid: cache hit
    eng.run_until_idle()
    assert eng.request(r2).n_cache_hit_tokens > 0
    eng.unregister_adapter("ad")
    eng.register_adapter(s, mk_weights(cfg, 61, scale=4.0))
    r3 = eng.submit(p, 2, adapter_name="ad")     # new uid: MUST miss
    eng.run_until_idle()
    assert eng.request(r3).n_cache_hit_tokens == 0
    assert eng.request(r3).adapter_key() != eng.request(r1).adapter_key()


def test_alora_base_reuse_survives_uid_keying(setup):
    """The paper's cross-model reuse must be unaffected: pre-activation
    aLoRA blocks stay base-aligned (no uid in their hash), so a base
    prefill still feeds an aLoRA request after re-registration."""
    cfg, params = setup
    s = AdapterSpec("uq", rank=8, invocation_tokens=INV)
    eng = Engine(cfg, params, adapters=[(s, mk_weights(cfg, 70))],
                 engine_cfg=EngineConfig())
    x = prompt_of(64, seed=7)
    rb = eng.submit(x, 4, adapter_name=None)     # base fills the prefix
    eng.run_until_idle()
    y = eng.request(rb).output_tokens
    r1 = eng.submit(x + y + list(INV), 2, adapter_name="uq")
    eng.run_until_idle()
    assert eng.request(r1).n_cache_hit_tokens > 0
    eng.unregister_adapter("uq")
    eng.register_adapter(s, mk_weights(cfg, 71))
    r2 = eng.submit(x + y + list(INV) + [3], 2, adapter_name="uq")
    eng.run_until_idle()
    assert eng.request(r2).n_cache_hit_tokens > 0   # base blocks reused


# ---------------------------------------------------------------------------
# 5. scheduler accounting under slot scarcity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["fcfs", "affinity"])
def test_admission_queues_behind_pinned_slots(eng_setup, policy):
    """With one adapter slot and two long-running adapter requests, the
    second must wait for the first to UNPIN (finish), then complete —
    no deadlock, no double-pin.  Strict FCFS pays an acquire_fail per
    retry of the blocked head; the affinity scan sees the doomed
    acquire coming (``can_take_slot``) and never issues it."""
    cfg, params, specs, weights = eng_setup
    eng = Engine(cfg, params, adapters=list(zip(specs[:2], weights[:2])),
                 engine_cfg=EngineConfig(adapter_slots=1, max_running=4,
                                         admission_policy=policy))
    r0 = eng.submit(prompt_of(24, seed=1)
                    + list(specs[0].invocation_tokens), 6,
                    adapter_name="ad0")
    r1 = eng.submit(prompt_of(24, seed=2)
                    + list(specs[1].invocation_tokens), 6,
                    adapter_name="ad1")
    eng.step()
    assert eng.request(r0).adapter_slot == 1
    assert eng.request(r1).adapter_slot == 0     # queued behind eviction
    if policy == "fcfs":
        assert eng.adapter_pool_stats().acquire_fails >= 1
    else:
        assert eng.adapter_pool_stats().acquire_fails == 0
    eng.run_until_idle()
    assert len(eng.request(r1).output_tokens) == 6
    assert eng.adapter_pool.pinned_slots() == 0


def test_affinity_admits_past_blocked_head(eng_setup):
    """A request whose adapter cannot pin a slot must not head-block a
    resident-adapter request queued behind it: the affinity scan skips
    the blocked head (bumping its admission_skips) and admits the
    resident one, while strict FCFS stays stuck on the head."""
    cfg, params, specs, weights = eng_setup

    def run(policy):
        eng = Engine(cfg, params,
                     adapters=list(zip(specs[:2], weights[:2])),
                     engine_cfg=EngineConfig(adapter_slots=1,
                                             max_running=3,
                                             admission_policy=policy))
        r0 = eng.submit(prompt_of(24, seed=1)
                        + list(specs[0].invocation_tokens), 8,
                        adapter_name="ad0", arrival_time=0.0)
        eng.step()                      # ad0 resident + pinned
        rb = eng.submit(prompt_of(24, seed=2)
                        + list(specs[1].invocation_tokens), 4,
                        adapter_name="ad1", arrival_time=1e-9)
        ra = eng.submit(prompt_of(24, seed=3)
                        + list(specs[0].invocation_tokens), 4,
                        adapter_name="ad0", arrival_time=2e-9)
        eng.step()
        admitted = {r.req_id for r in eng.running}
        skips = eng.request(rb).admission_skips
        eng.run_until_idle()
        return eng, rb, ra, admitted, skips

    eng, rb, ra, admitted, skips = run("affinity")
    assert ra in admitted and rb not in admitted
    assert skips >= 1                            # overtaken, and counted
    assert len(eng.request(rb).output_tokens) == 4   # still completes
    assert eng.adapter_pool.pinned_slots() == 0
    eng, rb, ra, admitted, _ = run("fcfs")
    assert ra not in admitted and rb not in admitted  # head-blocked


def test_starvation_cap_bounds_bypass(eng_setup):
    """Property: no waiting request is ever overtaken by younger
    admissions more than ``admission_starvation_cap`` times — once
    capped it barriers the window until it admits."""
    cfg, params, specs, weights = eng_setup
    cap = 2
    eng = Engine(cfg, params, adapters=list(zip(specs[:2], weights[:2])),
                 engine_cfg=EngineConfig(adapter_slots=1, max_running=2,
                                         admission_starvation_cap=cap))
    hold = eng.submit(prompt_of(24, seed=0)
                      + list(specs[0].invocation_tokens), 24,
                      adapter_name="ad0", arrival_time=0.0)
    eng.step()                          # ad0 pinned for a long time
    rb = eng.submit(prompt_of(24, seed=1)
                    + list(specs[1].invocation_tokens), 2,
                    adapter_name="ad1", arrival_time=1e-9)
    for k in range(6):                  # a stream of resident-adapter
        eng.submit(prompt_of(24, seed=2 + k)             # overtakers
                   + list(specs[0].invocation_tokens), 2,
                   adapter_name="ad0", arrival_time=1e-9 * (2 + k))
    admit_order, seen = [], set()
    for _ in range(500):
        if not (eng.pending or eng.waiting or eng.running):
            break
        eng.step()
        for r in eng.running:
            if r.req_id not in seen:
                seen.add(r.req_id)
                admit_order.append(r.req_id)
        # the property: the cap bounds every queued request's bypasses
        assert all(q.admission_skips <= cap for q in eng.waiting)
    else:
        raise AssertionError("engine did not drain")
    # exactly `cap` younger admissions overtook rb, then it barriered:
    # nothing younger admitted until rb itself got its slot
    assert admit_order.index(rb) == admit_order.index(hold) + 1 + cap
    assert eng.request(rb).admission_skips == cap
    assert len(eng.request(rb).output_tokens) == 2
    assert eng.adapter_pool.pinned_slots() == 0


def test_affinity_churn_with_preemption_matches_oracle(eng_setup):
    """Adapter churn + recompute-preemption under affinity reordering:
    a KV pool too small for the working set forces preemptions while
    slots cycle; tokens must still match the all-resident sequential
    oracle and every pin and stage must drain."""
    cfg, params, specs, weights = eng_setup
    ads = list(zip(specs, weights))

    def workload(eng, gen=4):
        # 61 + 3 invocation tokens = 64 = exactly 4 blocks: the first
        # decode token then needs a 5th block -> guaranteed starvation
        # at num_blocks=8 with two requests running
        rids = [eng.submit(prompt_of(61, seed=k)
                           + list(s.invocation_tokens), gen,
                           adapter_name=s.name, arrival_time=1e-9 * k)
                for k, s in enumerate(specs)]
        eng.run_until_idle()
        return [eng.request(r).output_tokens for r in rids]

    eng_o = Engine(cfg, params, adapters=ads,
                   engine_cfg=EngineConfig(execution_mode="sequential",
                                           max_running=2))
    oracle = workload(eng_o)
    assert eng_o.adapter_pool.evictions == 0     # oracle: all resident

    eng = Engine(cfg, params, adapters=ads,
                 engine_cfg=EngineConfig(adapter_slots=2, max_running=2,
                                         num_blocks=8))
    out = workload(eng)
    assert out == oracle
    assert eng.preemptions > 0                   # pool actually starved
    assert eng.adapter_pool.evictions > 0        # slots actually cycled
    assert eng.adapter_pool.pinned_slots() == 0
    assert eng.adapter_pool.staged_now == 0
    assert eng.kv_mgr.num_free() == eng.ecfg.num_blocks


def test_failed_admission_never_wastes_an_install(setup, monkeypatch):
    """The adapter slot is charged AFTER block allocation: a KV-side
    admission failure must leave the pool completely untouched — no
    pin, no install, no eviction paid for a request that can't run."""
    from repro.core.kv_manager import OutOfBlocks
    cfg, params = setup
    s = AdapterSpec("ad", rank=8)
    eng = Engine(cfg, params, adapters=[(s, mk_weights(cfg, 80))],
                 engine_cfg=EngineConfig(num_blocks=32))
    monkeypatch.setattr(eng.kv_mgr, "allocate",
                        lambda: (_ for _ in ()).throw(
                            OutOfBlocks("injected")))
    rid = eng.submit(prompt_of(48, seed=1), 2, adapter_name="ad")
    assert not eng._try_admit(eng.request(rid))
    monkeypatch.undo()
    pool = eng.adapter_pool
    assert pool.pinned_slots() == 0
    assert pool.installs == 0                    # never touched
    assert eng.request(rid).adapter_slot == 0
    eng.run_until_idle()
    assert len(eng.request(rid).output_tokens) == 2


def test_adapter_slot_failure_rolls_back_blocks(setup):
    """The converse path: blocks were allocated, then the adapter slot
    could not be pinned — everything block-side must be released."""
    cfg, params = setup
    sa, sb = AdapterSpec("A", rank=8), AdapterSpec("B", rank=8)
    eng = Engine(cfg, params,
                 adapters=[(sa, mk_weights(cfg, 81)),
                           (sb, mk_weights(cfg, 82))],
                 engine_cfg=EngineConfig(adapter_slots=1, max_running=4))
    ra = eng.submit(prompt_of(32, seed=1), 8, adapter_name="A")
    eng.step()                                   # A admitted, slot pinned
    free_before = eng.kv_mgr.num_free()
    rb = eng.submit(prompt_of(32, seed=2), 2, adapter_name="B")
    assert not eng._try_admit(eng.request(rb))   # no unpinned slot
    assert eng.kv_mgr.num_free() == free_before  # blocks rolled back
    assert eng.request(rb).block_ids == []
    eng.run_until_idle()                         # B runs once A finishes
    assert len(eng.request(rb).output_tokens) == 2
    assert len(eng.request(ra).output_tokens) == 8
