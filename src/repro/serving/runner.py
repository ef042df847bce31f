"""Paged model runner — executes the serving engine's jitted steps
against the paged KV pool / SSM state pools.

This is the engine-side analogue of vLLM's GPU model runner (paper §3 +
App. A/B): before each forward it assembles the aLoRA metadata (per-token
adapter indices — the activation-aware mask) and block tables, then runs
a jitted step.  The primary path is ``submit_batch`` + ``fetch_sampled``
— ONE jitted ragged step per engine iteration covering every
architecture family (attention, SSM/hybrid via a ragged SSD scan,
encoder-decoder via per-row cross-attention KV), dispatched without
blocking so the engine can retire it a step later (``execute_batch`` is
the submit-then-fetch sync wrapper); the v0-style
``prefill_chunk``/``decode_batch`` pair is kept for the explicit
sequential mode.  Host-side assembly reuses
persistent capacity-doubling buffers (``HostBufferPool``) instead of
reallocating per step.  The numerical sublayers are shared with the
distributed step functions (``repro.models``); shapes are bucketed
(powers of two) so jit caches a bounded set of traces.  The jitted step
functions are module-level with a hashable static ``RunnerSpec`` so
independent Engine instances over the same config share one compilation
cache (the analogue of vLLM's CUDA-graph reuse across server restarts in
a warm process).

With a mesh (``EngineConfig.mesh``) the SAME single jitted step runs
TP-sharded under GSPMD: params tensor-parallel, the paged K/V pool split
on its KV-head (or head_dim) dim, SSM pools on their head/channel dims,
adapter slot stacks column-parallel on B's output dim, and all per-token
metadata replicated (``distributed.sharding`` §Sharded serving).  The
static ``StepShardings`` in the spec pins output layouts so pools never
reshard between steps; the host-side assembly below is untouched.

Sampling happens ON DEVICE: the mixed step ends in an argmax over the
per-request logits rows and returns only the sampled ``int32`` token ids
— the full ``(R, vocab)`` logits never cross to host.  A device-resident
``tok_buf`` keeps each run slot's last sampled token so the NEXT step's
decode rows can reference it (``MixedBatch.from_buf``) before the host
has ever seen the value — the mechanism behind the engine's one-step-
lookahead async submission (``EngineConfig.async_submission``).
``submit_batch`` dispatches without blocking and returns a
:class:`StepHandle`; ``fetch_sampled`` is the step's ONLY device→host
transfer (logged in ``d2h_fetches`` so benchmarks can assert the payload
stays sampled-ids-sized).

Pools:
  k_pool/v_pool:     (La, NB, bs, KV, hd)   — last block id is a write
                                              dump for padded slots
  live_ssm/conv:     (Ls, MR, ...)          — per running-slot SSM state
  snap_ssm/conv:     (Ls, NS, ...)          — block-boundary snapshots
                                              (cross-model state reuse)
  tok_buf:           (MR,) int32            — last sampled token per run
                                              slot (async decode feed)
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, SSM, ModelConfig
from repro.distributed import sharding as shd
from repro.distributed.sharding import StepShardings
from repro.kernels.ref import packed_cross_attention_ref, paged_attention_ref
from repro.models import attention as attn_dispatch
from repro.models import layers as Lyr
from repro.models import model as M
from repro.models import ssm as ssm_lib
from repro.models.model import Runtime
from repro.obs.tracer import Tracer

NEG_INF = -1e30

# the named scopes of the mixed step (``_mixed_impl``), as its ops' paths
# carry them in the compiled module's metadata and the device trace;
# ``lora`` is ``lora_delta_dispatch``'s own, nested in ``qkv`` or ``ssd``
STEP_SCOPES = ("embed", "qkv", "lora", "kv_write", "attention", "out_proj",
               "mlp", "logits", "ssd")


def next_pow2(n: int, lo: int = 1) -> int:
    v = lo
    while v < n:
        v *= 2
    return v


# bounded device→host fetch log (``ModelRunner.d2h_fetches``): trim the
# OLDEST half in bulk at the threshold so a long-lived engine never
# accumulates one entry per step forever
D2H_LOG_MAX = 4096
D2H_LOG_KEEP = 2048


def log_d2h(log: List[Tuple[int, str, str]], elems: int, dtype: str,
            tag: str, tracer: Optional[Tracer] = None) -> None:
    """Record one blocking device→host transfer as ``(elems, dtype, tag)``.

    Every host sync on the serving path must route through this logger —
    the hot-path lint (``repro.analysis.hotpath_lint``) rejects any
    ``# hotpath: sync-ok`` site whose function doesn't.  Tags:

      "step"  — the per-step sampled-ids fetch (benchmarks/tests assert
                the ids-only payload over exactly these entries)
      "xkv"   — enc-dec encoder-KV restack on a batch-membership miss
      "admit" — admission-time prompt-embedding materialization

    Overflow trims in bulk, keeping the most recent ``D2H_LOG_KEEP``
    entries in order (unit-tested in ``tests/test_analysis.py``).

    ``tracer`` (the runner's, when tracing is on) counts the transfer
    per tag (``d2h_<tag>_transfers_total`` / ``d2h_<tag>_elems_total``,
    exported to Prometheus); the step fetch's time is the engine's
    ``fetch`` phase.
    """
    if len(log) >= D2H_LOG_MAX:
        del log[:len(log) - D2H_LOG_KEEP]
    log.append((elems, dtype, tag))
    if tracer is not None and tracer.enabled:
        tracer.count(f"d2h_{tag}_transfers_total")
        tracer.count(f"d2h_{tag}_elems_total", elems)


@dataclass(frozen=True)
class RunnerConfig:
    block_size: int = 16
    num_blocks: int = 512           # incl. 1 reserved dump block
    max_running: int = 9            # incl. 1 reserved dump slot
    num_state_slots: int = 65       # incl. 1 reserved dump slot
    chunk_tokens: int = 64          # max prefill chunk (multiple of bs)
    mixed_attn_impl: str = "ref"    # "ref" | "pallas" | "pallas_interpret"
    mixed_ssd_impl: str = "ref"     # "ref" | "pallas" | "pallas_interpret"
    # grouped-LoRA delta for the mixed step: "ref" (ragged jnp over the
    # step's active slots) | "pallas"/"pallas_interpret" (SGMV kernel) |
    # "dense" (the pre-pool full stacked scan; equivalence oracle)
    mixed_lora_impl: str = "ref"
    # shard the packed token axis of the mixed step over the mesh "data"
    # axis (per-token metadata + input embeds split; per-request arrays
    # and sampled ids replicated).  No-op without a mesh or with a
    # size-1 data axis; False keeps the replicate-everything TP layout.
    data_shard_tokens: bool = True


@dataclass(frozen=True)
class RunnerSpec:
    """Hashable static context for the jitted step functions."""
    cfg: ModelConfig
    block_size: int
    num_blocks: int
    window: int
    kinds: Tuple[str, ...]
    rt: Runtime = Runtime()
    attn_impl: str = "ref"
    ssd_impl: str = "ref"
    lora_impl: str = "ref"
    # TP-sharded execution over EngineConfig.mesh: pins the output
    # layouts of the mixed step (None = the single-device default path,
    # traced exactly as before)
    shard: Optional[StepShardings] = None


@dataclass
class MixedBatch:
    """One engine step's ragged token batch: all scheduled decode tokens
    plus all scheduled prefill chunks, packed along a single token axis
    with per-token metadata rows (vLLM v1-style single mixed batch).

    Per-token arrays (T,):
      tok_ids     — token id (embedded in-step; ignored where use_embeds)
      from_buf    — row's token id is NOT host-known: the step reads it
                    from the device-resident ``tok_buf`` at the row's run
                    slot instead (the previous step's sampled token —
                    async one-step-lookahead decode rows)
      use_embeds  — row comes from ``embeds`` instead (prefill rows,
                    incl. multimodal prefix embeds)
      positions   — absolute position in the request
      adapter_idx — activation-aware adapter index (0 = base)
      req_rows    — token → request row in the per-request arrays
      row_cols    — token's offset within its request's packed segment
                    (0 ⇒ segment start; SSM state/conv gather point)
      write_bids/write_offs — physical (block, offset) this token's K/V
                    is written to

    Per-request:
      block_tables — physical block ids (ragged list-of-lists)
      out_rows     — token index whose hidden state yields the request's
                    logits (chunk tail for prefill, the token itself for
                    decode); doubles as the segment-final index for the
                    SSM live-state scatter-back
      run_slots    — live-state slot per request (SSM/hybrid archs)
      xkv_list     — per-request projected encoder K/V (enc-dec archs)

    snap_rows — packed indices of prefill block-boundary tokens whose
    post-token SSM state is emitted for the prefix cache.
    """
    tok_ids: np.ndarray
    embeds: np.ndarray                       # (T, d)
    use_embeds: np.ndarray
    positions: np.ndarray
    adapter_idx: np.ndarray
    req_rows: np.ndarray
    row_cols: np.ndarray
    write_bids: np.ndarray
    write_offs: np.ndarray
    block_tables: List[List[int]]
    out_rows: np.ndarray
    run_slots: np.ndarray
    snap_rows: np.ndarray
    xkv_list: Optional[List[Tuple]] = None
    # ascending adapter-slot ids this step's tokens reference (grouped-
    # LoRA active set); padded with 0 (zero adapter) to a pow2 bucket
    active_slots: Optional[np.ndarray] = None
    # (T,) bool: token id comes from the device tok_buf, not tok_ids
    # (None -> all host-known, the sync-oracle assembly)
    from_buf: Optional[np.ndarray] = None


@dataclass
class StepHandle:
    """An in-flight mixed step: device futures only, nothing synced.

    ``sampled`` is the step's (Rb,) int32 on-device sampled-token array
    (argmax row per request, bucket-padded); ``boundary`` the SSM
    block-boundary state pair (or ``None``); ``n_requests`` the real row
    count.  ``ModelRunner.fetch_sampled`` performs the one blocking
    device→host transfer that retires the handle."""
    sampled: jax.Array
    boundary: Optional[Tuple]
    n_requests: int


def _chunk_attention(q, past_k, past_v, past_len, new_k, new_v,
                     start_pos, window: int):
    """Prefill-chunk attention over [cached past || current chunk].

    q/new_k/new_v: (1, C, H|KV, hd); past_k/past_v: (1, Sp, KV, hd);
    past entries valid where index < past_len.  Absolute positions:
    past j -> j, chunk i -> start_pos + i.
    """
    B, C, H, hd = q.shape
    KV = new_k.shape[2]
    G = H // KV
    Sp = past_k.shape[1]
    scale = 1.0 / (hd ** 0.5)
    qr = q.reshape(B, C, KV, G, hd)

    k_all = jnp.concatenate([past_k, new_k], axis=1)     # (1, Sp+C, KV, hd)
    v_all = jnp.concatenate([past_v, new_v], axis=1)
    s = jnp.einsum("bckgd,bskd->bkgcs", qr, k_all,
                   preferred_element_type=jnp.float32) * scale
    qpos = start_pos + jnp.arange(C, dtype=jnp.int32)    # (C,)
    kpos = jnp.concatenate([jnp.arange(Sp, dtype=jnp.int32),
                            start_pos + jnp.arange(C, dtype=jnp.int32)])
    valid = jnp.concatenate([jnp.arange(Sp) < past_len,
                             jnp.ones((C,), bool)])
    mask = valid[None, :] & (kpos[None, :] <= qpos[:, None])
    if window > 0:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgcs,bskd->bckgd", p, v_all.astype(jnp.float32))
    return o.reshape(B, C, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# jitted step functions (module level, static spec)
#
# The device pools (K/V, SSM live state, tok_buf) are DONATED to every
# step: each is consumed and returned updated, so without donation XLA
# would hold both generations live across the call — double the pool HBM.
# ``repro.analysis.step_audit`` statically verifies the aliasing survived
# compilation (input_output_alias) on every config × mesh; the HBM delta
# shows up in ``benchmarks/report.py``'s audit table.
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4, 5, 6))
def _prefill_impl(spec: RunnerSpec, params, adapter_layers, k_pool, v_pool,
                  live_ssm, live_conv, x_chunk, valid_len, start_pos,
                  block_table, adapter_idx, run_slot, xkv):
    cfg, rt = spec.cfg, spec.rt
    bs = spec.block_size
    Cb = x_chunk.shape[1]
    dump = spec.num_blocks - 1
    x = x_chunk
    positions = (start_pos + jnp.arange(Cb, dtype=jnp.int32))[None]  # (1,Cb)
    gpos = positions[0]
    i_valid = jnp.arange(Cb) < valid_len
    nbb = block_table.shape[0]
    bids = jnp.where(i_valid,
                     block_table[jnp.clip(gpos // bs, 0, nbb - 1)], dump)
    offs = gpos % bs
    boundary_ssm, boundary_conv = [], []
    ai = si = 0
    layers_params = [lp for _, lp in M.iter_layers(params, cfg)]
    for li, kind in enumerate(spec.kinds):
        lp = layers_params[li]
        al = adapter_layers[li]
        if kind == SSM:
            h = Lyr.rmsnorm(x, lp["ln"], cfg.norm_eps)
            st = live_ssm[si, run_slot][None]
            cv = live_conv[si, run_slot][None]
            y, st2, cv2, (bs_ssm, bs_conv) = ssm_lib.ssd_forward(
                lp["ssm"], cfg, h, ssm_state=st, conv_state=cv,
                alora=al, adapter_idx=adapter_idx,
                valid_len=valid_len, return_boundary_states=True)
            live_ssm = live_ssm.at[si, run_slot].set(st2[0])
            live_conv = live_conv.at[si, run_slot].set(cv2[0])
            boundary_ssm.append(bs_ssm[:, 0])          # (nc, nh, N, P)
            boundary_conv.append(bs_conv[:, 0])
            x = x + y
            si += 1
        else:
            h = Lyr.rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = Lyr.qkv_project(lp["attn"], cfg, h, al, adapter_idx)
            q = Lyr.apply_rope(q, positions, cfg.rope_theta)
            k = Lyr.apply_rope(k, positions, cfg.rope_theta)
            past_k = k_pool[ai][block_table].reshape(
                1, -1, cfg.num_kv_heads, cfg.head_dim)
            past_v = v_pool[ai][block_table].reshape(
                1, -1, cfg.num_kv_heads, cfg.head_dim)
            o = _chunk_attention(q, past_k, past_v, start_pos,
                                 k, v, start_pos, spec.window)
            x = x + Lyr.out_project(lp["attn"], cfg, o)
            k_pool = k_pool.at[ai, bids, offs].set(k[0])
            v_pool = v_pool.at[ai, bids, offs].set(v[0])
            if cfg.is_encoder_decoder:
                x = M.cross_attn_sublayer(
                    lp, cfg, x, xkv[0][ai][None], xkv[1][ai][None])
            x, _ = M.mlp_sublayer(lp, cfg, rt, x)
            ai += 1
    x = Lyr.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    last_h = jax.lax.dynamic_index_in_dim(
        x[0], jnp.maximum(valid_len - 1, 0), axis=0, keepdims=False)
    logits = M.logits_for(params, cfg, last_h)
    b_ssm = jnp.stack(boundary_ssm) if boundary_ssm else 0
    b_conv = jnp.stack(boundary_conv) if boundary_conv else 0
    return (k_pool, v_pool, live_ssm, live_conv, b_ssm, b_conv, logits)


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4, 5, 6))
def _decode_impl(spec: RunnerSpec, params, adapter_layers, k_pool, v_pool,
                 live_ssm, live_conv, tokens, positions, block_tables,
                 lengths, adapter_idx, run_slots, write_bids, write_offs,
                 xkv):
    cfg, rt = spec.cfg, spec.rt
    x = params["embed"]["tok"][tokens][:, None, :]       # (Bb, 1, d)
    pos2 = positions[:, None]                            # (Bb, 1)
    aidx2 = adapter_idx[:, None]
    ai = si = 0
    layers_params = [lp for _, lp in M.iter_layers(params, cfg)]
    for li, kind in enumerate(spec.kinds):
        lp = layers_params[li]
        al = adapter_layers[li]
        if kind == SSM:
            h = Lyr.rmsnorm(x, lp["ln"], cfg.norm_eps)
            st = live_ssm[si, run_slots]
            cv = live_conv[si, run_slots]
            y, st2, cv2 = ssm_lib.ssd_decode_step(
                lp["ssm"], cfg, h, st, cv, alora=al, adapter_idx=aidx2)
            live_ssm = live_ssm.at[si, run_slots].set(st2)
            live_conv = live_conv.at[si, run_slots].set(cv2)
            x = x + y
            si += 1
        else:
            h = Lyr.rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = Lyr.qkv_project(lp["attn"], cfg, h, al, aidx2)
            q = Lyr.apply_rope(q, pos2, cfg.rope_theta)
            k = Lyr.apply_rope(k, pos2, cfg.rope_theta)
            k_pool = k_pool.at[ai, write_bids, write_offs].set(k[:, 0])
            v_pool = v_pool.at[ai, write_bids, write_offs].set(v[:, 0])
            o = paged_attention_ref(q[:, 0], k_pool[ai], v_pool[ai],
                                    block_tables, lengths,
                                    window=spec.window)
            x = x + Lyr.out_project(lp["attn"], cfg, o[:, None])
            if cfg.is_encoder_decoder:
                x = M.cross_attn_sublayer(lp, cfg, x,
                                          xkv[0][:, ai], xkv[1][:, ai])
            x, _ = M.mlp_sublayer(lp, cfg, rt, x)
            ai += 1
    x = Lyr.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = M.logits_for(params, cfg, x[:, 0])
    return k_pool, v_pool, live_ssm, live_conv, logits


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4, 5, 6, 7))
def _mixed_impl(spec: RunnerSpec, params, adapter_layers, k_pool, v_pool,
                live_ssm, live_conv, tok_buf, tok_ids, embeds, use_embeds,
                from_buf, positions, q_lens, adapter_idx, active_slots,
                block_tables, req_rows, row_cols, write_bids, write_offs,
                out_rows, run_slots, tok_slots, snap_rows, xkv):
    """One jitted step over the whole mixed batch — every architecture
    family shares this single device call:

    * attention: all K/V rows are written to the paged pool first, then
      every token attends over its request's blocks through the ragged
      paged-attention path — intra-chunk causality is just the q_lens
      mask, so prefill chunks and decode tokens share one code path;
    * SSM (pure and hybrid): a ragged SSD scan over the packed token
      axis — each request's live recurrent/conv state is gathered at its
      segment start (``row_cols == 0``), scanned through its tokens, and
      scattered back at its final token, with block-boundary states
      emitted at ``snap_rows`` for the prefix cache;
    * encoder-decoder: every token cross-attends over its OWN request's
      projected encoder K/V, gathered per token by ``req_rows``.

    Sampling is part of the step: the per-request logits rows reduce to
    an argmax ON DEVICE, the sampled ids land in ``tok_buf`` at each
    request's run slot (next step's decode rows read them back through
    ``from_buf`` without a host round-trip), and only the (Rb,) int32
    ``sampled`` array is ever fetched by the host.
    """
    cfg, rt = spec.cfg, spec.rt
    # each part of the step runs under a named scope (``STEP_SCOPES``),
    # which names its ops in the device trace and changes nothing else
    with jax.named_scope("embed"):
        # decode rows submitted before their token reached the host read
        # the previous step's sampled token straight from the device
        # buffer
        tok_ids = jnp.where(from_buf, tok_buf[tok_slots], tok_ids)
        tok_emb = params["embed"]["tok"][tok_ids]
        x = jnp.where(use_embeds[:, None], embeds.astype(tok_emb.dtype),
                      tok_emb)[None]                         # (1, Tb, d)
    Tb = tok_ids.shape[0]
    pos2 = positions[None]                                   # (1, Tb)
    aidx2 = adapter_idx[None]
    ai = si = 0
    boundary_ssm, boundary_conv = [], []
    layers_params = [lp for _, lp in M.iter_layers(params, cfg)]
    for li, kind in enumerate(spec.kinds):
        lp = layers_params[li]
        al = adapter_layers[li]
        if kind == SSM:
            with jax.named_scope("ssd"):
                h = Lyr.rmsnorm(x, lp["ln"], cfg.norm_eps)
                y, l_ssm, l_conv, sb_s, sb_c = ssm_lib.ssd_ragged_forward(
                    lp["ssm"], cfg, h[0], live_ssm=live_ssm[si],
                    live_conv=live_conv[si], tok_slots=tok_slots,
                    row_cols=row_cols, seg_ids=req_rows,
                    snap_rows=snap_rows, last_rows=out_rows,
                    row_slots=run_slots, alora=al, adapter_idx=adapter_idx,
                    impl=spec.ssd_impl, lora_impl=spec.lora_impl,
                    active_slots=active_slots)
                live_ssm = live_ssm.at[si].set(l_ssm)
                live_conv = live_conv.at[si].set(l_conv)
                boundary_ssm.append(sb_s)
                boundary_conv.append(sb_c)
                x = x + y[None]
            si += 1
        else:
            with jax.named_scope("qkv"):
                h = Lyr.rmsnorm(x, lp["ln1"], cfg.norm_eps)
                q, k, v = Lyr.qkv_project(lp["attn"], cfg, h, al, aidx2,
                                          lora_impl=spec.lora_impl,
                                          active_slots=active_slots)
                q = Lyr.apply_rope(q, pos2, cfg.rope_theta)
                k = Lyr.apply_rope(k, pos2, cfg.rope_theta)
            with jax.named_scope("kv_write"):
                k_pool = k_pool.at[ai, write_bids, write_offs].set(k[0])
                v_pool = v_pool.at[ai, write_bids, write_offs].set(v[0])
            with jax.named_scope("attention"):
                o = attn_dispatch.ragged_paged_attention(
                    q[0], k_pool[ai], v_pool[ai], block_tables, req_rows,
                    q_lens, window=spec.window, impl=spec.attn_impl)
                if spec.shard is not None:
                    o = spec.shard.constrain(o, spec.shard.attn_out)
            with jax.named_scope("out_proj"):
                x = x + Lyr.out_project(lp["attn"], cfg, o[None])
            if cfg.is_encoder_decoder:
                hx = Lyr.rmsnorm(x, lp["xln"], cfg.norm_eps)
                qx = (hx[0] @ lp["xattn"]["wq"]).reshape(
                    Tb, cfg.num_heads, cfg.head_dim)
                ox = packed_cross_attention_ref(
                    qx, xkv[0][ai][req_rows], xkv[1][ai][req_rows])
                x = x + Lyr.out_project(lp["xattn"], cfg, ox[None])
            with jax.named_scope("mlp"):
                x, _ = M.mlp_sublayer(lp, cfg, rt, x)
            ai += 1
    with jax.named_scope("logits"):
        x = Lyr.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = M.logits_for(params, cfg, x[0][out_rows])   # (Rb, V)
        # on-device sampling: argmax per request row; the sampled ids are
        # the step's only host-visible output AND feed the next step's
        # decode rows through the per-run-slot token buffer.  Padded
        # request rows all target the reserved dump slot.
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok_buf = tok_buf.at[run_slots].set(sampled)
    b_ssm = jnp.stack(boundary_ssm) if boundary_ssm else 0
    b_conv = jnp.stack(boundary_conv) if boundary_conv else 0
    if spec.shard is not None:
        # pin the output layouts so the pools round-trip through the step
        # with the exact sharding they were created with (no resharding
        # between steps, no post-warmup recompiles); sampled ids and the
        # token buffer gather replicated — sampling is the step's single
        # cross-shard reduction beyond the row-parallel psums
        sh = spec.shard
        k_pool = sh.constrain(k_pool, sh.kv_pool)
        v_pool = sh.constrain(v_pool, sh.kv_pool)
        live_ssm = sh.constrain(live_ssm, sh.ssm_pool)
        live_conv = sh.constrain(live_conv, sh.conv_pool)
        if boundary_ssm:
            b_ssm = sh.constrain(b_ssm, sh.ssm_pool)
            b_conv = sh.constrain(b_conv, sh.conv_pool)
        tok_buf = sh.constrain(tok_buf, sh.tok_buf)
        sampled = sh.constrain(sampled, sh.tok_buf)
    return (k_pool, v_pool, live_ssm, live_conv, tok_buf, b_ssm, b_conv,
            sampled)


@partial(jax.jit, static_argnums=0)
def _encode_impl(spec: RunnerSpec, params, frames):
    cfg = spec.cfg
    enc_out = M._run_encoder(params["encoder"], cfg, spec.rt, frames[None])
    xks, xvs = [], []
    layers_params = [lp for _, lp in M.iter_layers(params, cfg)]
    for li, kind in enumerate(spec.kinds):
        if kind != ATTN:
            continue
        lp = layers_params[li]
        xk, xv = M.encoder_kv(lp, cfg, enc_out)
        xks.append(xk[0])
        xvs.append(xv[0])
    return jnp.stack(xks), jnp.stack(xvs)                # (La, Se, KV, hd)


def jit_cache_size() -> int:
    """Total cached traces across this module's jitted step functions —
    the recompile counter the churn/sharding zero-post-warmup-recompile
    invariants are asserted on (benchmarks + tests/test_sharded_step.py).
    Lives here so adding a jitted impl can't silently escape counting.
    """
    return sum(f._cache_size() for f in (
        _mixed_impl, _prefill_impl, _decode_impl, _encode_impl))


# ---------------------------------------------------------------------------
class HostBufferPool:
    """Persistent capacity-doubling numpy buffers for per-step batch
    assembly (ROADMAP "pinned buffer" item).

    The mixed path used to reallocate every host-side assembly array
    (tok_ids, embeds, write_bids, ...) each step; this pool hands out
    slices of long-lived buffers instead, growing a buffer by doubling
    only when a step outgrows it.  ``take`` re-fills the slice (memset,
    no allocation) so callers see the same zero/dump-initialized contents
    the old np.zeros/np.full calls produced.

    Set ``REPRO_HOST_BUF_REUSE=0`` to allocate fresh arrays per call —
    the pre-pool behavior, kept for A/B assembly-time measurements
    (``benchmarks/bench_mixed_batch.py`` reports assembly_us_per_step).

    The pool is DOUBLE-BUFFERED across submissions (``flip``): jax's CPU
    backend zero-copies suitably-aligned numpy arrays into device
    buffers, so a staging buffer may be aliased by a dispatched-but-
    unfinished step — refilling it for the next step would corrupt the
    in-flight computation.  With one-step-lookahead submission at most
    ONE step is ever in flight, so alternating between two buffer sets
    (one flip per submitted step) guarantees a submission never rewrites
    memory the previous step still reads.  A deeper pipeline would need
    ``depth + 1`` generations.
    """

    def __init__(self):
        self._bufs: dict = {}
        self._gen = 0
        self._reuse = os.environ.get("REPRO_HOST_BUF_REUSE", "1") != "0"

    def flip(self) -> None:
        """Advance to the other buffer generation — call once per
        submitted step, BEFORE taking that step's staging buffers."""
        self._gen ^= 1

    def take(self, name: str, n: int, dtype, *, trailing: Tuple[int, ...] = (),
             fill=0) -> np.ndarray:
        if not self._reuse:
            return np.full((n,) + trailing, fill, dtype)
        # trailing dims are part of the key: buffers whose width
        # oscillates between steps (block tables by nbb, xk/xv by Rb —
        # already pow2-bucketed) each keep their own pooled buffer
        # instead of thrashing a single slot
        key = (name, trailing, np.dtype(dtype).str, self._gen)
        buf = self._bufs.get(key)
        if buf is None or buf.shape[0] < n:
            cap = next_pow2(max(n, 1))
            buf = np.empty((cap,) + trailing, dtype)
            self._bufs[key] = buf
        view = buf[:n]
        view[...] = fill
        return view


class ModelRunner:
    def __init__(self, cfg: ModelConfig, params, rcfg: RunnerConfig,
                 adapter_layers: Optional[List[Any]] = None,
                 rt: Runtime = Runtime(),
                 mesh: Optional[jax.sharding.Mesh] = None,
                 tracer: Optional[Tracer] = None):
        """``adapter_layers``: per-layer stacked adapter pytrees (leaves
        with a leading slot axis) — normally the AdapterPool's live
        ``layers`` list, whose entries the pool replaces in place as
        adapters move through slots.  The runner keeps the list object
        and re-reads it every step.

        ``mesh``: TP-shard the mixed step over this mesh (see the
        "Sharded serving" section of ``distributed.sharding``): params go
        tensor-parallel, the paged K/V pool splits on its KV-head dim,
        SSM pools on their head/channel dims, and per-step metadata is
        replicated.  ``None`` keeps the single-device default path
        byte-identical to before."""
        if cfg.ssm is not None and cfg.ssm.chunk_size != rcfg.block_size:
            # align SSD chunk boundaries with KV-block boundaries so state
            # snapshots land exactly on block-hash boundaries
            import dataclasses as _dc
            cfg = cfg.replace(ssm=_dc.replace(cfg.ssm,
                                              chunk_size=rcfg.block_size))
        self.cfg = cfg
        self.rcfg = rcfg
        self.rt = rt
        self.mesh = mesh
        self._shard: Optional[StepShardings] = None
        self._meta_sharding = None
        self._rep_sharding = None
        # token-bucket floor: pow2 buckets double from here so the packed
        # token axis always divides the data-axis shard count
        self._tok_bucket_lo = 1
        if mesh is not None:
            allowed = (("attn", rcfg.mixed_attn_impl, ("ref",)),
                       ("ssd", rcfg.mixed_ssd_impl, ("ref",)),
                       ("lora", rcfg.mixed_lora_impl, ("ref", "dense")))
            for kind_, impl, ok in allowed:
                if impl not in ok:
                    raise ValueError(
                        f"mixed_{kind_}_impl={impl!r} is not usable under "
                        f"a mesh (Pallas kernels are single-device); the "
                        f"TP-sharded step requires one of {ok}, which "
                        "GSPMD partitions over the mesh")
            pshape = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            pspecs = shd.param_specs_tree(cfg, pshape, mesh=mesh)
            params = jax.device_put(params, shd.to_named(pspecs, mesh))
            data_axis = "data" if rcfg.data_shard_tokens \
                and "data" in mesh.axis_names else None
            self._shard = shd.mixed_step_shardings(cfg, mesh,
                                                   data_axis=data_axis)
            # data-sharded token axis: pad every token bucket to a
            # multiple of the data-axis size so P(data) always divides
            tok_ax = next((a for a in self._shard.tok_meta
                           if a is not None), None)
            if tok_ax is not None:
                self._tok_bucket_lo = int(mesh.shape[tok_ax])
            sh = self._shard
            tm, te = sh.named(sh.tok_meta), sh.named(sh.tok_embeds)
            rep = sh.named(sh.replicated)
            # per-leaf layout of the _assemble_mixed meta tuple:
            # (tok, emb, use, fb, pos, qln, ad, act, bt, rows, cols, wb,
            #  wo, out_rows, run_slots, tok_slots, snap) — token-axis
            # leaves split over data, request/slot-axis leaves replicated
            self._meta_sharding = (tm, te, tm, tm, tm, tm, tm, rep, rep,
                                   tm, tm, tm, tm, rep, rep, tm, rep)
            self._rep_sharding = rep
        self.params = params
        self.kinds = list(cfg.pattern())       # iter_layers' order
        self.attn_ids = [i for i, k in enumerate(self.kinds) if k == ATTN]
        self.ssm_ids = [i for i, k in enumerate(self.kinds) if k == SSM]
        self.La, self.Ls = len(self.attn_ids), len(self.ssm_ids)
        self.window = M.effective_window(cfg, rt)
        self._spec = RunnerSpec(cfg=cfg, block_size=rcfg.block_size,
                                num_blocks=rcfg.num_blocks,
                                window=self.window,
                                kinds=tuple(self.kinds), rt=rt,
                                attn_impl=rcfg.mixed_attn_impl,
                                ssd_impl=rcfg.mixed_ssd_impl,
                                lora_impl=rcfg.mixed_lora_impl,
                                shard=self._shard)
        self.host_bufs = HostBufferPool()
        self._xkv_stack = (None, None)   # (membership key, stacked xk/xv)
        # device-call accounting (what benchmarks/bench_mixed_batch.py
        # reports): one entry per jitted step dispatched
        self.call_counts = {"prefill_chunk": 0, "decode_batch": 0,
                            "mixed_step": 0, "encode": 0}
        # runner-side host prep time (bucket padding + xkv stacking);
        # the engine adds its packing time — the benchmark reports the sum
        self.t_assembly = 0.0
        # (elements, dtype, tag) of every blocking device→host fetch on
        # the serving path — benchmarks assert the per-step ("step" tag)
        # D2H payload is the sampled int32 ids, never the (R, vocab)
        # logits; see ``log_d2h`` for the tag vocabulary
        self.d2h_fetches: List[Tuple[int, str, str]] = []
        # trace recorder shared with the owning engine (a disabled one
        # when constructed standalone) — log_d2h counts into it
        self.tracer = tracer if tracer is not None \
            else Tracer(enabled=False)

        # per-layer adapter stacks aligned with layer order (the shared
        # AdapterPool list, or inert Nones for adapter-free engines)
        if adapter_layers is not None:
            assert len(adapter_layers) == len(self.kinds)
            self.adapter_layers = adapter_layers
        else:
            self.adapter_layers = [None] * len(self.kinds)

        dtype = Lyr.dtype_of(cfg)
        bs, NB = rcfg.block_size, rcfg.num_blocks
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        self.k_pool = self._pool(
            jnp.zeros((max(self.La, 1), NB, bs, KV, hd), dtype),
            None if self._shard is None else self._shard.kv_pool)
        self.v_pool = self._pool(
            jnp.zeros_like(self.k_pool),
            None if self._shard is None else self._shard.kv_pool)
        if self.Ls:
            s = cfg.ssm
            d_inner, nh, ch = ssm_lib.ssm_dims(cfg)
            MR, NS = rcfg.max_running, rcfg.num_state_slots
            sh = self._shard
            ssm_spec = None if sh is None else sh.ssm_pool
            conv_spec = None if sh is None else sh.conv_pool
            self.live_ssm = self._pool(
                jnp.zeros((self.Ls, MR, nh, s.state_dim, s.head_dim),
                          jnp.float32), ssm_spec)
            self.live_conv = self._pool(
                jnp.zeros((self.Ls, MR, s.conv_width - 1, ch), dtype),
                conv_spec)
            self.snap_ssm = self._pool(
                jnp.zeros((self.Ls, NS, nh, s.state_dim, s.head_dim),
                          jnp.float32), ssm_spec)
            self.snap_conv = self._pool(
                jnp.zeros((self.Ls, NS, s.conv_width - 1, ch), dtype),
                conv_spec)
        else:
            self.live_ssm = self.live_conv = None
            self.snap_ssm = self.snap_conv = None
        # last sampled token per run slot (every arch family): lets the
        # next step's decode rows reference a token the host has not yet
        # fetched (async one-step lookahead)
        self.tok_buf = self._pool(
            jnp.zeros((rcfg.max_running,), jnp.int32),
            None if self._shard is None else self._shard.tok_buf)

    # ------------------------------------------------------------------
    # sharded-execution helpers
    # ------------------------------------------------------------------
    def _pool(self, a: jax.Array, spec) -> jax.Array:
        """Place a device pool in its step layout (no-op when unsharded)."""
        if spec is None or self._shard is None:
            return a
        return jax.device_put(a, self._shard.named(spec))

    def _dev(self, a):
        """Stage host data on device, replicated over the mesh in sharded
        mode, the plain default placement otherwise.  Accepts a pytree."""
        if self._rep_sharding is not None:
            return jax.device_put(a, self._rep_sharding)
        return jax.tree.map(jnp.asarray, a)

    def _dev_meta(self, meta: Tuple):
        """Stage the mixed step's 17-leaf metadata tuple on device in its
        step layout — token-axis leaves split over the data axis when
        token sharding is on (replicated otherwise), per-request/slot
        leaves always replicated.  One batched transfer for the whole
        tuple rather than a dispatch per array."""
        if self._meta_sharding is not None:
            return jax.device_put(meta, self._meta_sharding)
        return jax.tree.map(jnp.asarray, meta)

    # ------------------------------------------------------------------
    # embeddings
    # ------------------------------------------------------------------
    def embed_tokens(self, tokens: np.ndarray) -> jax.Array:
        return self.params["embed"]["tok"][jnp.asarray(tokens)]

    def build_input_embeds(self, prompt: List[int],
                           prefix_embeds: Optional[np.ndarray]) -> np.ndarray:
        """Materialize a request's prompt embeddings HOST-SIDE (numpy) at
        admission, so every later mixed-batch assembly packs its rows
        with plain slice copies and zero device round-trips.  The one
        device→host sync this costs happens once per admitted request,
        never per step, and is logged under the "admit" tag."""
        emb = np.asarray(  # hotpath: sync-ok (once per admission)
            self.embed_tokens(np.array(prompt, np.int32)))
        log_d2h(self.d2h_fetches, int(emb.size), str(emb.dtype), "admit",
                self.tracer)
        if prefix_embeds is not None:
            pe = prefix_embeds.astype(emb.dtype, copy=False)
            # hashing pseudo-tokens already cover the patch prefix; the
            # embeds replace the leading len(pe) rows
            emb = np.concatenate([pe, emb[len(pe):]], axis=0) \
                if len(prompt) >= pe.shape[0] else pe
        return emb

    # ------------------------------------------------------------------
    # encoder (whisper)
    # ------------------------------------------------------------------
    def encode(self, frames: np.ndarray):
        self.call_counts["encode"] += 1
        return _encode_impl(self._spec, self.params, jnp.asarray(frames))

    @property
    def num_device_calls(self) -> int:
        return sum(self.call_counts.values())

    # ------------------------------------------------------------------
    # unified mixed-batch step (decode tokens + prefill chunks, one call)
    # ------------------------------------------------------------------
    def _assemble_mixed(self, mb: MixedBatch) -> Tuple:
        """Host-side half of :meth:`submit_batch`: bucket the ragged
        batch into the pooled pow2-padded staging buffers and stage the
        metadata on device.  Returns the EXACT positional argument tuple
        ``_mixed_impl`` is dispatched with — :meth:`lower_mixed` lowers
        the same tuple, so the static auditor analyzes precisely the
        compiled artifact production dispatches."""
        t_host = time.perf_counter()
        # new staging generation: never rewrite buffers the (at most
        # one) still-executing previous step may alias zero-copy
        self.host_bufs.flip()
        rc = self.rcfg
        T = len(mb.tok_ids)
        R = len(mb.block_tables)
        C = len(mb.snap_rows)
        dump_block = rc.num_blocks - 1
        dump_slot = rc.max_running - 1
        # bucketed shapes (powers of two) bound the jit trace count; the
        # token bucket doubles from the data-shard floor so P(data)
        # always divides the packed axis
        Tb = next_pow2(max(T, 1), lo=self._tok_bucket_lo)
        Rb = next_pow2(max(R, 1))
        Cb = next_pow2(max(C, 1))
        nbb = next_pow2(max(max((len(t) for t in mb.block_tables),
                                default=1), 1))

        dtype = Lyr.dtype_of(self.cfg)
        take = self.host_bufs.take
        tok = take("tok", Tb, np.int32)
        tok[:T] = mb.tok_ids
        emb = take("emb", Tb, np.float32, trailing=(self.cfg.d_model,))
        emb[:T] = mb.embeds
        use = take("use", Tb, bool)
        use[:T] = mb.use_embeds
        pos = take("pos", Tb, np.int32)
        pos[:T] = mb.positions
        # causal length per token; 0 fully masks padded rows
        qln = take("qln", Tb, np.int32)
        qln[:T] = mb.positions + 1
        ad = take("ad", Tb, np.int32)
        ad[:T] = mb.adapter_idx
        rows = take("rows", Tb, np.int32, fill=Rb - 1)
        rows[:T] = mb.req_rows
        cols = take("cols", Tb, np.int32)
        cols[:T] = mb.row_cols
        wb = take("wb", Tb, np.int32, fill=dump_block)
        wb[:T] = mb.write_bids
        wo = take("wo", Tb, np.int32)
        wo[:T] = mb.write_offs
        bt = take("bt", Rb, np.int32, trailing=(nbb,), fill=dump_block)
        for i, t in enumerate(mb.block_tables):
            bt[i, :len(t)] = t
        out_rows = take("out_rows", Rb, np.int32)
        out_rows[:R] = mb.out_rows
        run_slots = take("run_slots", Rb, np.int32, fill=dump_slot)
        run_slots[:R] = mb.run_slots
        # per-token run slot for the ragged SSD state/conv gathers
        tok_slots = take("tok_slots", Tb, np.int32, fill=dump_slot)
        tok_slots[:T] = run_slots[rows[:T]]
        fb = take("fb", Tb, bool)
        if mb.from_buf is not None:
            fb[:T] = mb.from_buf
        snap = take("snap", Cb, np.int32)
        snap[:C] = mb.snap_rows
        # active adapter slots, pow2-bucketed; padding entries are slot 0
        # (the zero adapter — an exact no-op term in the grouped delta)
        acts = mb.active_slots if mb.active_slots is not None \
            else np.zeros((0,), np.int32)
        Ab = next_pow2(max(len(acts), 1))
        act = take("act", Ab, np.int32)
        act[:len(acts)] = acts
        xkv = self._stack_xkv(mb.xkv_list, Rb, dtype) \
            if mb.xkv_list is not None else None
        self.t_assembly += time.perf_counter() - t_host

        meta = self._dev_meta((tok, emb, use, fb, pos, qln, ad, act, bt,
                               rows, cols, wb, wo, out_rows, run_slots,
                               tok_slots, snap))
        return (self._spec, self.params, self.adapter_layers, self.k_pool,
                self.v_pool, self.live_ssm, self.live_conv, self.tok_buf,
                *meta, xkv)

    def submit_batch(self, mb: MixedBatch) -> StepHandle:
        """Dispatch one mixed ragged batch as a single jitted device call
        WITHOUT blocking on its result.

        Returns a :class:`StepHandle` whose ``sampled`` array holds the
        on-device argmax-sampled token id per request row (taken at that
        request's last packed token) and whose ``boundary`` is ``None``
        for attention-only archs, else a ``(b_ssm (Ls, Cb, nh, N, P),
        b_conv (Ls, Cb, W-1, ch))`` pair of post-token SSM states at the
        batch's ``snap_rows`` (prefill block boundaries), in snap-row
        order, for prefix-cache state registration.  The caller retires
        the handle with :meth:`fetch_sampled` — in async mode only after
        the NEXT step has been submitted.

        The pools ride donated through the call (``_mixed_impl``'s
        ``donate_argnums``) and are immediately rebound to the step's
        outputs below — the pre-step arrays are dead the moment the step
        is dispatched, and XLA reuses their buffers for the outputs.
        """
        return self.dispatch_mixed(self._assemble_mixed(mb),
                                   len(mb.block_tables))

    def dispatch_mixed(self, args: Tuple, n_requests: int) -> StepHandle:
        """The device half of :meth:`submit_batch`: dispatch
        ``_mixed_impl`` on the tuple :meth:`_assemble_mixed` returned for
        a batch of ``n_requests`` rows, and rebind the donated pools."""
        self.call_counts["mixed_step"] += 1
        (self.k_pool, self.v_pool, live_ssm, live_conv, self.tok_buf,
         b_ssm, b_conv, sampled) = _mixed_impl(*args)
        boundary = None
        if self.Ls:
            self.live_ssm, self.live_conv = live_ssm, live_conv
            boundary = (b_ssm, b_conv)
        return StepHandle(sampled=sampled, boundary=boundary,
                          n_requests=n_requests)

    def lower_mixed(self, mb: MixedBatch):
        """Lower (but do not execute) the mixed step EXACTLY as
        :meth:`submit_batch` would dispatch it — same jitted function,
        same static spec, same donation, same bucketed shapes — and
        return the :class:`jax.stages.Lowered`.  This is the entry point
        of the compiled-step auditor (``repro.analysis.step_audit``):
        auditing anything other than this tuple would verify a step
        production never runs."""
        return _mixed_impl.lower(*self._assemble_mixed(mb))

    def fetch_sampled(self, handle: StepHandle) -> np.ndarray:
        """Block until ``handle``'s step finished and return its sampled
        token ids, (R,) int32 — the mixed path's ONLY per-step
        device→host transfer (a few bytes per request, never the full
        logits).  Retire-phase: the blocking sync is allowed here."""
        log_d2h(self.d2h_fetches, int(handle.sampled.size),
                str(np.dtype(handle.sampled.dtype)), "step", self.tracer)
        return np.asarray(handle.sampled)[:handle.n_requests]

    def execute_batch(self, mb: MixedBatch):
        """Synchronous submit+fetch convenience wrapper: returns
        (sampled (R,) int32, boundary)."""
        handle = self.submit_batch(mb)
        return self.fetch_sampled(handle), handle.boundary

    def _stack_xkv(self, xkv_list, Rb: int, dtype):
        """Stack per-request encoder K/V into an (La, Rb, Se, KV, hd)
        pair (``xkv_list``: [(req_id, (xk, xv)), ...] in batch-row order).

        Cached by batch membership: a request's encoder K/V never changes
        during its lifetime, so steady-state decode restacks nothing.
        """
        key = (tuple((rid, id(k)) for rid, (k, _) in xkv_list), Rb)
        if self._xkv_stack[0] == key:
            return self._xkv_stack[1]
        Se = xkv_list[0][1][0].shape[1]
        KV, hd = self.cfg.num_kv_heads, self.cfg.head_dim
        # FRESH arrays on every membership miss, never pooled: the
        # stacked device arrays are cached across steps, so they can
        # outlive both HostBufferPool generations — a pooled buffer
        # could be rewritten while an in-flight step still (zero-copy)
        # reads the cached stack.  Misses are rare (membership changes),
        # steady-state decode hits the cache and allocates nothing.
        xk = np.zeros((self.La, Rb, Se, KV, hd), dtype)
        xv = np.zeros_like(xk)
        for i, (_, (k_, v_)) in enumerate(xkv_list):
            xk[:, i] = np.asarray(k_)  # hotpath: sync-ok (membership miss)
            xv[:, i] = np.asarray(v_)  # hotpath: sync-ok (membership miss)
        log_d2h(self.d2h_fetches, int(xk.size + xv.size), str(xk.dtype),
                "xkv", self.tracer)
        stacked = (self._dev(xk), self._dev(xv))
        self._xkv_stack = (key, stacked)
        return stacked

    # ------------------------------------------------------------------
    # prefill chunk
    # ------------------------------------------------------------------
    def prefill_chunk(self, *, input_embeds, lo: int, hi: int,
                      block_ids: List[int], adapter_idx_row: np.ndarray,
                      run_slot: int, xkv=None):
        """Execute prefill of tokens [lo, hi) of one request.

        Returns (logits at token hi-1 (V,), boundary states).
        The chunk is padded to a bucket; the block table to pow2.
        """
        rc = self.rcfg
        C = hi - lo
        Cb = next_pow2(C, lo=min(rc.block_size, rc.chunk_tokens))
        x = jnp.zeros((1, Cb, self.cfg.d_model), input_embeds.dtype)
        x = x.at[0, :C].set(input_embeds[lo:hi])
        nbb = next_pow2(max(len(block_ids), 1))
        bt = np.full((nbb,), rc.num_blocks - 1, np.int32)
        bt[:len(block_ids)] = block_ids
        aidx = np.zeros((1, Cb), np.int32)
        aidx[0, :C] = adapter_idx_row
        self.call_counts["prefill_chunk"] += 1
        (self.k_pool, self.v_pool, live_ssm, live_conv, b_ssm, b_conv,
         logits) = _prefill_impl(
            self._spec, self.params, self.adapter_layers, self.k_pool,
            self.v_pool, self.live_ssm, self.live_conv, x,
            jnp.asarray(C, jnp.int32), jnp.asarray(lo, jnp.int32),
            jnp.asarray(bt), jnp.asarray(aidx),
            jnp.asarray(run_slot, jnp.int32), xkv)
        if self.Ls:
            self.live_ssm, self.live_conv = live_ssm, live_conv
        return logits, (b_ssm, b_conv)

    # ------------------------------------------------------------------
    # decode batch
    # ------------------------------------------------------------------
    def decode_batch(self, *, tokens: np.ndarray, positions: np.ndarray,
                     block_tables: List[List[int]], lengths: np.ndarray,
                     adapter_idx: np.ndarray, run_slots: np.ndarray,
                     xkv_list=None):
        """One decode step for a batch of requests (host-padded).

        Returns logits (B, V) for the real rows.
        """
        rc = self.rcfg
        B = len(tokens)
        Bb = next_pow2(B)
        dump_block = rc.num_blocks - 1
        dump_slot = rc.max_running - 1
        nbb = next_pow2(max(max((len(t) for t in block_tables), default=1),
                            1))
        tok = np.zeros((Bb,), np.int32)
        tok[:B] = tokens
        pos = np.zeros((Bb,), np.int32)
        pos[:B] = positions
        bt = np.full((Bb, nbb), dump_block, np.int32)
        for i, t in enumerate(block_tables):
            bt[i, :len(t)] = t
        ln = np.zeros((Bb,), np.int32)
        ln[:B] = lengths
        ad = np.zeros((Bb,), np.int32)
        ad[:B] = adapter_idx
        rs = np.full((Bb,), dump_slot, np.int32)
        rs[:B] = run_slots
        wb = np.full((Bb,), dump_block, np.int32)
        wo = np.zeros((Bb,), np.int32)
        for i in range(B):
            p = positions[i]
            if block_tables[i]:                # attn-free archs: no KV
                wb[i] = block_tables[i][p // rc.block_size]
                wo[i] = p % rc.block_size
        xkv = None
        if xkv_list is not None:
            Se = xkv_list[0][0].shape[1]
            KV, hd = self.cfg.num_kv_heads, self.cfg.head_dim
            xk = jnp.zeros((Bb, self.La, Se, KV, hd), xkv_list[0][0].dtype)
            xv = jnp.zeros_like(xk)
            for i, (k_, v_) in enumerate(xkv_list):
                xk = xk.at[i].set(k_)
                xv = xv.at[i].set(v_)
            xkv = (xk, xv)
        self.call_counts["decode_batch"] += 1
        (self.k_pool, self.v_pool, live_ssm, live_conv,
         logits) = _decode_impl(
            self._spec, self.params, self.adapter_layers, self.k_pool,
            self.v_pool, self.live_ssm, self.live_conv, jnp.asarray(tok),
            jnp.asarray(pos), jnp.asarray(bt), jnp.asarray(ln),
            jnp.asarray(ad), jnp.asarray(rs), jnp.asarray(wb),
            jnp.asarray(wo), xkv)
        if self.Ls:
            self.live_ssm, self.live_conv = live_ssm, live_conv
        return np.asarray(logits[:B])

    # ------------------------------------------------------------------
    # SSM state snapshot / restore
    # ------------------------------------------------------------------
    def snapshot_boundary(self, boundary, c_idx: int, slot: int):
        b_ssm, b_conv = boundary
        self.snap_ssm = self.snap_ssm.at[:, slot].set(b_ssm[:, c_idx])
        self.snap_conv = self.snap_conv.at[:, slot].set(b_conv[:, c_idx])

    def snapshot_live(self, run_slot: int, slot: int):
        self.snap_ssm = self.snap_ssm.at[:, slot].set(
            self.live_ssm[:, run_slot])
        self.snap_conv = self.snap_conv.at[:, slot].set(
            self.live_conv[:, run_slot])

    def restore_state(self, slot: int, run_slot: int):
        self.live_ssm = self.live_ssm.at[:, run_slot].set(
            self.snap_ssm[:, slot])
        self.live_conv = self.live_conv.at[:, run_slot].set(
            self.snap_conv[:, slot])

    def reset_live(self, run_slot: int):
        self.live_ssm = self.live_ssm.at[:, run_slot].set(0.0)
        self.live_conv = self.live_conv.at[:, run_slot].set(0.0)
