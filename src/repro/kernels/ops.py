"""jit'd public wrappers for the Pallas kernels: shape padding and dtype
handling.  ``interpret`` is an explicit argument of every wrapper: the
caller says whether the kernel runs in interpret mode (tests and CPU
benchmarks pass ``True``) or compiles for the TPU (``False``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.alora_qkv import alora_qkv
from repro.kernels.paged_attention import (paged_attention,
                                           ragged_paged_attention)


@partial(jax.jit, static_argnames=("t_block", "o_block", "interpret"))
def alora_qkv_op(x: jax.Array, w: jax.Array, a_stack: jax.Array,
                 b_stack: jax.Array, adapter_idx: jax.Array, *,
                 t_block: int = 256, o_block: int = 256,
                 interpret: bool) -> jax.Array:
    """Padded/jitted fused aLoRA projection.  x: (T, d) -> (T, out)."""
    T, d = x.shape
    out = w.shape[1]
    tb = min(t_block, max(T, 8))
    ob = min(o_block, out)
    Tp = ((T + tb - 1) // tb) * tb
    Op = ((out + ob - 1) // ob) * ob
    xp = jnp.pad(x, ((0, Tp - T), (0, 0)))
    ip = jnp.pad(adapter_idx, (0, Tp - T))
    wp = jnp.pad(w, ((0, 0), (0, Op - out)))
    bp = jnp.pad(b_stack, ((0, 0), (0, 0), (0, Op - out)))
    y = alora_qkv(xp, wp, a_stack, bp, ip, t_block=tb, o_block=ob,
                  interpret=interpret)
    return y[:T, :out]


@partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_op(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       block_tables: jax.Array, lengths: jax.Array, *,
                       window: int = 0,
                       interpret: bool) -> jax.Array:
    """Paged GQA decode attention.  q: (B, H, hd) -> (B, H, hd)."""
    return paged_attention(q, k_pool, v_pool, block_tables, lengths,
                           window=window, interpret=interpret)


@partial(jax.jit, static_argnames=("window", "interpret"))
def ragged_paged_attention_op(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, block_tables: jax.Array,
                              req_rows: jax.Array, q_lens: jax.Array, *,
                              window: int = 0,
                              interpret: bool
                              ) -> jax.Array:
    """Mixed-batch ragged paged attention.  q: (T, H, hd) -> (T, H, hd)."""
    return ragged_paged_attention(q, k_pool, v_pool, block_tables,
                                  req_rows, q_lens, window=window,
                                  interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan_op(x: jax.Array, B: jax.Array, C: jax.Array,
                      dA: jax.Array, dt: jax.Array, *, chunk: int = 128,
                      interpret: bool):
    """Padded/jitted SSD chunk scan.  Pads S to a chunk multiple with
    dt=0 (decay 1, zero input ⇒ state invariant)."""
    from repro.kernels.ssd_chunk import ssd_chunk_scan
    Bt, S, H, P = x.shape
    ch = min(chunk, max(S, 8))
    Sp = ((S + ch - 1) // ch) * ch
    pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
    xp = jnp.pad(x, pad)
    Bp = jnp.pad(B, pad[:2] + ((0, 0), (0, 0)))
    Cp = jnp.pad(C, pad[:2] + ((0, 0), (0, 0)))
    dAp = jnp.pad(dA, ((0, 0), (0, Sp - S), (0, 0)))
    dtp = jnp.pad(dt, ((0, 0), (0, Sp - S), (0, 0)))
    y, st = ssd_chunk_scan(xp, Bp, Cp, dAp, dtp, chunk=ch,
                           interpret=interpret)
    return y[:, :S], st


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ragged_ssd_scan_op(x: jax.Array, B: jax.Array, C: jax.Array,
                       dA: jax.Array, dt: jax.Array, seg_ids: jax.Array,
                       seg_starts: jax.Array, slot_rows: jax.Array,
                       init_states: jax.Array, *, chunk: int = 64,
                       interpret: bool):
    """Padded/jitted ragged SSD scan over a packed token axis.

    Pads T to a chunk multiple with dA=dt=0 (decay 1, zero input ⇒ carry
    invariant) and seg_starts=0 (padding continues the trailing segment,
    whose emitted rows the caller never gathers)."""
    from repro.kernels.ssd_chunk import ragged_ssd_chunk_scan
    T = x.shape[0]
    ch = min(chunk, max(T, 8))
    Tp = ((T + ch - 1) // ch) * ch
    pad = Tp - T
    xp = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    Bp = jnp.pad(B, ((0, pad), (0, 0), (0, 0)))
    Cp = jnp.pad(C, ((0, pad), (0, 0), (0, 0)))
    dAp = jnp.pad(dA, ((0, pad), (0, 0)))
    dtp = jnp.pad(dt, ((0, pad), (0, 0)))
    sidp = jnp.pad(seg_ids, (0, pad), mode="edge") if pad else seg_ids
    stp = jnp.pad(seg_starts.astype(jnp.int32), (0, pad))
    slp = jnp.pad(slot_rows, (0, pad), mode="edge") if pad else slot_rows
    y, st = ragged_ssd_chunk_scan(xp, Bp, Cp, dAp, dtp, sidp, stp, slp,
                                  init_states, chunk=ch,
                                  interpret=interpret)
    return y[:T], st[:T]


@partial(jax.jit, static_argnames=("t_block", "o_block", "interpret"))
def ragged_lora_op(x: jax.Array, a_stack: jax.Array, b_stack: jax.Array,
                   adapter_idx: jax.Array, active_slots: jax.Array, *,
                   t_block: int = 256, o_block: int = 256,
                   interpret: bool) -> jax.Array:
    """Padded/jitted SGMV-style grouped-LoRA delta over per-token slot
    indices.  x: (T, d) -> (T, out)."""
    from repro.kernels.ragged_lora import ragged_grouped_lora_padded
    return ragged_grouped_lora_padded(x, a_stack, b_stack, adapter_idx,
                                      active_slots, t_block=t_block,
                                      o_block=o_block, interpret=interpret)


# pure-jnp oracles re-exported for benchmarks/tests
paged_attention_ref = ref.paged_attention_ref
ragged_paged_attention_ref = ref.ragged_paged_attention_ref
alora_qkv_ref = ref.alora_qkv_ref
ssd_chunk_ref = ref.ssd_chunk_ref
ragged_ssd_scan_ref = ref.ragged_ssd_scan_ref
packed_cross_attention_ref = ref.packed_cross_attention_ref


def ragged_lora_ref(x, a_stack, b_stack, adapter_idx, active_slots):
    from repro.kernels.ragged_lora import ragged_grouped_lora_ref
    return ragged_grouped_lora_ref(x, a_stack, b_stack, adapter_idx,
                                   active_slots)
