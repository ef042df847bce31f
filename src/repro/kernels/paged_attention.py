"""Paged attention — Pallas TPU kernels (dense decode and ragged mixed).

The decode-side hot spot of the serving engine: query rows attend over
KV stored in non-contiguous PagedAttention blocks.

TPU adaptation of the CUDA PagedAttention kernel: the block table lives
in SMEM via **scalar prefetch**, so the BlockSpec ``index_map`` of the
K/V pools translates (row, block-step) grid coordinates into *physical*
block ids — the gather happens in the HBM→VMEM DMA itself, no
materialized (B, S, ...) gather.  Each grid step loads one whole block
with all its KV heads: the pool is viewed as (NB, bs, KV·hd), a free
reshape whose blocks end in (bs, KV·hd), the (8, 128)-divisible tiling
Mosaic requires (a one-KV-head block would end in (1, hd), which it
refuses).  GQA runs as a static loop over KV heads, each a (G, hd) ×
(hd, bs) MXU tile on a lane-aligned slice.  Online softmax runs in fp32
VMEM scratch across the block-step grid dimension (innermost, so the
accumulator carries correctly).

Sliding windows mask positions ≤ len-1-W (the engine keeps whole blocks;
ring-buffer compaction is the dense serve-path's job).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref,
                       o_ref, m_scr, l_scr, acc_scr, *,
                       bs: int, window: int, scale: float):
    """One grid step = one query row (all its heads) against one K/V
    block (all its KV heads).  q_ref/o_ref: (1, KV, G, hd); k_ref/v_ref:
    (1, bs, KV·hd) — the pool viewed with KV heads folded into the lane
    axis, so head ``h`` is the static lane slice ``[h·hd, (h+1)·hd)``."""
    b = pl.program_id(0)
    ib = pl.program_id(1)
    nb = pl.num_programs(1)
    KV, hd = q_ref.shape[1], q_ref.shape[3]

    @pl.when(ib == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    pos = ib * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < length
    if window > 0:
        valid = valid & (pos > length - 1 - window)
    for h in range(KV):                               # static unroll
        q = q_ref[0, h]                               # (G, hd)
        k = k_ref[0, :, h * hd:(h + 1) * hd]          # (bs, hd)
        v = v_ref[0, :, h * hd:(h + 1) * hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (G, bs)
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[h], l_scr[h]           # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[h] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(ib == nb - 1)
    def _fin():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_call(kernel, prefetch, q, k_pool, v_pool, n_rows: int, nb: int,
                row_of, interpret: bool):
    """Shared pallas_call of both paged kernels: grid (query row,
    block-step), block-step innermost so the online-softmax scratch
    carries across a row's blocks.  ``row_of(t, *prefetch)`` maps a grid
    row to its block-table row."""
    T, H, hd = q.shape
    NB, bs, KV, _ = k_pool.shape
    G = H // KV
    qr = q.reshape(T, KV, G, hd)
    # free (contiguous) view: KV heads folded into the lane axis, so a
    # block holds every KV head and its last two dims are (bs, KV·hd)
    kf = k_pool.reshape(NB, bs, KV * hd)
    vf = v_pool.reshape(NB, bs, KV * hd)

    def q_map(t, ib, *pre):
        return (t, 0, 0, 0)

    def kv_map(t, ib, *pre):
        return (pre[0][row_of(t, *pre), ib], 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_rows, nb),
            in_specs=[
                pl.BlockSpec((1, KV, G, hd), q_map),
                pl.BlockSpec((1, bs, KV * hd), kv_map),
                pl.BlockSpec((1, bs, KV * hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, KV, G, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((KV, G, 1), jnp.float32),      # m
                pltpu.VMEM((KV, G, 1), jnp.float32),      # l
                pltpu.VMEM((KV, G, hd), jnp.float32),     # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, KV, G, hd), q.dtype),
        interpret=interpret,
    )(*prefetch, qr, kf, vf)
    return out.reshape(T, H, hd)


def _ragged_paged_attn_kernel(tables_ref, rows_ref, lens_ref, q_ref, k_ref,
                              v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                              bs: int, window: int, scale: float):
    # the body is the dense-batch kernel with grid axis 0 meaning "token"
    # instead of "sequence"; rows_ref is consumed by the BlockSpec
    # index_maps (token → its request's block-table row), not here
    _paged_attn_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, bs=bs, window=window,
                       scale=scale)


def ragged_paged_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           req_rows: jax.Array, q_lens: jax.Array, *,
                           window: int = 0,
                           interpret: bool = False) -> jax.Array:
    """Mixed-batch variant of :func:`paged_attention`: one query row per
    packed token (decode singletons and prefill-chunk tokens in the same
    launch), with a second scalar-prefetch indirection ``req_rows`` so the
    K/V index_map resolves (token, block-step) → the token's *request's*
    physical block.

    q: (T, H, hd); k_pool/v_pool: (NB, bs, KV, hd);
    block_tables: (R, nb) int32; req_rows: (T,) int32;
    q_lens: (T,) int32 — causal length per token (position + 1).
    Returns (T, H, hd).  Matches
    ``repro.kernels.ref.ragged_paged_attention_ref``."""
    hd = q.shape[-1]
    bs = k_pool.shape[1]
    kernel = functools.partial(_ragged_paged_attn_kernel, bs=bs,
                               window=window, scale=1.0 / (hd ** 0.5))
    return _paged_call(kernel, (block_tables, req_rows, q_lens), q, k_pool,
                       v_pool, q.shape[0], block_tables.shape[1],
                       lambda t, tables, rows, lens: rows[t], interpret)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    window: int = 0, interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k_pool/v_pool: (NB, bs, KV, hd);
    block_tables: (B, nb) int32; lengths: (B,) int32.  Returns (B, H, hd).
    Matches ``repro.kernels.ref.paged_attention_ref``."""
    hd = q.shape[-1]
    bs = k_pool.shape[1]
    kernel = functools.partial(_paged_attn_kernel, bs=bs, window=window,
                               scale=1.0 / (hd ** 0.5))
    return _paged_call(kernel, (block_tables, lengths), q, k_pool, v_pool,
                       q.shape[0], block_tables.shape[1],
                       lambda t, tables, lens: t, interpret)
