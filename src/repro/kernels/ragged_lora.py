"""Ragged grouped-LoRA delta — Pallas TPU kernel (+ jnp reference).

The serving engine's mixed step used to accumulate the multi-adapter
low-rank delta with a dense stacked scan over EVERY adapter index in the
device stack (``repro.models.layers.lora_delta``): cost O(n_slots·T·d·r)
per projection regardless of how many adapters the batch actually uses.
With the dynamic adapter pool the device stack holds S slots cycling
through a much larger registry, while a typical step touches only a
handful — so the mixed step instead runs this SGMV-style grouped kernel
(S-LoRA / Punica lineage) over the **active-slot list**:

  delta[t] = (x[t] @ A[idx_t]) @ B[idx_t]
           = sum_{s in active_slots} ((x * [idx == s]) @ A[s]) @ B[s]

The scheduler knows exactly which slots this step's tokens reference and
hands the (pow2-bucketed, ascending, 0-padded) ``active_slots`` list to
the kernel — compute scales with slots *used in the batch*, not slots
resident, and certainly not adapters registered.  Padding entries are
slot 0, the pool's permanently-zero adapter: an exact no-op term, so no
separate count operand is needed.

TPU mapping: grid over (token tiles, output tiles); the x-tile stays
resident in VMEM across the (short, static) active-slot loop; the slot
ids arrive via scalar prefetch so each iteration dynamically indexes the
A/B slot stacks (rank r ≤ 64 keeps all slots' A/B tiles VMEM-resident).
Masked tokens contribute exact zeros, so slot summation order (ascending)
matches the dense reference bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def ragged_grouped_lora_ref(x: jax.Array, a_stack: jax.Array,
                            b_stack: jax.Array, adapter_idx: jax.Array,
                            active_slots: jax.Array) -> jax.Array:
    """jnp oracle for the grouped kernel.

    x:            (T, d)
    a_stack:      (S+1, d, r)   — slot 0 must be zeros
    b_stack:      (S+1, r, out)
    adapter_idx:  (T,) int32    — per-token slot index (0 = base)
    active_slots: (K,) int32    — ascending slot ids, padded with 0

    Returns the delta (T, out).  Summation runs in active-slot order, so
    the result is bit-identical to ``lora_delta``'s full dense scan
    (inactive slots there contribute exact zeros).
    """
    out_dim = b_stack.shape[-1]

    def body(acc, s):
        sel = ((adapter_idx == s) & (s > 0))[:, None].astype(x.dtype)
        acc = acc + ((x * sel) @ a_stack[s]) @ b_stack[s]
        return acc, None

    acc0 = jnp.zeros(x.shape[:-1] + (out_dim,), dtype=x.dtype)
    acc, _ = jax.lax.scan(body, acc0, active_slots)
    return acc


def _ragged_lora_kernel(slots_ref, idx_ref, x_ref, a_ref, b_ref, o_ref, *,
                        n_active: int):
    x = x_ref[...]                                     # (Tt, d)
    idx = idx_ref[...]                                 # (Tt, 1)
    acc = jnp.zeros(x.shape[:1] + o_ref.shape[1:], jnp.float32)
    for i in range(n_active):                          # static unroll
        s = slots_ref[i]                               # dynamic slot id
        sel = (idx == s) & (s > 0)                     # (Tt, 1)
        xm = jnp.where(sel, x, jnp.zeros_like(x))
        xa = jnp.dot(xm, a_ref[s],
                     preferred_element_type=jnp.float32)    # (Tt, r)
        acc = acc + jnp.dot(xa.astype(x.dtype), b_ref[s],
                            preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def ragged_grouped_lora(x: jax.Array, a_stack: jax.Array,
                        b_stack: jax.Array, adapter_idx: jax.Array,
                        active_slots: jax.Array, *,
                        t_block: int = 256, o_block: int = 256,
                        interpret: bool = False) -> jax.Array:
    """Pallas grouped-LoRA delta.  Shapes as in the ref; T % t_block == 0
    and out % o_block == 0 (use :func:`ragged_grouped_lora_padded` for
    auto-padding call sites)."""
    T, d = x.shape
    n, _, r = a_stack.shape
    out = b_stack.shape[-1]
    K = active_slots.shape[0]
    assert T % t_block == 0 and out % o_block == 0, (T, out)
    grid = (T // t_block, out // o_block)

    kernel = functools.partial(_ragged_lora_kernel, n_active=K)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                     # active_slots
            grid=grid,
            in_specs=[
                # per-token slot ids as a (T, 1) column: a 2-D block
                # Mosaic lays out directly (a 1-D bool mask would need a
                # vector<T x i1> -> <T x 1 x i1> reshape it refuses)
                pl.BlockSpec((t_block, 1), lambda i, j, slots: (i, 0)),
                pl.BlockSpec((t_block, d), lambda i, j, slots: (i, 0)),
                pl.BlockSpec((n, d, r), lambda i, j, slots: (0, 0, 0)),
                pl.BlockSpec((n, r, o_block),
                             lambda i, j, slots: (0, 0, j)),
            ],
            out_specs=pl.BlockSpec((t_block, o_block),
                                   lambda i, j, slots: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, out), x.dtype),
        interpret=interpret,
    )(active_slots, adapter_idx.reshape(T, 1), x, a_stack, b_stack)


def ragged_grouped_lora_padded(x: jax.Array, a_stack: jax.Array,
                               b_stack: jax.Array, adapter_idx: jax.Array,
                               active_slots: jax.Array, *,
                               t_block: int = 256, o_block: int = 256,
                               interpret: bool = False) -> jax.Array:
    """Shape-padding wrapper: pads T and out up to tile multiples (the
    mixed step's token axis is already pow2-bucketed; projection widths
    need not be).  Traced inline by the jitted mixed step."""
    T, d = x.shape
    out = b_stack.shape[-1]
    tb = min(t_block, max(T, 8))
    ob = min(o_block, out)
    Tp = ((T + tb - 1) // tb) * tb
    Op = ((out + ob - 1) // ob) * ob
    xp = jnp.pad(x, ((0, Tp - T), (0, 0)))
    ip = jnp.pad(adapter_idx, (0, Tp - T))
    bp = jnp.pad(b_stack, ((0, 0), (0, 0), (0, Op - out)))
    y = ragged_grouped_lora(xp, a_stack, bp, ip, active_slots,
                            t_block=tb, o_block=ob, interpret=interpret)
    return y[:T, :out]
