"""Mamba2 SSD chunk scan — Pallas TPU kernel.

The compute hot-spot of the SSM architectures (mamba2-2.7b, zamba2-2.7b):
the chunked state-space-duality scan.  Per (batch, head) the sequence is
processed in chunks of Q tokens; within a chunk the computation is three
MXU matmuls (C·Bᵀ (Q×Q), the masked-decay weighted W·x (Q×P), and the
inter-chunk C·state (Q×N)(N×P)); across chunks a (N×P) recurrent state
carries in fp32 VMEM scratch — the same accumulate-over-innermost-grid-dim
pattern as the paged-attention kernel.

TPU adaptation of the paper's (Dao & Gu) CUDA kernel: the chunk dim Q is
the MXU-aligned tile (128/256), the state (N×P ≤ 128×64) stays resident
in VMEM for the whole (b, h) row of the grid, and the decay matrix
L = exp(segsum(dA)) is built in-register from a cumulative sum rather
than shared-memory shuffles.

Semantics (matching ``repro.kernels.ref.ssd_chunk_ref``):
  state_t = exp(dA_t) · state_{t-1} + dt_t · B_t ⊗ x_t
  y_t     = C_t · state_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, da_ref, dt_ref, y_ref, st_ref,
                state_scr, *, Q: int):
    c_idx = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0].astype(jnp.float32)            # (Q, P)
    B = b_ref[0, :, 0].astype(jnp.float32)            # (Q, N)
    C = c_ref[0, :, 0].astype(jnp.float32)            # (Q, N)
    dA = da_ref[0, :, 0]                              # (Q,)
    dt = dt_ref[0, :, 0]                              # (Q,)

    csum = jnp.cumsum(dA)                             # (Q,)
    total = csum[-1]
    # intra-chunk: y_diag[q] = sum_{k<=q} C_q·B_k e^{csum_q-csum_k} dt_k x_k
    diff = csum[:, None] - csum[None, :]              # (Q, Q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(qi >= ki, jnp.exp(diff), 0.0)
    CB = jnp.dot(C, B.T, preferred_element_type=jnp.float32)
    W = CB * L * dt[None, :]
    y = jnp.dot(W, x, preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried state
    state = state_scr[...]
    y = y + jnp.dot(C * jnp.exp(csum)[:, None], state,
                    preferred_element_type=jnp.float32)
    # state update
    decay = jnp.exp(total - csum) * dt                # (Q,)
    state = jnp.exp(total) * state + \
        jnp.dot((B * decay[:, None]).T, x,
                preferred_element_type=jnp.float32)
    state_scr[...] = state
    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _fin():
        st_ref[0, 0] = state.astype(st_ref.dtype)


def ssd_chunk_scan(x: jax.Array, B: jax.Array, C: jax.Array,
                   dA: jax.Array, dt: jax.Array, *, chunk: int = 128,
                   interpret: bool = False):
    """x: (Bt, S, H, P); B/C: (Bt, S, H, N); dA/dt: (Bt, S, H) fp32.
    S % chunk == 0 (use ``repro.kernels.ops.ssd_chunk_scan_op`` for
    auto-padding).  Returns (y (Bt,S,H,P), final_state (Bt,H,N,P))."""
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    grid = (Bt, H, nc)                                # chunk innermost

    kernel = functools.partial(_ssd_kernel, Q=chunk)
    y, st = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P),
                         lambda b, h, c: (b, c, h, 0)),   # x
            pl.BlockSpec((1, chunk, 1, N),
                         lambda b, h, c: (b, c, h, 0)),   # B
            pl.BlockSpec((1, chunk, 1, N),
                         lambda b, h, c: (b, c, h, 0)),   # C
            pl.BlockSpec((1, chunk, 1),
                         lambda b, h, c: (b, c, h)),      # dA
            pl.BlockSpec((1, chunk, 1),
                         lambda b, h, c: (b, c, h)),      # dt
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P),
                         lambda b, h, c: (b, c, h, 0)),   # y
            pl.BlockSpec((1, 1, N, P),
                         lambda b, h, c: (b, h, 0, 0)),   # final state
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bt, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, B, C, dA, dt)
    return y, st


# ---------------------------------------------------------------------------
# Ragged (packed-axis) variant — the mixed serving step's SSD scan
# ---------------------------------------------------------------------------
def _ragged_ssd_kernel(has_ref, slot_ref, x_ref, bt_ref, c_ref, sw_ref,
                       decay_ref, init_ref, y_ref, st_ref, state_scr, *,
                       Q: int):
    """One (head, chunk) of the ragged SSD scan over the PACKED token
    axis; a chunk may span several request segments.

    Per-chunk quantities arrive precomputed (``ragged_ssd_chunk_scan``):
    ``sw`` (Q, Q) the same-segment causal decay weights
    ``e^{csum_q - csum_k}·dt_k``, ``decay`` (Q, P) the decay from each
    token's entry state (repeated along P: Mosaic cannot broadcast a
    (1, 1) value over both axes of the state), and per token (scalar
    prefetch) whether its segment starts inside the chunk and which
    live-state slot it starts from.  Post-token state of token q:

      state_q = Bᵀ·diag(sw[q])·x + decay_q · entry_q

    with entry_q the pool row of its segment's start slot, or the state
    carried in from the previous chunk.  Emits every token's state (the
    caller gathers segment-final and block-boundary rows).
    """
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)                  # (Q, P)
    bt = bt_ref[0, 0]                                 # (N, Q)
    cm = c_ref[0]                                     # (Q, N)
    sw = sw_ref[0, 0]                                 # (Q, Q)
    decay = decay_ref[0, 0]                           # (Q, P)
    carry = state_scr[...]
    st = carry
    for q in range(Q):                                # static unroll
        t = c * Q + q
        entry = jnp.where(has_ref[t] > 0, init_ref[slot_ref[t], 0], carry)
        st = jnp.dot(bt * sw[q:q + 1, :], x,
                     preferred_element_type=jnp.float32) \
            + decay[q:q + 1, :] * entry               # (N, P)
        st_ref[q, 0] = st
        y_ref[0, q:q + 1, :] = jnp.dot(
            cm[q:q + 1, :], st,
            preferred_element_type=jnp.float32).astype(y_ref.dtype)
    state_scr[...] = st


def ragged_ssd_chunk_scan(x: jax.Array, B: jax.Array, C: jax.Array,
                          dA: jax.Array, dt: jax.Array, seg_ids: jax.Array,
                          seg_starts: jax.Array, slot_rows: jax.Array,
                          init_states: jax.Array, *, chunk: int = 64,
                          interpret: bool = False):
    """Ragged SSD scan over a packed token axis (mixed serving batch).

    x: (T, H, P); B/C: (T, H, N); dA/dt: (T, H) fp32; seg_ids /
    seg_starts / slot_rows: (T,) int32; init_states: (S, H, N, P) fp32.
    T % chunk == 0 (``repro.kernels.ops.ragged_ssd_scan_op`` auto-pads).
    Returns (y (T,H,P), states (T,H,N,P) fp32 — post-token states).
    Matches ``repro.kernels.ref.ragged_ssd_scan_ref``.

    Layout: the kernel's blocks end in whole dims or (Q, ·) tiles, as
    Mosaic requires — x, C and y head-major (H, T, ·); B per chunk and
    transposed (H, nc, N, Q); states and init token-major with (N, P)
    last.  The (Q, Q) segment masks, cumulative decays and entry slots
    are computed here with plain jnp, once per call.
    """
    T, H, P = x.shape
    N = B.shape[-1]
    S = init_states.shape[0]
    Q = chunk
    assert T % Q == 0, (T, Q)
    nc = T // Q
    f32 = jnp.float32

    dA_c = dA.T.reshape(H, nc, Q)
    csum = jnp.cumsum(dA_c, axis=-1)                   # (H, nc, Q)
    tok = jnp.arange(Q)
    # most recent in-chunk segment start at or before each token
    run_start = jax.lax.cummax(
        jnp.where(seg_starts.reshape(nc, Q) > 0, tok, -1), axis=1)
    has = run_start >= 0
    rs = jnp.maximum(run_start, 0)                     # (nc, Q)
    at_rs = lambda a: jnp.take_along_axis(a, jnp.broadcast_to(rs, a.shape),
                                          axis=-1)
    e0 = jnp.where(has, at_rs(csum) - at_rs(dA_c), 0.0)
    decay = jnp.broadcast_to(jnp.exp(csum - e0)[..., None],
                             (H, nc, Q, P))
    sid = seg_ids.reshape(nc, Q)
    mask = (tok[:, None] >= tok[None, :]) & \
        (sid[:, :, None] == sid[:, None, :])           # (nc, Q, Q)
    sw = jnp.where(mask, jnp.exp(csum[..., :, None] - csum[..., None, :]),
                   0.0) * dt.T.reshape(H, nc, 1, Q)    # (H, nc, Q, Q)
    entry_slot = jnp.take_along_axis(slot_rows.reshape(nc, Q), rs, axis=1)

    y, st = pl.pallas_call(
        functools.partial(_ragged_ssd_kernel, Q=Q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H, nc),                              # chunk innermost
            in_specs=[
                pl.BlockSpec((1, Q, P), lambda h, c, *_: (h, c, 0)),
                pl.BlockSpec((1, 1, N, Q), lambda h, c, *_: (h, c, 0, 0)),
                pl.BlockSpec((1, Q, N), lambda h, c, *_: (h, c, 0)),
                pl.BlockSpec((1, 1, Q, Q), lambda h, c, *_: (h, c, 0, 0)),
                pl.BlockSpec((1, 1, Q, P), lambda h, c, *_: (h, c, 0, 0)),
                pl.BlockSpec((S, 1, N, P), lambda h, c, *_: (0, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, Q, P), lambda h, c, *_: (h, c, 0)),
                pl.BlockSpec((Q, 1, N, P), lambda h, c, *_: (c, h, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((N, P), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((H, T, P), x.dtype),
            jax.ShapeDtypeStruct((T, H, N, P), f32),
        ],
        interpret=interpret,
    )(has.reshape(T).astype(jnp.int32),
      entry_slot.reshape(T).astype(jnp.int32),
      x.transpose(1, 0, 2),
      B.astype(f32).transpose(1, 2, 0).reshape(H, N, nc, Q)
      .transpose(0, 2, 1, 3),
      C.astype(f32).transpose(1, 0, 2), sw, decay,
      init_states.astype(f32))
    return y.transpose(1, 0, 2), st
