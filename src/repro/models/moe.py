"""Mixture-of-Experts MLP sublayer.

Two interchangeable implementations:

* ``masked_dense`` — reference: every expert computes every token, masked
  accumulation.  Exact (no capacity drops); used for CPU tests / smoke.
* ``expert_parallel`` — production: experts sharded over the ``model``
  mesh axis via ``shard_map``.  Activations are replicated across the
  model axis between sublayers (Megatron convention), so each expert
  shard *gathers* its own tokens locally (capacity-bounded), runs its
  experts, scatters back, and a single ``psum`` over the model axis
  combines shards — the same collective cost as a dense TP MLP, with no
  all-to-all.  Capacity overflow drops tokens (standard top-k dropping).

Both share the router.  ``masked_dense`` also returns the load-balancing
auxiliary loss used in training.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    std = 0.02
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    p = {
        "router": (jax.random.normal(ks[0], (d, m.num_experts))
                   * std).astype(jnp.float32),
        "w_up": (jax.random.normal(ks[1], (m.num_experts, d, m.d_ff))
                 * std).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (m.num_experts, m.d_ff, d))
                   * out_std).astype(dtype),
    }
    if cfg.activation == "swiglu":
        p["w_gate"] = (jax.random.normal(ks[3], (m.num_experts, d, m.d_ff))
                       * std).astype(dtype)
    return p


def _expert_ffn(p: Params, cfg: ModelConfig, x: jax.Array,
                e_slice=slice(None)) -> jax.Array:
    """x: (E, C, d) -> (E, C, d), expert e applied to row e."""
    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["w_gate"][e_slice])) \
            * jnp.einsum("ecd,edf->ecf", x, p["w_up"][e_slice])
    else:
        h = jnp.einsum("ecd,edf->ecf", x, p["w_up"][e_slice])
        h = jnp.square(jax.nn.relu(h)) if cfg.activation == "squared_relu" \
            else jax.nn.gelu(h, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"][e_slice])


def route(p: Params, cfg: ModelConfig, x: jax.Array):
    """Router: returns (weights (..., k), idx (..., k), aux_loss)."""
    m = cfg.moe
    logits = x.astype(jnp.float32) @ p["router"]           # (..., E)
    gates = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(gates, m.experts_per_token)
    weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    pe = gates.mean(axis=tuple(range(gates.ndim - 1)))     # (E,)
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32).sum(-2)
    fe = onehot.mean(axis=tuple(range(onehot.ndim - 1)))
    aux = m.num_experts * jnp.sum(fe * pe) * m.load_balance_coef
    return weights, idx, aux


def moe_masked_dense(p: Params, cfg: ModelConfig, x: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Reference impl: (B, S, d) -> (B, S, d), exact, E× compute."""
    m = cfg.moe
    weights, idx, aux = route(p, cfg, x)

    def body(acc, inp):
        e = inp["_e"]
        sel = (idx == e).astype(jnp.float32) * weights     # (..., k)
        w_tok = sel.sum(-1).astype(x.dtype)[..., None]     # (..., 1)
        we = {k: v[None] for k, v in inp.items() if k != "_e"}
        ye = _expert_ffn(we, cfg, x.reshape(1, -1, x.shape[-1]))
        # routing weight scales the expert OUTPUT (FFN is nonlinear)
        return acc + ye.reshape(x.shape) * w_tok, None

    xs = {k: v for k, v in p.items() if k != "router"}
    xs["_e"] = jnp.arange(m.num_experts)
    acc0 = jnp.zeros_like(x)
    out, _ = jax.lax.scan(body, acc0, xs)
    return out, aux


def moe_expert_parallel(p: Params, cfg: ModelConfig, x: jax.Array, *,
                        mesh: jax.sharding.Mesh,
                        batch_axes: Tuple[str, ...],
                        model_axis: str,
                        capacity_factor: float = 1.25
                        ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel impl under shard_map.  x: (B, S, d)."""
    m = cfg.moe
    E = m.num_experts
    model_size = mesh.shape[model_axis]
    assert E % model_size == 0, (E, model_size)
    e_loc = E // model_size
    # drop batch axes the batch can't shard over (e.g. long_500k B=1:
    # tokens are replicated across `data`; experts still parallel)
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    if x.shape[0] % max(bsz, 1) != 0:
        batch_axes = ()

    def local(x_loc, router, w_stack):
        # x_loc: (B_loc, S, d) — replicated across the model axis.
        Bl, S, d = x_loc.shape
        T = Bl * S
        xf = x_loc.reshape(T, d)
        p_loc = dict(w_stack)
        p_loc["router"] = router
        weights, idx, aux = route(p_loc, cfg, xf)          # (T,k)
        k = m.experts_per_token
        cap = int(math.ceil(T * k / E * capacity_factor))

        midx = jax.lax.axis_index(model_axis)
        e_lo = midx * e_loc
        flat_e = idx.reshape(-1)                           # (T*k,)
        flat_w = weights.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(T), k)
        # position of each assignment within its expert
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)   # (T*k, E)
        pos = jnp.cumsum(onehot, axis=0) * onehot              # 1-based
        pos_in_e = (pos.sum(-1) - 1)                           # (T*k,)
        keep = pos_in_e < cap
        mine = (flat_e >= e_lo) & (flat_e < e_lo + e_loc) & keep
        # scatter assignment into (e_loc, cap) slot -> token id (+1), weight
        slot_e = jnp.where(mine, flat_e - e_lo, 0)
        slot_c = jnp.where(mine, pos_in_e, cap)            # cap = dump slot
        tok_buf = jnp.zeros((e_loc, cap + 1), jnp.int32)
        w_buf = jnp.zeros((e_loc, cap + 1), jnp.float32)
        tok_buf = tok_buf.at[slot_e, slot_c].set(
            jnp.where(mine, flat_tok + 1, 0))
        w_buf = w_buf.at[slot_e, slot_c].set(jnp.where(mine, flat_w, 0.0))
        tok_buf = tok_buf[:, :cap]
        w_buf = w_buf[:, :cap]
        valid = tok_buf > 0
        gather_idx = jnp.maximum(tok_buf - 1, 0)           # (e_loc, cap)
        x_e = xf[gather_idx] * valid[..., None].astype(xf.dtype)
        y_e = _local_ffn(w_stack, cfg, x_e)   # w_stack here is the LOCAL shard
        y_e = y_e * w_buf[..., None].astype(y_e.dtype)
        y = jnp.zeros((T, d), x_loc.dtype)
        y = y.at[gather_idx.reshape(-1)].add(
            y_e.reshape(-1, d) * valid.reshape(-1, 1).astype(y_e.dtype))
        y = jax.lax.psum(y, model_axis)
        # aux varies across batch shards (different tokens) — average over
        # the batch axes; it is already invariant along the model axis
        # (router + x are replicated there).
        if batch_axes:
            aux = jax.lax.pmean(aux, tuple(batch_axes))
        return y.reshape(Bl, S, d), aux

    def _local_ffn(w_stack, cfg, x_e):
        if cfg.activation == "swiglu":
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_e, w_stack["w_gate"])) \
                * jnp.einsum("ecd,edf->ecf", x_e, w_stack["w_up"])
        else:
            h = jnp.einsum("ecd,edf->ecf", x_e, w_stack["w_up"])
            h = jnp.square(jax.nn.relu(h)) if cfg.activation == "squared_relu" \
                else jax.nn.gelu(h, approximate=True)
        return jnp.einsum("ecf,efd->ecd", h, w_stack["w_down"])

    w_stack = {k: v for k, v in p.items() if k != "router"}
    bspec = P(batch_axes, None, None)
    wspec = jax.tree.map(lambda _: P(model_axis), w_stack)
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(bspec, P(), wspec),
        out_specs=(bspec, P()),
    )(x, p["router"], w_stack)
    return out, aux


def moe_apply(p: Params, cfg: ModelConfig, x: jax.Array, *,
              impl: str = "masked_dense",
              mesh: Optional[jax.sharding.Mesh] = None,
              batch_axes: Tuple[str, ...] = (),
              model_axis: str = "model",
              capacity_factor: float = 1.25
              ) -> Tuple[jax.Array, jax.Array]:
    if impl == "masked_dense":
        return moe_masked_dense(p, cfg, x)
    if impl == "expert_parallel":
        assert mesh is not None
        return moe_expert_parallel(p, cfg, x, mesh=mesh,
                                   batch_axes=batch_axes,
                                   model_axis=model_axis,
                                   capacity_factor=capacity_factor)
    raise ValueError(f"unknown moe impl {impl!r}")
