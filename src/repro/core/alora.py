"""Activated-LoRA adapter weights (and the vanilla-LoRA baseline).

Adapter weights mirror the model's segment stacking
(``repro.models.model.period_segments``): for each attention segment a
pytree {"aq","bq","ak","bk","av","bv"} with leading (repeats, count)
layer dims; for each SSM segment {"a","b"} targeting the SSM input
projection — B spans the full fused [z|xBC|dt] in_dim and the delta is
sliced onto the split in_z/in_xbc/in_dt matmuls (the beyond-paper SSM
extension).  ``stack_adapters`` inserts the **zero
adapter at index 0** and stacks the active set along a new adapter axis —
the layout consumed by ``repro.models.layers.lora_delta``.

Numerically, aLoRA and vanilla LoRA weights are identical objects; the
difference is *where they apply* (activation-aware adapter indices,
``repro.core.activation_mask``) and *how their blocks hash*
(``repro.core.block_hash``).  Per the paper §4.1, adapter VALUES don't
affect serving speed — benchmark adapters are random; rank defaults are
the paper's (LoRA r=8, aLoRA r=32).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, SSM, ModelConfig
from repro.models import ssm as ssm_lib
from repro.models.layers import dtype_of
from repro.models.model import period_segments

Params = Dict[str, Any]

PAPER_LORA_RANK = 8
PAPER_ALORA_RANK = 32


@dataclass(frozen=True)
class AdapterSpec:
    """A registered adapter.

    ``invocation_tokens`` present ⇒ Activated LoRA (the engine identifies
    aLoRA requests by this field, paper §3); absent ⇒ vanilla LoRA.
    """
    name: str
    rank: int
    invocation_tokens: Optional[Tuple[int, ...]] = None

    @property
    def kind(self) -> str:
        return "alora" if self.invocation_tokens is not None else "lora"


def init_adapter_weights(key, cfg: ModelConfig, rank: int,
                         zero_b: bool = False) -> Params:
    """One adapter's weights, segment-stacked to match the model params."""
    dtype = dtype_of(cfg)
    repeats, segs = period_segments(cfg)
    H, KV, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    out: Params = {}
    a_std = 1.0 / math.sqrt(d)
    b_std = 0.0 if zero_b else 0.02 / math.sqrt(rank)

    def mk(key, shape, std):
        if std == 0.0:
            return jnp.zeros(shape, dtype)
        return (jax.random.normal(key, shape) * std).astype(dtype)

    for si, (kind, count) in enumerate(segs):
        n = repeats * count
        ks = jax.random.split(jax.random.fold_in(key, si), 6 * n)
        if kind == ATTN:
            def stack(j, shape, std):
                return jnp.stack([mk(ks[6 * i + j], shape, std)
                                  for i in range(n)]).reshape(
                    (repeats, count) + shape)
            out[f"seg{si}"] = {
                "aq": stack(0, (d, rank), a_std),
                "bq": stack(1, (rank, H * hd), b_std),
                "ak": stack(2, (d, rank), a_std),
                "bk": stack(3, (rank, KV * hd), b_std),
                "av": stack(4, (d, rank), a_std),
                "bv": stack(5, (rank, KV * hd), b_std),
            }
        else:
            in_dim = ssm_lib.ssm_dims(cfg)[0] * 2 \
                + 2 * cfg.ssm.ngroups * cfg.ssm.state_dim \
                + ssm_lib.ssm_dims(cfg)[1]
            def stack2(j, shape, std):
                return jnp.stack([mk(ks[6 * i + j], shape, std)
                                  for i in range(n)]).reshape(
                    (repeats, count) + shape)
            out[f"seg{si}"] = {
                "a": stack2(0, (d, rank), a_std),
                "b": stack2(1, (rank, in_dim), b_std),
            }
    return out


def zero_adapter_weights(cfg: ModelConfig, rank: int) -> Params:
    """The index-0 'no adapter' entry (all zeros ⇒ delta is exactly 0)."""
    w = jax.eval_shape(
        lambda k: init_adapter_weights(k, cfg, rank), jax.random.key(0))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), w)


def adapter_rank_of(weights: Params) -> int:
    """Read an adapter's rank off its first segment's A matrix."""
    seg = weights[sorted(weights)[0]]
    a = seg["aq"] if "aq" in seg else seg["a"]
    return a.shape[-1]


def pad_adapter_rank(weights: Params, target_rank: int) -> Params:
    """Zero-extend an adapter's rank dimension to ``target_rank``.

    The **zero-block invariant**: for every A/B pair the delta is
    ``x @ A @ B``; appending zero *columns* to A (axis -1) and matching
    zero *rows* to B (axis -2) adds only exact zeros, so
    ``x @ [A|0] @ [B;0] == x @ A @ B`` up to float rounding (XLA may
    order a longer contraction differently, so it is not bit-identical).
    This is what lets heterogeneous ranks share one bucketed slot shape
    in the device-resident adapter pool without changing aLoRA semantics:
    pre-activation tokens still see an exactly zero delta through
    adapter index 0.
    """
    r = adapter_rank_of(weights)
    if r == target_rank:
        return weights
    assert r < target_rank, (r, target_rank)

    def pad(path_key: str, leaf):
        pads = [(0, 0)] * leaf.ndim
        if path_key.startswith("a"):            # A: (..., d, r) — pad cols
            pads[-1] = (0, target_rank - r)
        else:                                   # B: (..., r, out) — pad rows
            assert path_key.startswith("b"), path_key
            pads[-2] = (0, target_rank - r)
        return jnp.pad(leaf, pads)

    return {seg: {k: pad(k, v) for k, v in leaves.items()}
            for seg, leaves in weights.items()}


def stack_adapters(cfg: ModelConfig, adapters: List[Params],
                   rank: int) -> Params:
    """Stack [zero, ad_1, ..., ad_n] along a new adapter axis.

    ``rank`` is the stacked (slot-bucket) rank: adapters of any rank
    ≤ ``rank`` are zero-extended into the bucket shape first
    (``pad_adapter_rank`` — exact, see the zero-block invariant there),
    so heterogeneous-rank adapter sets stack into one tensor.

    Output leaves: (repeats, count, n+1, ...) — sliced per layer inside
    the model scan, then indexed per token by ``lora_delta``.
    """
    all_ads = [zero_adapter_weights(cfg, rank)] + \
        [pad_adapter_rank(w, rank) for w in adapters]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=2), *all_ads)


def per_layer_adapters(cfg: ModelConfig, stacked: Params) -> List[Params]:
    """Slice a segment-stacked adapter tree into the per-layer list the
    serving runner (and the adapter pool) consume: one pytree per model
    layer, leaves keeping their leading adapter axis."""
    out: List[Params] = []
    repeats, segs = period_segments(cfg)
    for r in range(repeats):
        for si, (kind, count) in enumerate(segs):
            seg = stacked[f"seg{si}"]
            for c in range(count):
                out.append(jax.tree.map(lambda a: a[r, c], seg))
    return out


def adapter_param_specs(cfg: ModelConfig, rank: int, n_adapters: int
                        ) -> Params:
    """Abstract stacked-adapter tree for dry-run lowering."""
    one = jax.eval_shape(
        lambda k: init_adapter_weights(k, cfg, rank), jax.random.key(0))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape[:2] + (n_adapters + 1,) + s.shape[2:], s.dtype), one)
