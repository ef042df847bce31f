"""Sharding rules: map every parameter / batch / cache leaf to a
PartitionSpec over the production mesh.

Conventions (Megatron-style TP over ``model``, DP over ``pod``+``data``):

* attention: Wq/Wk/Wv column-parallel (fused head dim), Wo row-parallel;
* MLP: up/gate column-parallel, down row-parallel;
* MoE: experts sharded over ``model`` (expert parallelism; the shard_map
  dispatch in ``repro.models.moe`` gathers locally and psums);
* SSM: the input projection is split per consumer slice — in_z / in_xbc
  / in_dt each column-parallel on its own output dim, so z, the fused
  xBC conv block and dt land already aligned with their consumers (the
  former fused in_proj forced GSPMD to reshard every slice);
  out_proj row-parallel;
* embeddings / unembedding vocab-sharded (vocabs padded to %512);
* KV caches: kv-head-sharded when num_kv_heads % model_size == 0, else
  head-dim-sharded (head_dim of every assigned arch divides 16);
* optimizer moments: parameter specs, plus ZeRO-1 (shard the first
  un-sharded divisible dim over ``data``).

Sharded serving (the TP-sharded mixed ragged step)
--------------------------------------------------
The serving engine's ONE jitted mixed step (``serving.runner._mixed_impl``)
runs tensor-parallel over ``EngineConfig.mesh`` using the specs below.
The host-side scheduler, block manager and adapter registry stay
single-process; only the step's inputs/outputs are sharded arrays.
Per-input layout contract:

* **params** — :func:`param_specs_tree` with ``mesh=`` (Megatron TP as
  above; any dim that does not divide its mesh axes falls back to
  replicated, so every config lowers on every mesh);
* **paged K/V pools** ``(La, NB, bs, KV, hd)`` — split on the KV-head
  dim when both head counts divide the model axis, else on ``hd``
  (:func:`mixed_step_shardings`; the paged analogue of
  :func:`kv_cache_spec` / :func:`cache_specs_tree`, which keep the
  dense-cache ``(repeats, count, B, S, KV, hd)`` layout);
* **SSM live/snapshot state pools** ``(Ls, slots, nh, N, P)`` /
  ``(Ls, slots, W-1, ch)`` — sharded on ``nh`` / channel when divisible;
* **adapter slot stacks** (``serving.adapter_pool``) — leaves
  ``(S+1, d, r)`` for A are REPLICATED (rank ≪ d, the A matmul is
  cheap and its output feeds every shard), leaves ``(S+1, r, out)``
  for B are column-parallel on ``out`` (:func:`adapter_slot_specs`), so
  the ragged grouped-LoRA delta is computed locally per shard and added
  to the already column-parallel base projection with NO extra
  collective;
* **per-token scheduler metadata** (token ids, positions, adapter
  indices, block tables, write indices, ...) — replicated (``P()``);
* **sampled-token outputs + the per-run-slot token buffer** — both
  replicated (:attr:`StepShardings.tok_buf`): the in-step argmax over
  the vocab-gathered logits is the single cross-shard reduction point
  on the delta path (row-parallel wo/w_down/out_proj psums are the only
  other collectives, exactly as in training TP), and every shard must
  hold the full token buffer so the next step's ``from_buf`` gathers
  stay collective-free;
* **boundary-state outputs** — boundary SSM states keep the state-pool
  layout.

``jax.jit`` + GSPMD partitions the step from these input layouts; the
``StepShardings`` carried statically in the runner spec pins the output
layouts with ``with_sharding_constraint`` so pools never reshard between
steps (zero post-warmup recompiles).  ``tests/test_sharded_step.py``
asserts token-for-token equivalence with the single-device path on an
8-way host mesh across attention, SSM and encoder-decoder families.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Mapping, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

Tree = Any
# mesh-shaped things: a real Mesh, or a {axis: size} mapping (property
# tests probe mesh shapes larger than the host's device count)
MeshLike = Union[Mesh, Mapping[str, int]]


def _axis_sizes(mesh: MeshLike) -> Mapping[str, int]:
    return mesh.shape if isinstance(mesh, Mesh) else mesh


def _shards_of(axes, sizes: Mapping[str, int]) -> int:
    names = axes if isinstance(axes, tuple) else (axes,)
    n = 1
    for a in names:
        n *= int(sizes[a])
    return n


def fit_spec(spec: P, shape: Tuple[int, ...], mesh: MeshLike) -> P:
    """Drop (to replicated) every spec dim whose axis product does not
    divide the corresponding array dim — the guarantee that makes every
    spec tree valid on every mesh (property-tested)."""
    sizes = _axis_sizes(mesh)
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out = [ax if ax is not None and d % _shards_of(ax, sizes) == 0
           else None
           for d, ax in zip(shape, dims)]
    return P(*out)


def _leaf_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               cfg: ModelConfig, model: str, n_lead: int) -> P:
    """Spec for one parameter leaf.  ``n_lead`` = stacking dims (layer
    repeats/count, adapter index) prepended as None."""
    name = path[-1]
    lead = (None,) * n_lead
    core = len(shape) - n_lead

    def spec(*dims):
        assert len(dims) == core, (path, shape, dims)
        return P(*(lead + dims))

    if name in ("tok",):
        return P(model, None)
    if name in ("unembed",):
        return P(None, model)
    if name in ("wq", "wk", "wv", "w_up", "w_gate",
                "in_z", "in_xbc", "in_dt"):
        if core == 3:                       # MoE expert stacks (E, d, ff)
            return spec(model, None, None)
        return spec(None, model)
    if name in ("wo", "w_down", "out_proj"):
        if core == 3:                       # MoE (E, ff, d)
            return spec(model, None, None)
        return spec(model, None)
    if name in ("aq", "ak", "av", "a"):     # adapter A: (d, r)
        return spec(None, None)
    if name in ("bq", "bk", "bv"):          # adapter B: (r, out)
        return spec(None, model)
    if name == "b":                         # ssm adapter B
        return spec(None, model)
    # everything else (norms, router, conv, A_log, dt_bias, D, biases)
    return P(*((None,) * len(shape)))


def _n_lead_dims(path) -> int:
    """blocks/segN leaves carry (repeats, count) stacking; encoder blocks
    carry (L, 1); adapter stacks additionally an adapter dim."""
    keys = [str(getattr(p, "key", "")) for p in path]
    n = 0
    if any(k.startswith("seg") for k in keys) or "blocks" in keys:
        n = 2
    return n


def param_specs_tree(cfg: ModelConfig, params_shape: Tree,
                     model_axis: str = "model",
                     extra_lead: int = 0,
                     mesh: Optional[MeshLike] = None) -> Tree:
    """PartitionSpec tree matching ``params_shape`` (a ShapeDtypeStruct
    tree from ``jax.eval_shape``).  ``extra_lead`` adds leading dims
    (e.g. the stacked-adapter axis).  With ``mesh`` given, every spec is
    validated against the mesh's axis sizes: a dim that does not divide
    falls back to replicated (``fit_spec``), so the returned tree is
    always directly lowerable on that mesh."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        n_lead = _n_lead_dims(path) + extra_lead
        names = tuple(str(getattr(p, "key", p)) for p in path)
        s = _leaf_spec(names, leaf.shape, cfg, model_axis,
                       min(n_lead, len(leaf.shape)))
        if mesh is not None:
            s = fit_spec(s, leaf.shape, mesh)
        specs.append(s)
    return tdef.unflatten(specs)


def fsdp_param_specs_tree(cfg: ModelConfig, params_shape: Tree,
                          mesh: Mesh, data_axis: str = "data") -> Tree:
    """Context-parallel / FSDP weight layout (§Perf iteration 3): every
    matrix shards its first core dim over ``data`` (gathered per use);
    nothing lives on ``model`` — that axis carries the SEQUENCE shard of
    the activations instead.  Memory per chip matches the TP layout
    (params / 16)."""
    ds = mesh.shape[data_axis]
    flat, tdef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        n_lead = min(_n_lead_dims(path), len(leaf.shape))
        core = leaf.shape[n_lead:]
        dims = [None] * len(leaf.shape)
        if len(core) >= 2 and core[0] % ds == 0 and core[0] >= ds:
            dims[n_lead] = data_axis
        specs.append(P(*dims))
    return tdef.unflatten(specs)


def adapter_specs_tree(cfg: ModelConfig, ad_shape: Tree,
                       model_axis: str = "model",
                       mesh: Optional[MeshLike] = None) -> Tree:
    """Adapter stacks: leaves are (repeats, count, n_adapters, ...)."""
    return param_specs_tree(cfg, ad_shape, model_axis, extra_lead=1,
                            mesh=mesh)


def adapter_slot_specs(cfg: ModelConfig, layer_shape: Tree,
                       mesh: Optional[MeshLike] = None,
                       model_axis: str = "model") -> Tree:
    """Specs for ONE layer's device-resident adapter slot stack (the
    ``AdapterPool.layers`` entries): leaves ``(S+1, d, r)`` for A —
    replicated (rank ≪ d) — and ``(S+1, r, out)`` for B — column-
    parallel on ``out``, matching the base projection it adds into, so
    the grouped-LoRA delta needs no collective of its own."""
    return param_specs_tree(cfg, layer_shape, model_axis, extra_lead=1,
                            mesh=mesh)


def batch_specs(batch_axes: Tuple[str, ...]) -> Dict[str, P]:
    return {
        "tokens": P(batch_axes, None),
        "labels": P(batch_axes, None),
        "mask": P(batch_axes, None),
        "extra_embeds": P(batch_axes, None, None),
    }


def kv_cache_spec(cfg: ModelConfig, batch_axes, model_axis: str,
                  batch_shardable: bool = True,
                  mesh: Optional[MeshLike] = None) -> P:
    """(repeats, count, B, S, KV, hd) — heads only when BOTH q and kv
    head counts divide the model axis, else head_dim: the one rule every
    K/V layout helper (this, :func:`cache_specs_tree`,
    :func:`mixed_step_shardings`) shares.  Without a mesh, assumes the
    production 16-way model axis."""
    b = batch_axes if batch_shardable else None
    ms = 16 if mesh is None else _axis_sizes(mesh)[model_axis]
    if _kv_on_heads(cfg, ms):
        return P(None, None, b, None, model_axis, None)
    return P(None, None, b, None, None,
             model_axis if cfg.head_dim % ms == 0 else None)


def _kv_on_heads(cfg: ModelConfig, ms: int) -> bool:
    """THE heads-vs-head_dim rule every K/V layout helper shares
    (:func:`kv_cache_spec`, :func:`cache_specs_tree`,
    :func:`mixed_step_shardings`): shard the KV-head dim only when BOTH
    q and kv head counts divide the model axis (GQA attention stays
    fully head-parallel), else fall back to the head_dim dim."""
    return cfg.num_kv_heads % ms == 0 and cfg.num_heads % ms == 0


def cache_specs_tree(cfg: ModelConfig, caches_shape: Tree, mesh: MeshLike,
                     batch_axes: Tuple[str, ...],
                     model_axis: str = "model",
                     batch_shardable: bool = True) -> Tree:
    """Specs for decode/prefill cache trees."""
    ms = _axis_sizes(mesh)[model_axis]
    b = batch_axes if batch_shardable else None

    def leaf(path, s):
        name = str(getattr(path[-1], "key", path[-1]))
        shape = s.shape
        if name in ("k", "v", "xk", "xv"):
            # (repeats, count, B, S, KV, hd) — layout must match
            # models.model._attn_head_specs (the shared _kv_on_heads
            # rule); dense dry-run caches ASSERT on a non-divisible
            # head_dim rather than silently replicating a hot tensor
            if _kv_on_heads(cfg, ms):
                return P(None, None, b, None, model_axis, None)
            assert cfg.head_dim % ms == 0, (cfg.name, cfg.head_dim, ms)
            return P(None, None, b, None, None, model_axis)
        if name in ("ks", "vs"):
            # int8-cache scales: (repeats, count, B, S, KV)
            if _kv_on_heads(cfg, ms):
                return P(None, None, b, None, model_axis)
            return P(None, None, b, None, None)
        if name == "ssm":
            # (repeats, count, B, nh, N, P)
            nh = shape[3]
            return P(None, None, b,
                     model_axis if nh % ms == 0 else None, None, None)
        if name == "conv":
            # (repeats, count, B, W-1, ch)
            ch = shape[4]
            return P(None, None, b, None,
                     model_axis if ch % ms == 0 else None)
        return P(*((None,) * len(shape)))

    flat, tdef = jax.tree_util.tree_flatten_with_path(caches_shape)
    return tdef.unflatten([leaf(p, s) for p, s in flat])


# ---------------------------------------------------------------------------
# Sharded serving: layout of the mixed ragged step's device state
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class StepShardings:
    """Static (hashable) sharding context for the serving runner's jitted
    mixed step — carried inside ``RunnerSpec`` so output layouts are
    pinned with ``with_sharding_constraint`` and pools never reshard
    between steps.  ``None`` state specs mean the arch has no SSM pools.
    """
    mesh: Mesh
    kv_pool: P                       # (La, NB, bs, KV, hd)
    ssm_pool: Optional[P] = None     # (Ls, slots, nh, N, P)
    conv_pool: Optional[P] = None    # (Ls, slots, W-1, ch)
    # (T, H, hd) per-token attention output — follows the K/V layout
    # (heads when both head counts divide, else head_dim); annotating it
    # keeps the ragged-attention PV einsum shard-local instead of letting
    # the partitioner rematerialize the gathered V rows
    attn_out: Optional[P] = None
    # (MR,) per-run-slot last-sampled-token buffer AND the (Rb,) sampled
    # ids — replicated: the step's argmax all-gathers once at the
    # unembed, then every shard keeps the full int32 buffer so the next
    # step's from_buf token gathers stay collective-free
    tok_buf: P = P()
    # (Tb,) per-token metadata rows / (Tb, d) input embeds.  P() (the
    # TP-only layout) replicates the packed token axis on every device;
    # data-parallel token sharding sets these to P(data) / P(data, None)
    # so each data shard holds only its slice of the step's tokens and
    # ``max_batched_tokens`` scales with the data axis.  Per-REQUEST
    # arrays (block tables, out_rows, run_slots) and the sampled ids
    # stay replicated — retirement and the next step's from_buf gathers
    # still see every request on every shard.
    tok_meta: P = P()
    tok_embeds: P = P()
    replicated: P = P()

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constrain(self, x, spec: Optional[P]):
        if x is None or spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.named(spec))


def mixed_step_shardings(cfg: ModelConfig, mesh: MeshLike,
                         model_axis: str = "model",
                         data_axis: Optional[str] = None) -> StepShardings:
    """Layouts for the paged serving pools over ``mesh``.

    The K/V pool follows the same head-vs-head_dim rule as
    :func:`cache_specs_tree` (heads only when BOTH q and kv head counts
    divide the model axis); SSM pools shard their head / channel dims
    when divisible, else replicate.  (Property tests pass a plain
    ``{axis: size}`` mapping; the serving runner passes the real mesh.)

    ``data_axis`` (when present in the mesh with size > 1) additionally
    shards the packed TOKEN axis of the mixed step over that axis:
    per-token metadata rows and input embeds split so each data shard
    computes only its slice of the step's tokens (the runner pads the
    token bucket to a multiple of the axis size).  Per-request arrays,
    the token buffer and the sampled ids stay replicated.
    """
    sizes = _axis_sizes(mesh)
    ms = sizes[model_axis]
    tok_ax = data_axis if data_axis is not None \
        and sizes.get(data_axis, 1) > 1 else None
    if _kv_on_heads(cfg, ms):
        kv = P(None, None, None, model_axis, None)
        attn_out = P(tok_ax, model_axis, None)
    else:
        hd_ax = model_axis if cfg.head_dim % ms == 0 else None
        kv = P(None, None, None, None, hd_ax)
        attn_out = P(tok_ax, None, hd_ax)
    ssm_pool = conv_pool = None
    if cfg.num_ssm_layers() > 0:
        from repro.models.ssm import ssm_dims
        _, nh, ch = ssm_dims(cfg)
        ssm_pool = P(None, None, model_axis if nh % ms == 0 else None,
                     None, None)
        conv_pool = P(None, None, None,
                      model_axis if ch % ms == 0 else None)
    return StepShardings(mesh=mesh, kv_pool=kv, ssm_pool=ssm_pool,
                         conv_pool=conv_pool, attn_out=attn_out,
                         tok_meta=P(tok_ax), tok_embeds=P(tok_ax, None))


def zero1_specs(param_spec_tree: Tree, params_shape: Tree, mesh: Mesh,
                data_axis: str = "data") -> Tree:
    """ZeRO-1: shard optimizer moments over ``data`` on the first dim
    that is unsharded and divisible (beyond-paper memory optimization)."""
    ds = mesh.shape[data_axis]

    def leaf(spec: P, s) -> P:
        dims = list(spec) + [None] * (len(s.shape) - len(spec))
        for i, (d, cur) in enumerate(zip(s.shape, dims)):
            if cur is None and d % ds == 0 and d >= ds:
                dims[i] = data_axis
                return P(*dims)
        return spec

    return jax.tree.map(leaf, param_spec_tree, params_shape,
                        is_leaf=lambda x: isinstance(x, P))


def to_named(tree_specs: Tree, mesh: Mesh) -> Tree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))
