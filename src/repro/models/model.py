"""Transformer assembly: config-driven model construction.

One implementation serves every assigned architecture:

* homogeneous dense / MoE decoder stacks (stablelm, nemotron, starcoder2,
  minitron, phi-3-vision, phi3.5-moe, granite-moe),
* pure SSM stacks (mamba2),
* periodic hybrid stacks (zamba2: 5×SSM + 1×attn per period),
* encoder-decoder (whisper: bidirectional encoder over stub audio-frame
  embeddings + causal decoder with cross-attention).

Layers are **stacked by period segment and scanned** (``jax.lax.scan``):
the layer pattern is decomposed into its smallest repeating period
(e.g. zamba2: ``(ssm×5, attn×1) × 9``); the outer scan runs over period
repeats, inner scans over the run of each kind.  The lowered HLO contains
each distinct layer body once — essential to keep compile times bounded
when lowering 40-layer models onto a 512-device mesh.

Forward drivers:

* ``forward_full``   — teacher-forced full-sequence pass (train / prefill);
  optionally returns per-layer KV caches + SSM states.
* ``decode_step``    — one-token autoregressive step against dense caches
  (the distributed ``serve_step``; ring-buffer when sliding-window).
* ``iter_layers``    — unstacked per-layer view for the paged serving
  engine's Python-loop model runner.

aLoRA (the paper's technique) threads through every driver as
``(adapters, adapter_idx)``: per-token adapter indices realize the
activation-aware mask of paper Alg. 1 (index 0 = base weights — both
base-model tokens and pre-activation tokens of an aLoRA request).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTN, SSM, ModelConfig
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Runtime knobs (distribution / perf) — orthogonal to the architecture.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Runtime:
    moe_impl: str = "masked_dense"        # masked_dense | expert_parallel
    mesh: Optional[jax.sharding.Mesh] = None
    batch_axes: Tuple[str, ...] = ()
    model_axis: str = "model"
    q_block: int = 512
    kv_block: int = 1024
    skip_masked_blocks: bool = False      # §Perf: triangular flash schedule
    capacity_factor: float = 1.25
    remat: bool = False
    window_override: int = 0              # force sliding window (long_500k)
    shard_activations: bool = False
    # unroll layer scans into a python loop — used by the dry-run cost
    # extrapolation (XLA cost_analysis counts a while body ONCE, so
    # scanned-layer FLOPs must be measured on small unrolled variants)
    unroll_layers: bool = False
    # sequence-parallel activations: shard the S axis of residual-stream
    # activations over `model` between blocks (norms/residuals are
    # pointwise).  §Perf optimization for long-sequence training.
    sequence_parallel: bool = False
    # memory-efficient flash backward (custom_vjp, recompute-in-bwd):
    # §Perf iteration 1 — removes the O(S²) softmax-product saves that
    # dominate train_4k temp memory.
    flash_remat: bool = False
    # store decode KV caches in int8 with per-(head,step) scales:
    # §Perf iteration for the memory-bound decode shapes.
    kv_cache_quant: bool = False
    # context-parallel prefill (§Perf iteration 3): residual activations
    # sharded over `model` on the SEQUENCE axis, weights FSDP-sharded
    # over `data` and gathered per layer, attention under shard_map with
    # an all-gathered K/V.  Replaces two per-layer (B,S,d) tensor-parallel
    # all-reduces with one layer-weights all-gather + one (B,S,KV,hd)
    # K/V all-gather — ~2.3× less wire traffic for GQA prefill.
    # Dense decoder-only archs.
    context_parallel: bool = False


def effective_window(cfg: ModelConfig, rt: Runtime) -> int:
    return cfg.sliding_window if cfg.sliding_window else rt.window_override


def _constrain(x, rt: Runtime, spec):
    if rt.shard_activations and rt.mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(rt.mesh, spec))
    return x


def _attn_head_specs(cfg: ModelConfig, rt: Runtime, batch_shardable=True,
                     mode: str = "prefill"):
    """(q_spec, kv_spec) for (B, S, H|KV, hd) activations.

    prefill (compute-heavy, KV transient): shard Q heads over ``model``
    when divisible and REPLICATE K/V there when kv-heads don't divide —
    GQA attention is then fully head-parallel with zero collectives in
    the S×S score path (replicating the small K/V costs one all-gather
    per layer instead of a psum per score block).

    decode (cache-resident): q/k/v adopt the PERSISTENT cache layout —
    kv-heads over ``model`` when both H and KV divide, else head_dim —
    so the cache is never resharded between steps.  Archs whose head
    count doesn't divide the mesh (starcoder2 24H, minitron 24H,
    whisper 20H) fall back to head_dim sharding; the score psum this
    induces is visible in the roofline and is a §Perf item.
    """
    if rt.mesh is None or not rt.shard_activations:
        return None, None
    ms = rt.mesh.shape[rt.model_axis]
    b = rt.batch_axes if batch_shardable else None
    m = rt.model_axis
    heads_ok = cfg.num_heads % ms == 0
    kv_ok = cfg.num_kv_heads % ms == 0
    if mode == "prefill":
        if heads_ok:
            q = P(b, None, m, None)
            kv = P(b, None, m, None) if kv_ok else P(b, None, None, None)
            return q, kv
        assert cfg.head_dim % ms == 0, (cfg.name, cfg.head_dim, ms)
        return P(b, None, None, m), P(b, None, None, m)
    # decode: match the cache layout
    if heads_ok and kv_ok:
        return P(b, None, m, None), P(b, None, m, None)
    assert cfg.head_dim % ms == 0, (cfg.name, cfg.head_dim, ms)
    return P(b, None, None, m), P(b, None, None, m)


# ---------------------------------------------------------------------------
# Period segmentation
# ---------------------------------------------------------------------------
def period_segments(cfg: ModelConfig) -> Tuple[int, List[Tuple[str, int]]]:
    """Smallest repeating period of the layer pattern, run-length encoded.

    Returns (repeats, [(kind, count), ...]) with
    repeats * sum(counts) == num_layers.
    """
    pat = cfg.pattern()
    n = len(pat)
    period = pat
    for p in range(1, n + 1):
        if n % p == 0 and pat == pat[:p] * (n // p):
            period = pat[:p]
            break
    segs: List[Tuple[str, int]] = []
    for kind in period:
        if segs and segs[-1][0] == kind:
            segs[-1] = (kind, segs[-1][1] + 1)
        else:
            segs.append((kind, 1))
    return n // len(period), segs


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------
def _init_layer(key, cfg: ModelConfig, kind: str, dtype,
                cross: bool = False) -> Params:
    if kind == SSM:
        return {"ln": L.init_rmsnorm(cfg.d_model, dtype),
                "ssm": ssm_lib.init_ssm(key, cfg, dtype)}
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attn(ks[0], cfg, dtype),
        "ln2": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.moe is not None:
        p["moe"] = moe_lib.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg, cfg.d_ff, dtype)
    if cross:
        p["xln"] = L.init_rmsnorm(cfg.d_model, dtype)
        p["xattn"] = L.init_attn(ks[2], cfg, dtype)
    return p


@partial(jax.jit, donate_argnums=0)
def _set_layer(stacked: Params, r, c, layer: Params) -> Params:
    return jax.tree.map(lambda s, x: s.at[r, c].set(x), stacked, layer)


def _stack_layers(key, cfg: ModelConfig, kind: str, repeats: int, count: int,
                  dtype, cross: bool = False, shardings=None) -> Params:
    """(repeats, count, ...)-stacked layer params, written layer by layer
    into preallocated leaves (donated, so updated in place): the device
    holds one stacked copy plus one layer, never a per-layer list and its
    stacked twin.  Layer ``i`` (row-major) is exactly
    ``_init_layer(split(key, repeats*count)[i])``.  ``shardings`` (a tree
    like the result) allocates the leaves sharded."""
    keys = jax.random.split(key, repeats * count)
    init = partial(_init_layer, cfg=cfg, kind=kind, dtype=dtype, cross=cross)
    shapes = jax.eval_shape(init, keys[0])
    if shardings is None:
        shardings = jax.tree.map(lambda _: None, shapes)
    stacked = jax.tree.map(
        lambda a, sh: jnp.zeros((repeats, count) + a.shape, a.dtype,
                                device=sh), shapes, shardings)
    for i in range(repeats * count):
        stacked = _set_layer(stacked, i // count, i % count, init(keys[i]))
    return stacked


def init_params(key, cfg: ModelConfig, shardings: Optional[Params] = None
                ) -> Params:
    """Seeded random parameters.  ``shardings`` (a ``NamedSharding``
    tree like the result, e.g. from ``distributed.sharding``) places
    every leaf on a mesh as it is built, so a model larger than one
    device never has to exist on one."""
    sh = shardings or {}

    def put(x, s):
        return x if s is None else jax.device_put(x, s)

    dtype = L.dtype_of(cfg)
    repeats, segs = period_segments(cfg)
    k_emb, k_blocks, k_enc = jax.random.split(key, 3)
    seg_keys = jax.random.split(k_blocks, len(segs))
    params: Params = {
        "embed": put(L.init_embeddings(k_emb, cfg, dtype), sh.get("embed")),
        "final_norm": put(L.init_rmsnorm(cfg.d_model, dtype),
                          sh.get("final_norm")),
        "blocks": {
            f"seg{i}": _stack_layers(seg_keys[i], cfg, kind, repeats, count,
                                     dtype,
                                     cross=cfg.is_encoder_decoder
                                     and kind == ATTN,
                                     shardings=sh.get("blocks", {}).get(
                                         f"seg{i}"))
            for i, (kind, count) in enumerate(segs)
        },
    }
    if cfg.is_encoder_decoder:
        ek = jax.random.split(k_enc, 2)
        esh = sh.get("encoder", {})
        params["encoder"] = {
            "blocks": _stack_layers(ek[0], cfg, ATTN, cfg.num_encoder_layers,
                                    1, dtype, cross=False,
                                    shardings=esh.get("blocks")),
            "final_norm": put(L.init_rmsnorm(cfg.d_model, dtype),
                              esh.get("final_norm")),
        }
    return params


def param_specs(cfg: ModelConfig) -> Params:
    """Abstract parameter tree (no allocation) for dry-run lowering."""
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))


def iter_layers(params: Params, cfg: ModelConfig):
    """Yield (kind, per-layer params) in network order — for the paged
    serving engine's Python-loop runner (reduced models)."""
    repeats, segs = period_segments(cfg)
    for r in range(repeats):
        for si, (kind, count) in enumerate(segs):
            seg = params["blocks"][f"seg{si}"]
            for c in range(count):
                yield kind, jax.tree.map(lambda a: a[r, c], seg)


# ---------------------------------------------------------------------------
# Sublayer applications (shared by all drivers, incl. the paged engine)
# ---------------------------------------------------------------------------
def attn_sublayer_full(lp: Params, cfg: ModelConfig, rt: Runtime,
                       x: jax.Array, positions: jax.Array,
                       alora: Optional[Params], adapter_idx,
                       *, causal: bool = True,
                       return_kv: bool = False):
    """Full-sequence attention sublayer.  x: (B, S, d)."""
    h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = L.qkv_project(lp["attn"], cfg, h, alora, adapter_idx)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    window = effective_window(cfg, rt) if causal else 0
    if rt.context_parallel and rt.mesh is not None:
        o = _context_parallel_attention(cfg, rt, q, k, v, causal, window)
    else:
        q_spec, kv_spec = _attn_head_specs(cfg, rt)
        if q_spec is not None:
            q = _constrain(q, rt, q_spec)
            k = _constrain(k, rt, kv_spec)
            v = _constrain(v, rt, kv_spec)
        if rt.flash_remat:
            o = attn_lib.flash_attention_remat(
                q, k, v, causal, window, 0, rt.q_block, rt.kv_block)
        else:
            o = attn_lib.flash_attention(
                q, k, v, causal=causal, window=window,
                q_block=rt.q_block, kv_block=rt.kv_block,
                skip_masked_blocks=rt.skip_masked_blocks)
    x = x + L.out_project(lp["attn"], cfg, o)
    if return_kv:
        return x, (k, v)
    return x, None


def _context_parallel_attention(cfg: ModelConfig, rt: Runtime, q, k, v,
                                causal: bool, window: int):
    """Attention with the SEQUENCE axis sharded over ``model``: each
    shard all-gathers K/V (cheap for GQA — KV·hd ≪ d) and runs flash
    over its local query rows at the correct absolute offset."""
    m = rt.model_axis
    b = rt.batch_axes

    def local(q_loc, k_loc, v_loc):
        S_loc = q_loc.shape[1]
        k_full = jax.lax.all_gather(k_loc, m, axis=1, tiled=True)
        v_full = jax.lax.all_gather(v_loc, m, axis=1, tiled=True)
        off = jax.lax.axis_index(m) * S_loc
        return attn_lib.flash_attention(
            q_loc, k_full, v_full, causal=causal, window=window,
            q_offset=off, q_block=rt.q_block, kv_block=rt.kv_block,
            skip_masked_blocks=rt.skip_masked_blocks)

    spec = P(b, m, None, None)
    # check_vma off: flash_attention's scan carries start as invariant
    # zeros, which the varying-axes checker rejects inside shard_map
    return jax.shard_map(local, mesh=rt.mesh,
                         in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def cross_attn_sublayer(lp: Params, cfg: ModelConfig, x: jax.Array,
                        xk: jax.Array, xv: jax.Array):
    """Decoder→encoder cross attention given projected encoder K/V."""
    h = L.rmsnorm(x, lp["xln"], cfg.norm_eps)
    q = (h @ lp["xattn"]["wq"]).reshape(
        h.shape[:-1] + (cfg.num_heads, cfg.head_dim))
    o = attn_lib.cross_attention(q, xk, xv)
    return x + L.out_project(lp["xattn"], cfg, o)


def encoder_kv(lp: Params, cfg: ModelConfig, enc_out: jax.Array):
    """Project encoder output to this decoder layer's cross K/V."""
    B, Se, _ = enc_out.shape
    xk = (enc_out @ lp["xattn"]["wk"]).reshape(
        B, Se, cfg.num_kv_heads, cfg.head_dim)
    xv = (enc_out @ lp["xattn"]["wv"]).reshape(
        B, Se, cfg.num_kv_heads, cfg.head_dim)
    return xk, xv


def mlp_sublayer(lp: Params, cfg: ModelConfig, rt: Runtime, x: jax.Array):
    """MLP / MoE sublayer.  Returns (x, aux_loss)."""
    h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, aux = moe_lib.moe_apply(
            lp["moe"], cfg, h, impl=rt.moe_impl, mesh=rt.mesh,
            batch_axes=rt.batch_axes, model_axis=rt.model_axis,
            capacity_factor=rt.capacity_factor)
    else:
        y, aux = L.mlp_apply(lp["mlp"], cfg, x=h), jnp.zeros((), jnp.float32)
    return x + y, aux


def ssm_sublayer_full(lp: Params, cfg: ModelConfig, x: jax.Array,
                      alora: Optional[Params], adapter_idx,
                      ssm_state=None, conv_state=None):
    h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
    y, st, cv = ssm_lib.ssd_forward(lp["ssm"], cfg, h,
                                    ssm_state=ssm_state,
                                    conv_state=conv_state,
                                    alora=alora, adapter_idx=adapter_idx)
    return x + y, st, cv


# ---------------------------------------------------------------------------
# Scan helpers
# ---------------------------------------------------------------------------
def _scan(body, carry, params_stacked, al_stacked, extra_xs=None,
          unroll: bool = False):
    """scan over the leading axis of params (+ optional adapters/extras).

    body(carry, lp, al, extra) -> (carry, ys)
    ``unroll=True`` runs a python loop instead (dry-run cost analysis).
    """
    if unroll:
        n = jax.tree.leaves(params_stacked)[0].shape[0]
        ys_all = []
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params_stacked)
            al = None if al_stacked is None else \
                jax.tree.map(lambda a: a[i], al_stacked)
            ex = None if extra_xs is None else \
                jax.tree.map(lambda a: a[i], extra_xs)
            carry, ys = body(carry, lp, al, ex)
            ys_all.append(ys)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ys_all)
        return carry, stacked
    if al_stacked is None and extra_xs is None:
        return jax.lax.scan(lambda c, lp: body(c, lp, None, None),
                            carry, params_stacked)
    if al_stacked is None:
        return jax.lax.scan(lambda c, i: body(c, i[0], None, i[1]),
                            carry, (params_stacked, extra_xs))
    if extra_xs is None:
        return jax.lax.scan(lambda c, i: body(c, i[0], i[1], None),
                            carry, (params_stacked, al_stacked))
    return jax.lax.scan(lambda c, i: body(c, i[0], i[1], i[2]),
                        carry, (params_stacked, al_stacked, extra_xs))


def _seg_tree(tree: Optional[Params], si: int):
    return None if tree is None else tree[f"seg{si}"]


# ---------------------------------------------------------------------------
# forward_full — train / prefill
# ---------------------------------------------------------------------------
def forward_full(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 rt: Runtime = Runtime(), *,
                 positions: Optional[jax.Array] = None,
                 extra_embeds: Optional[jax.Array] = None,
                 adapters: Optional[Params] = None,
                 adapter_idx: Optional[jax.Array] = None,
                 return_caches: bool = False):
    """Teacher-forced pass.

    tokens: (B, S) int32.  ``extra_embeds``:
      * vlm   — (B, num_patches, d) patch embeddings, prepended to the
        token embeddings (ordinary prefix positions);
      * audio — (B, encoder_seq_len, d) frame embeddings, consumed by the
        encoder stack; the decoder cross-attends.

    Returns (hidden (B, S_total, d), aux_loss, caches | None) where
    caches = {"seg{i}": {"k","v"[,"xk","xv"]} | {"ssm","conv"}} with
    leading dims (repeats, count) per segment.
    """
    x = L.embed(params["embed"], tokens)
    enc_out = None
    if cfg.is_encoder_decoder:
        assert extra_embeds is not None, "audio arch needs frame embeddings"
        enc_out = _run_encoder(params["encoder"], cfg, rt, extra_embeds)
    elif extra_embeds is not None:                     # vlm: prepend patches
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        if adapter_idx is not None:
            pad = jnp.zeros(extra_embeds.shape[:2], adapter_idx.dtype)
            adapter_idx = jnp.concatenate([pad, adapter_idx], axis=1)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    res_spec = P(rt.batch_axes, rt.model_axis, None) \
        if rt.context_parallel else P(rt.batch_axes, None, None)
    x = _constrain(x, rt, res_spec)

    repeats, segs = period_segments(cfg)

    def layer_body(kind):
        def body(x, lp, al, _):
            if kind == SSM:
                def f(x):
                    x2, st, cv = ssm_sublayer_full(lp, cfg, x, al,
                                                   adapter_idx)
                    return x2, (jnp.zeros((), jnp.float32),
                                {"ssm": st, "conv": cv})
            else:
                def f(x):
                    x2, kv = attn_sublayer_full(lp, cfg, rt, x, positions,
                                                al, adapter_idx,
                                                return_kv=True)
                    cache = {"k": kv[0], "v": kv[1]}
                    if cfg.is_encoder_decoder:
                        xk, xv = encoder_kv(lp, cfg, enc_out)
                        x2 = cross_attn_sublayer(lp, cfg, x2, xk, xv)
                        cache.update({"xk": xk, "xv": xv})
                    x2, aux = mlp_sublayer(lp, cfg, rt, x2)
                    return x2, (aux, cache)
            if rt.remat:
                f = jax.checkpoint(f)
            x, (aux, cache) = f(x)
            x = _constrain(x, rt, res_spec)
            return x, (aux, cache if return_caches else 0)
        return body

    def period_body(x, seg_inputs, _al=None, _ex=None):
        """One period: run each segment's inner scan in order.
        seg_inputs: tuple over segments of (params, adapters|None), each
        leaf with leading dim = count."""
        auxs = jnp.zeros((), jnp.float32)
        seg_caches = []
        for si, (kind, count) in enumerate(segs):
            lp, al = seg_inputs[si]
            x, (a, cs) = _scan(layer_body(kind), x, lp, al,
                               unroll=rt.unroll_layers)
            auxs = auxs + a.sum()
            seg_caches.append(cs)
        return x, (auxs, tuple(seg_caches))

    # xs for the outer (repeats) scan: tuple over segments of (params, al)
    outer_xs = tuple(
        (params["blocks"][f"seg{si}"],
         _seg_tree(adapters, si))
        for si in range(len(segs)))
    if len(segs) == 1 and outer_xs[0][1] is None:
        # fast path: single homogeneous stack — one scan of repeats*count
        kind = segs[0][0]
        flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                            outer_xs[0][0])
        x, (aux, cs) = _scan(layer_body(kind), x, flat, None,
                             unroll=rt.unroll_layers)
        aux_total = aux.sum()
        caches = None
        if return_caches:
            cs = jax.tree.map(
                lambda a: a.reshape((repeats, segs[0][1]) + a.shape[1:]), cs)
            caches = {"seg0": cs}
    else:
        def outer(x, xs):
            return period_body(x, xs)
        if rt.unroll_layers:
            x, (auxs, seg_caches) = _scan(
                lambda c, lp, al, ex: outer(c, lp), x, outer_xs, None,
                unroll=True)
        else:
            x, (auxs, seg_caches) = jax.lax.scan(outer, x, outer_xs)
        aux_total = auxs.sum()
        caches = None
        if return_caches:
            # ys have leading (repeats, count)
            caches = {f"seg{si}": seg_caches[si]
                      for si in range(len(segs))}

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total, caches


def _run_encoder(enc_params: Params, cfg: ModelConfig, rt: Runtime,
                 frames: jax.Array) -> jax.Array:
    """Bidirectional encoder over stub frame embeddings (B, Se, d)."""
    x = frames.astype(L.dtype_of(cfg))
    B, Se, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(x, lp, al, _):
        x, _ = attn_sublayer_full(lp, cfg, rt, x, positions, None, None,
                                  causal=False)
        x, _ = mlp_sublayer(lp, cfg, rt, x)
        return x, 0

    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                        enc_params["blocks"])
    x, _ = _scan(body, x, flat, None, unroll=rt.unroll_layers)
    return L.rmsnorm(x, enc_params["final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decode_step — one token against dense caches (distributed serve_step)
# ---------------------------------------------------------------------------
def init_decode_caches(cfg: ModelConfig, batch: int, max_len: int,
                       rt: Runtime = Runtime()) -> Params:
    """Allocate (or eval_shape) dense decode caches.

    Attention segments: K/V (repeats, count, B, S_cache, KV, hd) where
    S_cache = min(max_len, window) for sliding-window archs (ring buffer).
    SSM segments: fp32 state (repeats, count, B, nh, N, P) + conv state.
    Encoder-decoder additionally stores projected cross K/V per layer.
    """
    dtype = L.dtype_of(cfg)
    repeats, segs = period_segments(cfg)
    window = effective_window(cfg, rt)
    s_cache = min(max_len, window) if window else max_len
    caches: Params = {}
    for si, (kind, count) in enumerate(segs):
        if kind == SSM:
            s = cfg.ssm
            d_inner, nh, conv_ch = ssm_lib.ssm_dims(cfg)
            caches[f"seg{si}"] = {
                "ssm": jnp.zeros((repeats, count, batch, nh, s.state_dim,
                                  s.head_dim), jnp.float32),
                "conv": jnp.zeros((repeats, count, batch, s.conv_width - 1,
                                   conv_ch), dtype),
            }
        else:
            kv_dtype = jnp.int8 if rt.kv_cache_quant else dtype
            c = {
                "k": jnp.zeros((repeats, count, batch, s_cache,
                                cfg.num_kv_heads, cfg.head_dim), kv_dtype),
                "v": jnp.zeros((repeats, count, batch, s_cache,
                                cfg.num_kv_heads, cfg.head_dim), kv_dtype),
            }
            if rt.kv_cache_quant:
                c["ks"] = jnp.zeros((repeats, count, batch, s_cache,
                                     cfg.num_kv_heads), jnp.float32)
                c["vs"] = jnp.zeros_like(c["ks"])
            if cfg.is_encoder_decoder:
                c["xk"] = jnp.zeros((repeats, count, batch,
                                     cfg.encoder_seq_len, cfg.num_kv_heads,
                                     cfg.head_dim), dtype)
                c["xv"] = jnp.zeros_like(c["xk"])
            caches[f"seg{si}"] = c
    return caches


def decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                caches: Params, cache_len, rt: Runtime = Runtime(), *,
                adapters: Optional[Params] = None,
                adapter_idx: Optional[jax.Array] = None):
    """One autoregressive step.

    token: (B, 1) int32.  ``cache_len``: scalar int32 — number of tokens
    already in the cache (the new token is written at this position).
    Returns (logits (B, 1, V), new_caches).
    """
    x = L.embed(params["embed"], token)
    B = x.shape[0]
    pos = jnp.asarray(cache_len, jnp.int32)
    positions = jnp.full((B, 1), pos, jnp.int32)
    window = effective_window(cfg, rt)
    repeats, segs = period_segments(cfg)

    def layer_body(kind):
        def body(x, lp, al, cache):
            if kind == SSM:
                h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
                y, st, cv = ssm_lib.ssd_decode_step(
                    lp["ssm"], cfg, h, cache["ssm"], cache["conv"],
                    alora=al, adapter_idx=adapter_idx)
                return x + y, {"ssm": st, "conv": cv}
            h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = L.qkv_project(lp["attn"], cfg, h, al, adapter_idx)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            bsh = x.shape[0] > 1
            q_spec, kv_spec = _attn_head_specs(cfg, rt, bsh, mode="decode")
            if q_spec is not None:
                q = _constrain(q, rt, q_spec)
                k = _constrain(k, rt, kv_spec)
                v = _constrain(v, rt, kv_spec)
            if rt.kv_cache_quant:
                kq, ks = attn_lib.quantize_kv(k)
                vq, vs = attn_lib.quantize_kv(v)
                kc, vc = attn_lib.write_kv_cache(cache["k"], cache["v"],
                                                 kq, vq, pos,
                                                 window=window)
                ksc, vsc = attn_lib.write_kv_cache(
                    cache["ks"][..., None], cache["vs"][..., None],
                    ks[..., None], vs[..., None], pos, window=window)
                ksc, vsc = ksc[..., 0], vsc[..., 0]
                k_de = attn_lib.dequantize_kv(kc, ksc, k.dtype)
                v_de = attn_lib.dequantize_kv(vc, vsc, v.dtype)
                o = attn_lib.decode_attention(q, k_de, v_de, pos + 1,
                                              window=window)
                new_cache = {"k": kc, "v": vc, "ks": ksc, "vs": vsc}
            else:
                kc, vc = attn_lib.write_kv_cache(cache["k"], cache["v"],
                                                 k, v, pos, window=window)
                o = attn_lib.decode_attention(q, kc, vc, pos + 1,
                                              window=window)
                new_cache = {"k": kc, "v": vc}
            x = x + L.out_project(lp["attn"], cfg, o)
            if cfg.is_encoder_decoder:
                x = cross_attn_sublayer(lp, cfg, x, cache["xk"], cache["xv"])
                new_cache.update({"xk": cache["xk"], "xv": cache["xv"]})
            x, _ = mlp_sublayer(lp, cfg, rt, x)
            x = _constrain(x, rt, P(rt.batch_axes, None, None))
            return x, new_cache
        return body

    new_caches: Params = {}
    if len(segs) == 1:
        kind = segs[0][0]
        flat_p = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                              params["blocks"]["seg0"])
        flat_al = None if adapters is None else jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), adapters["seg0"])
        flat_c = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                              caches["seg0"])
        x, cs = _scan(layer_body(kind), x, flat_p, flat_al, flat_c,
                      unroll=rt.unroll_layers)
        new_caches["seg0"] = jax.tree.map(
            lambda a: a.reshape((repeats, segs[0][1]) + a.shape[1:]), cs)
    else:
        def outer(x, xs):
            seg_caches = []
            for si, (kind, count) in enumerate(segs):
                lp, al, cache = xs[si]
                x, cs = _scan(layer_body(kind), x, lp, al, cache,
                              unroll=rt.unroll_layers)
                seg_caches.append(cs)
            return x, tuple(seg_caches)

        outer_xs = tuple(
            (params["blocks"][f"seg{si}"], _seg_tree(adapters, si),
             caches[f"seg{si}"])
            for si in range(len(segs)))
        if rt.unroll_layers:
            x, seg_caches = _scan(lambda c, lp, al, ex: outer(c, lp),
                                  x, outer_xs, None, unroll=True)
        else:
            x, seg_caches = jax.lax.scan(outer, x, outer_xs)
        new_caches = {f"seg{si}": seg_caches[si]
                      for si in range(len(segs))}

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_for(params, cfg, x)
    return logits, new_caches


def logits_for(params: Params, cfg: ModelConfig, hidden: jax.Array
               ) -> jax.Array:
    """Logits over the embedding's ``padded_vocab`` rows, whose count
    divides the mesh's model axis, so the logits shard as the embedding
    does.  The rows past ``vocab_size`` are not tokens: their logits are
    ``-inf``, so no sampler picks them and a softmax gives them 0."""
    logits = L.unembed(params["embed"], hidden, cfg.tie_embeddings)
    if logits.shape[-1] == cfg.vocab_size:
        return logits
    real = jnp.arange(logits.shape[-1]) < cfg.vocab_size
    return jnp.where(real, logits, -jnp.inf)


def prefill_to_decode_caches(cfg: ModelConfig, prefill_caches: Params,
                             seq_len: int, max_len: int,
                             rt: Runtime = Runtime()) -> Params:
    """Convert ``forward_full(..., return_caches=True)`` caches into the
    dense decode-cache layout of :func:`init_decode_caches`.

    Full attention: K/V padded out to ``max_len``.  Sliding window: the
    decode cache is a ring buffer of W slots with invariant
    ``slot(p) = p % W``; the last ``min(S, W)`` prefilled tokens are
    scattered to their ring slots.
    """
    window = effective_window(cfg, rt)
    s_cache = min(max_len, window) if window else max_len
    S = seq_len

    def conv_kv(a):
        # a: (repeats, count, B, S, KV, hd)
        if not window or S <= s_cache:
            pad = s_cache - min(S, s_cache)
            out = jnp.zeros(a.shape[:3] + (s_cache,) + a.shape[4:], a.dtype)
            return out.at[:, :, :, :min(S, s_cache)].set(
                a[:, :, :, :s_cache] if S > s_cache else a)
        # windowed, S > W: place token p (p in [S-W, S)) at slot p % W
        tail = a[:, :, :, S - s_cache:]
        pos = jnp.arange(S - s_cache, S)
        slots = pos % s_cache
        out = jnp.zeros(a.shape[:3] + (s_cache,) + a.shape[4:], a.dtype)
        return out.at[:, :, :, slots].set(tail)

    new: Params = {}
    for seg, c in prefill_caches.items():
        if "ssm" in c:
            new[seg] = {"ssm": c["ssm"], "conv": c["conv"]}
        else:
            e = {"k": conv_kv(c["k"]), "v": conv_kv(c["v"])}
            if "xk" in c:
                e.update({"xk": c["xk"], "xv": c["xv"]})
            new[seg] = e
    return new
