"""Shared neural-net building blocks (pure JAX, functional).

All functions take explicit parameter pytrees; nothing is stateful.  The
transformer assembly in ``repro.models.model`` composes these; the serving
engine's model runner (``repro.serving.runner``) reuses the same sublayer
functions so the engine and the distributed step functions share one
numerical implementation.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def padded_vocab(cfg: ModelConfig, multiple: int = 512) -> int:
    """Vocab rounded up so embedding/logit matrices shard over the mesh."""
    v = cfg.vocab_size
    return ((v + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    orig = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32)).astype(orig)


def init_rmsnorm(d: int, dtype) -> jax.Array:
    return jnp.ones((d,), dtype=dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / MLP
# ---------------------------------------------------------------------------
def activation_fn(name: str):
    if name == "swiglu":
        raise ValueError("swiglu is handled in mlp_apply (gated)")
    if name == "squared_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name}")


def init_mlp(key, cfg: ModelConfig, d_ff: int, dtype) -> Params:
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    p = {
        "w_up": (jax.random.normal(k1, (d, d_ff)) * std).astype(dtype),
        "w_down": (jax.random.normal(k2, (d_ff, d)) * out_std).astype(dtype),
    }
    if cfg.activation == "swiglu":
        p["w_gate"] = (jax.random.normal(k3, (d, d_ff)) * std).astype(dtype)
    return p


def mlp_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.activation == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = activation_fn(cfg.activation)(x @ p["w_up"])
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Embeddings / unembedding
# ---------------------------------------------------------------------------
def init_embeddings(key, cfg: ModelConfig, dtype) -> Params:
    v = padded_vocab(cfg)
    k1, k2 = jax.random.split(key)
    p = {"tok": (jax.random.normal(k1, (v, cfg.d_model)) * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(k2, (cfg.d_model, v)) * 0.02
                        ).astype(dtype)
    return p


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return p["tok"][tokens]


def unembed(p: Params, x: jax.Array, tie: bool) -> jax.Array:
    if tie:
        return x @ p["tok"].T
    return x @ p["unembed"]


# ---------------------------------------------------------------------------
# QKV projection with aLoRA activation-aware masking (paper Alg. 1)
# ---------------------------------------------------------------------------
def init_attn(key, cfg: ModelConfig, dtype, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    std = 0.02
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    return {
        "wq": (jax.random.normal(ks[0], (d, H * hd)) * std).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, KV * hd)) * std).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, KV * hd)) * std).astype(dtype),
        "wo": (jax.random.normal(ks[3], (H * hd, d)) * out_std).astype(dtype),
    }


def lora_delta(x: jax.Array, a_stack: jax.Array, b_stack: jax.Array,
               adapter_idx: jax.Array) -> jax.Array:
    """Batched multi-adapter low-rank delta with activation-aware masking.

    This is the TPU-native realization of the paper's Algorithm 1: instead
    of ``base*mask + adapted*(1-mask)``, every token carries an adapter
    index (0 = "no adapter": base tokens AND pre-activation tokens of an
    aLoRA request — the mask of Alg. 1 collapses into index 0), and the
    delta is accumulated per adapter with a masked low-rank matmul.

    x:            (..., T, d)
    a_stack:      (n_adapters, d, r)      — index 0 must be zeros
    b_stack:      (n_adapters, r, out)
    adapter_idx:  (..., T) int32 in [0, n_adapters)
    returns       (..., T, out)
    """
    n = a_stack.shape[0]

    def body(acc, inputs):
        i, a, b = inputs
        sel = (adapter_idx == i)[..., None].astype(x.dtype)
        acc = acc + ((x * sel) @ a) @ b
        return acc, None

    out_dim = b_stack.shape[-1]
    acc0 = jnp.zeros(x.shape[:-1] + (out_dim,), dtype=x.dtype)
    # adapter 0 is the zero adapter; skip it.
    idxs = jnp.arange(1, n)
    acc, _ = jax.lax.scan(body, acc0, (idxs, a_stack[1:], b_stack[1:]))
    return acc


def lora_delta_dispatch(x: jax.Array, a_stack: jax.Array,
                        b_stack: jax.Array, adapter_idx: jax.Array,
                        active_slots: Optional[jax.Array] = None, *,
                        impl: str = "dense") -> jax.Array:
    """Multi-adapter delta with a pluggable implementation (the serving
    engine's ``EngineConfig.mixed_lora_impl``):

    "dense" — :func:`lora_delta`'s stacked scan over EVERY slot in the
    device stack (the pre-pool behavior; equivalence oracle);
    "ref"   — ragged grouped jnp scan over only the step's active slots;
    "pallas"/"pallas_interpret" — the SGMV-style Pallas kernel.

    x / adapter_idx may carry leading batch dims; the grouped paths
    flatten them onto the token axis.  The delta's ops run under the
    named scope ``lora``.
    """
    with jax.named_scope("lora"):
        if impl == "dense" or active_slots is None:
            return lora_delta(x, a_stack, b_stack, adapter_idx)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        idx2 = adapter_idx.reshape(-1)
        if impl == "ref":
            from repro.kernels.ragged_lora import ragged_grouped_lora_ref
            d = ragged_grouped_lora_ref(x2, a_stack, b_stack, idx2,
                                        active_slots)
        elif impl in ("pallas", "pallas_interpret"):
            from repro.kernels.ragged_lora import ragged_grouped_lora_padded
            d = ragged_grouped_lora_padded(
                x2, a_stack, b_stack, idx2, active_slots,
                interpret=(impl == "pallas_interpret"))
        else:
            raise ValueError(f"unknown grouped-LoRA impl {impl!r}: "
                             "expected 'dense', 'ref', 'pallas' or "
                             "'pallas_interpret'")
        return d.reshape(lead + (d.shape[-1],))


def qkv_project(p: Params, cfg: ModelConfig, x: jax.Array,
                alora: Optional[Params] = None,
                adapter_idx: Optional[jax.Array] = None, *,
                lora_impl: str = "dense",
                active_slots: Optional[jax.Array] = None):
    """Project to q, k, v.  When ``alora`` is given, apply the activation-
    aware masked low-rank update of the paper to each of Q/K/V.

    alora: {"aq","bq","ak","bk","av","bv"} with leading adapter dim.
    ``lora_impl``/``active_slots`` select the grouped ragged delta used
    by the mixed serving step (:func:`lora_delta_dispatch`).
    """
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if alora is not None:
        assert adapter_idx is not None
        q = q + lora_delta_dispatch(x, alora["aq"], alora["bq"],
                                    adapter_idx, active_slots,
                                    impl=lora_impl)
        k = k + lora_delta_dispatch(x, alora["ak"], alora["bk"],
                                    adapter_idx, active_slots,
                                    impl=lora_impl)
        v = v + lora_delta_dispatch(x, alora["av"], alora["bv"],
                                    adapter_idx, active_slots,
                                    impl=lora_impl)
    *lead, _ = x.shape
    q = q.reshape(*lead, cfg.num_heads, cfg.head_dim)
    k = k.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def out_project(p: Params, cfg: ModelConfig, attn_out: jax.Array) -> jax.Array:
    *lead, H, hd = attn_out.shape
    return attn_out.reshape(*lead, H * hd) @ p["wo"]
