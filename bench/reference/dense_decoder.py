"""Plain reference of a dense decoder with aLoRA adapters, for the check
that decides ``correct``.  It imports nothing of the program: it reads
the benchmark's own configuration file and the weight tree the benchmark
made (``bench/weights.py``), by the names of its leaves.

The block, per layer ``l`` and token at position ``p``::

    h = rmsnorm(x) * ln1
    q, k, v = h @ wq, h @ wk, h @ wv     (+ (h @ A) @ B for each of q, k,
                                          v once p >= the adapter's start)
    q, k = rope(q, p), rope(k, p)        (halves rotated, theta^(-2i/hd))
    o = softmax(q k^T / sqrt(hd)) v      (keys j <= p)
    x = x + o @ wo
    x = x + (silu(h wg) * (h wu)) @ wd   (h = rmsnorm(x) * ln2)

then ``rmsnorm(x) * final_norm`` and logits against the tied embedding
(or the separate unembedding).  Computed in float32 at the highest
matmul precision, one sequence at a time, scanned over layers, attention
in blocks of query rows.

``quant="fp8"`` is the check's control: every weight matrix rounded to
float8 (e4m3) with one scale per output channel (per row for the
embedding), the rest as above.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
PAD = 1024           # sequences are padded to a multiple of this


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def quantize(w, axis: int):
    """``w`` rounded to fp8 (e4m3) with one scale per slice along ``axis``
    (the reduced axis is every other one), returned dequantized in
    float32."""
    red = tuple(i for i in range(w.ndim) if i != axis % w.ndim)
    s = jnp.max(jnp.abs(w), axis=red, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _f32(tree, quant: Optional[str]):
    """One layer's (or the embedding's) leaves in float32; under the
    control, each weight matrix rounded per output channel."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown control precision {quant!r}")

    def one(path, a):
        a = a.astype(jnp.float32)
        name = path[-1].key
        if quant is None or a.ndim < 2:
            return a
        return quantize(a, 0 if name == "tok" else -1)

    return jax.tree_util.tree_map_with_path(one, tree)


@partial(jax.jit, static_argnames=("cfg", "quant"))
def _logits(params, adapter, tokens, n_real, adapter_from, out_pos, *,
            cfg, quant):
    """Logits (len(out_pos), vocab) of one padded sequence."""
    (L, d, H, KV, hd, theta, eps, tied, vocab) = cfg
    G = H // KV
    with jax.default_matmul_precision("highest"):
        emb = _f32(params["embed"], quant)
        S = tokens.shape[0]
        pos = jnp.arange(S, dtype=jnp.int32)
        x = emb["tok"][tokens]
        adapted = (pos >= adapter_from)[:, None].astype(jnp.float32)
        blk = params["blocks"]["seg0"]          # leaves (layers, 1, ...)
        ad = None if adapter is None else adapter["seg0"]

        def proj(h, w, al, name):
            y = h @ w
            if al is not None:
                y = y + adapted * ((h @ al["a" + name]) @ al["b" + name])
            return y

        def attend(q, k, v):
            # q (S, H, hd) in blocks of rows; k, v (S, KV, hd)
            qb = q.reshape(S // Q_BLOCK, Q_BLOCK, KV, G, hd)
            kpos = pos[None, :]

            def one(i_q):
                i, qi = i_q
                qp = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
                ok = (kpos <= qp) & (kpos < n_real)
                s = jnp.einsum("qkgd,skd->kgqs", qi, k) / jnp.sqrt(
                    jnp.float32(hd))
                s = jnp.where(ok[None, None], s, -jnp.inf)
                return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, -1),
                                  v)

            o = jax.lax.map(one, (jnp.arange(S // Q_BLOCK), qb))
            return o.reshape(S, H * hd)

        def layer(x, lw):
            lp, al = jax.tree.map(lambda t: t[0], lw)
            lp, al = _f32(lp, quant), _f32(al, quant)
            h = _rms(x, lp["ln1"], eps)
            at = lp["attn"]
            q = proj(h, at["wq"], al, "q").reshape(S, H, hd)
            k = proj(h, at["wk"], al, "k").reshape(S, KV, hd)
            v = proj(h, at["wv"], al, "v").reshape(S, KV, hd)
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
            x = x + attend(q, k, v) @ at["wo"]
            h = _rms(x, lp["ln2"], eps)
            m = lp["mlp"]
            y = jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])
            return x + y @ m["w_down"], None

        x, _ = jax.lax.scan(layer, x, (blk, ad))
        h = _rms(x[out_pos], params["final_norm"].astype(jnp.float32), eps)
        w = emb["tok"].T if tied else emb["unembed"]
        return (h @ w)[:, :vocab]


def static_cfg(model: dict) -> tuple:
    """The hashable shape tuple ``_logits`` is specialised on, from the
    benchmark configuration's ``model`` block (SwiGLU, full attention)."""
    if model["activation"] != "swiglu" or model["sliding_window"]:
        raise ValueError("dense_decoder: SwiGLU with full attention only")
    return (model["num_layers"], model["d_model"], model["num_heads"],
            model["num_kv_heads"], model["head_dim"],
            float(model["rope_theta"]), float(model["norm_eps"]),
            bool(model["tie_embeddings"]), int(model["vocab_size"]))


def find_start(prompt, inv) -> int:
    """First index of the last occurrence of ``inv`` in ``prompt``; the
    prompt's length if absent."""
    m = len(inv)
    for s in range(len(prompt) - m, -1, -1):
        if list(prompt[s:s + m]) == list(inv):
            return s
    return len(prompt)


def sequence_logits(params, adapter, model: dict, prompt, output,
                    inv, quant: Optional[str] = None) -> np.ndarray:
    """Teacher-forced logits at every served position: row ``j`` is the
    distribution the ``j``-th output token was drawn from."""
    seq = list(prompt) + list(output[:-1])
    n = len(seq)
    S = -(-n // PAD) * PAD
    toks = np.zeros(S, np.int32)
    toks[:n] = seq
    start = find_start(prompt, inv) if adapter is not None else S
    n_out = len(output)
    out_pos = np.full(max(16, 1 << (n_out - 1).bit_length()), n - 1,
                      np.int32)         # a few shapes, so a few programs
    out_pos[:n_out] = np.arange(len(prompt) - 1, n)
    lg = _logits(params, adapter, jnp.asarray(toks), jnp.int32(n),
                 jnp.int32(start), jnp.asarray(out_pos),
                 cfg=static_cfg(model), quant=quant)
    return np.asarray(lg, np.float32)[:n_out]


def gaps(ref: np.ndarray, tokens) -> np.ndarray:
    """(row max - row[token]) / row std, per row of reference logits."""
    tok = np.asarray(tokens)
    picked = np.take_along_axis(ref, tok[:, None], -1)[:, 0]
    return (ref.max(-1) - picked) / ref.std(-1)
