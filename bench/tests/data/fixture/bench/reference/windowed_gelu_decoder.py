"""Plain reference of the fixture's windowed GELU decoder, in float32 at
the highest matmul precision.  As ``bench/reference/dense_decoder.py``
(whose helpers it shares: norms, rope, the fp8 control, the gap), with two
changes to the block::

    o = softmax(q k^T / sqrt(hd)) v      (keys p - window < j <= p)
    x = x + gelu_tanh(h @ w_up) @ w_down (h = rmsnorm(x) * ln2)
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_decoder import (PAD, Q_BLOCK, _f32, _rms,  # noqa
                                           _rope, find_start, gaps)


@partial(jax.jit, static_argnames=("cfg", "quant"))
def _logits(params, adapter, tokens, n_real, adapter_from, out_pos, *,
            cfg, quant):
    """Logits (len(out_pos), vocab) of one padded sequence."""
    (L, d, H, KV, hd, theta, eps, tied, vocab, window) = cfg
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
            qb = q.reshape(S // Q_BLOCK, Q_BLOCK, KV, G, hd)
            kpos = pos[None, :]

            def one(i_q):
                i, qi = i_q
                qp = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
                # a padding row's window ends at the last real key, so no
                # row is empty (its NaN would reach the real rows as 0 * v)
                last = jnp.minimum(qp, n_real - 1)
                ok = (kpos <= qp) & (kpos > last - window) & (kpos < n_real)
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
            y = jax.nn.gelu(h @ m["w_up"], approximate=True)
            return x + y @ m["w_down"], None

        x, _ = jax.lax.scan(layer, x, (blk, ad))
        h = _rms(x[out_pos], params["final_norm"].astype(jnp.float32), eps)
        w = emb["tok"].T if tied else emb["unembed"]
        return (h @ w)[:, :vocab]


def static_cfg(model: dict) -> tuple:
    if model["activation"] != "gelu" or not model["sliding_window"]:
        raise ValueError("windowed_gelu_decoder: GELU with a window only")
    return (model["num_layers"], model["d_model"], model["num_heads"],
            model["num_kv_heads"], model["head_dim"],
            float(model["rope_theta"]), float(model["norm_eps"]),
            bool(model["tie_embeddings"]), int(model["vocab_size"]),
            int(model["sliding_window"]))


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
                      np.int32)
    out_pos[:n_out] = np.arange(len(prompt) - 1, n)
    lg = _logits(params, adapter, jnp.asarray(toks), jnp.int32(n),
                 jnp.int32(start), jnp.asarray(out_pos),
                 cfg=static_cfg(model), quant=quant)
    return np.asarray(lg, np.float32)[:n_out]
