"""Training step: chunked-vocab cross-entropy + AdamW, pjit-ready.

``make_train_step`` builds a pure (state, batch) -> (state, stats)
function; ``launch/train.py`` wraps it in jit with mesh shardings.  The
loss is computed **chunked over the sequence** so the (B, S, V) logits
tensor is never materialized — with 256k vocabs at 4k×256 tokens that
tensor would be ~0.5 TB; chunking bounds it to (B, chunk, V).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import forward_full, logits_for
from repro.models.model import Runtime
from repro.training.optimizer import (AdamWConfig, AdamWState, adamw_update,
                                      init_adamw)


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState


def chunked_ce_loss(params, cfg: ModelConfig, hidden: jax.Array,
                    labels: jax.Array, mask: jax.Array,
                    chunk: int = 512) -> jax.Array:
    """Cross entropy with seq-chunked logits.  hidden: (B, S, d)."""
    B, S, _ = hidden.shape
    nch = max(S // min(chunk, S), 1)
    ch = S // nch
    h = hidden[:, :nch * ch].reshape(B, nch, ch, -1).swapaxes(0, 1)
    y = labels[:, :nch * ch].reshape(B, nch, ch).swapaxes(0, 1)
    m = mask[:, :nch * ch].reshape(B, nch, ch).swapaxes(0, 1)

    def body(carry, inp):
        hc, yc, mc = inp
        # the padded vocab tail is -inf (logits_for): it adds 0 to lse
        logits = logits_for(params, cfg, hc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None],
                                   axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return (carry[0] + nll.sum(), carry[1] + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (h, y, m))
    return tot / jnp.maximum(cnt, 1.0)


def make_train_step(cfg: ModelConfig, ocfg: AdamWConfig,
                    rt: Runtime = Runtime(), loss_chunk: int = 512):
    """Returns train_step(state, batch) -> (state, stats)."""

    def loss_fn(params, batch):
        extra = batch.get("extra_embeds")
        hidden, aux, _ = forward_full(params, cfg, batch["tokens"], rt,
                                      extra_embeds=extra)
        # vlm: hidden includes the patch prefix — predictions for text
        # positions only
        if extra is not None and not cfg.is_encoder_decoder:
            hidden = hidden[:, extra.shape[1]:]
        ce = chunked_ce_loss(params, cfg, hidden, batch["labels"],
                             batch["mask"], loss_chunk)
        return ce + aux, (ce, aux)

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        (loss, (ce, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        new_params, new_opt, stats = adamw_update(
            ocfg, grads, state.opt, state.params)
        stats.update({"loss": loss, "ce": ce, "aux": aux})
        return TrainState(new_params, new_opt), stats

    return train_step


def init_train_state(params) -> TrainState:
    return TrainState(params=params, opt=init_adamw(params))
