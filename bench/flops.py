"""The work a mixed step needs, counted from what it computed: floating
point operations of the algorithm, whatever implements it.

Per token whose K/V a step writes (tokens served from the prefix cache
are not computed and not counted):

* 2 x the non-embedding weights of every layer (Q/K/V/O and the MLP);
* attention over the token's causal context, clipped to the window
  where the model has one: 2 x 2 x heads x head_dim x keys per layer
  (scores and the weighted sum of values);
* for a token past its adapter's start, the low-rank deltas of Q, K and
  V: 2 x rank x (d_model + out) each, per layer.

Per request in the step, the logits of its sampled row: 2 x d_model x
vocabulary.
"""
from __future__ import annotations

from typing import Iterable, Optional, Tuple


def layer_weights(m: dict) -> int:
    """Non-embedding weights of one layer (norms left out)."""
    d, H, KV, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    attn = d * H * hd * 2 + d * KV * hd * 2
    mlp = (3 if m["activation"] == "swiglu" else 2) * d * ff
    return attn + mlp


def attended_keys(lo: int, hi: int, window: int) -> int:
    """Sum over query positions p in [lo, hi) of the keys p attends to:
    min(p + 1, window), or p + 1 without a window."""
    def total(n):               # sum_{p < n} min(p + 1, window)
        if not window or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return total(hi) - total(lo)


def span_flops(m: dict, lo: int, hi: int, adapter_from: Optional[int],
               rank: int) -> int:
    """FLOPs of computing positions [lo, hi) of one request, and its
    sampled row's logits."""
    L, d, H, KV, hd = (m["num_layers"], m["d_model"], m["num_heads"],
                       m["num_kv_heads"], m["head_dim"])
    n = hi - lo
    f = 2 * layer_weights(m) * n * L
    f += 4 * H * hd * attended_keys(lo, hi, m["sliding_window"]) * L
    if adapter_from is not None:
        n_ad = max(0, hi - max(lo, adapter_from))
        per = 2 * rank * ((d + H * hd) + 2 * (d + KV * hd))
        f += per * n_ad * L
    f += 2 * d * m["vocab_size"]
    return f


def step_flops(m: dict, spans: Iterable[Tuple[int, int, Optional[int]]],
               rank: int) -> int:
    return sum(span_flops(m, lo, hi, a, rank) for lo, hi, a in spans)
