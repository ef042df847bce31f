"""``bench/flops.py`` against counts made by hand for a toy step."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops  # noqa: E402

# d=8, 2 heads of 4 over 1 KV head, d_ff 16, 3 layers, vocab 10
TOY = {"num_layers": 3, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
       "head_dim": 4, "d_ff": 16, "vocab_size": 10, "activation": "swiglu",
       "sliding_window": 0}
# q 8x8 + o 8x8 + k 8x4 + v 8x4 = 192; gated MLP 3 x 8 x 16 = 384
W = 192 + 384


def test_layer_weights_by_hand():
    assert flops.layer_weights(TOY) == W
    assert flops.layer_weights(dict(TOY, activation="gelu")) == 192 + 256


def test_decode_token():
    # one token at position 9 attends 10 keys
    want = 2 * W * 3 + 4 * 2 * 4 * 10 * 3 + 2 * 8 * 10
    assert flops.span_flops(TOY, 9, 10, None, 2) == want


def test_prefill_chunk_leaves_cached_tokens_out():
    # positions 4..7 computed (0..3 came from the prefix cache): keys
    # 5 + 6 + 7 + 8 = 26
    want = 2 * W * 4 * 3 + 4 * 2 * 4 * 26 * 3 + 2 * 8 * 10
    assert flops.span_flops(TOY, 4, 8, None, 2) == want


def test_window_clips_attention():
    m = dict(TOY, sliding_window=6)
    # positions 4..7 attend min(p + 1, 6): 5 + 6 + 6 + 6 = 23
    assert flops.attended_keys(4, 8, 6) == 23
    want = 2 * W * 4 * 3 + 4 * 2 * 4 * 23 * 3 + 2 * 8 * 10
    assert flops.span_flops(m, 4, 8, None, 2) == want


def test_adapter_tokens_past_the_start():
    # rank 2; q: 2*2*(8+8), k and v: 2*2*(8+4) each -> 64 + 48 + 48 = 160
    # per adapted token per layer; positions 6, 7 are past the start at 6
    base = flops.span_flops(TOY, 4, 8, None, 2)
    assert flops.span_flops(TOY, 4, 8, 6, 2) == base + 160 * 2 * 3
    assert flops.span_flops(TOY, 4, 8, 99, 2) == base


def test_step_sums_its_requests():
    spans = [(9, 10, None), (4, 8, 6)]
    assert flops.step_flops(TOY, spans, 2) == \
        flops.span_flops(TOY, 9, 10, None, 2) \
        + flops.span_flops(TOY, 4, 8, 6, 2)
