"""Golden values that pin the granite cells where they were before the
harness read models through ``bench/models/<model_type>.py``, took the
weight-init rule from that module and learnt shared documents: the
program's model settings and ``ModelConfig``, the weights and adapters,
the init rule of every leaf, and each mix's planned sessions, token ids,
prompt lengths and context bounds.

Every value below was taken on commit 9086ed6, the tree before that
change, on the CPU, by the same computation as the test's (there the
token ids were ``rng_for(seed, 8, sid, k)``'s draw of each message, and
the init rule was read from ``make_params``' ``scale_of`` as it traced):
a change that moves any of them moves what the two cells measure.
"""
from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FIXTURE = BENCH / "tests" / "data" / "fixture"
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import driver, harness, traffic, weights  # noqa: E402

CONFIGS = {
    "tiny": FIXTURE / "bench" / "configs" / "tiny.json",
    "granite-3.2-8b-stage0": BENCH / "configs" / "granite-3.2-8b-stage0.json",
}
FIELDS = {
    "tiny": {"name": "tiny", "num_layers": 2, "d_model": 256,
             "num_heads": 8, "num_kv_heads": 2, "head_dim": 32,
             "d_ff": 512, "vocab_size": 512, "activation": "swiglu",
             "tie_embeddings": True, "rope_theta": 10000.0,
             "sliding_window": 0, "norm_eps": 1e-05, "dtype": "bfloat16"},
    "granite-3.2-8b-stage0": {
        "name": "granite-3.2-8b-stage0", "num_layers": 20, "d_model": 4096,
        "num_heads": 32, "num_kv_heads": 8, "head_dim": 128, "d_ff": 12800,
        "vocab_size": 49155, "activation": "swiglu", "tie_embeddings": True,
        "rope_theta": 10000000.0, "sliding_window": 0, "norm_eps": 1e-05,
        "dtype": "bfloat16"},
}
# sha256 of every leaf's path, dtype, shape and bytes (``digest``)
TINY_WEIGHTS = {
    3: ("115fdfb512fac83f536fd0552f5d0fcf6763d4a5f5a4c98920add69bac644c23",
        "afaf0a25f380c101541f870188c30aa13f65a81a42542078e1c82c69ed649b40"),
    2**31 + 12345: (
        "904293b05d93804593b4e72f19d4e20903bf0ff8f0d0c44c1dfa438cde2d2aa2",
        "044b2f649abee29f0571307f02e74db92347de74b415f9061057c035b6038fa5"),
}
OUT_STD = 0.0022360679774997894      # 0.02 / sqrt(2 x 40 published layers)
STAGE0_RULE = {
    "['blocks']['seg0']['attn']['wk']": (0.0, 0.02),
    "['blocks']['seg0']['attn']['wo']": (0.0, OUT_STD),
    "['blocks']['seg0']['attn']['wq']": (0.0, 0.02),
    "['blocks']['seg0']['attn']['wv']": (0.0, 0.02),
    "['blocks']['seg0']['ln1']": (1.0, 0.1),
    "['blocks']['seg0']['ln2']": (1.0, 0.1),
    "['blocks']['seg0']['mlp']['w_down']": (0.0, OUT_STD),
    "['blocks']['seg0']['mlp']['w_gate']": (0.0, 0.02),
    "['blocks']['seg0']['mlp']['w_up']": (0.0, 0.02),
    "['embed']['tok']": (0.0, 0.02),
    "['final_norm']": (1.0, 0.1),
}
# per mix and seed: sessions planned for a 51 s window, and sha256 of the
# plan (``plan_digest``) and of the client's token ids (``token_digest``)
PLANS = {
    ("pipeline", 1): (
        26, "13c39ac9f1a8a836dc39de5dcaace6b0a55b628edd4e0ae7e7e18b5857644e6b",
        "127478999a6ce75bff96fffcb75b38926c0d269790bd63c93df1bfa3d3f8c5bf",
        (1056, 1969)),
    ("pipeline", 77): (
        32, "95b84808859335137919a07f32c6849368cefd1a0fe2f9619e77efd16b815a54",
        "754045781e2710062a78e3ca306fa8695755893a82fbb1e1211db7b523d2c4e5",
        (1056, 2001)),
    ("pipeline", 2**31 + 12345): (
        29, "da5a15a8f7703b05139bc5bbb4ed8539eb9bbb1f3488b8c7ef2a81730237b661",
        "7053e220bb0c708254735891abdfca13bd916fed84aab55cf429210d0cc512d8",
        (1056, 2032)),
    ("short-batch", 1): (
        4096, "f247fe97ad8dd5e983adf865a5b5ab055966869f7007195bee450d00b2d3e3ec",
        "258e1a36e2749722d877a7c6d9db7a0abbca75ad93ed2509c1745d80786e5abf",
        (264, 483)),
    ("short-batch", 77): (
        4096, "75ae976f4c5ef6f2c76998d2001ca293b10133a376c9dc8c4f26ea08720b51be",
        "768de97e898161f4fd6140c6d49c42122a78f6915998149a9e18b533ba679e29",
        (264, 483)),
    ("short-batch", 2**31 + 12345): (
        4096, "39de7e4b9b27bdc598c9d7ac3f35f15573c2493147fb5ca315a25dd68a2cac3a",
        "5e6b58e7eef53f0d880c07a978219e4c79f1ae3e125b62ad18952c718a76da46",
        (264, 483)),
}
SUPPORT = {
    "pipeline": [1056] + [v for b in range(1088, 2016, 32)
                          for v in (b, b + 3)] + [2019],
    "short-batch": [v for b in range(264, 360, 8) for v in (b, b + 3)],
}
GRANITE_VOCAB = 49155


def conf(name):
    return json.loads(CONFIGS[name].read_text())


def digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def plan_digest(sessions) -> str:
    h = hashlib.sha256()
    for s in sessions:
        h.update(repr((s.sid, s.arrival,
                       [(t.message, t.answer, t.evals, t.direct)
                        for t in s.turns], s.think)).encode())
    return h.hexdigest()


def token_digest(client, sessions) -> str:
    h = hashlib.sha256()
    for s in sessions:
        for k in range(len(s.turns)):
            h.update(np.asarray(client._message(s, k), np.int64).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_model_fields_as_before(name):
    assert harness.model_fields(conf(name)) == FIELDS[name]
    assert harness.model_module(conf(name)).__file__ == str(
        BENCH / "models" / "granite.py")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_model_config_as_before(name):
    from repro.configs.base import ModelConfig
    assert harness.model_config(conf(name)) == ModelConfig(
        arch_type="dense", **FIELDS[name])


@pytest.mark.parametrize("seed", sorted(TINY_WEIGHTS))
def test_tiny_weights_and_adapters_bit_equal(seed):
    import jax
    from repro.core.alora import init_adapter_weights
    from repro.models import init_params
    c = conf("tiny")
    cfg = harness.model_config(c)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    rule = weights.init_rule(harness.model_module(c),
                             c["published"]["num_hidden_layers"],
                             c["init_std"])
    params = weights.make_params(shapes, seed, rule)
    s = c["serving"]
    ash = jax.eval_shape(
        lambda k: init_adapter_weights(k, cfg, s["adapter_rank"]),
        jax.random.key(0))
    ads = [weights.make_adapter(ash, seed, i, cfg.d_model,
                                s["adapter_rank"], s["adapter_gain"])
           for i in range(s["adapters"])]
    assert (digest(params), digest(ads)) == TINY_WEIGHTS[seed]


def test_stage0_init_rule_per_leaf_as_before():
    """Every leaf of the stage-0 parameter tree (shapes only: nothing is
    allocated) is drawn with the (mean, std) it was drawn with before."""
    import jax
    from repro.models import init_params
    c = conf("granite-3.2-8b-stage0")
    cfg = harness.model_config(c)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    rule = weights.init_rule(harness.model_module(c),
                             c["published"]["num_hidden_layers"],
                             c.get("init_std", 0.02))
    got = {jax.tree_util.keystr(p): rule(p[-1].key)
           for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == STAGE0_RULE


@pytest.mark.parametrize("mix,seed", sorted(PLANS))
def test_plan_tokens_and_lengths_as_before(mix, seed):
    params = traffic.load_mix(BENCH / "traffic" / f"{mix}.json")
    m = traffic.Mix.build(params, seed, 3)
    sessions = m.planned(params["warmup_s"] + 51, n_closed=4096)
    client = driver.Client(None, m, [f"a{i}" for i in range(4)], [3, 4, 5],
                           GRANITE_VOCAB)
    n, plan, tokens, bounds = PLANS[mix, seed]
    assert len(sessions) == n
    assert all(t.doc is None for s in sessions for t in s.turns)
    assert plan_digest(sessions) == plan
    assert token_digest(client, sessions) == tokens
    assert traffic.context_bounds(sessions, 3) == bounds
    assert traffic.length_support(m) == SUPPORT[mix]
