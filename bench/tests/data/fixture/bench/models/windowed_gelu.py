"""A fixture architecture, to show that a model the harness has never
seen enters by new files alone: a dense decoder with a non-gated
GELU-tanh MLP and a sliding attention window, under starcoder2's
published key names.

Reads ``name``, ``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``vocab_size``, ``hidden_act`` (``gelu_pytorch_tanh``), ``sliding_window``,
``norm_epsilon``, ``rope_theta``, ``tie_word_embeddings``, ``torch_dtype``
and ``use_bias``, which it refuses when true: the program's block has no
linear biases.  Returns the program's ``ModelConfig`` keywords.
"""
from __future__ import annotations

ARCH_TYPE = "dense"
ACTIVATION = {"gelu_pytorch_tanh": "gelu"}


def fields(conf: dict) -> dict:
    if conf["use_bias"]:
        raise ValueError(f"{conf['name']}: the program's block has no "
                         "linear biases")
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "name": conf["name"],
        "num_layers": conf["num_hidden_layers"],
        "d_model": d,
        "num_heads": H,
        "num_kv_heads": conf["num_key_value_heads"],
        "head_dim": d // H,
        "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "activation": ACTIVATION[conf["hidden_act"]],
        "tie_embeddings": conf["tie_word_embeddings"],
        "rope_theta": conf["rope_theta"],
        "sliding_window": conf["sliding_window"],
        "norm_eps": conf["norm_epsilon"],
        "dtype": conf["torch_dtype"],
    }
