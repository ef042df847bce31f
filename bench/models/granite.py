"""Granite's published configuration keys → the program's model settings.

A model module maps one ``model_type`` (the configuration file's key, as
in the published ``config.json``).  It gives ``ARCH_TYPE``, the
program's ``ModelConfig.arch_type``, and ``fields(conf)``: every other
``ModelConfig`` keyword, the dict that ``run.model``, the reference and
``bench/flops.py`` see.  It may give ``NORMS`` and ``OUT_PROJ``, the leaf
names ``bench/weights.py`` draws as norm scales and output projections.
Every published key it runs at another value, or cannot express, is
listed under ``assumed`` in the configuration file.

This one reads ``name``, ``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim`` (else
hidden / heads), ``intermediate_size``, ``vocab_size``, ``hidden_act``,
``tie_word_embeddings``, ``rope_theta``, ``sliding_window`` (absent: full
attention), ``rms_norm_eps`` (else ``norm_epsilon``, else 1e-5) and
``torch_dtype``.
"""
from __future__ import annotations

ARCH_TYPE = "dense"
ACTIVATION = {"silu": "swiglu"}


def fields(conf: dict) -> dict:
    hf = conf
    d, H = hf["hidden_size"], hf["num_attention_heads"]
    return {
        "name": conf["name"],
        "num_layers": hf["num_hidden_layers"],
        "d_model": d,
        "num_heads": H,
        "num_kv_heads": hf["num_key_value_heads"],
        "head_dim": hf.get("head_dim", d // H),
        "d_ff": hf["intermediate_size"],
        "vocab_size": hf["vocab_size"],
        "activation": ACTIVATION[hf["hidden_act"]],
        "tie_embeddings": hf["tie_word_embeddings"],
        "rope_theta": hf["rope_theta"],
        "sliding_window": hf.get("sliding_window") or 0,
        "norm_eps": hf.get("rms_norm_eps", hf.get("norm_epsilon", 1e-5)),
        "dtype": hf["torch_dtype"],
    }
