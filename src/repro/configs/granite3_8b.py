"""granite-3.2-8b — the paper's own evaluation model (Table 1).

``CONFIG`` is the published model.  ``chip_share()`` is what one TPU v5e
chip (16 GB HBM) holds of the stated deployment ``DEPLOYMENT``: 20 of
the 40 layers at every published width, in bf16.  ``CHIP_SHARE_REDUCED``
lists the keys cut from ``CONFIG``.  ``reduced()`` is the tiny float32
variant the CPU tests run.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-3.2-8b",
    arch_type="dense",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=12800,
    vocab_size=49155,
    activation="swiglu",
    tie_embeddings=True,
    source="paper Table 1 / hf:ibm-granite/granite-3.2-8b-instruct",
)


def reduced() -> ModelConfig:
    return CONFIG.replace(
        name="granite-3.2-8b-reduced",
        num_layers=2, d_model=256, num_heads=8, num_kv_heads=2,
        head_dim=32, d_ff=512, vocab_size=512, max_seq_len=2048,
        dtype="float32",
    )


# Two v5e chips serve the model as two pipeline stages; each stage holds
# 20 whole layers (no layer is split across chips).  One chip's share is
# therefore the first stage: the layers beyond it would lie on the second
# chip.  bf16 weights are 8.4 GB per stage, leaving the rest of the 16 GB
# for the paged KV pool and step temporaries.
DEPLOYMENT = ("2x TPU v5e, pipeline-parallel: 2 stages of 20 layers, "
              "each layer whole on its chip")
CHIP_SHARE_REDUCED = ("num_layers",)


def chip_share() -> ModelConfig:
    return CONFIG.replace(name="granite-3.2-8b-stage0", num_layers=20)
