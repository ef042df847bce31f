"""Compile the serving path for a TPU v5e without one.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  These tests
compile, at published widths, what the chip's compiler would otherwise
first refuse on the chip: the ragged paged-attention and grouped-LoRA
Pallas kernels (granite-3.2-8b), the ragged SSD kernel (mamba2-2.7b),
and the whole mixed serving step; and they lower the dry-run's sharded
prefill and decode steps on a 4-chip mesh.  Nothing runs; a
compile that passes says nothing about results or times.

Only one process may load the TPU library, and it keeps it until it
exits.  So the topology is described inside a module-scoped fixture,
never while a module is imported, and every such test stays in this one
file (one xdist worker loads the library).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_chip_share, get_config
from repro.core.alora import init_adapter_weights
from repro.models import param_specs
from repro.serving.runner import RunnerSpec, _mixed_impl

V5E_HBM = 15.75 * (1 << 30)     # what the v5e compiler reports as usable


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else it logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding):
    def f(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return f


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


GRANITE = get_config("granite-3.2-8b")


@pytest.mark.parametrize("T", [1, 128])
@pytest.mark.parametrize("ragged", [True, False])
def test_paged_attention_compiles_at_granite_widths(one_chip, T, ragged):
    from repro.kernels.paged_attention import (paged_attention,
                                               ragged_paged_attention)
    S = _shape(one_chip)
    cfg = GRANITE
    H, KV, hd, bs, NB, R, nb = (cfg.num_heads, cfg.num_kv_heads,
                                cfg.head_dim, 16, 2049, 8, 128)
    q = S((T, H, hd), jnp.bfloat16)
    pool = S((NB, bs, KV, hd), jnp.bfloat16)
    lens = S((T,), jnp.int32)
    if ragged:
        c = _compile(ragged_paged_attention, q, pool, pool,
                     S((R, nb), jnp.int32), S((T,), jnp.int32), lens)
    else:
        c = _compile(paged_attention, q, pool, pool,
                     S((T, nb), jnp.int32), lens)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("T", [8, 128])
@pytest.mark.parametrize("out", [4096, 1024])
def test_grouped_lora_compiles_at_granite_widths(one_chip, T, out):
    from repro.kernels.ragged_lora import ragged_grouped_lora_padded
    S = _shape(one_chip)
    d, r, slots = GRANITE.d_model, 32, 3
    c = _compile(ragged_grouped_lora_padded, S((T, d), jnp.bfloat16),
                 S((slots, d, r), jnp.bfloat16),
                 S((slots, r, out), jnp.bfloat16), S((T,), jnp.int32),
                 S((2,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("T", [16, 128])
def test_ragged_ssd_compiles_at_mamba2_widths(one_chip, T):
    from repro.kernels.ops import ragged_ssd_scan_op
    S = _shape(one_chip)
    cfg = get_config("mamba2-2.7b")
    s = cfg.ssm
    H = s.expand * cfg.d_model // s.head_dim
    P, N, MR, f32 = s.head_dim, s.state_dim, 9, jnp.float32
    c = _compile(lambda *a: ragged_ssd_scan_op(*a, chunk=16,
                                               interpret=False),
                 S((T, H, P), f32), S((T, H, N), f32), S((T, H, N), f32),
                 S((T, H), f32), S((T, H), f32), S((T,), jnp.int32),
                 S((T,), jnp.bool_), S((T,), jnp.int32),
                 S((MR, H, N, P), f32))
    assert "tpu_custom_call" in c.as_text()


def mixed_step_args(cfg, sharding, *, T: int, R: int, nbb: int,
                    num_blocks: int, max_running: int, n_slots: int,
                    rank: int, impl: str = "ref"):
    """``(spec, args)`` of one ``_mixed_impl`` call, as shapes on
    ``sharding``: what ``ModelRunner.submit_batch`` dispatches for an
    attention-only ``cfg`` at token bucket ``T``, request bucket ``R`` and
    block-table bucket ``nbb``, with ``n_slots`` adapter slots of rank
    ``rank`` (slot 0, the zero adapter, included)."""
    S = _shape(sharding)
    on = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
    La, bs, d = cfg.num_layers, 16, cfg.d_model
    spec = RunnerSpec(cfg=cfg, block_size=bs, num_blocks=num_blocks,
                      window=0, kinds=tuple(cfg.pattern()), attn_impl=impl,
                      lora_impl=impl)
    params = on(param_specs(cfg))
    ad = jax.eval_shape(lambda k: init_adapter_weights(k, cfg, rank),
                        jax.random.key(0))["seg0"]
    layer = {k: S((n_slots,) + a.shape[2:], a.dtype) for k, a in ad.items()}
    pool = S((La, num_blocks, bs, cfg.num_kv_heads, cfg.head_dim),
             jnp.bfloat16)
    i32 = jnp.int32
    tok = S((T,), i32)
    args = (params, [layer] * La, pool, pool, None, None,
            S((max_running,), i32), tok, S((T, d), jnp.float32),
            S((T,), jnp.bool_), S((T,), jnp.bool_), tok, tok, tok,
            S((2,), i32), S((R, nbb), i32), tok, tok, tok, tok,
            S((R,), i32), S((R,), i32), tok, S((8,), i32), None)
    return spec, args


@pytest.mark.parametrize("shape", ["prefill_32k", "decode_32k"])
def test_dryrun_step_lowers_with_odd_vocab(topo, shape):
    """The dry-run's prefill and decode steps shard the logits' vocab
    axis over ``model``.  granite-3.2-8b's 49,155 tokens divide by no
    model axis; the logits keep the embedding's padded rows, so the
    step lowers on a (data=1, model=4) mesh."""
    from jax.sharding import Mesh

    from repro.configs import INPUT_SHAPES
    from repro.launch.steps import build_step
    mesh = Mesh(np.asarray(topo.devices).reshape(1, -1), ("data", "model"))
    spec = build_step(GRANITE, INPUT_SHAPES[shape], mesh)
    with mesh:
        lowered = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                          out_shardings=spec.out_shardings,
                          donate_argnums=spec.donate_argnums
                          ).lower(*spec.args)
    logits = lowered.out_info[0]
    assert logits.shape[-1] % mesh.shape["model"] == 0


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_mixed_step_compiles_at_granite_widths(one_chip, impl):
    """Two layers of granite-3.2-8b at published widths through the
    whole mixed step, at the one-chip smoke's largest bucket (128
    tokens, 8 requests, 2k-token block tables, 2,049-block pool)."""
    cfg = get_chip_share("granite-3.2-8b").replace(num_layers=2)
    spec, args = mixed_step_args(cfg, one_chip, T=128, R=8, nbb=128,
                                 num_blocks=2049, max_running=9,
                                 n_slots=3, rank=32, impl=impl)
    c = _mixed_impl.lower(spec, *args).compile()
    m = c.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < V5E_HBM
    assert ("tpu_custom_call" in c.as_text()) == (impl == "pallas")
