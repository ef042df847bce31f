"""Per-architecture smoke tests (assignment requirement): reduced
variants of all 10 assigned archs — one forward pass and one train step
on CPU, asserting output shapes and finiteness, plus the
prefill+decode == full-forward consistency invariant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_reduced
from repro.models import (decode_step, forward_full, init_decode_caches,
                          init_params, logits_for)
from repro.models.layers import padded_vocab
from repro.models.model import Runtime, prefill_to_decode_caches
from repro.training import AdamWConfig, init_train_state, make_train_step

KEY = jax.random.key(0)


def extra_for(cfg, B, seed=0):
    rng = np.random.RandomState(seed)
    if cfg.frontend == "audio":
        return jnp.asarray(rng.randn(B, cfg.encoder_seq_len, cfg.d_model)
                           * 0.05, jnp.float32)
    if cfg.frontend == "vision":
        return jnp.asarray(rng.randn(B, cfg.num_patches, cfg.d_model)
                           * 0.05, jnp.float32)
    return None


@pytest.fixture(scope="module")
def models():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_reduced(arch)
            cache[arch] = (cfg, init_params(KEY, cfg))
        return cache[arch]
    return get


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_shapes_and_finite(models, arch):
    cfg, params = models(arch)
    assert cfg.num_layers <= 4 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4
    B, S = 2, 32
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    h, aux, _ = forward_full(params, cfg, toks,
                             extra_embeds=extra_for(cfg, B))
    n_prefix = cfg.num_patches if cfg.frontend == "vision" else 0
    assert h.shape == (B, S + n_prefix, cfg.d_model)
    logits = logits_for(params, cfg, h)
    assert logits.shape == (B, S + n_prefix, padded_vocab(cfg))
    assert bool(jnp.isfinite(logits).all())


def test_padded_vocab_tail_is_masked():
    """With a vocabulary that is not a multiple of 512, the logits keep
    the embedding's padded width (so they shard like it); the padding
    rows are -inf, the real rows are the plain unembedding, and the
    loss and its gradient see only the real rows."""
    from repro.models import layers as L
    from repro.training.train_loop import chunked_ce_loss
    cfg = get_reduced("granite-3.2-8b").replace(vocab_size=500)
    params = init_params(KEY, cfg)
    B, S = 2, 16
    h = jax.random.normal(KEY, (B, S, cfg.d_model))
    logits = logits_for(params, cfg, h)
    assert padded_vocab(cfg) > 500
    assert logits.shape == (B, S, padded_vocab(cfg))
    assert bool(jnp.isneginf(logits[..., 500:]).all())
    full = L.unembed(params["embed"], h, cfg.tie_embeddings)
    np.testing.assert_array_equal(logits[..., :500], full[..., :500])
    assert int(logits.argmax(-1).max()) < 500

    labels = jax.random.randint(KEY, (B, S), 0, 500)
    mask = jnp.ones((B, S))
    loss, grad = jax.value_and_grad(
        lambda p: chunked_ce_loss(p, cfg, h, labels, mask, chunk=8))(params)
    real = full[..., :500]
    want = (jax.nn.logsumexp(real, -1)
            - jnp.take_along_axis(real, labels[..., None], -1)[..., 0]).mean()
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grad))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step(models, arch):
    cfg, params = models(arch)
    B, S = 2, 32
    state = init_train_state(params)
    step = make_train_step(cfg, AdamWConfig(total_steps=10), Runtime(),
                           loss_chunk=16)
    batch = {
        "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
        "mask": jnp.ones((B, S), jnp.float32),
    }
    ex = extra_for(cfg, B)
    if ex is not None:
        batch["extra_embeds"] = ex
    state2, stats = jax.jit(step)(state, batch)
    assert bool(jnp.isfinite(stats["loss"]))
    assert bool(jnp.isfinite(stats["grad_norm"]))
    # every parameter leaf received a (finite, nonzero) update
    changed = [
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(state2.params))
    ]
    assert all(changed), f"{sum(changed)}/{len(changed)} leaves updated"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_prefill_decode_consistency(models, arch):
    """KV-cache/state correctness: prefill S-1 tokens + decode token S
    must equal the teacher-forced forward at position S-1."""
    cfg, params = models(arch)
    B, S = 2, 33
    toks = jax.random.randint(jax.random.key(1), (B, S), 0,
                              cfg.vocab_size)
    ex = extra_for(cfg, B)
    h, _, _ = forward_full(params, cfg, toks, extra_embeds=ex)
    want = logits_for(params, cfg, h)[:, -1]

    h2, _, pc = forward_full(params, cfg, toks[:, :S - 1],
                             extra_embeds=ex, return_caches=True)
    npre = (S - 1) + (cfg.num_patches if cfg.frontend == "vision" else 0)
    dc = prefill_to_decode_caches(cfg, pc, npre, 128)
    got, _ = decode_step(params, cfg, toks[:, S - 1:S], dc, npre)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_sliding_window_ring_buffer():
    """Decode past the window: ring cache must equal a fresh prefill
    truncated to the window (starcoder2 family, native window)."""
    cfg = get_reduced("starcoder2-3b").replace(sliding_window=16)
    params = init_params(KEY, cfg)
    B, S = 1, 40
    toks = jax.random.randint(jax.random.key(2), (B, S + 1), 0,
                              cfg.vocab_size)
    # reference: full forward (window masking internal)
    h, _, _ = forward_full(params, cfg, toks)
    want = logits_for(params, cfg, h)[:, -1]
    # prefill S then decode 1 with ring cache
    _, _, pcaches = forward_full(params, cfg, toks[:, :S],
                                 return_caches=True)
    dc = prefill_to_decode_caches(cfg, pcaches, S, 64)
    got, _ = decode_step(params, cfg, toks[:, S:], dc, S)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["granite-3.2-8b", "zamba2-2.7b",
                                  "whisper-large-v3"])
def test_init_params_matches_per_layer_stacking(arch):
    """``init_params`` writes each layer into preallocated stacked leaves;
    the values must equal building every layer on its own and stacking
    the list, for the same key (dense, hybrid and encoder-decoder
    stacks)."""
    from repro.models import layers as L
    from repro.models.model import ATTN, _init_layer, period_segments
    cfg = get_reduced(arch)
    key = jax.random.key(3)

    def stack(k, kind, repeats, count, cross):
        ps = [_init_layer(kk, cfg, kind, L.dtype_of(cfg), cross)
              for kk in jax.random.split(k, repeats * count)]
        return jax.tree.map(lambda *xs: jnp.stack(xs).reshape(
            (repeats, count) + xs[0].shape), *ps)

    repeats, segs = period_segments(cfg)
    _, k_blocks, k_enc = jax.random.split(key, 3)
    seg_keys = jax.random.split(k_blocks, len(segs))
    got = init_params(key, cfg)
    for i, (kind, count) in enumerate(segs):
        want = stack(seg_keys[i], kind, repeats, count,
                     cfg.is_encoder_decoder and kind == ATTN)
        jax.tree.map(np.testing.assert_array_equal,
                     got["blocks"][f"seg{i}"], want)
    if cfg.is_encoder_decoder:
        want = stack(jax.random.split(k_enc, 2)[0], ATTN,
                     cfg.num_encoder_layers, 1, False)
        jax.tree.map(np.testing.assert_array_equal,
                     got["encoder"]["blocks"], want)


def check_sharded_init(model: int) -> None:
    """``init_params(..., shardings)`` on a (data=1, model=``model``)
    host mesh places every leaf with its sharding, splits some over
    ``model``, and gives the values of the unsharded init."""
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.models import param_specs
    cfg = get_reduced("granite-3.2-8b")
    mesh = make_host_mesh(data=1, model=model)
    named = shd.to_named(shd.param_specs_tree(cfg, param_specs(cfg),
                                              mesh=mesh), mesh)
    got = init_params(jax.random.key(0), cfg, named)
    want = init_params(jax.random.key(0), cfg)
    jax.tree.map(np.testing.assert_array_equal, got, want)
    for a, s in zip(jax.tree.leaves(got), jax.tree.leaves(named)):
        assert a.sharding.is_equivalent_to(s, a.ndim)
    assert any(not a.sharding.is_fully_replicated
               for a in jax.tree.leaves(got["blocks"]))


def test_init_params_with_shardings_places_each_leaf():
    """Sharded init on 4 host devices.  A process with fewer (the plain
    one-device run) checks it in a child process started with 4."""
    n = 4
    if jax.device_count() >= n:
        check_sharded_init(n)
        return
    import os
    import subprocess
    import sys
    from pathlib import Path
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + f" --xla_force_host_platform_device_count={n}",
               PYTHONPATH=os.pathsep.join(
                   [str(here.parent / "src"), str(here),
                    os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run(
        [sys.executable, "-c",
         f"import test_models_smoke as t; t.check_sharded_init({n})"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
