"""Seeded weights, made by the benchmark on the device in one jitted call
each, in the type they are served in.

The leaves follow the program's parameter layout (their shapes are read
from ``jax.eval_shape`` of the program's initialiser); their values are
the benchmark's: projections N(0, std), output projections N(0, std /
sqrt(2 * layers)) with std 0.02 unless the configuration states
``init_std``, norms 1 + N(0, 0.1).  Which leaves are norm scales and
output projections, by the last name on their path, the configuration's
model module says (``NORMS``, ``OUT_PROJ``; these defaults where it does
not).  Adapters: A ~ N(0, 1/sqrt(d)), B ~ N(0, gain * 0.02 / sqrt(rank)).
The reference reads the same trees.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

OUT_PROJ = ("wo", "w_down")
NORMS = ("ln1", "ln2", "final_norm", "ln", "xln")


def _fill(shapes, key, scale_of):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, s) in zip(keys, leaves):
        name = path[-1].key
        mean, std = scale_of(name)
        x = jax.random.normal(k, s.shape, jnp.float32) * std + mean
        out.append(x.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _key(seed: int):
    """A key for any whole number: its low and high 32 bits."""
    seed = abs(int(seed))
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def init_rule(model_mod, n_layers_published: int, std: float = 0.02):
    """``scale_of(leaf name) -> (mean, std)`` of the model weights, with the
    leaf names of ``model_mod`` (a ``bench/models`` module)."""
    norms = getattr(model_mod, "NORMS", NORMS)
    out_proj = getattr(model_mod, "OUT_PROJ", OUT_PROJ)
    out_std = std / math.sqrt(2 * n_layers_published)

    def scale_of(name):
        if name in norms:
            return 1.0, 0.1
        return 0.0, out_std if name in out_proj else std

    return scale_of


def make_params(shapes, seed: int, scale_of):
    """Model weights shaped like ``shapes`` (a ShapeDtypeStruct tree),
    each leaf drawn as ``scale_of`` (``init_rule``) says."""
    return jax.jit(partial(_fill, shapes, scale_of=scale_of))(_key(seed))


def make_adapter(shapes, seed: int, index: int, d_model: int, rank: int,
                 gain: float):
    """One aLoRA adapter's weights shaped like ``shapes``."""
    def scale_of(name):
        if name.startswith("a"):
            return 0.0, 1.0 / math.sqrt(d_model)
        return 0.0, gain * 0.02 / math.sqrt(rank)

    fn = jax.jit(partial(_fill, shapes, scale_of=scale_of))
    return fn(jax.random.fold_in(_key(seed), 1000 + index))
