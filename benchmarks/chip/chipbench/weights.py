"""Seeded random weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights: ``make_params`` walks the
program's parameter shapes (``abstract_params`` of the model under test) and
fills every leaf from the seed in the dtype it is served in.  The plain
reference reads the same arrays by their names.

Each leaf is uniform with the standard deviation its name calls for:
normalisation gains 1 +- 0.1, biases 0.1, the embedding 0.02, and every
matrix 1/sqrt(fan-in) (fan-in is the second-to-last axis).  Uniform and not
normal: it needs no float32 buffer of the leaf's size on the way.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_SQRT3 = math.sqrt(3.0)


def leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def leaf_std(name: str, shape: tuple) -> tuple[float, float]:
    """(mean, standard deviation) of the leaf called `name`."""
    if name.startswith("ln") or name.endswith("norm"):
        return 1.0, 0.1
    if name in ("bq", "bk", "bv"):
        return 0.0, 0.1
    if name == "embed":
        return 0.0, 0.02
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return 0.0, 1.0 / math.sqrt(max(fan_in, 1))


def root_key(seed: int):
    """A key from any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_params(abstract, seed: int):
    """Arrays shaped and typed as the ShapeDtypeStruct tree `abstract`."""
    paths = jax.tree_util.tree_flatten_with_path(abstract)[0]
    treedef = jax.tree_util.tree_structure(abstract)
    specs = [(leaf_name(p), s.shape, s.dtype) for p, s in paths]

    def build(key):
        out = []
        for i, (name, shape, dtype) in enumerate(specs):
            mean, std = leaf_std(name, shape)
            a = _SQRT3 * std
            u = jax.random.uniform(jax.random.fold_in(key, i), shape,
                                   jnp.float32, -a, a)
            out.append((u + mean).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(root_key(seed))
