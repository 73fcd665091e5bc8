"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call, in the type they are served in.

The tree's structure and shapes come from the program's model
(``jax.eval_shape`` of its ``init``); the values come from here, so the
plain reference can take the same arrays without taking anything the
program made. Each leaf is drawn by the rule for its name: matrices
N(0, 1/fan_in), norm scales 1 + N(0, 0.1^2), biases N(0, 0.1^2), the token
table N(0, 1). A leaf with no rule is an error.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# (parent, leaf) -> how many trailing-from-the-front axes are fan-in, after
# the stacked-layer axis; None marks a vector drawn around 0 or 1
_RULES = {
    ("embed", "tok"): "unit",
    ("embed", "head"): 1,      # [V, d]: fan-in is d (last axis)
    ("attn", "wq"): 0, ("attn", "wk"): 0, ("attn", "wv"): 0,   # [d, h, k]
    ("attn", "wo"): 2,         # [h, k, d]
    ("attn", "bq"): "bias", ("attn", "bk"): "bias", ("attn", "bv"): "bias",
    ("mlp", "wi"): 0, ("mlp", "wg"): 0, ("mlp", "wo"): 0,       # [in, out]
    ("ln1", "scale"): "scale", ("ln2", "scale"): "scale",
    ("ln_f", "scale"): "scale",
    ("ln1", "bias"): "bias", ("ln2", "bias"): "bias", ("ln_f", "bias"): "bias",
}


def _names(path):
    keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    return keys[-2] if len(keys) > 1 else "", keys[-1], keys[0] == "blocks"


def _fan_in(shape, rule, stacked):
    dims = shape[1:] if stacked else shape
    if rule == 0:
        return dims[0]
    if rule == 1:
        return dims[-1]
    if rule == 2:
        return math.prod(dims[:-1])
    raise ValueError(rule)


def make_weights(model, seed: int):
    """Parameters for ``model`` drawn from ``seed`` (any whole number)."""
    abstract = jax.eval_shape(model.init, jax.random.key(0), 0)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = []
    for path, leaf in leaves:
        parent, name, stacked = _names(path)
        if (parent, name) not in _RULES:
            raise KeyError(f"no weight rule for parameter {'/'.join(map(str, path))}")
        specs.append((_RULES[(parent, name)], leaf.shape, leaf.dtype, stacked))

    lo, hi = int(seed) % 2 ** 32, (int(seed) // 2 ** 32) % 2 ** 32

    @jax.jit
    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        out = []
        for i, (rule, shape, dtype, stacked) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, dtype)
            if rule == "unit":
                x = z
            elif rule == "scale":
                x = 1 + 0.1 * z
            elif rule == "bias":
                x = 0.1 * z
            else:
                x = z * (1.0 / math.sqrt(_fan_in(shape, rule, stacked)))
            out.append(x.astype(dtype))
        return out

    arrays = draw(jnp.uint32(lo), jnp.uint32(hi))
    return jax.tree_util.tree_unflatten(treedef, arrays)
