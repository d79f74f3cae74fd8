"""Weights made by the benchmark from ``--seed``, in one jitted call.

The program under test says only the tree's structure and each leaf's
shape and dtype (``jax.eval_shape`` of its own init); every value comes
from here, by the rules of the configuration's reference module, so the
reference never takes anything the program made.  Each leaf draws from
its own key, ``fold_in(seed key, leaf index)``, so one leaf can be made
again alone with the same values.
"""
from __future__ import annotations

import math
import re
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any non-negative ``seed``, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_paths(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]


def _rule(rules: Sequence[Tuple[str, str, dict]], path: str):
    for pattern, dist, args in rules:
        if re.search(pattern, path):
            return dist, args
    raise KeyError(f"no weight rule matches leaf {path!r}")


def draw(key, path: str, shape, dtype, rules, stacked: bool):
    """One leaf: the rule that matches ``path`` first, drawn in f32 and
    cast to the leaf's dtype."""
    dist, a = _rule(rules, path)
    body = shape[1:] if stacked else shape
    if dist == "normal_fan_in":
        fan_in = body[0] if a.get("fan_in", "first") == "first" else \
            math.prod(body[:-1])
        x = jax.random.normal(key, shape, jnp.float32) \
            * (a.get("gain", 1.0) / fan_in ** 0.5)
    elif dist == "normal":
        x = jax.random.normal(key, shape, jnp.float32) * a["std"]
    elif dist == "one_plus_normal":
        x = 1.0 + jax.random.normal(key, shape, jnp.float32) * a["std"]
    elif dist == "uniform":
        x = jax.random.uniform(key, shape, jnp.float32, a["lo"], a["hi"])
    elif dist == "embedding":
        # rows past the published vocabulary (the program pads it) are 0,
        # so their logits never lead and no padded id is served
        x = jax.random.normal(key, shape, jnp.float32) * a["std"]
        rows = jnp.arange(shape[0])[:, None] < a["vocab"]
        x = jnp.where(rows, x, 0.0)
    else:
        raise ValueError(f"unknown weight distribution {dist!r}")
    return x.astype(dtype)


def _stacked(path: str, stacked_prefixes) -> bool:
    return any(path.startswith(p) for p in stacked_prefixes)


def make(shape_tree, seed: int, rules, stacked_prefixes=("units/",),
         shardings=None):
    """The whole tree from ``seed`` in one jitted call on the device."""
    flat, treedef = jax.tree.flatten(shape_tree)
    paths = leaf_paths(shape_tree)

    def build(key):
        leaves = [draw(jax.random.fold_in(key, i), p, s.shape, s.dtype,
                       rules, _stacked(p, stacked_prefixes))
                  for i, (p, s) in enumerate(zip(paths, flat))]
        return jax.tree.unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=shardings) if shardings is not None \
        else jax.jit(build)
    return fn(seed_key(seed))


def make_leaf(shape_tree, seed: int, rules, index: int,
              stacked_prefixes=("units/",)):
    """Leaf ``index`` of :func:`make`'s tree, made again alone."""
    flat = jax.tree.leaves(shape_tree)
    path = leaf_paths(shape_tree)[index]
    s = flat[index]
    return jax.jit(lambda key: draw(jax.random.fold_in(key, index), path,
                                    s.shape, s.dtype, rules,
                                    _stacked(path, stacked_prefixes)))(
        seed_key(seed))
