"""Random weights made from ``--seed``, on the device, in one jitted call.

The benchmark makes the weights, not the program: the program is handed
this tree, and the plain reference reads the same arrays.  The tree and
each leaf's scale come from the module of the configuration's
architecture (``models/``, ``shapes_tree`` and ``leaf_std``): any
nesting, in the program's parameter layout.  Every leaf is drawn
N(0, std^2) in float32 from its own key, split from the seed's key in
the order of the flattened tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import arch


def prng_key(seed: int):
    """A key from any non-negative seed, all of its bits used."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes_tree(conf: dict) -> dict:
    """The tree's leaf shapes: tuples of ints."""
    return arch.module(conf).shapes_tree(conf)


def _path_names(path) -> tuple:
    out = []
    for p in path:
        out.append(getattr(p, "key", getattr(p, "idx", p)))
    return tuple(out)


def make_params(conf: dict, seed: int):
    """Every leaf in float32, drawn on the default device in one program."""
    mod = arch.module(conf)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        mod.shapes_tree(conf), is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(i, int) for i in x))
    specs = [(shape, mod.leaf_std(_path_names(p), shape))
             for p, shape in leaves]
    fn = jax.jit(functools.partial(_draw, specs=tuple(specs)))
    return jax.tree_util.tree_unflatten(treedef, fn(prng_key(seed)))


def _draw(key, specs):
    keys = jax.random.split(key, len(specs))
    return [jax.random.normal(k, shape, jnp.float32) * jnp.float32(std)
            for k, (shape, std) in zip(keys, specs)]
