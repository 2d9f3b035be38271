"""Random weights made from ``--seed``, on the device, in one jitted call.

The benchmark makes the weights, not the program: the program is handed
this tree, and the plain reference reads the same arrays.  The tree has
the layout that the program's parameter tree has (``segments[0][0]``
holds every layer's leaves stacked on a leading layer axis):

* ``embed.table`` (V, d): token embeddings, tied to the LM head;
* ``final_norm.scale``, ``ln1.scale``, ``ln2.scale``, ``attn.qn.scale``,
  ``attn.kn.scale``: RMSNorm weights stored as offsets from one, i.e. the
  norm multiplies by ``1 + scale``;
* ``attn.{q,k,v,o}.wc`` and ``mlp.{up,gate,down}.wc`` (p, q, k): the
  first-column generators of a block-circulant ``W`` (n_out x n_in),
  block (i, j) being ``C[r, c] = w[i, j, (r - c) mod k]`` and ``y = W x``;
* ``attn.{q,k,v}.b``: the QKV biases, where the configuration has them.

Scales: embeddings N(0, 1/d), generators N(0, 1/n_in) (a dense layer's
variance), norm offsets N(0, 0.1^2), biases N(0, 0.5^2) so that a lost
bias shows in the logits.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from work import Shapes


def prng_key(seed: int):
    """A key from any non-negative seed, all of its bits used."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _blocks(n: int, k: int) -> int:
    return -(-n // k)


def shapes_tree(conf: dict) -> dict:
    """The tree's leaf shapes."""
    s = Shapes.of(conf)
    L, d, dff, k_a, k_f = (s.layers, s.d_model, s.d_ff, s.block_attn,
                           s.block_ffn)
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim

    def circ(n_in, n_out, k):
        return (L, _blocks(n_out, k), _blocks(n_in, k), k)

    attn = {"q": {"wc": circ(d, qd, k_a)}, "k": {"wc": circ(d, kvd, k_a)},
            "v": {"wc": circ(d, kvd, k_a)}, "o": {"wc": circ(qd, d, k_a)}}
    if conf.get("qkv_bias"):
        attn["q"]["b"] = (L, qd)
        attn["k"]["b"] = (L, kvd)
        attn["v"]["b"] = (L, kvd)
    if conf.get("qk_norm"):
        attn["qn"] = {"scale": (L, s.head_dim)}
        attn["kn"] = {"scale": (L, s.head_dim)}
    block = {"ln1": {"scale": (L, d)}, "attn": attn,
             "ln2": {"scale": (L, d)},
             "mlp": {"up": {"wc": circ(d, dff, k_f)},
                     "down": {"wc": circ(dff, d, k_f)},
                     "gate": {"wc": circ(d, dff, k_f)}}}
    return {"embed": {"table": (s.vocab, d)},
            "final_norm": {"scale": (d,)},
            "segments": [(block,)]}


def _leaf_std(path, shape) -> float:
    name = path[-1]
    if name == "table":
        return shape[-1] ** -0.5
    if name == "wc":                         # (L, p, q, k): n_in = q * k
        return 1.0 / math.sqrt(shape[-2] * shape[-1])
    if name == "scale":
        return 0.1
    if name == "b":
        return 0.5
    raise ValueError(f"no scale for leaf {'.'.join(map(str, path))}")


def _path_names(path) -> tuple:
    out = []
    for p in path:
        out.append(getattr(p, "key", getattr(p, "idx", p)))
    return tuple(out)


def make_params(conf: dict, seed: int):
    """Every leaf in float32, drawn on the default device in one program."""
    tree = shapes_tree(conf)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(i, int) for i in x))
    specs = [(_path_names(p), shape) for p, shape in leaves]
    fn = jax.jit(functools.partial(_draw, specs=tuple(specs)))
    return jax.tree_util.tree_unflatten(treedef, fn(prng_key(seed)))


def _draw(key, specs):
    keys = jax.random.split(key, len(specs))
    return [jax.random.normal(k, shape, jnp.float32)
            * jnp.float32(_leaf_std(path, shape))
            for k, (path, shape) in zip(keys, specs)]
