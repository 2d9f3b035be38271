"""Plain float32 reference of the served models, for the check that
decides ``correct``.  It imports nothing of the program: it reads the
weights that ``weights.py`` made and computes, for whole token sequences,
the forward pass of the configuration's architecture as published
(``logits`` of its module under ``models/``, which holds the layer
equations), one layer at a time.  This file holds the pieces the
modules share: the projections' block-circulant weights materialized
densely, RMSNorm (weight ``1 + scale``), rotary embeddings on the two
halves of each head, and causal grouped-query softmax attention.

Every matmul runs at ``highest`` precision.  ``precision="fp8"`` is the
control: the same pass with each matmul's operands rounded to float8
e4m3 under a per-tensor absmax scale.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

import arch

Q_BLOCK = 512                      # query rows per attention block


def fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec, a, b, low):
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def dense_circulant(w):
    """(p, q, k) generators -> dense (p*k, q*k) W with y = W x."""
    p, q, k = w.shape
    r = jnp.arange(k)
    idx = (r[:, None] - r[None, :]) % k
    return w[:, :, idx].transpose(0, 2, 1, 3).reshape(p * k, q * k)


def linear(node, x, n_out, low):
    """``W x (+ b)`` of a block-circulant node ``{"wc", "b"?}``."""
    w = dense_circulant(node["wc"])[:n_out, :x.shape[-1]]
    y = mm("...i,oi->...o", x, w, low)
    if "b" in node:
        y = y + node["b"]
    return y


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, theta):
    """x (B, L, H, D), positions 0..L-1."""
    L, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, low):
    """Causal GQA.  q (B, L, H, D), k/v (B, L, Hkv, D) -> (B, L, H*D)."""
    B, L, H, D = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for s0 in range(0, L, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        n = qb.shape[1]
        sc = mm("bqhd,bkhd->bhqk", qb, k, low) * D ** -0.5
        rows = s0 + jnp.arange(n)[:, None]
        sc = jnp.where(jnp.arange(L)[None, :] <= rows, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(mm("bhqk,bkhd->bqhd", p, v, low))
    return jnp.concatenate(outs, axis=1).reshape(B, L, H * D)


class _Static:
    """A configuration as a static argument of a jitted function."""

    def __init__(self, conf: dict):
        self.conf = conf
        self._key = json.dumps(conf, sort_keys=True)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Static) and self._key == other._key


@functools.partial(jax.jit, static_argnames=("conf", "precision"))
def _logit_rows(params, tokens, pos, ids, conf, precision):
    c = conf.conf
    logits = arch.module(c).logits(params, c, tokens, pos,
                                   precision == "fp8")
    at = jnp.take_along_axis(logits, ids, axis=-1)               # (B, N, m)
    return logits.max(-1), jnp.argmax(logits, -1).astype(jnp.int32), at


def logit_rows(params, conf: dict, tokens, pos, ids, precision="f32"):
    """For sequences ``tokens`` (B, L) and positions ``pos`` (B, N), the
    reference's logits at those positions, reduced to: the row maximum
    (B, N), the row's argmax (B, N) and the logits of ``ids`` (B, N, m).
    Positions past a sequence's end may hold anything: attention is
    causal, so padding after a sequence changes none of its rows."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision {precision!r}")
    return _logit_rows(params, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(pos, jnp.int32),
                       jnp.asarray(ids, jnp.int32),
                       conf=_Static(conf), precision=precision)
