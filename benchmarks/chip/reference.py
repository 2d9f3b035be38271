"""Plain float32 reference of the served decoder, for the check that
decides ``correct``.  It imports nothing of the program: it reads the
weights that ``weights.py`` made (layout in that module's docstring) and
computes, for whole token sequences, the forward pass of a Qwen-style
decoder as published, with the projections' block-circulant weights
materialized densely, one layer at a time:

    x = E[tokens]
    per layer:  h = x + Wo . attn(rope(qn(Wq n1(x) + bq)), rope(kn(Wk n1(x) + bk)), Wv n1(x) + bv)
                x = h + Wdown (silu(Wgate n2(h)) * Wup n2(h))
    logits = nf(x) . E^T

with RMSNorm (eps from the configuration, weight ``1 + scale``), rotary
embeddings on the two halves of each head (theta from the configuration),
causal grouped-query softmax attention, q/k norms only where the
configuration has ``qk_norm`` and biases only where it has ``qkv_bias``.

Every matmul runs at ``highest`` precision.  ``precision="fp8"`` is the
control: the same pass with each matmul's operands rounded to float8
e4m3 under a per-tensor absmax scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512                      # query rows per attention block


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def dense_circulant(w):
    """(p, q, k) generators -> dense (p*k, q*k) W with y = W x."""
    p, q, k = w.shape
    r = jnp.arange(k)
    idx = (r[:, None] - r[None, :]) % k
    return w[:, :, idx].transpose(0, 2, 1, 3).reshape(p * k, q * k)


def _linear(node, x, n_out, low):
    w = dense_circulant(node["wc"])[:n_out, :x.shape[-1]]
    y = _mm("...i,oi->...o", x, w, low)
    if "b" in node:
        y = y + node["b"]
    return y


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x (B, L, H, D), positions 0..L-1."""
    L, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, low):
    """Causal GQA.  q (B, L, H, D), k/v (B, L, Hkv, D) -> (B, L, H*D)."""
    B, L, H, D = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for s0 in range(0, L, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        n = qb.shape[1]
        sc = _mm("bqhd,bkhd->bhqk", qb, k, low) * D ** -0.5
        rows = s0 + jnp.arange(n)[:, None]
        sc = jnp.where(jnp.arange(L)[None, :] <= rows, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm("bhqk,bkhd->bqhd", p, v, low))
    return jnp.concatenate(outs, axis=1).reshape(B, L, H * D)


def _layer(conf, low, x, lp):
    H, Hkv, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    eps, theta = conf["rms_norm_eps"], float(conf["rope_theta"])
    B, L, d = x.shape
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _linear(a["q"], h, H * D, low).reshape(B, L, H, D)
    k = _linear(a["k"], h, Hkv * D, low).reshape(B, L, Hkv, D)
    v = _linear(a["v"], h, Hkv * D, low).reshape(B, L, Hkv, D)
    if conf.get("qk_norm"):
        q = _rms(q, a["qn"]["scale"], eps)
        k = _rms(k, a["kn"]["scale"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    x = x + _linear(a["o"], _attention(q, k, v, low), d, low)
    m = lp["mlp"]
    h = _rms(x, lp["ln2"]["scale"], eps)
    f = conf["intermediate_size"]
    up = _linear(m["up"], h, f, low)
    gate = _linear(m["gate"], h, f, low)
    return x + _linear(m["down"], jax.nn.silu(gate) * up, d, low), None


@functools.partial(jax.jit, static_argnames=("conf_items", "precision"))
def _logit_rows(params, tokens, pos, ids, conf_items, precision):
    conf = dict(conf_items)
    low = precision == "fp8"
    table = params["embed"]["table"]
    x = table[tokens]
    layers = params["segments"][0][0]
    x, _ = jax.lax.scan(functools.partial(_layer, conf, low), x, layers)
    rows = jnp.take_along_axis(x, pos[..., None], axis=1)      # (B, N, d)
    rows = _rms(rows, params["final_norm"]["scale"], conf["rms_norm_eps"])
    logits = _mm("bnd,vd->bnv", rows, table, low)
    at = jnp.take_along_axis(logits, ids, axis=-1)               # (B, N, m)
    return logits.max(-1), jnp.argmax(logits, -1).astype(jnp.int32), at


def _freeze(conf: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "intermediate_size", "qk_norm",
            "qkv_bias")
    return tuple((k, conf.get(k)) for k in keys)


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
                       conf_items=_freeze(conf), precision=precision)
