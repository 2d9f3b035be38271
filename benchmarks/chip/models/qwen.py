"""Qwen2 and Qwen3: a dense decoder with grouped-query attention, a gated
SiLU MLP and an LM head tied to the token embeddings.  Qwen3 normalizes
each query and key head (``qk_norm``), Qwen2 adds biases to the q, k and
v projections (``qkv_bias``).

Weights (``shapes_tree``), in the program's parameter layout:
``segments[0][0]`` holds every layer's leaves stacked on a leading layer
axis.

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

The reference (``logits``), with the projections materialized densely:

    x = E[tokens]
    per layer:  h = x + Wo . attn(rope(qn(Wq n1(x) + bq)), rope(kn(Wk n1(x) + bk)), Wv n1(x) + bv)
                x = h + Wdown (silu(Wgate n2(h)) * Wup n2(h))
    logits = nf(x) . E^T

with RMSNorm (eps from the configuration) and rotary embeddings (theta
from the configuration).

Work (``work.py``'s rules): q, k, v, o and the gated MLP at their block
sizes, attention at each token's context, the tied head once per decode
token and prompt; the kernel reads K and V of every layer.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

import reference as ref
import work

MODEL_TYPES = ("qwen2", "qwen3")
READS = ("hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "vocab_size", "rope_theta", "rms_norm_eps", "hidden_act",
         "attention_bias", "use_sliding_window", "tie_word_embeddings",
         "qk_norm", "qkv_bias", "circulant_block")


def _built(conf: dict) -> None:
    """Refuse what this module does not compute."""
    wrong = {k: conf[k] for k, ok in (
        ("hidden_act", "silu"), ("attention_bias", False),
        ("use_sliding_window", False), ("tie_word_embeddings", True))
        if k in conf and conf[k] != ok}
    if wrong:
        raise ValueError(f"{conf.get('name')!r}: models/qwen.py computes "
                         f"none of {wrong}")


# ---------------------------------------------------------------------------
# The program's configuration
# ---------------------------------------------------------------------------
def program_fields(conf: dict) -> dict:
    _built(conf)
    return {
        "name": conf["name"], "family": "dense",
        "num_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"], "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "attention": {
            "num_heads": conf["num_attention_heads"],
            "num_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["head_dim"],
            "rope_theta": float(conf["rope_theta"]),
            "qk_norm": bool(conf.get("qk_norm")),
            "qkv_bias": bool(conf.get("qkv_bias"))},
        "compression": {
            "enabled": True, "block_attn": conf["circulant_block"]["attn"],
            "block_ffn": conf["circulant_block"]["ffn"],
            "block_embed": conf["circulant_block"]["head"]},
        "tie_embeddings": bool(conf["tie_word_embeddings"]),
    }


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def _blocks(n: int, k: int) -> int:
    return -(-n // k)


def shapes_tree(conf: dict) -> dict:
    _built(conf)
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


def leaf_std(path, shape) -> float:
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


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------
def _layer(conf, low, x, lp):
    H, Hkv, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    eps, theta = conf["rms_norm_eps"], float(conf["rope_theta"])
    B, L, d = x.shape
    a = lp["attn"]
    h = ref.rms(x, lp["ln1"]["scale"], eps)
    q = ref.linear(a["q"], h, H * D, low).reshape(B, L, H, D)
    k = ref.linear(a["k"], h, Hkv * D, low).reshape(B, L, Hkv, D)
    v = ref.linear(a["v"], h, Hkv * D, low).reshape(B, L, Hkv, D)
    if conf.get("qk_norm"):
        q = ref.rms(q, a["qn"]["scale"], eps)
        k = ref.rms(k, a["kn"]["scale"], eps)
    q, k = ref.rope(q, theta), ref.rope(k, theta)
    x = x + ref.linear(a["o"], ref.attention(q, k, v, low), d, low)
    m = lp["mlp"]
    h = ref.rms(x, lp["ln2"]["scale"], eps)
    f = conf["intermediate_size"]
    up = ref.linear(m["up"], h, f, low)
    gate = ref.linear(m["gate"], h, f, low)
    return x + ref.linear(m["down"], jax.nn.silu(gate) * up, d, low), None


def logits(params, conf: dict, tokens, pos, low: bool):
    """(B, N, V) logits at positions ``pos`` (B, N) of ``tokens`` (B, L)."""
    _built(conf)
    table = params["embed"]["table"]
    x = table[tokens]
    layers = params["segments"][0][0]
    x, _ = jax.lax.scan(functools.partial(_layer, conf, low), x, layers)
    rows = jnp.take_along_axis(x, pos[..., None], axis=1)  # (B, N, d)
    rows = ref.rms(rows, params["final_norm"]["scale"], conf["rms_norm_eps"])
    return ref.mm("bnd,vd->bnv", rows, table, low)


# ---------------------------------------------------------------------------
# Work
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    block_attn: int
    block_ffn: int
    kv_bytes: int                  # bytes per stored K or V element

    @staticmethod
    def of(conf: dict) -> "Shapes":
        dtype_bytes = {"float32": 4, "bfloat16": 2, "int8": 1}
        return Shapes(
            layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
            d_ff=conf["intermediate_size"],
            heads=conf["num_attention_heads"],
            kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
            vocab=conf["vocab_size"],
            block_attn=conf["circulant_block"]["attn"],
            block_ffn=conf["circulant_block"]["ffn"],
            kv_bytes=dtype_bytes[conf["served"]["kv_pool_dtype"]])


def layer_projection_flops(s: Shapes) -> float:
    """q, k, v, o and the gated MLP of one layer, per token."""
    p = work.projection_flops
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    attn = (p(s.d_model, qd, s.block_attn)
            + 2 * p(s.d_model, kvd, s.block_attn)
            + p(qd, s.d_model, s.block_attn))
    mlp = (2 * p(s.d_model, s.d_ff, s.block_ffn)
           + p(s.d_ff, s.d_model, s.block_ffn))
    return attn + mlp


def head_flops(s: Shapes) -> float:
    return 2.0 * s.d_model * s.vocab


def prefill_flops(conf: dict, prompt_len: int) -> float:
    s = Shapes.of(conf)
    n = prompt_len
    per_layer = (n * layer_projection_flops(s)
                 + 4.0 * s.heads * s.head_dim * n * (n + 1) / 2)
    return s.layers * per_layer + head_flops(s)


def decode_flops(conf: dict, context: int) -> float:
    s = Shapes.of(conf)
    return (s.layers * (layer_projection_flops(s) + work.attention_flops(
        s.heads, s.head_dim, context)) + head_flops(s))


def kv_read_bytes(conf: dict, context: int) -> float:
    """K and V of ``context`` positions, every layer."""
    s = Shapes.of(conf)
    return 2.0 * s.layers * context * s.kv_heads * s.head_dim * s.kv_bytes


def kernel_flops(conf: dict, context: int) -> float:
    """Every layer's scores and value sums."""
    s = Shapes.of(conf)
    return s.layers * work.attention_flops(s.heads, s.head_dim, context)
