"""Operations and bytes that the served model's mathematics needs, counted
from a configuration's shapes alone.  The count is the same whatever
lowering runs: a program that computes more (padded prompt positions,
logits of every prompt position, DFTs as matmuls) does not raise it.

* A block-circulant projection of ``n_in -> n_out`` at block size ``k``
  takes, per token, the paper's decoupled pipeline: ``q = n_in/k`` real
  forward FFTs and ``p = n_out/k`` real inverse FFTs of length ``k``, at
  2.5 k log2 k operations each (half of the 5 k log2 k of a complex
  radix-2 FFT), and ``p*q*(k/2+1)`` complex multiply-adds of 8 real
  operations each.
* Attention of one query at context ``c`` (the positions it attends, its
  own included): ``2*Hq*D*c`` for the scores and as many for the values.
* The tied LM head: ``2*d*V`` per token whose logits are needed: each
  decode token, and only the last position of a prompt.
* Elementwise work (norms, RoPE, activations, residuals) is not counted.
"""
from __future__ import annotations

import dataclasses
import math


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


def fft_flops(k: int) -> float:
    """One real FFT (or inverse) of length k."""
    return 2.5 * k * math.log2(k)


def projection_flops(n_in: int, n_out: int, k: int) -> float:
    """Per token: dense 2*n_in*n_out when k is 0, else the circulant
    pipeline of the module docstring."""
    if not k:
        return 2.0 * n_in * n_out
    p, q = -(-n_out // k), -(-n_in // k)
    return (p + q) * fft_flops(k) + 8.0 * p * q * (k // 2 + 1)


def layer_projection_flops(s: Shapes) -> float:
    """q, k, v, o and the gated MLP of one layer, per token."""
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    attn = (projection_flops(s.d_model, qd, s.block_attn)
            + 2 * projection_flops(s.d_model, kvd, s.block_attn)
            + projection_flops(qd, s.d_model, s.block_attn))
    mlp = (2 * projection_flops(s.d_model, s.d_ff, s.block_ffn)
           + projection_flops(s.d_ff, s.d_model, s.block_ffn))
    return attn + mlp


def attention_flops(s: Shapes, context: int) -> float:
    """One query over ``context`` positions, one layer."""
    return 4.0 * s.heads * s.head_dim * context


def head_flops(s: Shapes) -> float:
    return 2.0 * s.d_model * s.vocab


def prefill_flops(s: Shapes, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens through every layer, causal
    attention, and the head at its last position only."""
    n = prompt_len
    per_layer = (n * layer_projection_flops(s)
                 + 4.0 * s.heads * s.head_dim * n * (n + 1) / 2)
    return s.layers * per_layer + head_flops(s)


def decode_flops(s: Shapes, context: int) -> float:
    """One decode token whose query attends ``context`` positions."""
    return (s.layers * (layer_projection_flops(s) + attention_flops(s, context))
            + head_flops(s))


def kv_read_bytes(s: Shapes, context: int) -> float:
    """K and V of ``context`` positions, every layer: what the paged
    attention kernel has to read for one decode token."""
    return 2.0 * s.layers * context * s.kv_heads * s.head_dim * s.kv_bytes


def kernel_flops(s: Shapes, context: int) -> float:
    """The paged attention kernel's share of one decode token: every
    layer's scores and value sums."""
    return s.layers * attention_flops(s, context)
