"""Operations and bytes that the served model's mathematics needs, counted
from a configuration's shapes alone by the module of its architecture
(``models/``).  The count is the same whatever lowering runs: a program
that computes more (padded prompt positions, logits of every prompt
position, DFTs as matmuls) does not raise it.  The rules the modules
share:

* A block-circulant projection of ``n_in -> n_out`` at block size ``k``
  takes, per token, the paper's decoupled pipeline: ``q = n_in/k`` real
  forward FFTs and ``p = n_out/k`` real inverse FFTs of length ``k``, at
  2.5 k log2 k operations each (half of the 5 k log2 k of a complex
  radix-2 FFT), and ``p*q*(k/2+1)`` complex multiply-adds of 8 real
  operations each.
* Attention of one query at context ``c`` (the positions it attends, its
  own included): ``2*Hq*D*c`` for the scores and as many for the values.
* The LM head: ``2*d*V`` per token whose logits are needed: each decode
  token, and only the last position of a prompt.
* Elementwise work (norms, RoPE, activations, residuals) is not counted.
"""
from __future__ import annotations

import math

import arch


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


def attention_flops(heads: int, head_dim: int, context: int) -> float:
    """One query over ``context`` positions, one layer."""
    return 4.0 * heads * head_dim * context


def prefill_flops(conf: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens through every layer, causal
    attention, and the head at its last position only."""
    return arch.module(conf).prefill_flops(conf, prompt_len)


def decode_flops(conf: dict, context: int) -> float:
    """One decode token whose query attends ``context`` positions."""
    return arch.module(conf).decode_flops(conf, context)


def kv_read_bytes(conf: dict, context: int) -> float:
    """What the paged attention kernel has to read from the KV pool for
    one decode token whose query attends ``context`` positions."""
    return arch.module(conf).kv_read_bytes(conf, context)


def kernel_flops(conf: dict, context: int) -> float:
    """The paged attention kernel's share of one decode token."""
    return arch.module(conf).kernel_flops(conf, context)
