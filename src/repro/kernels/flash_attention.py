"""Pallas TPU kernel: online-softmax (flash) attention forward.

Supports the mask/score variants the assigned architectures need: causal,
sliding-window (mixtral / gemma2-local / recurrentgemma-local), logit softcap
(gemma2), GQA (kv-head sharing via the index map — no materialized repeat),
and a kv offset for decode-style queries.

Grid: (B, Hq, nQ, nKV); the last dimension is sequential on TPU, so the
running max / sum / accumulator live in VMEM scratch across kv steps
(the classic flash recurrence).  Block shapes are multiples of the MXU tile
(128) in the model dims; softmax statistics are kept in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, softcap, block_q, block_k, num_kv_blocks,
            kv_offset, seq_kv):
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, D)
    # zero the grid-padding kv rows: uninitialized pad values must not reach
    # the dot products (0 * NaN = NaN would poison whole rows)
    kv_ids = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
    kv_valid = kv_ids < seq_kv
    k = jnp.where(kv_valid, k, 0.0)
    v = jnp.where(kv_valid, v_ref[0, 0].astype(jnp.float32), 0.0)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    iq = pl.program_id(2)
    rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + kv_offset
    cols = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = cols < seq_kv                 # grid padding beyond the kv length
    if causal:
        mask &= cols <= rows
    if window:
        mask &= cols > rows - window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)                            # fully-masked-row guard
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, 0] * alpha + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[:, 0] = m_new
    l_scr[:, 0] = l_new

    @pl.when(jk == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, kv_offset=0, block_q=128, block_k=128,
                    interpret=False):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq, nkv = -(-Sq // bq), -(-Skv // bk)

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, jk: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, jk: (b, h // group, jk, 0))
    o_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, jk: (b, h, iq, 0))

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, num_kv_blocks=nkv, kv_offset=kv_offset,
        seq_kv=Skv)

    return pl.pallas_call(
        kern,
        grid=(B, Hq, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
