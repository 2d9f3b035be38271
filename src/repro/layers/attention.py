"""Attention: GQA/MQA/MHA with RoPE / learned positions, qk-norm, QKV bias,
logit softcap, sliding windows, cross-attention, and KV caches.

Two lowerings of the same math:
  * 'chunked' — pure-XLA two-level online-softmax: a static python loop over
    query chunks, each running a `lax.scan` over exactly the KV chunks its
    causal/window extent needs (no wasted FLOPs on fully-masked blocks, no
    S×S materialization; differentiable for training).
  * 'kernel'  — the Pallas flash kernel (kernels/flash_attention.py).

All models route through `attend()`; projections route through the paper's
`apply_linear`, so block-circulant compression applies to q/k/v/o uniformly.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.circulant import LinearSpec, apply_linear, init_linear
from ..dist.ctx import shard_heads
from ..kernels import ops as kops
from . import norms

_NEG = -1e30


# ---------------------------------------------------------------------------
# Core chunked online-softmax attention
# ---------------------------------------------------------------------------
def _mask(rows, cols, causal: bool, window: int):
    m = jnp.ones(jnp.broadcast_shapes(rows.shape, cols.shape), jnp.bool_)
    if causal:
        m &= cols <= rows
    if window:
        m &= cols > rows - window
    m &= cols >= 0                    # ring-buffer slots not yet written
    return m


def chunked_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                      scale=None, q_pos0=0, kv_positions=None,
                      q_chunk=1024, kv_chunk=1024):
    """q: (B, Sq, Hq, D);  k/v: (B, Skv, Hkv, D)  ->  (B, Sq, Hq, D).

    ``q_pos0``: absolute position of q[:,0] (decode: cache length).  May be
    a per-row ``(B,)`` array (paged decode: every slot sits at its own
    position); the masks then broadcast per row.
    ``kv_positions``: explicit kv absolute positions (ring buffers); default
    is contiguous `arange(Skv)`.  May be ``(B, Skv)`` (paged decode).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    cq = min(q_chunk, Sq)
    ck = min(kv_chunk, Skv)
    nq = -(-Sq // cq)

    qh = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 3, 1, 4)   # (B,Hkv,G,Sq,D)
    kh = k.transpose(0, 2, 1, 3)                                 # (B,Hkv,Skv,D)
    vh = v.transpose(0, 2, 1, 3)

    outs = []
    for iq in range(nq):
        q_blk = qh[:, :, :, iq * cq:(iq + 1) * cq].astype(jnp.float32) * scale
        if getattr(q_pos0, "ndim", 0) == 1:               # per-row positions
            rows = q_pos0[:, None] + iq * cq + jnp.arange(q_blk.shape[3])
        else:
            rows = q_pos0 + iq * cq + jnp.arange(q_blk.shape[3])

        # static kv extent for this q chunk (contiguous-position case only)
        if kv_positions is None and causal and not isinstance(q_pos0, jax.Array):
            hi = min(Skv, q_pos0 + (iq + 1) * cq)
        else:
            hi = Skv
        if (kv_positions is None and window
                and not isinstance(q_pos0, jax.Array)):
            lo = max(0, (q_pos0 + iq * cq - window + 1) // ck * ck)
        else:
            lo = 0
        nkv = -(-(hi - lo) // ck)
        pad = nkv * ck - (hi - lo)
        k_blk = jax.lax.slice_in_dim(kh, lo, hi, axis=2)
        v_blk = jax.lax.slice_in_dim(vh, lo, hi, axis=2)
        if pad:
            k_blk = jnp.pad(k_blk, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v_blk = jnp.pad(v_blk, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if kv_positions is None:
            kpos = lo + jnp.arange(nkv * ck)
        elif kv_positions.ndim == 2:                      # (B, Skv) per-row
            kpos = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                           constant_values=-1)
        else:
            kpos = jnp.pad(kv_positions, (0, pad), constant_values=-1)
        kpos = jnp.where(jnp.arange(nkv * ck) < (hi - lo), kpos, -1)

        # (nkv, B, Hkv, ck, D) stacked chunks for the scan
        ks = k_blk.reshape(B, Hkv, nkv, ck, D).transpose(2, 0, 1, 3, 4)
        vs = v_blk.reshape(B, Hkv, nkv, ck, D).transpose(2, 0, 1, 3, 4)
        if kpos.ndim == 2:
            kps = kpos.reshape(B, nkv, ck).transpose(1, 0, 2)
        else:
            kps = kpos.reshape(nkv, ck)

        m0 = jnp.full((B, Hkv, G, q_blk.shape[3]), _NEG, jnp.float32)
        l0 = jnp.zeros_like(m0)
        a0 = jnp.zeros((*m0.shape, D), jnp.float32)

        def body(carry, xs):
            m_p, l_p, acc = carry
            kc, vc, kp = xs
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk,
                           kc.astype(jnp.float32))
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            if rows.ndim == 2 or kp.ndim == 2:            # per-row masking
                r = rows if rows.ndim == 2 else rows[None, :]
                c = kp if kp.ndim == 2 else kp[None, :]
                msk = _mask(r[:, None, None, :, None],
                            c[:, None, None, None, :], causal, window)
            else:
                msk = _mask(rows[:, None], kp[None, :], causal, window)
            s = jnp.where(msk, s, _NEG)
            m_n = jnp.maximum(m_p, s.max(-1))
            p = jnp.exp(s - m_n[..., None])
            p = jnp.where(msk, p, 0.0)
            alpha = jnp.exp(m_p - m_n)
            l_n = l_p * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32))
            return (m_n, l_n, acc), None

        (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kps))
        o = acc / jnp.maximum(l_f, 1e-30)[..., None]
        outs.append(o)

    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D).astype(q.dtype)


def attend(q, k, v, *, impl="chunked", **kw):
    if impl == "kernel":
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        o = kops.flash_attention(qt, kt, vt, causal=kw.get("causal", True),
                                 window=kw.get("window", 0),
                                 softcap=kw.get("softcap", 0.0),
                                 scale=kw.get("scale"),
                                 kv_offset=kw.get("q_pos0", 0))
        return o.transpose(0, 2, 1, 3)
    return chunked_attention(q, k, v, **kw)


# ---------------------------------------------------------------------------
# Attention block: projections + rope + cache plumbing
# ---------------------------------------------------------------------------
def init_attention(key, cfg, d_model: int, comp=None) -> Dict:
    a = cfg.attention
    spec = LinearSpec.from_config(comp, "attn", bias=a.qkv_bias)
    ospec = LinearSpec.from_config(comp, "attn")
    ks = jax.random.split(key, 6)
    p = {
        "q": init_linear(ks[0], d_model, a.num_heads * a.head_dim, spec),
        "k": init_linear(ks[1], d_model, a.num_kv_heads * a.head_dim, spec),
        "v": init_linear(ks[2], d_model, a.num_kv_heads * a.head_dim, spec),
        "o": init_linear(ks[3], a.num_heads * a.head_dim, d_model, ospec),
    }
    if a.qk_norm:
        p["qn"] = norms.init_rmsnorm(a.head_dim)
        p["kn"] = norms.init_rmsnorm(a.head_dim)
    return p


def attention_block(params, x, *, cfg, causal=True, window=0,
                    positions=None, cache=None, cache_pos=None,
                    cross_kv=None, mode="train", impl="chunked",
                    q_chunk=1024, kv_chunk=1024,
                    block_table=None, layer=None,
                    paged_impl="stream") -> Tuple[jax.Array, Optional[Dict]]:
    """Full attention block.  Returns (out, updated_cache).

    cache: {"k": (B, Smax, Hkv, D), "v": ..., "pos": (Smax,) int32} or None.
    cache_pos: scalar absolute position of the first new token (decode).
    cross_kv: precomputed (k, v) from the encoder (cross-attention).

    Paged decode (``block_table`` set): cache is the stacked page POOL
    {"k": (n, P, page, Hkv, D), "v": ...} shared by every slot and every
    layer of the scan, and ``layer`` (int32 scalar) is this block's index
    in it; ``block_table`` (B, maxp) maps slot positions onto pages and
    ``cache_pos`` is per-slot (B,) — position ``i`` of slot ``b`` lives at
    ``(layer, block_table[b, i // page], i % page)``.  The new K/V row is
    written there in place and attention reads the layer's pages out of
    the stacked leaf: the layer's slab is never sliced out, so the pool is
    updated in place and held once.  A slot with ``cache_pos == -1`` is
    idle: its write routes to the reserved trash page 0 and its attention
    is fully masked (output discarded upstream).

    ``paged_impl`` picks the paged attention lowering: "stream" (default)
    runs the fused paged flash-decode (``kernels.ops.paged_attention`` —
    pages stream through online-softmax, no gathered KV view); "gather"
    keeps the legacy ``paged_gather`` + dense-attention path (the parity
    oracle, O(B * maxp * page) traffic and peak memory per token).
    """
    a = cfg.attention
    comp = cfg.compression
    spec = LinearSpec.from_config(comp, "attn", bias=a.qkv_bias)
    ospec = LinearSpec.from_config(comp, "attn")
    B, S, _ = x.shape
    H, Hkv, D = a.num_heads, a.num_kv_heads, a.head_dim

    fuse = (comp is not None and getattr(comp, "fuse_projections", False)
            and spec.kind == "block_circulant" and cross_kv is None)
    if fuse:
        from ..core.circulant import bc_matmul_fused
        # serve: contract against the offline-FFT'd fused planes when the
        # precompute pass baked them (serve/params.py)
        qkv_cache = params.get("qkv_cache") if mode != "train" else None
        q, k, v = bc_matmul_fused(
            x, [params["q"]["wc"], params["k"]["wc"], params["v"]["wc"]],
            [H * D, Hkv * D, Hkv * D], mode, cache=qkv_cache,
            gauss=spec.gauss)
        if "b" in params["q"]:
            q = q + params["q"]["b"].astype(q.dtype)
            k = k + params["k"]["b"].astype(k.dtype)
            v = v + params["v"]["b"].astype(v.dtype)
        q = q.reshape(B, S, H, D)
        k = k.reshape(B, S, Hkv, D)
        v = v.reshape(B, S, Hkv, D)
    else:
        q = apply_linear(params["q"], x, spec, H * D, mode).reshape(B, S, H, D)
        if cross_kv is not None:
            k, v = cross_kv
        else:
            k = apply_linear(params["k"], x, spec, Hkv * D, mode).reshape(
                B, S, Hkv, D)
            v = apply_linear(params["v"], x, spec, Hkv * D, mode).reshape(
                B, S, Hkv, D)

    if "qn" in params:                                   # qwen3 qk-norm
        q = norms.rmsnorm(params["qn"], q)
        k = norms.rmsnorm(params["kn"], k)

    paged = block_table is not None and cache is not None and cross_kv is None
    q_pos0 = 0 if cache_pos is None else cache_pos
    if paged:
        q_pos0 = jnp.maximum(cache_pos, 0)           # -1 marks idle slots
    if positions is None:
        if getattr(q_pos0, "ndim", 0) == 1:          # per-slot (B,) positions
            positions = q_pos0[:, None] + jnp.arange(S)
        else:
            positions = q_pos0 + jnp.arange(S)
            if positions.ndim == 1:
                positions = jnp.broadcast_to(positions, (B, S))
    if not a.learned_pos and cross_kv is None:
        from .embeddings import apply_rope
        q = apply_rope(q, positions, a.rope_theta)
        k = apply_rope(k, positions, a.rope_theta)

    new_cache = None
    kv_positions = None
    streamed = None
    if paged:
        assert S == 1, "paged KV path is decode-only (S == 1)"
        assert not window, "paged KV path serves linear caches only"
        pool_k, pool_v = cache["k"], cache["v"]
        k_sc = v_sc = None
        page = pool_k.shape[2]
        maxp = block_table.shape[1]
        col = jnp.minimum(q_pos0 // page, maxp - 1)
        pid = jnp.where(cache_pos >= 0,
                        block_table[jnp.arange(B), col], 0)   # 0 = trash page
        off = q_pos0 % page
        with jax.named_scope("kv_write"):
            if "k_scale" in cache:
                # int8 pool (repro.quant): per-(page, head) absmax
                # scatter, requantize-on-grow
                from ..quant import codec as qcodec
                pool_k, k_sc = qcodec.page_scatter(
                    pool_k, cache["k_scale"], layer, pid, off, k[:, 0])
                pool_v, v_sc = qcodec.page_scatter(
                    pool_v, cache["v_scale"], layer, pid, off, v[:, 0])
                new_cache = {"k": pool_k, "v": pool_v,
                             "k_scale": k_sc, "v_scale": v_sc}
            else:
                pool_k = pool_k.at[layer, pid, off].set(
                    k[:, 0].astype(pool_k.dtype))
                pool_v = pool_v.at[layer, pid, off].set(
                    v[:, 0].astype(pool_v.dtype))
                new_cache = {"k": pool_k, "v": pool_v}
        if paged_impl == "stream":
            # fused paged flash-decode: pages stream through the online
            # softmax (dequantizing in-register on the int8 lane); the
            # gathered (B, maxp*page, Hkv, D) view is never formed.  Idle
            # slots (cache_pos == -1) come back exactly zero, the same
            # rows the masked gather path produced.
            qd = shard_heads(q[:, 0])
            streamed = shard_heads(kops.paged_attention(
                qd, pool_k, pool_v, block_table, cache_pos, layer,
                softcap=a.logit_softcap, k_scale=k_sc, v_scale=v_sc))[:, None]
        else:
            k = kops.paged_gather(pool_k, block_table, layer)
            v = kops.paged_gather(pool_v, block_table, layer)
            if k_sc is not None:                 # dequantize the gathered
                rep = lambda s: jnp.repeat(     # view: page scales repeat
                    s[layer, block_table], page, axis=1)[..., None]
                k = k.astype(jnp.float32) * rep(k_sc)
                v = v.astype(jnp.float32) * rep(v_sc)
            idx = jnp.arange(k.shape[1])[None, :]
            kv_positions = jnp.where(idx <= cache_pos[:, None], idx, -1)
    elif cache is not None and cross_kv is None:
        with jax.named_scope("kv_write"):
            Smax = cache["k"].shape[1]
            if window and Smax <= window:                # ring buffer (SWA)
                if S == 1:                               # decode: one slot
                    slot = cache_pos % Smax
                    upd = lambda c, new: jax.lax.dynamic_update_slice(
                        c, new.astype(c.dtype), (0, slot, 0, 0))
                    kc, vc = upd(cache["k"], k), upd(cache["v"], v)
                    pos_c = jax.lax.dynamic_update_slice(
                        cache["pos"],
                        positions[0].astype(cache["pos"].dtype), (slot,))
                    new_cache = {"k": kc, "v": vc, "pos": pos_c}
                    k, v, kv_positions = kc, vc, pos_c
                else:                                    # prefill: keep tail
                    assert S >= Smax, "SWA prefill shorter than window"
                    kc = k[:, -Smax:].astype(cache["k"].dtype)
                    vc = v[:, -Smax:].astype(cache["v"].dtype)
                    pos_c = positions[0][-Smax:].astype(cache["pos"].dtype)
                    new_cache = {"k": kc, "v": vc, "pos": pos_c}
            else:                                        # linear cache
                upd = lambda c, new: jax.lax.dynamic_update_slice(
                    c, new.astype(c.dtype), (0, cache_pos, 0, 0))
                kc, vc = upd(cache["k"], k), upd(cache["v"], v)
                pos_c = jax.lax.dynamic_update_slice(
                    cache["pos"], positions[0].astype(cache["pos"].dtype),
                    (cache_pos,))
                new_cache = {"k": kc, "v": vc, "pos": pos_c}
                if S == 1:                               # decode reads cache
                    k, v, kv_positions = kc, vc, pos_c

    if streamed is not None:
        o = streamed
    else:
        o = attend(q, k, v, impl=impl, causal=causal and cross_kv is None,
                   window=window, softcap=a.logit_softcap,
                   q_pos0=q_pos0, kv_positions=kv_positions,
                   q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = apply_linear(params["o"], o.reshape(B, S, H * D), ospec,
                       x.shape[-1], mode)
    return out, new_cache


def init_kv_cache(batch: int, seq: int, cfg, window: int = 0,
                  dtype=jnp.bfloat16) -> Dict:
    a = cfg.attention
    size = min(window, seq) if window else seq
    return {
        "k": jnp.zeros((batch, size, a.num_kv_heads, a.head_dim), dtype),
        "v": jnp.zeros((batch, size, a.num_kv_heads, a.head_dim), dtype),
        "pos": jnp.full((size,), -1, jnp.int32),
    }
