"""Fused paged flash-decode attention: stream pool pages through the
online-softmax recurrence instead of materializing the gathered KV view.

The PR 3 paged decode path paid O(max_seq) HBM traffic *twice* per emitted
token: ``paged_gather`` wrote a dense ``(B, maxp * page, Hkv, D)`` copy of
every live slot's whole KV history, then dense attention read it back.  The
paper's hardware chapter wins by never letting the hot loop touch more
memory than it must ("effective reconfiguration, batch processing, deep
pipelining, resource re-using"); this kernel applies the same discipline to
paged decode: each slot's pages stream one at a time through the classic
flash m/l/acc carry, so the gathered view is never formed — per-token
attention traffic drops to one read of the live positions with an O(page)
working set.

Masking reproduces the gather path exactly: a kv position ``i`` of slot
``b`` is valid iff ``i <= positions[b]`` — that single predicate covers
trash-page-0 reads (unowned table entries only appear beyond the length),
the partially-filled last page, and idle slots (``positions == -1`` masks
everything, so the output is exactly zero, as the gather path produced).

Both lowerings read the pool where the serving stack keeps it: the whole
stacked ``(n, P, page, Hkv, D)`` leaf of the layer scan, with the layer
as an argument.  A decode step writes its rows into that leaf in place
and reads its layer's pages out of it at ``(layer, page)``; no layer's
``(P, page, Hkv, D)`` slab is ever sliced out, so the pool is never
copied and the decode program holds it once.

Two lowerings, dispatched by ``kernels.ops.paged_attention``:

* ``paged_attention_stream`` — pure XLA: a live-length-bounded
  ``lax.while_loop`` over page-sized KV chunks (one tiny per-chunk gather
  each step; serving-only — a while loop is not reverse-differentiable).
  Same memory win under XLA alone; this is what every non-TPU backend
  (the CPU, and the 512-chip dry-run) lowers.
* ``paged_attention_kernel`` — Pallas: the block table (its entries
  ``layer * P + page``) and per-slot positions ride scalar prefetch
  (``PrefetchScalarGridSpec``), so each grid step DMAs exactly one page
  of one layer from the pool in HBM straight into VMEM next to the
  running softmax state — the paper's hierarchical-control split with the
  data plane never leaving on-chip memory.  It takes ``(table, positions,
  q, pool K, pool V[, K scales, V scales])``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# Pages streamed per 'off'-scan step (the streamed working set is
# B * BLOCK_PAGES * page positions; serve/kvcache.attention_memory_est
# accounts the same factor in its peak estimate).
BLOCK_PAGES = 4


# ---------------------------------------------------------------------------
# Pure-XLA streamed lowering ('off' dispatch)
# ---------------------------------------------------------------------------
def paged_attention_stream(q, pool_k, pool_v, table, positions, layer, *,
                           scale=None, softcap: float = 0.0,
                           block_pages: int = BLOCK_PAGES,
                           k_scale=None, v_scale=None) -> jax.Array:
    """q: (B, Hq, D); pool: the stacked (n, P, page, Hkv, D) leaf; table:
    (B, maxp) int32 page ids; positions: (B,) int32 per-slot absolute
    position of the decode token (-1 = idle slot, fully masked); layer:
    int32 scalar, the stack index whose pages are read (pages are gathered
    at ``(layer, page)``: the layer's slab is never sliced out).  Returns
    (B, Hq, D) in q.dtype.

    ``k_scale``/``v_scale`` (both (n, P, Hkv) f32, or both None) enable the
    quantized lane: the pool leaves are int8 and each streamed page chunk
    is dequantized IN-REGISTER right next to the m/l/acc carry — HBM
    traffic stays int8 bytes, the softmax recurrence stays f32.

    The streaming loop is a ``lax.while_loop`` bounded by the LIVE page
    count (``max(positions) + 1`` over the batch), not the table width: a
    fully-masked page updates nothing (p == 0 everywhere, m/l/acc carry
    through bit-exact), so skipping the reservation tail beyond the longest
    live slot changes no result — per-token traffic is O(seq_len), not
    O(max_seq).  ``block_pages`` pages stream per step: enough MXU/AVX work
    per iteration to amortize loop overhead, still an O(page) working set.
    """
    _, _, page, Hkv, D = pool_k.shape
    B, maxp = table.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qh = q.reshape(B, Hkv, G, D).astype(jnp.float32) * scale

    bp = min(block_pages, maxp)
    n_blocks = -(-maxp // bp)                    # static bound
    if maxp % bp:                                # pad tables to block width
        table = jnp.pad(table, ((0, 0), (0, n_blocks * bp - maxp)))
    # live extent: blocks holding any position <= max(positions)
    n_live = jnp.maximum(jnp.max(positions), -1) + 1
    live_blocks = jnp.minimum((n_live + bp * page - 1) // (bp * page),
                              n_blocks)

    m0 = jnp.full((B, Hkv, G), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, D), jnp.float32)

    def body(st):
        j, m_p, l_p, acc = st
        pids = jax.lax.dynamic_slice_in_dim(table, j * bp, bp, 1)  # (B, bp)
        kc = pool_k[layer, pids].astype(jnp.float32)  # (B,bp,page,Hkv,D)
        vc = pool_v[layer, pids].astype(jnp.float32)
        if k_scale is not None:                  # int8 lane: dequantize the
            kc = kc * k_scale[layer, pids][:, :, None, :, None]  # in-register
            vc = vc * v_scale[layer, pids][:, :, None, :, None]
        kc = kc.reshape(B, bp * page, Hkv, D)
        vc = vc.reshape(B, bp * page, Hkv, D)
        s = jnp.einsum("bhgd,bkhd->bhgk", qh, kc)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        cols = j * bp * page + jnp.arange(bp * page)
        msk = (cols[None, :] <= positions[:, None])[:, None, None, :]
        s = jnp.where(msk, s, _NEG)
        m_n = jnp.maximum(m_p, s.max(-1))
        p = jnp.exp(s - m_n[..., None])
        p = jnp.where(msk, p, 0.0)               # fully-masked-page guard
        alpha = jnp.exp(m_p - m_n)
        l_n = l_p * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhgk,bkhd->bhgd", p, vc)
        return (j + 1, m_n, l_n, acc)

    _, _, l_f, acc = jax.lax.while_loop(
        lambda st: st[0] < live_blocks, body,
        (jnp.int32(0), m0, l0, a0))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return out.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel (compiled on TPU; interpret mode when a caller asks)
# ---------------------------------------------------------------------------
def _pa_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, *refs,
               scale, softcap, page, maxp, hkv, quantized):
    if quantized:                                # int8 lane: (1, 1, Hkv)
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs   # page scales
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    jp = pl.program_id(1)                        # sequential page dim

    @pl.when(jp == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Pages past the slot's live extent are fully masked and contribute
    # nothing to the carry — skip their softmax update entirely (the grid
    # is static at maxp; their index map repeats the last live page, so
    # the pipeline issues no DMA for them either).
    @pl.when(jp * page <= pos_ref[b])
    def _update():
        # One grid step holds one page with ALL its KV heads.  Rows are the
        # Hq query heads, columns the page's (position, kv head) pairs in
        # pool order, c = position * Hkv + head: both matmuls stay 2-D and
        # tile-aligned, and a query head only keeps the columns of its own
        # kv head (the other heads' columns are masked like dead positions).
        q = q_ref[0].astype(jnp.float32) * scale                # (Hq, D)
        k = k_ref[0].astype(jnp.float32)                        # (page,Hkv,D)
        v = v_ref[0].astype(jnp.float32)
        k = k.reshape(page * hkv, k.shape[-1])
        v = v.reshape(page * hkv, v.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        group = s.shape[0] // hkv                # query heads per kv head
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = rows // group                     # kv head of each query row
        if quantized:
            # a kept column's kv head IS the row's head, so the per-(page,
            # head) scale is a per-row factor: dequantize the scores and the
            # value contraction instead of the int8 page
            hshape = (s.shape[0], hkv)
            onehot = (jax.lax.broadcasted_iota(jnp.int32, hshape, 0) // group
                      == jax.lax.broadcasted_iota(jnp.int32, hshape, 1))
            k_row = jnp.sum(jnp.where(onehot, ks_ref[0], 0.0), axis=1,
                            keepdims=True)               # (Hq, 1)
            v_row = jnp.sum(jnp.where(onehot, vs_ref[0], 0.0), axis=1,
                            keepdims=True)
            s = s * k_row
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = ((cols % hkv == head)
                & (jp * page + cols // hkv <= pos_ref[b]))   # pos -1: none
        s = jnp.where(mask, s, _NEG)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)  # (Hq, D)
        if quantized:
            pv = pv * v_row
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(jp == maxp - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_kernel(q, pool_k, pool_v, table, positions, layer, *,
                           scale=None, softcap: float = 0.0,
                           interpret: bool = False,
                           k_scale=None, v_scale=None) -> jax.Array:
    """Same contract as ``paged_attention_stream``; grid (B, maxp) with the
    page dim sequential, block table + positions scalar-prefetched so the
    page id is known before each step's pool DMA issues.

    The kernel takes the whole stacked pool leaf, which stays in HBM and is
    read in place.  The layer rides inside the prefetched table: its
    entries are ``layer * P + page``, and the index map splits each back
    into ``(layer, page)``, so the operands stay ``(table, positions, q,
    pool K, pool V[, K scales, V scales])``.  Each step moves one whole
    page, ``(page, Hkv, D)`` of one layer: its last two dims are the
    pool's own, which is what the TPU's block-shape rule asks for in every
    dtype.  With ``k_scale``/``v_scale`` ((n, P, Hkv) f32) the pool is
    int8: the page DMA moves int8 bytes, and the page's ``(1, Hkv)`` scale
    row rides along and dequantizes in VMEM."""
    _, P, page, Hkv, D = pool_k.shape
    B, maxp = table.shape
    Hq = q.shape[1]
    scale = scale if scale is not None else D ** -0.5
    quantized = k_scale is not None
    entries = layer * P + table                  # (layer, page) in one int

    def page_of(b, jp, tref, pref):
        # dead steps repeat the slot's last live page (no new DMA)
        last = jnp.maximum(pref[b], 0) // page
        e = tref[b, jnp.minimum(jp, last)]
        return e // P, e % P

    pool_spec = pl.BlockSpec(
        (pl.squeezed, 1, page, Hkv, D),
        lambda b, jp, tref, pref: (*page_of(b, jp, tref, pref), 0, 0, 0))
    row_spec = pl.BlockSpec((1, Hq, D),
                            lambda b, jp, tref, pref: (b, 0, 0))
    in_specs = [row_spec, pool_spec, pool_spec]
    operands = [q, pool_k, pool_v]
    if quantized:
        scale_spec = pl.BlockSpec(
            (pl.squeezed, 1, 1, Hkv),
            lambda b, jp, tref, pref: (*page_of(b, jp, tref, pref), 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale[:, :, None, :], v_scale[:, :, None, :]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # (table, positions)
        grid=(B, maxp),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),    # running max
            pltpu.VMEM((Hq, 1), jnp.float32),    # running sum
            pltpu.VMEM((Hq, D), jnp.float32),    # output accumulator
        ],
    )
    kern = functools.partial(_pa_kernel, scale=scale, softcap=softcap,
                             page=page, maxp=maxp, hkv=Hkv,
                             quantized=quantized)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(entries, positions, *operands)
