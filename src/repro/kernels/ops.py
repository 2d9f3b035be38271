"""jit'd public wrappers around the Pallas kernels with XLA lowerings.

Each wrapper picks its lowering from the platform (``kernel_mode``):
  'tpu'       — compiled Pallas, on a TPU backend.
  'off'       — the pure-XLA lowering, on every other backend (the CPU, and
                the 512-device dry-run: the einsum/chunked-scan forms lower
                to the same collectives and FLOPs the roofline needs).
  'interpret' — the Pallas kernel bodies in interpret mode; only when a
                caller passes ``mode="interpret"`` (how the CPU tests check
                the TPU kernels against the XLA lowerings).
"""
from __future__ import annotations

import jax

from ..core import circulant as _cc
from . import bc_fused as _bcf
from . import flash_attention as _fa
from . import paged as _paged
from . import paged_attention as _pa
from . import ref as _ref
from . import spectral_matmul as _sm


def kernel_mode() -> str:
    """Compiled Pallas on a TPU backend, the XLA lowering elsewhere."""
    return "tpu" if jax.default_backend() == "tpu" else "off"


# ---------------------------------------------------------------------------
def spectral_matmul(xr, xi, wr, ws1, ws2, mode: str | None = None):
    """(F,B,Q) x (F,Q,P) complex contraction via real planes + Gauss trick."""
    mode = mode or kernel_mode()
    if mode == "off":
        wi = ws1 + wr           # recover plain planes for the einsum fallback
        return _ref.spectral_matmul_ref(xr, xi, wr, wi)
    return _sm.spectral_matmul(xr, xi, wr, ws1, ws2,
                               interpret=(mode == "interpret"))


def bc_linear_fused(x, w, n_out: int, mode: str | None = None, **block_kw):
    """Whole three-phase block-circulant linear (DFT -> spectral MAC -> iDFT)
    as one fused kernel; 'off' lowers the same math through the XLA
    cached-spectral path (bit-equal contraction, separate HLO ops)."""
    mode = mode or kernel_mode()
    if mode == "off":
        return _cc.bc_matmul_spectral(x, _cc.spectral_cache(w),
                                      w.shape[-1], n_out)
    return _bcf.bc_linear_fused_kernel(x, w, n_out,
                                       interpret=(mode == "interpret"),
                                       **block_kw)


def paged_gather(pool, table, layer, mode: str | None = None):
    """Gather a slot-contiguous KV view of one layer out of a paged pool.

    pool: the stacked (n, P, page, H, D) leaf; table: (B, maxp) int32 page
    ids; layer: int32 scalar stack index -> (B, maxp * page, H, D).  'off'
    lowers through a plain XLA gather (``pool[layer, table]``); kernel
    modes run the scalar-prefetch Pallas gather.
    """
    mode = mode or kernel_mode()
    if mode == "off":
        _, _, page, H, D = pool.shape
        B, maxp = table.shape
        return pool[layer, table].reshape(B, maxp * page, H, D)
    return _paged.paged_gather_kernel(pool, table, layer,
                                      interpret=(mode == "interpret"))


def paged_attention(q, pool_k, pool_v, table, positions, layer, *,
                    scale=None, softcap=0.0, k_scale=None, v_scale=None,
                    mode: str | None = None):
    """Fused paged flash-decode: stream pool pages through online-softmax.

    q: (B, Hq, D) one decode query per slot; pool: the stacked
    (n, P, page, Hkv, D) leaf, read in place at ``(layer, page)``; table:
    (B, maxp) int32 page ids; positions: (B,) int32 per-slot absolute
    position of the decode token (-1 = idle, fully masked; the output row
    is exactly zero); layer: int32 scalar stack index -> (B, Hq, D).

    The gathered ``(B, maxp * page, Hkv, D)`` KV view of the old
    ``paged_gather`` + dense-attention path is never formed: 'off' lowers a
    live-length-bounded ``lax.while_loop`` over page-sized chunks (same
    masking semantics, O(page) working set under pure XLA; serving-only —
    not reverse-differentiable); kernel modes run the scalar-prefetch
    Pallas flash-decode kernel (kernels/paged_attention.py).

    ``k_scale``/``v_scale`` ((n, P, Hkv) f32, both or neither) select the
    QUANTIZED lane: the pool leaves are int8 (repro.quant) and every
    lowering dequantizes page chunks in-register beside the m/l/acc carry
    — attention HBM traffic is measured in int8 bytes.
    """
    mode = mode or kernel_mode()
    if mode == "off":
        return _pa.paged_attention_stream(q, pool_k, pool_v, table,
                                          positions, layer, scale=scale,
                                          softcap=softcap,
                                          k_scale=k_scale, v_scale=v_scale)
    return _pa.paged_attention_kernel(q, pool_k, pool_v, table, positions,
                                      layer, scale=scale, softcap=softcap,
                                      k_scale=k_scale, v_scale=v_scale,
                                      interpret=(mode == "interpret"))


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, kv_offset=0, mode: str | None = None,
                    **block_kw):
    mode = mode or kernel_mode()
    if mode == "off":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale,
                                  kv_offset=kv_offset)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               kv_offset=kv_offset,
                               interpret=(mode == "interpret"), **block_kw)
