"""Fixed-point inference codec: symmetric absmax int8 (and packed-int4)
quantization for the two serving-state tensors the paper's hardware keeps
in reduced precision.

The AAAI'18 paper's accelerator half earns its energy-efficiency headline
by running the whole FFT->MAC->IFFT datapath in 12-16-bit fixed point on
top of block-circulant compression; CirCNN (arXiv:1708.08917) makes the
same argument for the quantized-spectral datapath.  This module is that
fixed-point layer for the serving stack:

* **Spectral weight planes** — the offline-FFT'd ``wr/wi/ws1/ws2`` planes
  baked by ``serve/params.py`` are quantized per BLOCK ROW (one scale per
  output block ``p``, the granularity one accelerator PE column owns), so
  the serve-mode contraction reads int8 planes and folds the f32 scale
  into the output once per row: ``y[..., p, f] = s[p] * (x . q[p])``.
* **Paged KV pool** — the stacked ``(n, num_pages, page_size, Hkv, D)``
  pool of serve/kvcache.py stores int8 with one scale per (layer, page,
  kv-head).  Pages
  fill incrementally (one decode token at a time), so the page scale is a
  RUNNING absmax: when a new token's magnitude exceeds the page's scale,
  the resident int8 entries are rescaled in-register to the grown scale
  (``page_scatter``) — dequantization then always uses one scale per page
  and the attention kernels read int8 bytes from HBM.

Everything here is pure jnp (jit/vmap/eval_shape-safe) and standalone —
the codec imports nothing from the rest of the package, so kernels,
layers, and core can all depend on it without cycles.

Quantization convention (symmetric absmax):

    scale = absmax / Q           (Q = 127 for int8, 7 for int4)
    q     = clip(round(x / scale), -Q, Q)
    dq    = q * scale            with  |x - dq| <= scale / 2

A scale of exactly 0 encodes an all-zero block; ``quantize`` maps it to
q = 0 and ``dequantize`` back to 0.0 (no division by zero anywhere).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

INT8_QMAX = 127.0
INT4_QMAX = 7.0
_EPS = 1e-30

# Plane names a spectral serving cache may carry (serve/params.py) and the
# suffix their per-block-row scales use.  `wr_s` etc. live NEXT TO the int8
# plane inside the same `*_cache` dict.
PLANE_NAMES = ("wr", "wi", "ws1", "ws2")
SCALE_SUFFIX = "_s"


# ---------------------------------------------------------------------------
# Policy
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """What the serving stack quantizes, threaded through the engine.

    ``kv_dtype`` is the FIRST-CLASS pool storage dtype ("f32" | "bf16" |
    "int8") — `serve/kvcache.build_pool` and `pack_prefill_cache` derive
    everything from it instead of an ad-hoc positional dtype argument.
    ``quant_weights`` switches the precomputed spectral weight planes to
    int8 (or int4-packed with ``weight_bits=4``: two nibbles per byte,
    widened to int8 before the f32-accumulating contraction).
    """
    kv_dtype: str = "f32"
    quant_weights: bool = False
    weight_bits: int = 8

    def __post_init__(self):
        if self.kv_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(f"kv_dtype {self.kv_dtype!r}: "
                             f"expected 'f32', 'bf16' or 'int8'")
        if self.weight_bits not in (8, 4):
            raise ValueError(f"weight_bits {self.weight_bits}: "
                             f"expected 8 or 4")

    @property
    def kv_quantized(self) -> bool:
        return self.kv_dtype == "int8"

    @property
    def pool_dtype(self):
        return {"f32": jnp.float32, "bf16": jnp.bfloat16,
                "int8": jnp.int8}[self.kv_dtype]

    def describe(self) -> Dict:
        """JSON-able form for telemetry (`ContinuousEngine.stats()`)."""
        return {"kv_dtype": self.kv_dtype,
                "quant_weights": bool(self.quant_weights),
                "weight_bits": int(self.weight_bits)}


# ---------------------------------------------------------------------------
# Scalar codec
# ---------------------------------------------------------------------------
def absmax_scale(x: jax.Array, axes, qmax: float = INT8_QMAX) -> jax.Array:
    """Symmetric absmax scale over ``axes`` (reduced away, no keepdims)."""
    return jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes) / qmax


def quantize(x: jax.Array, scale: jax.Array,
             qmax: float = INT8_QMAX) -> jax.Array:
    """clip(round(x / scale)) as int8; ``scale`` broadcasts against ``x``
    and a zero scale quantizes to 0 (the all-zero block encoding)."""
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, _EPS))
    return jnp.clip(q, -qmax, qmax).astype(jnp.int8)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def saturation_counts(q: jax.Array,
                      qmax: float = INT8_QMAX) -> Tuple[jax.Array, int]:
    """``(clipped, total)`` for a quantized array: how many entries sit AT
    the ±qmax rail, out of how many.

    With symmetric absmax scaling nothing ever lands OUTSIDE the rail —
    the block-max element maps to exactly ±qmax by construction — so this
    is a saturation-pressure census, not an overflow count: a rising clip
    rate means more of the distribution is crowding the top code, i.e.
    the block's dynamic range is outgrowing the quantization grid.
    ``clipped`` is a device scalar (jit-safe); ``total`` is the static
    element count, so ``clipped + unclipped == total`` is exact."""
    sat = jnp.abs(q.astype(jnp.float32)) >= float(qmax)
    return jnp.sum(sat).astype(jnp.float32), int(q.size)


# ---------------------------------------------------------------------------
# int4 nibble packing (weights-only stretch mode)
# ---------------------------------------------------------------------------
def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int8 values in [-7, 7] two-per-byte along the last axis.

    Odd lengths are zero-padded; the consumer recovers the true length
    from context (the frequency count ``kf`` for spectral planes).  The
    packed array is uint8 — the dtype is the int4 marker downstream.
    """
    n = q.shape[-1]
    if n % 2:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, 1)])
    lo = q[..., 0::2].astype(jnp.uint8) & 0xF        # two's-complement nibble
    hi = q[..., 1::2].astype(jnp.uint8) & 0xF
    return lo | (hi << 4)


def unpack_int4(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of ``pack_int4``: (..., ceil(n/2)) uint8 -> (..., n) int8."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    lo = ((lo ^ 8) - 8).astype(jnp.int8)             # sign-extend nibble
    hi = ((hi ^ 8) - 8).astype(jnp.int8)
    out = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1],
                                               2 * packed.shape[-1])
    return out[..., :n]


# ---------------------------------------------------------------------------
# Spectral weight planes: per-block-row quantization
# ---------------------------------------------------------------------------
def quantize_plane(w: jax.Array, bits: int = 8
                   ) -> Tuple[jax.Array, jax.Array]:
    """One (..., p, q, kf) spectral plane -> (int plane, (..., p, 1) scale).

    The scale reduces over the input-block and frequency dims — one value
    per OUTPUT block row, shaped (..., p, 1) so it right-broadcasts against
    the (..., p, kf) contraction output when folded post-einsum.
    """
    qmax = INT8_QMAX if bits == 8 else INT4_QMAX
    scale = absmax_scale(w, axes=(-2, -1), qmax=qmax)[..., None]  # (..., p, 1)
    q = quantize(w, scale[..., None], qmax)
    if bits == 4:
        q = pack_int4(q)
    return q, scale.astype(jnp.float32)


def quantize_plane_cache(cache: Dict[str, jax.Array],
                         bits: int = 8) -> Dict[str, jax.Array]:
    """Quantize a spectral serving cache dict ({'wr','wi','ws1','ws2'} ->
    same keys as int8/uint8 planes + ``<name>_s`` per-block-row scales).
    Idempotent: an already-quantized dict passes through unchanged."""
    if any(k + SCALE_SUFFIX in cache for k in PLANE_NAMES):
        return dict(cache)
    out = {}
    for name, w in cache.items():
        if name in PLANE_NAMES:
            out[name], out[name + SCALE_SUFFIX] = quantize_plane(w, bits)
        else:
            out[name] = w
    return out


def plane_from_cache(cache: Dict[str, jax.Array], name: str, kf: int
                     ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Fetch one plane ready to contract: (f32 plane, fold-scale or None).

    int8 planes come back cast to f32 with the (..., p, 1) scale returned
    separately (fold AFTER the contraction — the HBM read stays int8);
    int4-packed (uint8) planes are widened to int8 nibbles first, ``kf``
    recovering the true frequency count.  Unquantized caches return the
    plane as-is with scale None.
    """
    w = cache[name]
    scale = cache.get(name + SCALE_SUFFIX)
    if scale is None:
        return w, None
    if w.dtype == jnp.uint8:
        w = unpack_int4(w, kf)
    return w.astype(jnp.float32), scale


def quantize_serving_params(params, bits: int = 8):
    """Quantize every baked spectral serving cache in a parameter tree.

    Pure transform over the tree `serve/params.precompute_serving_params`
    produced: each ``*_cache`` dict gains int planes + per-block-row
    scales; generators (``wc``), dense weights, and everything else pass
    through untouched (training still differentiates through ``wc``).
    Idempotent, and works under ``jax.eval_shape``... except scale values
    (not shapes) obviously need real weights.
    """
    def walk(node):
        if isinstance(node, dict):
            out = {}
            for key, v in node.items():
                if (key.endswith("_cache") and isinstance(v, dict)
                        and "wr" in v):
                    out[key] = quantize_plane_cache(v, bits)
                else:
                    out[key] = walk(v)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node
    return walk(params)


def plane_clip_report(params) -> Dict[str, int]:
    """Host-side saturation census over every quantized spectral plane in
    a serving parameter tree: ``{"clipped", "total", "planes"}``.

    Weights are static, so this runs ONCE at engine wiring time (not per
    dispatch) and feeds the ``quant.clip.plane_*`` counters.  int4-packed
    (uint8) planes are unpacked to nibbles first and counted against the
    int4 rail; the odd-length zero pad nibble counts as unclipped (a
    <=1-per-row dilution of ``total``, noted so the rate reads exact on
    even frequency counts)."""
    counts = {"clipped": 0, "total": 0, "planes": 0}

    def census(plane):
        if plane.dtype == jnp.uint8:
            q = unpack_int4(plane, 2 * plane.shape[-1])
            qmax = INT4_QMAX
        else:
            q = plane
            qmax = INT8_QMAX
        clipped, total = saturation_counts(q, qmax)
        counts["clipped"] += int(clipped)
        counts["total"] += total
        counts["planes"] += 1

    def walk(node):
        if isinstance(node, dict):
            for key, v in node.items():
                if (key.endswith("_cache") and isinstance(v, dict)
                        and "wr" in v):
                    for name in PLANE_NAMES:
                        if name in v and name + SCALE_SUFFIX in v:
                            census(v[name])
                else:
                    walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    return counts


# ---------------------------------------------------------------------------
# Paged KV pool: per-page-per-head quantization
# ---------------------------------------------------------------------------
def quantize_page_block(vals: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Whole-page quantization for the prefill pack path.

    vals: (..., page, H, D) float -> (int8 same shape, (..., H) scales).
    One scale per (page, head): the reduction spans the in-page offset and
    head_dim axes, never the head axis — heads differ in magnitude by
    design (RoPE'd keys vs values), pages differ over time.
    """
    scale = absmax_scale(vals, axes=(-3, -1))                  # (..., H)
    q = quantize(vals, scale[..., None, :, None])
    return q, scale.astype(jnp.float32)


def page_scatter(pool_q: jax.Array, scales: jax.Array, layer,
                 pid: jax.Array, off: jax.Array, x: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Decode-path write of one token per slot into an int8 page pool.

    pool_q: the stacked (n, P, page, H, D) int8 leaf;  scales: (n, P, H)
    f32;  layer: int32 scalar stack index;  pid/off: (B,) int32 page id /
    in-page offset per slot;  x: (B, H, D) new K or V rows.  Writes land
    at ``(layer, pid, off)`` of the stacked leaves in place.  Returns the
    updated (pool_q, scales).

    Per-page scales must stay valid for values ALREADY in the page, so the
    scale only ever grows: ``s_new = max(s_old, absmax(x)/127)`` per head,
    and when it grows the page's resident int8 entries are requantized to
    the new scale in-register (one extra half-step of rounding error per
    grow event, bounded by page_size growths — see docs/quantization.md).
    The requantizing read-modify-write of the whole page runs only UNDER
    the grow predicate (``lax.cond``): in the steady state — page absmax
    settled, no slot grew this step — the write is the same single-row
    scatter the unquantized pool pays, so int8 decode write traffic stays
    O(token), not O(page).  Idle slots carry pid == 0 (the trash page);
    duplicate trash writes are unordered but trash content and trash
    scale are never read unmasked.

    A write at offset 0 is a slot's first write into a page it was just
    given (decode advances one position at a time), so the page's old
    scale and resident values belong to a previous owner: they are taken
    as zero, and a recycled page quantizes exactly like a fresh one.

    Because scales only GROW while a page has one owner, the serving
    telemetry can count grow events without threading a counter through
    the jit'd loop: the continuous engine diffs host shadows of the scale
    leaves around decode dispatches into the ``quant.scale_growths``
    counter (docs/observability.md).
    """
    page = pool_q.shape[2]
    s_old = jnp.where((off == 0)[:, None], 0.0,
                      scales[layer, pid])                      # (B, H)
    s_new = jnp.maximum(s_old, absmax_scale(x, axes=-1))       # (B, H)

    def requant(carry):
        pq, sc = carry
        ratio = s_old / jnp.maximum(s_new, _EPS)               # <= 1
        resident = pq[layer, pid]                              # (B,page,H,D)
        resident = jnp.round(resident.astype(jnp.float32)
                             * ratio[:, None, :, None]).astype(jnp.int8)
        tok = quantize(x, s_new[..., None])                    # (B, H, D)
        hit = (jnp.arange(page)[None, :] == off[:, None])      # (B, page)
        resident = jnp.where(hit[..., None, None], tok[:, None], resident)
        return (pq.at[layer, pid].set(resident),
                sc.at[layer, pid].set(s_new))

    def fast(carry):
        pq, sc = carry                                         # s_new == s_old
        return (pq.at[layer, pid, off].set(quantize(x, s_new[..., None])),
                sc.at[layer, pid].set(s_new))

    return jax.lax.cond(jnp.any(s_new > s_old), requant, fast,
                        (pool_q, scales))
