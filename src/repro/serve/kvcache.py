"""Paged KV-cache pool: fixed-size blocks, per-request block tables, and a
free-list allocator.

The paper's accelerator wins its throughput by keeping the compute units fed
— batch processing + resource re-use under a hierarchical controller.  The
dense serving cache breaks that on the memory side: every request owns a
``(max_seq, Hkv, D)`` slab per layer until the *slowest* request in its
batch finishes.  This module replaces the slab with vLLM-style paging:

* the pool is one stacked ``(n, num_pages, page_size, Hkv, D)`` leaf per
  attention block of the layer pattern (``n`` scan groups, like the dense
  cache it replaces); decode indexes it by ``(layer, page)`` in place —
  the leaf rides the layer scan's carry, each layer writes its row at
  ``(layer, page, offset)`` and its attention reads ``(layer, page)``
  blocks — so no layer's slab is sliced out and the pool is held once,
* a request owns an ordered list of page ids; position ``i`` lives at page
  ``table[i // page_size]``, offset ``i % page_size``,
* pages come from a host-side free list, are RESERVED up front for a
  request's worst case (prompt + budget — admission can never deadlock
  mid-decode), and go back to the free list the moment the request
  retires (EOS / budget), not when its batch drains.

Page id 0 is the TRASH page: never allocated, it absorbs the masked writes
of idle/frozen decode slots (see layers/attention.py paged branch).

Host bookkeeping (``PageAllocator`` / ``BlockTable``) is pure python so the
scheduler invariants are hypothesis-testable without a device; the device
pool is a plain pytree built by ``build_pool``, threaded through the
decode loop's carry and donated at its jit boundary.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models.registry import build_model
from ..quant.codec import QuantPolicy, quantize_page_block

TRASH_PAGE = 0


def pages_for(n_positions: int, page_size: int) -> int:
    """Pages needed to hold ``n_positions`` cache slots."""
    return max(1, -(-int(n_positions) // page_size))


class PageAllocator:
    """LIFO free-list over ``num_pages`` pages; page 0 (trash) is reserved.

    ``alloc`` returns None instead of raising when the pool is exhausted —
    the scheduler treats that as "request stays queued" (or, under
    optimistic admission, as a preemption trigger).  ``fault`` is an
    optional hook (``fault(n) -> bool``; see serve/faults.py): when it
    returns True an alloc is forced to fail as if the pool were empty —
    the chaos suite drives the preemption/stall paths with it.

    ``free`` raises on a double free, on a page the allocator never
    handed out, and on the reserved trash page — all three silently
    corrupt the free list otherwise (a page ends up owned by two slots).

    With a metrics ``registry`` (repro.obs) the allocator keeps the
    ``pool.free_pages`` gauge and the ``pool.pages_alloc`` /
    ``pool.pages_freed`` churn counters current on every alloc/free — the
    over-time view of what ``in_use`` reports point-in-time.
    """

    def __init__(self, num_pages: int, registry=None, fault=None):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is the trash)")
        self.num_pages = int(num_pages)
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._held: set = set()
        self.fault = fault
        self._free_gauge = self._alloc_ctr = self._freed_ctr = None
        if registry is not None:
            self._free_gauge = registry.gauge("pool.free_pages")
            self._free_gauge.set(len(self._free))
            self._alloc_ctr = registry.counter("pool.pages_alloc")
            self._freed_ctr = registry.counter("pool.pages_freed")

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._held)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        if self.fault is not None and self.fault(n):
            return None                    # injected failure: as-if empty
        pages = [self._free.pop() for _ in range(n)]
        self._held.update(pages)
        if self._alloc_ctr is not None:
            self._alloc_ctr.inc(n)
            self._free_gauge.set(len(self._free))
        return pages

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p == TRASH_PAGE:
                raise ValueError("freeing the reserved trash page "
                                 f"{TRASH_PAGE}")
            if p not in self._held:
                if 0 < p < self.num_pages:
                    raise ValueError(f"double free of page {p}")
                raise ValueError(f"foreign page {p} (allocator holds "
                                 f"1..{self.num_pages - 1})")
            self._held.discard(p)
            self._free.append(p)
        if self._freed_ctr is not None:
            self._freed_ctr.inc(len(pages))
            self._free_gauge.set(len(self._free))


class BlockTable:
    """Per-slot page ownership over a shared allocator.

    Rows are dense ``(max_slots, max_pages_per_slot)`` int32 (device-ready);
    unowned entries hold TRASH_PAGE.  ``reserve`` grows a slot's mapping to
    cover ``n_positions`` cache slots (False = pool exhausted, nothing
    changes); ``release`` returns every page of a slot to the free list and
    is IDEMPOTENT (releasing an already-released slot is a no-op — the
    engine's cancel/timeout/preempt paths may race a natural retire).

    ``version`` increments on every mutation that changes the dense table
    (page growth, release) — the engine re-uploads its device copy only
    when the version moved, instead of hand-invalidating a cached array.
    """

    def __init__(self, allocator: PageAllocator, max_slots: int,
                 page_size: int, max_pages_per_slot: int):
        self.allocator = allocator
        self.page_size = int(page_size)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.table = np.full((max_slots, max_pages_per_slot), TRASH_PAGE,
                             np.int32)
        self.owned: List[List[int]] = [[] for _ in range(max_slots)]
        self.version = 0

    def reserve(self, slot: int, n_positions: int) -> bool:
        need = pages_for(n_positions, self.page_size)
        if need > self.max_pages_per_slot:
            raise ValueError(
                f"request needs {need} pages > max_pages_per_slot "
                f"{self.max_pages_per_slot} (raise max_seq/page budget)")
        extra = need - len(self.owned[slot])
        if extra <= 0:
            return True
        pages = self.allocator.alloc(extra)
        if pages is None:
            return False
        start = len(self.owned[slot])
        self.owned[slot].extend(pages)
        self.table[slot, start:start + extra] = pages
        self.version += 1
        return True

    def release(self, slot: int) -> None:
        if not self.owned[slot]:
            return                          # idempotent: already released
        self.allocator.free(self.owned[slot])
        self.owned[slot] = []
        self.table[slot, :] = TRASH_PAGE
        self.version += 1

    def pages(self, slot: int) -> List[int]:
        return list(self.owned[slot])

    def device_table(self) -> jax.Array:
        return jnp.asarray(self.table)

    def utilization(self) -> float:
        usable = self.allocator.num_pages - 1
        return self.allocator.in_use / max(usable, 1)


# ---------------------------------------------------------------------------
# Device pool construction + prefill packing
# ---------------------------------------------------------------------------
def _is_kv_leaf(node: Any) -> bool:
    return isinstance(node, dict) and "k" in node and "v" in node


def servable_reasons(cfg: ArchConfig) -> List[str]:
    """Why a config can NOT be served by the paged continuous engine.

    Paged serving needs per-slot positions and linear KV caches: sliding
    windows (ring buffers), recurrent state (position-free but prefill is
    not right-pad safe), learned positions, and encoder-decoder stacks stay
    on the batch engine.  Empty list = servable.
    """
    from ..models import transformer as tfm
    reasons = []
    if cfg.is_encoder_decoder:
        reasons.append("encoder-decoder (cross-attention cache)")
    if cfg.attention.learned_pos or cfg.max_position:
        reasons.append("learned positions (scalar-position table lookup)")
    kinds = {k for pattern, _ in tfm.segments_for(cfg) for k in pattern}
    bad = kinds - {"attn", "moe"}
    if bad:
        reasons.append(f"block kinds {sorted(bad)} (sliding-window ring "
                       f"buffers / recurrent state)")
    return reasons


def build_pool(cfg: ArchConfig, num_pages: int, page_size: int,
               policy: Optional[QuantPolicy] = None):
    """Paged pool pytree mirroring ``model.init_cache``'s structure.

    Every attention cache leaf ``{"k": (n, B, S, Hkv, D), "v": ..., "pos"}``
    becomes ``{"k": (n, num_pages, page_size, Hkv, D), "v": ...}`` — one
    shared pool per layer, indexed by the same block table at every layer
    (a logical page id is valid for the whole stack); decode reads and
    writes it at ``(layer, page)`` without slicing the stack.  The "pos" leaf is
    dropped: validity is carried by the per-slot position vector.

    The storage dtype is a first-class ``QuantPolicy`` field
    (``policy.kv_dtype``: "f32" default | "bf16" | "int8").  An int8 pool
    additionally carries per-(page, head) absmax scales next to each leaf
    (``{"k", "v", "k_scale", "v_scale"}`` — scales are f32
    ``(n, num_pages, Hkv)``, written by the prefill pack and the decode
    page-scatter, read by the quantized paged-attention lane).
    """
    policy = policy or QuantPolicy()
    if servable_reasons(cfg):
        raise ValueError(f"{cfg.name}: not paged-servable: "
                         f"{'; '.join(servable_reasons(cfg))}")
    dtype = policy.pool_dtype
    struct = jax.eval_shape(
        lambda: build_model(cfg).init_cache(1, page_size, dtype=jnp.float32))

    def transform(node):
        if _is_kv_leaf(node):
            n, _, _, hkv, d = node["k"].shape
            shape = (n, num_pages, page_size, hkv, d)
            out = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            if policy.kv_quantized:
                sshape = (n, num_pages, hkv)
                out["k_scale"] = jnp.zeros(sshape, jnp.float32)
                out["v_scale"] = jnp.zeros(sshape, jnp.float32)
            return out
        if isinstance(node, dict):
            return {k: transform(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(transform(v) for v in node)
        raise ValueError(f"unexpected cache leaf {node!r} in paged pool")

    return transform(struct)


def pack_prefill_cache(pool, dense_cache, pages: jax.Array, page_size: int,
                       true_len=None, with_stats: bool = False):
    """Scatter a B=1 dense prefill cache into a slot's reserved pages.

    ``dense_cache`` leaves are (n, 1, Spad, Hkv, D) with Spad a multiple of
    ``page_size``; ``pages`` is (Spad // page_size,) int32.  Pure function
    (jit with the pool donated); returns the updated pool tree.

    An int8 pool (``k_scale`` present) quantizes each prefill page whole:
    one absmax scale per (page, head) over the page's Spad slice.  With
    ``true_len`` (the unpadded prompt length, traced scalar) the right-pad
    tail is ZEROED before the scale derivation — pad positions hold real
    K/V activations whose magnitude would otherwise inflate the last
    page's scale and with it the quantization error of every real token
    sharing that page (the tail itself stays position-masked on read and
    is overwritten by decode either way).  Unquantized pools ignore
    ``true_len`` (garbage tail values are free when no scale reads them).

    With ``with_stats`` the return becomes ``(pool, clipped, total)`` —
    device scalar counts of page-write values saturating the int8 rail
    (|q| == qmax) and of values written, both restricted to VALID
    (non-pad) positions.  With absmax scaling the block-max element sits
    at the rail by construction, so the clip rate is a saturation-
    pressure signal, not an overflow count (docs/quantization.md); f32
    pools report zeros.
    """
    acc = {"clipped": jnp.float32(0.0), "total": jnp.float32(0.0)}

    def pack(pnode, dnode):
        if _is_kv_leaf(pnode):
            out = {}
            for key in ("k", "v"):
                leaf = dnode[key]                       # (n, 1, Spad, H, D)
                n, _, spad, hkv, d = leaf.shape
                npg = spad // page_size
                vals = leaf.reshape(n, npg, page_size, hkv, d)
                if key + "_scale" in pnode:             # int8 pool
                    valid = None
                    if true_len is not None:
                        valid = (jnp.arange(spad) < true_len).reshape(
                            npg, page_size)
                        vals = jnp.where(
                            valid[None, :, :, None, None], vals, 0.0)
                    qvals, scales = quantize_page_block(vals)
                    if with_stats:
                        sat = jnp.abs(qvals.astype(jnp.int32)) >= 127
                        if valid is not None:
                            mask = valid[None, :, :, None, None]
                            sat = sat & mask
                            nvalid = (jnp.sum(valid).astype(jnp.float32)
                                      * n * hkv * d)
                        else:
                            nvalid = jnp.float32(qvals.size)
                        acc["clipped"] += jnp.sum(sat).astype(jnp.float32)
                        acc["total"] += nvalid
                    out[key] = pnode[key].at[:, pages].set(qvals)
                    out[key + "_scale"] = pnode[
                        key + "_scale"].at[:, pages].set(scales)
                else:
                    vals = vals.astype(pnode[key].dtype)
                    out[key] = pnode[key].at[:, pages].set(vals)
            return out
        if isinstance(pnode, dict):
            return {k: pack(v, dnode[k]) for k, v in pnode.items()}
        if isinstance(pnode, (list, tuple)):
            return type(pnode)(pack(v, d) for v, d in zip(pnode, dnode))
        raise ValueError(f"unexpected pool node {pnode!r}")

    packed = pack(pool, dense_cache)
    if with_stats:
        return packed, acc["clipped"], acc["total"]
    return packed


def pool_bytes(pool) -> int:
    """Total bytes of the device pool (telemetry; includes quantization
    scales when the pool is int8 — works on ShapeDtypeStructs too)."""
    return sum(int(leaf.size) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(pool))


def page_bytes(cfg: ArchConfig, page_size: int,
               policy: Optional[QuantPolicy] = None) -> int:
    """Bytes one page costs across every layer of the stack (scales
    included for int8).  Zero allocation (eval_shape); the equal-KV-memory
    benchmarks use this to size pools of different dtypes to one byte
    budget: ``num_pages = budget // page_bytes(...)``."""
    return pool_bytes(jax.eval_shape(
        lambda: build_pool(cfg, 1, page_size, policy)))


def attention_bytes_per_position(pool) -> Dict[str, int]:
    """Per-position attention byte terms of a pool tree.

    ``per_pos`` — HBM bytes one live cache position costs a decode-step
    attention read (K+V over every layer/group, in the pool's storage
    dtype); ``widest`` — K+V bytes of one position in the widest single
    layer (the unit of a transient gathered/streamed buffer).  Shared by
    the worst-case estimate below and the engine's per-dispatch
    ``attn.bytes_per_token`` histogram (which multiplies ``per_pos`` by
    the LIVE slot lengths instead of the worst case).
    """
    per_pos, widest = 0, 0

    def walk(node):
        nonlocal per_pos, widest
        if _is_kv_leaf(node):
            n = node["k"].shape[0]
            hkv, d = node["k"].shape[-2:]
            item = np.dtype(node["k"].dtype).itemsize
            per_pos += 2 * n * hkv * d * item          # k + v, all groups
            widest = max(widest, 2 * hkv * d * item)
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(pool)
    return {"per_pos": per_pos, "widest": widest}


def pool_scales(pool) -> Optional[np.ndarray]:
    """Flat host copy of every quantization-scale leaf (``k_scale`` /
    ``v_scale``), or None for an unquantized pool.  The engine diffs two
    of these around a decode dispatch to count ``quant.scale_growths``
    (page-scatter requantize-on-grow events — codec.page_scatter scales
    only ever grow in place, so ``new > old`` identifies them); the
    transfer is a few KB and runs only when obs tracing is enabled."""
    leaves = []

    def walk(node):
        if _is_kv_leaf(node):
            for key in ("k_scale", "v_scale"):
                if key in node:
                    leaves.append(np.asarray(node[key]).ravel())
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(pool)
    if not leaves:
        return None
    return np.concatenate(leaves)


def pool_scale_map(pool) -> Optional[Dict[str, np.ndarray]]:
    """Like ``pool_scales`` but split per plane:
    ``{"k_scale": flat, "v_scale": flat}`` host copies (or None for an
    unquantized pool).  The engine's scale-shadow diff uses this to
    attribute requantize-on-grow events and the saturation histograms to
    the K vs V plane separately (``quant.k_scale`` / ``quant.v_scale``,
    docs/observability.md "Numerics & quality health")."""
    leaves: Dict[str, list] = {"k_scale": [], "v_scale": []}

    def walk(node):
        if _is_kv_leaf(node):
            for key in ("k_scale", "v_scale"):
                if key in node:
                    leaves[key].append(np.asarray(node[key]).ravel())
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(pool)
    if not any(leaves.values()):
        return None
    return {k: np.concatenate(v) for k, v in leaves.items() if v}


def attention_memory_est(pool, max_slots: int, max_pages_per_slot: int,
                         page_size: int, impl: str = "stream") -> Dict:
    """Analytic decode-attention memory estimates over a pool tree.

    Worst case (every slot serving a full ``max_pages_per_slot * page_size``
    history), for the telemetry the serving benchmarks record:

    * ``attention_bytes_per_token`` — HBM bytes attention touches to emit
      ONE token for one slot, summed over every attention layer.  The
      streamed flash-decode reads each live position's K+V once; the legacy
      gather path additionally writes and re-reads the dense gathered view
      (3x the traffic).
    * ``peak_attention_bytes`` — the largest transient attention buffer of
      one decode step: gather materializes ``(B, maxp * page, Hkv, D)`` k+v
      views of the widest layer, the streamed path holds one
      ``BLOCK_PAGES``-page chunk per slot (the 'off' scan streams that many
      pages per step — kernels/paged_attention.py).

    Byte terms follow the pool leaf dtype, so an int8 pool's traffic is
    counted in int8 bytes (the per-(page, head) scale reads are < 1% of
    the K/V bytes and excluded).
    """
    from ..kernels.paged_attention import BLOCK_PAGES
    terms = attention_bytes_per_position(pool)
    per_pos, widest = terms["per_pos"], terms["widest"]
    max_len = max_pages_per_slot * page_size
    if impl == "gather":
        return {"attention_bytes_per_token": 3 * per_pos * max_len,
                "peak_attention_bytes": max_slots * max_len * widest}
    chunk = min(BLOCK_PAGES, max_pages_per_slot) * page_size
    return {"attention_bytes_per_token": per_pos * max_len,
            "peak_attention_bytes": max_slots * chunk * widest}
