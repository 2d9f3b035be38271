"""Pallas TPU kernel: paged KV gather (block table -> contiguous KV view).

The continuous-batching engine stores KV state as fixed-size pages in a
shared pool (``serve/kvcache.py``); decode needs each slot's pages laid out
contiguously for attention.  On TPU the block table rides scalar prefetch
(``PrefetchScalarGridSpec``), so the page id is known before the grid step
runs and the pool page is DMA'd straight into the output block — one page
per grid step, no gather materialization in HBM beyond the output itself.

This mirrors the paper's hierarchical control: the block table is the
"control plane" (tiny, scalar memory), the pool is the "data plane"
(weights-sized, streamed) — the same split the FPGA controller uses between
its instruction BRAM and the data buffers.

Call through ``kernels.ops.paged_gather`` — the platform dispatch
('tpu'/'off', 'interpret' on request) lives there; 'off' lowers the same
gather as plain XLA ``pool[table]`` indexing (see ops).

LEGACY / ORACLE PATH: the decode hot loop now streams pages through the
fused paged flash-decode (``kernels/paged_attention.py``) and never forms
this gathered view; the gather survives as the parity oracle
(``ContinuousEngine(paged_attn="gather")``, ``tests/test_paged_attention``)
and for tooling that genuinely needs a contiguous KV copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(table_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...].reshape(out_ref.shape)


def paged_gather_kernel(pool: jax.Array, table: jax.Array, layer,
                        interpret: bool = False) -> jax.Array:
    """pool: the stacked (n, P, page, H, D) leaf; table: (B, maxp) int32
    page ids; layer: int32 scalar stack index.

    Returns (B, maxp * page, H, D): slot b's pages of that layer
    concatenated in table order (position ``i`` of slot b lives at page
    ``table[b, i // page]``, offset ``i % page``).  The layer rides inside
    the prefetched table (entries ``layer * P + page``, split back by the
    index map), as in the paged attention kernel.
    """
    _, P, page, H, D = pool.shape
    B, maxp = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((pl.squeezed, 1, page, H, D),
                         lambda b, p, tref: (tref[b, p] // P,
                                             tref[b, p] % P, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, page, H, D),
                               lambda b, p, tref: (b, p, 0, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, maxp, page, H, D), pool.dtype),
        interpret=interpret,
        name="paged_gather",
    )(layer * P + table, pool)
    return out.reshape(B, maxp * page, H, D)
