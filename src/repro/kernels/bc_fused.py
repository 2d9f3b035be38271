"""Pallas TPU kernel: the paper's whole three-phase pipeline in one kernel.

Phase 1 (FFT), phase 2 (spectral element-wise MAC), phase 3 (IFFT) — the
FPGA time-multiplexes one butterfly block across the phases; the TPU
version keeps the (k × kf) DFT matrices and the spectral weight planes
VMEM-resident and runs all three phases as MXU dots per grid step, so the
intermediate spectra never touch HBM (the paper's on-chip dataflow).

    xb (B, q, k)  --Cr/Ci-->  Xr/Xi (B, q, kf)
    Gauss 3-mult MAC over q against wr/ws1/ws2 (p, q, kf)
    Yr/Yi (B, p, kf)  --Dr/Di-->  y (B, p, k)

Grid: (B/bB, p/bP); weight tiles re-read per batch tile (they are k×
compressed, so the re-read traffic is what the paper's compression already
paid for).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import circulant as cc


def _kernel(x_ref, wr_ref, ws1_ref, ws2_ref, cr_ref, ci_ref, dr_ref, di_ref,
            y_ref):
    bB, q, k = x_ref.shape
    kf = cr_ref.shape[1]
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    x2 = x_ref[...].reshape(bB * q, k)
    xr = dot(x2, cr_ref[...]).reshape(bB, q, kf)          # phase 1: DFT
    xi = dot(x2, ci_ref[...]).reshape(bB, q, kf)
    t1 = jnp.einsum("bqf,pqf->bpf", xr + xi, wr_ref[...],
                    preferred_element_type=jnp.float32)   # phase 2: MAC
    t2 = jnp.einsum("bqf,pqf->bpf", xr, ws1_ref[...],
                    preferred_element_type=jnp.float32)
    t3 = jnp.einsum("bqf,pqf->bpf", xi, ws2_ref[...],
                    preferred_element_type=jnp.float32)
    yr = (t1 - t3).reshape(-1, kf)
    yi = (t1 + t2).reshape(-1, kf)
    y = dot(yr, dr_ref[...]) + dot(yi, di_ref[...])       # phase 3: iDFT
    y_ref[...] = y.reshape(*y_ref.shape).astype(y_ref.dtype)


def bc_fused_matmul(xb: jax.Array, wr, ws1, ws2, *, k: int,
                    block_b: int = 128, block_p: int = 8,
                    interpret: bool = False) -> jax.Array:
    """xb: (B, q, k) blockified input; w planes: (p, q, kf).  -> (B, p, k)."""
    B, q, _ = xb.shape
    p, _, kf = wr.shape
    bB, bP = min(block_b, B), min(block_p, p)
    Cr, Ci, Dr, Di = (jnp.asarray(m) for m in cc.dft_mats(k))
    grid = (-(-B // bB), -(-p // bP))
    x_spec = pl.BlockSpec((bB, q, k), lambda ib, ip: (ib, 0, 0))
    w_spec = pl.BlockSpec((bP, q, kf), lambda ib, ip: (ip, 0, 0))
    c_spec = pl.BlockSpec((k, kf), lambda ib, ip: (0, 0))
    d_spec = pl.BlockSpec((kf, k), lambda ib, ip: (0, 0))
    y_spec = pl.BlockSpec((bB, bP, k), lambda ib, ip: (ib, ip, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[x_spec, w_spec, w_spec, w_spec, c_spec, c_spec, d_spec,
                  d_spec],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct((B, p, k), xb.dtype),
        interpret=interpret,
        name="bc_fused",
    )(xb, wr, ws1, ws2, Cr, Ci, Dr, Di)


def bc_linear_fused_kernel(x: jax.Array, w: jax.Array, n_out: int,
                           interpret: bool = False, block_b: int = 128,
                           block_p: int = 8) -> jax.Array:
    """Drop-in for bc_matmul_spectral using the fused kernel.

    x: (..., n_in); w: (p, q, k) first-row generators.  Call through
    ``kernels.ops.bc_linear_fused`` — the platform dispatch ('tpu'/'off',
    'interpret' on request) lives there, like the other kernels; direct
    callers must pass ``interpret`` explicitly (compiled Pallas is the
    default, matching a real TPU target)."""
    p, q, k = w.shape
    lead = x.shape[:-1]
    xb = cc._blockify(x, q, k).reshape(-1, q, k).astype(jnp.float32)
    cache = cc.spectral_cache(w)
    y = bc_fused_matmul(xb, cache["wr"], cache["ws1"], cache["ws2"], k=k,
                        block_b=block_b, block_p=block_p, interpret=interpret)
    y = y.reshape(*lead, p * k)[..., :n_out]
    return y.astype(x.dtype)
