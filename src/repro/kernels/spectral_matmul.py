"""Pallas TPU kernel: frequency-domain block-circulant matmul (the paper's
"spectral element-wise MAC" phase, re-cast for the MXU).

Per frequency bin ``f`` the decoupled computation is a dense complex matmul
``Y[f] = X[f] @ W[f]`` with ``X (B, Q)``, ``W (Q, P)`` — the contraction runs
over the *input block index* q.  The FPGA implementation did this with scalar
MAC pipelines; on TPU we batch the bins on the grid and feed each one to the
MXU as real matmuls using Gauss's 3-multiplication complex product:

    t1 = (Xr + Xi) @ Wr          t2 = Xr @ (Wi - Wr)         t3 = Xi @ (Wr + Wi)
    Yr = t1 - t3                 Yi = t1 + t2

The weight-side combinations (Wi-Wr, Wr+Wi) are precomputed offline together
with the weight rfft (paper: weights FFT'd before inference), so runtime cost
is 3 MXU matmuls per bin instead of 4.

VMEM budget per grid step (f32): bB·Q + 3·Q·bP + 2·bB·bP.  With the default
bB=bP=128 and Q ≤ 512 this is < 1.5 MiB — deep pipelining across the grid
(the paper's phase-2 pipeline) is handled by the Pallas double-buffered DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xr_ref, xi_ref, wr_ref, ws1_ref, ws2_ref, yr_ref, yi_ref):
    xr = xr_ref[0]                                   # (bB, Q)
    xi = xi_ref[0]
    wr = wr_ref[0]                                   # (Q, bP)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    t1 = dot(xr + xi, wr)
    t2 = dot(xr, ws1_ref[0])
    t3 = dot(xi, ws2_ref[0])
    yr_ref[0] = (t1 - t3).astype(yr_ref.dtype)
    yi_ref[0] = (t1 + t2).astype(yi_ref.dtype)


def spectral_matmul(xr, xi, wr, ws1, ws2, *, block_b: int = 128,
                    block_p: int = 128, interpret: bool = False):
    """Y = X·W in the frequency domain, real planes.

    xr/xi: (F, B, Q);  wr/ws1/ws2: (F, Q, P)  ->  yr/yi: (F, B, P)
    F = number of retained rfft bins (k//2+1), padded by the caller if needed.
    """
    F, B, Q = xr.shape
    P = wr.shape[-1]
    bB, bP = min(block_b, B), min(block_p, P)
    grid = (F, -(-B // bB), -(-P // bP))
    x_spec = pl.BlockSpec((1, bB, Q), lambda f, ib, jp: (f, ib, 0))
    w_spec = pl.BlockSpec((1, Q, bP), lambda f, ib, jp: (f, 0, jp))
    y_spec = pl.BlockSpec((1, bB, bP), lambda f, ib, jp: (f, ib, jp))
    out_shape = [jax.ShapeDtypeStruct((F, B, P), xr.dtype)] * 2
    yr, yi = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[x_spec, x_spec, w_spec, w_spec, w_spec],
        out_specs=[y_spec, y_spec],
        out_shape=out_shape,
        interpret=interpret,
        name="spectral_matmul",
    )(xr, xi, wr, ws1, ws2)
    return yr, yi
