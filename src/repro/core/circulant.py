"""Block-circulant linear algebra — the paper's core contribution, in JAX.

A weight matrix ``W ∈ R^{m×n}`` is represented by first-row/column vectors
``w ∈ R^{p×q×k}`` (``p=m/k``, ``q=n/k``): block ``(i,j)`` of ``W`` is the
circulant matrix ``C_ij`` generated by ``w[i,j]``.  Convention used throughout
(matching the circulant convolution theorem as stated in the paper):

    C[r, c] = w[(r - c) mod k]        (w is the first *column* generator)
    (C x)   = irfft( rfft(w) * rfft(x) )      — circular convolution

so ``y_i = Σ_j C_ij x_j = irfft( Σ_j rfft(w_ij) ∘ rfft(x_j) )`` (paper Fig. 1,
with the decoupling technique: per-block-row single IFFT, per-input-block
single FFT, weights FFT'd offline).

Three lowerings of the same math (selected by ``path``):

* ``direct``   — materialize the circulant blocks and run a dense matmul.
                 O(n²) compute; the oracle, and the fastest choice for tiny k.
* ``fft``      — per-call rfft/irfft pipeline with a complex einsum in the
                 middle.  O(n log n + mn/k).  Used in *training*: autodiff
                 through rfft/irfft reproduces the paper's block-circulant
                 backward pass (Eqns. 2–3) exactly.
* ``spectral`` — frequency-domain real-plane contraction against *cached*
                 spectral weights (the paper's offline-FFT'd weights), with
                 Gauss's 3-multiplication complex product.  This is the
                 inference hot path and what the Pallas kernel implements.

All paths compute bit-identical math (tested against each other and against a
hand-derived gradient).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------
def num_blocks(dim: int, k: int) -> int:
    """Number of circulant blocks covering ``dim`` (zero-pad if k ∤ dim)."""
    return -(-dim // k)


def init_block_circulant(key, n_in: int, n_out: int, k: int,
                         dtype=jnp.float32, scale: Optional[float] = None):
    """First-row parameters ``w (p, q, k)`` for a (n_out × n_in) weight.

    Variance matches a dense ``1/sqrt(n_in)`` init: each output element is a
    sum of ``n_in`` products of independent entries.
    """
    p, q = num_blocks(n_out, k), num_blocks(n_in, k)
    scale = scale if scale is not None else 1.0 / math.sqrt(max(n_in, 1))
    return jax.random.normal(key, (p, q, k), dtype) * jnp.asarray(scale, dtype)


def materialize_dense(w: jax.Array, m: int, n: int) -> jax.Array:
    """Expand first-row params (p,q,k) into the dense (m,n) block-circulant W."""
    p, q, k = w.shape
    r = jnp.arange(k)
    idx = (r[:, None] - r[None, :]) % k            # C[r,c] = w[(r-c) mod k]
    blocks = w[:, :, idx]                          # (p, q, k, k)
    dense = blocks.transpose(0, 2, 1, 3).reshape(p * k, q * k)
    return dense[:m, :n]


# ---------------------------------------------------------------------------
# DFT-as-matmul lowering (TPU co-design, DESIGN.md §2)
#
# XLA's SPMD partitioner does NOT partition the HLO FFT op — it gathers the
# operand to full global batch (measured: ~24 GB per fft on the tinyllama
# 256-chip dry-run).  For the paper's block sizes (k ≤ 256) we therefore
# lower rfft/irfft as dense matmuls against precomputed DFT matrices:
# ~k/(2·log2 k) more MACs, but they run on the MXU at near-peak (vs the VPU)
# and partition like any other dot.  The FPGA's butterfly pipeline becomes a
# 128x65 constant matrix resident in VMEM.  ``FFT_IMPL`` switches back to
# true FFTs for the O(n log n) scaling benchmarks on host.
# ---------------------------------------------------------------------------
FFT_IMPL = "dft"          # "dft" (matmul, partitionable) | "xla_fft"
_DFT_MATMUL_MAX = 512     # fall back to xla fft above this block size


@functools.lru_cache(maxsize=32)
def dft_mats(k: int):
    """Real rfft/irfft as matrices: X = x@C (planes), x = Xr@Dr + Xi@Di."""
    n = np.arange(k)[:, None]
    f = np.arange(k // 2 + 1)[None, :]
    ang = 2.0 * np.pi * n * f / k
    Cr = np.cos(ang)                                   # (k, kf)
    Ci = -np.sin(ang)
    # irfft weights: bin 0 (and k/2 when k even) count once, others twice
    wgt = np.full((k // 2 + 1,), 2.0 / k)
    wgt[0] = 1.0 / k
    if k % 2 == 0:
        wgt[-1] = 1.0 / k
    Dr = (Cr * wgt[None, :]).T                         # (kf, k)
    Di = (Ci * wgt[None, :]).T                         # y = Xr@Dr + Xi@Di
    return (Cr.astype(np.float32), Ci.astype(np.float32),
            Dr.astype(np.float32), Di.astype(np.float32))


def _use_dft(k: int) -> bool:
    return FFT_IMPL == "dft" and k <= _DFT_MATMUL_MAX


def rfft_planes(x: jax.Array, k: int):
    """rfft of a real (..., k) array as two real planes (..., kf)."""
    if _use_dft(k):
        Cr, Ci, _, _ = dft_mats(k)
        return x @ Cr, x @ Ci
    xf = jnp.fft.rfft(x, axis=-1)
    return jnp.real(xf), jnp.imag(xf)


def irfft_planes(yr: jax.Array, yi: jax.Array, k: int):
    """irfft from real planes (..., kf) -> (..., k)."""
    if _use_dft(k):
        _, _, Dr, Di = dft_mats(k)
        return yr @ Dr + yi @ Di
    return jnp.fft.irfft(jax.lax.complex(yr, yi), n=k, axis=-1)


# ---------------------------------------------------------------------------
# Spectral weight cache (paper: offline FFT of weights + decoupling)
# ---------------------------------------------------------------------------
def spectral_cache(w: jax.Array, gauss: bool = True) -> Dict[str, jax.Array]:
    """Precompute rfft(w) as real planes (+ Gauss combos, free at runtime).

    Hermitian symmetry of the rfft of a real signal means only k//2+1 bins are
    kept (paper's real-FFT optimization — halves spectral storage & MACs).
    """
    wr, wi = rfft_planes(w.astype(jnp.float32), w.shape[-1])   # (p, q, kf)
    out = {"wr": wr, "wi": wi}
    if gauss:
        out["ws1"] = wi - wr                           # d - c
        out["ws2"] = wr + wi                           # c + d
    return out


def _cached_plane(cache, name, kf):
    """One serving-cache plane ready to contract: (plane, fold-scale|None).

    Quantized caches (repro.quant: int8 planes, or uint8 int4-packed pairs,
    with ``<name>_s`` per-block-row scales next to them) come back widened
    to f32 with the (..., p, 1) scale returned separately — the contraction
    reads int8 from memory and the scale folds into the (..., p, kf) output
    ONCE per row, after the einsum.
    """
    from ..quant.codec import plane_from_cache
    return plane_from_cache(cache, name, kf)


def _fold(t, scale):
    return t if scale is None else t * scale


def _gauss_contract(xr, xi, cache, contract: str):
    """Complex contraction  Y = X · W  with Gauss's 3-real-multiplication trick.

    ``contract`` is an einsum of the *real* contraction, e.g. '...qf,pqf->...pf'.
    re(XW) = t1 - t3, im(XW) = t1 + t2 with
      t1 = (xr+xi)·wr,  t2 = xr·(wi-wr),  t3 = xi·(wr+wi)
    3 big contractions instead of 4 (weight-side sums are precomputed offline).
    Quantized caches scale-fold per term (the identity is linear in each
    plane, so per-plane absmax scales commute with the contraction).
    """
    kf = xr.shape[-1]
    wr, s1 = _cached_plane(cache, "wr", kf)
    ws1, s2 = _cached_plane(cache, "ws1", kf)
    ws2, s3 = _cached_plane(cache, "ws2", kf)
    t1 = _fold(jnp.einsum(contract, xr + xi, wr), s1)
    t2 = _fold(jnp.einsum(contract, xr, ws1), s2)
    t3 = _fold(jnp.einsum(contract, xi, ws2), s3)
    return t1 - t3, t1 + t2


def _naive_complex_contract(xr, xi, cache, contract: str):
    """4-multiplication reference complex contraction (paper-faithful MAC count)."""
    kf = xr.shape[-1]
    wr, sr = _cached_plane(cache, "wr", kf)
    wi, si = _cached_plane(cache, "wi", kf)
    yr = (_fold(jnp.einsum(contract, xr, wr), sr)
          - _fold(jnp.einsum(contract, xi, wi), si))
    yi = (_fold(jnp.einsum(contract, xr, wi), si)
          + _fold(jnp.einsum(contract, xi, wr), sr))
    return yr, yi


# ---------------------------------------------------------------------------
# The three lowerings
# ---------------------------------------------------------------------------
def _blockify(x: jax.Array, q: int, k: int) -> jax.Array:
    """(..., n) -> (..., q, k) with zero padding up to q*k."""
    n = x.shape[-1]
    if n < q * k:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, q * k - n)]
        x = jnp.pad(x, pad)
    return x.reshape(*x.shape[:-1], q, k)


def bc_matmul_direct(x: jax.Array, w: jax.Array, n_out: int) -> jax.Array:
    """Oracle: y = W x via materialized dense W.  (..., n) -> (..., n_out)."""
    p, q, k = w.shape
    n_in = x.shape[-1]
    dense = materialize_dense(w, p * k, q * k)
    xp = _blockify(x, q, k).reshape(*x.shape[:-1], q * k)
    y = jnp.einsum("...n,mn->...m", xp, dense.astype(x.dtype))
    return y[..., :n_out]


def _cplx_contract(xr, xi, wr, wi, contract: str, gauss: bool = True):
    """Complex einsum on real planes; Gauss 3-mult by default."""
    if gauss:
        t1 = jnp.einsum(contract, xr + xi, wr)
        t2 = jnp.einsum(contract, xr, wi - wr)
        t3 = jnp.einsum(contract, xi, wr + wi)
        return t1 - t3, t1 + t2
    yr = jnp.einsum(contract, xr, wr) - jnp.einsum(contract, xi, wi)
    yi = jnp.einsum(contract, xr, wi) + jnp.einsum(contract, xi, wr)
    return yr, yi


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bc_fft_core(xb: jax.Array, w: jax.Array, gauss: bool = True) -> jax.Array:
    """y[..., p, k] = Σ_j circular_conv(w[p, j], xb[..., j, :]).

    The paper's decoupled pipeline (Fig. 1): q block-rffts on the input,
    complex MAC over the input-block index, p block-irffts — all lowered as
    real-plane dots (DFT-as-matmul above) so SPMD partitions them.
    """
    k = w.shape[-1]
    xr, xi = rfft_planes(xb, k)                          # (..., q, kf)
    wr, wi = rfft_planes(w, k)                           # (p, q, kf)
    yr, yi = _cplx_contract(xr, xi, wr, wi, "...qf,pqf->...pf", gauss)
    return irfft_planes(yr, yi, k)                       # (..., p, k)


def _bc_fft_fwd(xb, w, gauss: bool = True):
    return _bc_fft_core(xb, w, gauss), (xb, w)


def _bc_fft_bwd(gauss, res, gy):
    """The paper's backward pass (Eqns. 2-3): both gradients are themselves
    "FFT -> element-wise multiply -> IFFT" block-circulant products.

      dL/dx_j = Σ_i C_ij^T g_i          = irfft( Σ_i conj(Wf_ij) ∘ Gf_i )
      dL/dw_ij = g_i ⋆ x_j (circ corr)  = irfft( Σ_batch Gf_i ∘ conj(Xf_j) )

    Hand-deriving this keeps training O(n log n) *in the lowering too*:
    JAX's generic rfft transpose materializes full-length c64 intermediates
    (measured: 44 GB/layer on the tinyllama dry-run), whereas this VJP is
    exactly the forward pipeline with conjugated spectra.  Primals (not
    spectra) are saved; block FFTs are recomputed — the activation-memory
    profile the paper's in-place FPGA schedule implies.
    """
    xb, w = res
    k = w.shape[-1]
    gy = gy.astype(jnp.float32)
    gr, gi = rfft_planes(gy, k)                          # (..., p, kf)
    wr, wi = rfft_planes(w, k)                           # (p, q, kf)
    xr, xi = rfft_planes(xb, k)                          # (..., q, kf)
    # dL/dx: contract G with conj(W) over p
    vr, vi = _cplx_contract(gr, gi, wr, -wi, "...pf,pqf->...qf", gauss)
    gx = irfft_planes(vr, vi, k)
    # dL/dw: batch-contract G with conj(X)
    bdims = "".join(chr(ord("a") + i) for i in range(gy.ndim - 2))
    spec = f"{bdims}pf,{bdims}qf->pqf"
    ur = jnp.einsum(spec, gr, xr) + jnp.einsum(spec, gi, xi)
    ui = jnp.einsum(spec, gi, xr) - jnp.einsum(spec, gr, xi)
    gw = irfft_planes(ur, ui, k)
    return gx.astype(xb.dtype), gw.astype(w.dtype)


_bc_fft_core.defvjp(_bc_fft_fwd, _bc_fft_bwd)


def bc_matmul_fft(x: jax.Array, w: jax.Array, n_out: int,
                  gauss: bool = True) -> jax.Array:
    """Training path: rfft -> complex einsum -> irfft, with the paper's
    hand-derived block-circulant backward (custom_vjp above)."""
    p, q, k = w.shape
    dtype = x.dtype
    xb = _blockify(x, q, k).astype(jnp.float32)
    y = _bc_fft_core(xb, w.astype(jnp.float32), gauss)
    y = y.reshape(*x.shape[:-1], p * k)[..., :n_out]
    return y.astype(dtype)


def bc_matmul_spectral(x: jax.Array, cache: Dict[str, jax.Array], k: int,
                       n_out: int, gauss: bool = True,
                       kernel_fn=None) -> jax.Array:
    """Inference path: cached spectral weights, real-plane Gauss contraction.

    ``kernel_fn(xr, xi, cache)`` may override the contraction with the Pallas
    kernel (kernels/ops.py); default is the XLA einsum lowering.
    """
    p, q, kf = cache["wr"].shape
    dtype = x.dtype
    xb = _blockify(x, q, k).astype(jnp.float32)
    xr, xi = rfft_planes(xb, k)
    if kernel_fn is not None and "wr_s" not in cache:
        # quantized caches stay on the scale-folding einsum lowering: the
        # Pallas spectral kernel consumes f32 planes
        yr, yi = kernel_fn(xr, xi, cache)
    elif gauss and "ws1" in cache:
        yr, yi = _gauss_contract(xr, xi, cache, "...qf,pqf->...pf")
    else:
        yr, yi = _naive_complex_contract(xr, xi, cache, "...qf,pqf->...pf")
    y = irfft_planes(yr, yi, k)
    y = y.reshape(*x.shape[:-1], p * k)[..., :n_out]
    return y.astype(dtype)


def fused_spectral_cache(ws, gauss: bool = True) -> Dict[str, jax.Array]:
    """Spectral cache of several generators concatenated on the output-block
    axis — the offline form `bc_matmul_fused` consumes at serve time.

    The rfft acts per block (last axis), so FFT-then-concat equals
    concat-then-FFT; the concatenation order must match the `ws` order the
    fused call site uses (q/k/v, up/gate).
    """
    return spectral_cache(jnp.concatenate(ws, axis=-3), gauss)


def bc_matmul_fused(x: jax.Array, ws, n_outs, mode: str = "train",
                    cache: Optional[Dict[str, jax.Array]] = None,
                    gauss: bool = True, kernel_fn=None):
    """One FFT pipeline for several projections sharing the same input.

    Extends the paper's decoupling (reuse FFT(x_j) across block-rows) ACROSS
    projections: q/k/v (and gate/up) all consume the same x, so their
    generators are concatenated along the output-block axis p and the input
    transform + spectral MAC run ONCE.  Beyond-paper optimization recorded
    in EXPERIMENTS.md §Perf: saves (m-1)/m of the input DFT cost for m fused
    projections and feeds the MXU one large contraction instead of m small
    ones.

    Training lowers through the fft path (custom block-circulant VJP); at
    serve time the contraction runs against cached spectral planes —
    ``cache`` is the concatenated `fused_spectral_cache(ws)` precomputed
    offline (serve/params.py), or is derived on the fly when absent.
    """
    ps = [w.shape[-3] for w in ws]
    k = ws[0].shape[-1]
    with jax.named_scope("spectral"):
        if mode == "train":
            w_cat = jnp.concatenate(ws, axis=-3)       # (Σp_i, q, k)
            y = bc_matmul_fft(x, w_cat, w_cat.shape[-3] * k, gauss=gauss)
        else:
            if cache is None:
                cache = fused_spectral_cache(ws, gauss)
            y = bc_matmul_spectral(x, cache, k, cache["wr"].shape[-3] * k,
                                   gauss, kernel_fn)
    outs = []
    off = 0
    for p_i, n_out in zip(ps, n_outs):
        outs.append(y[..., off:off + n_out])
        off += p_i * k
    return outs


# ---------------------------------------------------------------------------
# LinearSpec dispatch — every projection in every model goes through here.
# ---------------------------------------------------------------------------
class LinearSpec:
    """How one projection is parameterized and lowered."""

    __slots__ = ("kind", "block_size", "path", "gauss", "bias")

    def __init__(self, kind: str = "dense", block_size: int = 0,
                 path: str = "auto", gauss: bool = True, bias: bool = False):
        assert kind in ("dense", "block_circulant")
        self.kind = kind
        self.block_size = block_size
        self.path = path
        self.gauss = gauss
        self.bias = bias

    @staticmethod
    def from_config(comp, layer_class: str, bias: bool = False) -> "LinearSpec":
        k = comp.block_for(layer_class) if comp is not None else 0
        if k and comp.enabled:
            return LinearSpec("block_circulant", k, comp.path, comp.gauss_trick, bias)
        return LinearSpec("dense", 0, "auto", True, bias)

    def resolve_path(self, mode: str) -> str:
        if self.path != "auto":
            return self.path
        # training differentiates through the fft pipeline; serving uses the
        # cached-spectral decoupled form; tiny blocks go direct.
        if self.block_size <= 8:
            return "direct"
        return "fft" if mode == "train" else "spectral"


def init_linear(key, n_in: int, n_out: int, spec: LinearSpec,
                dtype=jnp.float32) -> Dict[str, jax.Array]:
    params: Dict[str, jax.Array] = {}
    if spec.kind == "dense":
        scale = 1.0 / math.sqrt(max(n_in, 1))
        params["w"] = jax.random.normal(key, (n_in, n_out), dtype) * scale
    else:
        params["wc"] = init_block_circulant(key, n_in, n_out, spec.block_size, dtype)
    if spec.bias:
        params["b"] = jnp.zeros((n_out,), dtype)
    return params


def apply_linear(params: Dict[str, jax.Array], x: jax.Array, spec: LinearSpec,
                 n_out: int, mode: str = "train", kernel_fn=None) -> jax.Array:
    """y = x W (+ b). For block-circulant, W is the (n_out×n_in) generator.
    Its ops run under the named scope ``dense`` or ``spectral`` (the
    device trace's layer kinds, repro.obs.scopes)."""
    if spec.kind == "dense":
        with jax.named_scope("dense"):
            y = x @ params["w"].astype(x.dtype)
    else:
        path = spec.resolve_path(mode)
        w = params["wc"]
        # Precomputed spectral planes (serve/params.py) short-circuit the
        # weight-side FFT; training must keep differentiating through `wc`,
        # so the cache is consulted only outside train mode.
        with jax.named_scope("spectral"):
            if mode != "train" and "wc_cache" in params:
                y = bc_matmul_spectral(x, params["wc_cache"], spec.block_size,
                                       n_out, spec.gauss, kernel_fn)
            elif path == "direct":
                y = bc_matmul_direct(x, w, n_out)
            elif path == "spectral":
                y = bc_matmul_spectral(x, spectral_cache(w, spec.gauss),
                                       spec.block_size, n_out, spec.gauss,
                                       kernel_fn)
            else:
                y = bc_matmul_fft(x, w, n_out, gauss=spec.gauss)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Accounting (used by benchmarks and the roofline)
# ---------------------------------------------------------------------------
def dense_flops(batch: int, n_in: int, n_out: int) -> int:
    return 2 * batch * n_in * n_out


def bc_flops(batch: int, n_in: int, n_out: int, k: int, gauss: bool = True) -> int:
    """FLOPs of the decoupled spectral pipeline (paper complexity analysis)."""
    p, q, kf = num_blocks(n_out, k), num_blocks(n_in, k), k // 2 + 1
    fft = int(2.5 * batch * (q + p) * k * max(math.log2(k), 1))  # rfft+irfft, split-radix est.
    muls = 3 if gauss else 4
    mac = 2 * muls * batch * p * q * kf                           # complex MACs as real ops
    return fft + mac


def dense_param_bytes(n_in: int, n_out: int, bytes_per: int = 2) -> int:
    return n_in * n_out * bytes_per


def bc_param_bytes(n_in: int, n_out: int, k: int, bytes_per: int = 4,
                   spectral: bool = False) -> int:
    p, q = num_blocks(n_out, k), num_blocks(n_in, k)
    if spectral:   # cached rfft planes: 2 * kf reals per block
        return p * q * (k // 2 + 1) * 2 * bytes_per
    return p * q * k * bytes_per
