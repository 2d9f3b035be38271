"""Paper complexity claim — O(n²) -> O(n log n) compute, O(n²) -> O(n)
storage, verified from COMPILED artifacts: jit cost_analysis FLOPs for the
dense vs FFT lowering over a sweep of layer sizes n and block sizes k.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import circulant as cc

from .common import emit


def compiled_flops(fn, *args) -> float:
    compiled = jax.jit(fn).lower(*args).compile()
    return float(compiled.cost_analysis()["flops"])  # loud if XLA omits it


def main():
    print("# bench_complexity (compiled-FLOPs scaling)")
    rows = []
    old = cc.FFT_IMPL
    cc.FFT_IMPL = "xla_fft"            # true FFT: the asymptotic claim
    try:
        for n in (256, 512, 1024, 2048, 4096):
            x = jax.ShapeDtypeStruct((1, n), jnp.float32)
            wd = jax.ShapeDtypeStruct((n, n), jnp.float32)
            f_dense = compiled_flops(lambda x, w: x @ w, x, wd)
            for k in (64, 128, 256):
                wc = jax.ShapeDtypeStruct((n // k, n // k, k), jnp.float32)
                f_bc = compiled_flops(
                    lambda x, w: cc.bc_matmul_fft(x, w, n), x, wc)
                rows.append({
                    "n": n, "k": k,
                    "dense_flops": int(f_dense), "bc_flops": int(f_bc),
                    "reduction": round(f_dense / max(f_bc, 1), 1),
                    "dense_params": n * n, "bc_params": n * n // k,
                    "storage_reduction": k,
                })
    finally:
        cc.FFT_IMPL = old
    emit(rows, ["n", "k", "dense_flops", "bc_flops", "reduction",
                "dense_params", "bc_params", "storage_reduction"])


if __name__ == "__main__":
    main()
