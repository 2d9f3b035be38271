"""Fused three-phase block-circulant Pallas kernel vs the pure-jnp oracle,
swept over shapes/dtypes (interpret mode), plus the platform dispatch
through kernels/ops.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import circulant as cc
from repro.kernels import bc_fused
from repro.kernels import ops as kops


@pytest.mark.parametrize("n_in,n_out,k,B", [
    (64, 64, 16, 4), (128, 64, 32, 8), (48, 80, 16, 3), (256, 128, 64, 2),
])
def test_fused_kernel_matches_oracle(n_in, n_out, k, B):
    w = cc.init_block_circulant(jax.random.PRNGKey(0), n_in, n_out, k)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, n_in))
    ref = cc.bc_matmul_direct(x, w, n_out)
    out = bc_fused.bc_linear_fused_kernel(x, w, n_out, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_kernel_dtypes(dtype):
    w = cc.init_block_circulant(jax.random.PRNGKey(0), 64, 64, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64), dtype)
    ref = cc.bc_matmul_fft(x, w, 64)
    out = bc_fused.bc_linear_fused_kernel(x, w, 64, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_fused_kernel_grid_tiling():
    """Multiple grid steps on both axes (B and p tiling)."""
    w = cc.init_block_circulant(jax.random.PRNGKey(0), 64, 256, 16)  # p=16
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 64))
    ref = cc.bc_matmul_direct(x, w, 256)
    out_tiled = bc_fused.bc_linear_fused_kernel(x, w, 256, interpret=True)
    np.testing.assert_allclose(np.asarray(out_tiled), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Dispatch policy: bc_linear_fused routes through ops.py like the other two
# kernels — 'off' lowers to the XLA cached-spectral path, 'interpret' runs
# the Pallas body, and the platform drives the default.
# ---------------------------------------------------------------------------
def test_ops_dispatch_off_matches_interpret():
    w = cc.init_block_circulant(jax.random.PRNGKey(0), 64, 96, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    ref = cc.bc_matmul_direct(x, w, 96)
    off = kops.bc_linear_fused(x, w, 96, mode="off")
    interp = kops.bc_linear_fused(x, w, 96, mode="interpret")
    np.testing.assert_allclose(np.asarray(off), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(interp), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ops_dispatch_env_default(monkeypatch):
    w = cc.init_block_circulant(jax.random.PRNGKey(0), 32, 32, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 32))
    assert kops.kernel_mode() == "off"              # CPU backend: XLA
    out = kops.bc_linear_fused(x, w, 32)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(cc.bc_matmul_direct(x, w, 32)),
                               rtol=2e-3, atol=2e-3)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kops.kernel_mode() == "tpu"              # TPU backend: Pallas
