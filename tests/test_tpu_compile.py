"""Compile the served path's Pallas kernel for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what the chip would (block
shapes off the tiling, too much fast memory).  Interpret-mode tests cannot
see those refusals.  The topology is described inside a fixture, never at
import, so every pytest worker collects the same tests and only the worker
running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.paged_attention import paged_attention_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                             # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one: keep these compiles out of any persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


LANES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.mark.parametrize("lane", sorted(LANES))
def test_paged_attention_compiles_for_v5e(one_chip, lane):
    """qwen3-4b decode attention widths: 8 slots, Hq 32, Hkv 8, head_dim
    128, page 16, a 512-token table; the int8 lane adds its (P, Hkv)
    per-page scales."""
    a = get_config("qwen3-4b").attention
    B, page, maxp = 8, 16, 32
    P = B * maxp + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    pool_dt = LANES[lane]
    q_dt = jnp.bfloat16 if lane == "bf16" else jnp.float32
    args = [sds((B, a.num_heads, a.head_dim), q_dt),
            sds((P, page, a.num_kv_heads, a.head_dim), pool_dt),
            sds((P, page, a.num_kv_heads, a.head_dim), pool_dt),
            sds((B, maxp), jnp.int32), sds((B,), jnp.int32)]
    if lane == "int8":
        args += [sds((P, a.num_kv_heads), jnp.float32)] * 2

        def fn(q, k, v, t, p, ks, vs):
            return paged_attention_kernel(q, k, v, t, p, k_scale=ks,
                                          v_scale=vs)
    else:
        fn = paged_attention_kernel
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
