"""Compile the served path for a described TPU v5e chip: its Pallas
kernel, and the paged decode program around it.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what the chip would (block
shapes off the tiling, too much fast memory).  Interpret-mode tests cannot
see those refusals, nor what the compiler makes of the program: whether
it copies the KV pool.  The topology is described inside a fixture, never
at import, so every pytest worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import ops as kops
from repro.kernels.paged_attention import paged_attention_kernel
from repro.models.registry import build_model
from repro.quant import QuantPolicy
from repro.serve import decode as dec
from repro.serve import kvcache as kvc
from repro.serve.params import precompute_serving_params


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
    128, page 16, a 512-token table, reading layer 2 of a 4-layer stacked
    pool; the int8 lane adds its (n, P, Hkv) per-page scales."""
    a = get_config("qwen3-4b").attention
    B, page, maxp, n = 8, 16, 32, 4
    P = B * maxp + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    pool_dt = LANES[lane]
    q_dt = jnp.bfloat16 if lane == "bf16" else jnp.float32
    args = [sds((B, a.num_heads, a.head_dim), q_dt),
            sds((n, P, page, a.num_kv_heads, a.head_dim), pool_dt),
            sds((n, P, page, a.num_kv_heads, a.head_dim), pool_dt),
            sds((B, maxp), jnp.int32), sds((B,), jnp.int32),
            sds((), jnp.int32)]
    if lane == "int8":
        args += [sds((n, P, a.num_kv_heads), jnp.float32)] * 2

        def fn(q, k, v, t, p, layer, ks, vs):
            return paged_attention_kernel(q, k, v, t, p, layer, k_scale=ks,
                                          v_scale=vs)
    else:
        fn = paged_attention_kernel
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# an HLO instruction: its name, its result type(s) and its opcode
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def pool_moves(hlo: str, leaf_shape, dtype: str):
    """Instructions of an optimized HLO text that copy, slice or restack a
    stacked pool leaf ``leaf_shape`` or one layer's slab of it: by opcode,
    or by the fusion's name, which XLA builds from the ops it fused."""
    shapes = [f"{dtype}[{','.join(map(str, s))}]"
              for s in (leaf_shape, leaf_shape[1:])]
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or not any(s in m.group(2) for s in shapes):
            continue
        name, op = m.group(1), m.group(3)
        if any(op.startswith(w) or w in name for w in _MOVES):
            found.append(f"{name}: {op} {m.group(2)[:60]}")
    return found


def test_paged_decode_holds_the_pool_once(one_chip, monkeypatch):
    """The continuous engine's decode program, at qwen3-4b's widths with 4
    layers, 40 pages of 128, 16 slots and an f32 pool, the Pallas kernel
    in: no copy, dynamic slice or dynamic update of a stacked pool leaf or
    of one layer's slab is left, and its temporaries are less than one
    pool.  The vocabulary is cut to 4096: the tied head's bf16 copy of the
    table is a temporary too, and at 151936 entries (0.78 GB) it alone
    outweighs a 4-layer pool (0.17 GB)."""
    monkeypatch.setattr(kops, "kernel_mode", lambda: "tpu")
    cfg = get_config("qwen3-4b").replace(num_layers=4, vocab_size=4096)
    B, page, P, maxp = 16, 128, 40, 8
    policy = QuantPolicy(kv_dtype="f32")
    place = lambda tree: jax.tree.map(               # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = jax.eval_shape(lambda: precompute_serving_params(
        build_model(cfg).init(jax.random.PRNGKey(0)), cfg, policy))
    pool = jax.eval_shape(lambda: kvc.build_pool(cfg, P, page, policy))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    loop = jax.jit(dec.make_paged_decode_loop(cfg, 8, capture_stats=True),
                   donate_argnums=(2,))
    compiled = loop.lower(place(params), i32(B), place(pool), i32(B, maxp),
                          i32(B), i32(B)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    leaf = pool[0][0]["k"]
    assert leaf.shape == (4, P, page, 8, 128)
    assert pool_moves(hlo, leaf.shape, "f32") == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < kvc.pool_bytes(pool), (temp, kvc.pool_bytes(pool))
