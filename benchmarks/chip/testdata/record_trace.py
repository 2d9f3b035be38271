"""Record the small trace that tests/bench/test_trace_reduce.py reads.

    python benchmarks/chip/testdata/record_trace.py   # on a TPU

Inside one ``bench.window`` span: three calls of a jitted
``prefill_pack`` (a matmul chain), a 50 ms ``bench.wait`` sleep, then two
calls of a jitted ``decode_loop`` that runs the program's paged attention
kernel.  Writes ``testdata/small.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402


def main():
    assert jax.devices()[0].platform == "tpu", "record on a TPU"

    def prefill_pack(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    def decode_loop(q, k, v, table, pos):
        return ops.paged_attention(q, k, v, table, pos)

    pf = jax.jit(prefill_pack)
    dl = jax.jit(decode_loop)
    x = jnp.ones((1024, 1024), jnp.float32)
    w = jnp.full((1024, 1024), 1e-3, jnp.float32)
    B, P, page, Hkv, D, Hq = 4, 33, 128, 2, 128, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((P, page, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((P, page, Hkv, D)), jnp.float32)
    table = jnp.asarray(np.arange(1, P).reshape(B, 8), jnp.int32)
    pos = jnp.asarray([1000, 500, 0, -1], jnp.int32)
    jax.block_until_ready((pf(x, w), dl(q, k, v, table, pos)))
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(pf(x, w))
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.05)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(dl(q, k, v, table, pos))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                    recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "small.xplane.pb"))
    shutil.rmtree(logdir)
    print(os.path.getsize(os.path.join(HERE, "small.xplane.pb")))


if __name__ == "__main__":
    main()
