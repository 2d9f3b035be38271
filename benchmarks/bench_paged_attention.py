"""Paged decode attention: gather-then-attend (PR 3) vs the fused streamed
flash-decode path, at the oversubscribed serving shape where the gather
path's O(B * maxp * page) materialization hurts.

Two axes per impl, on the jitted attention step alone (pool write and the
rest of the decode step are identical between impls):

* ``tokens/s`` — one decode token per live slot per step; min wall over
  iters (shared host, same convention as bench_decode).
* ``peak bytes`` — the compiled step's XLA temp allocation
  (``compiled.memory_analysis().temp_size_in_bytes``: the gathered KV view
  lives here) plus total ``bytes accessed`` from cost analysis, with the
  analytic worst-case estimates from ``serve.kvcache.attention_memory_est``
  alongside.

The oversubscribed setting mirrors bench_serving's continuous engine:
more slots than the dense engine's batch, every slot's table spanning the
full ``max_seq`` reservation — the regime where the gathered view is
``maxp * page`` wide regardless of how short the live history is.

  PYTHONPATH=src python benchmarks/bench_paged_attention.py \
      --out BENCH_paged_attention.json
  PYTHONPATH=src python benchmarks/bench_paged_attention.py --smoke
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.layers.attention import chunked_attention


def make_case(*, slots, max_seq, page, Hkv, G, D, live_len, seed=0):
    """Random one-layer stacked pool sized for ``slots`` full reservations;
    every slot owns its worst case (the scheduler's up-front reservation)
    but only ``live_len`` positions are live — the oversubscribed-decode
    shape."""
    rng = np.random.RandomState(seed)
    maxp = -(-max_seq // page)
    num_pages = slots * maxp + 1                  # + trash page 0
    pool_k = rng.randn(1, num_pages, page, Hkv, D).astype(np.float32)
    pool_v = rng.randn(1, num_pages, page, Hkv, D).astype(np.float32)
    free = list(range(1, num_pages))
    rng.shuffle(free)
    table = np.zeros((slots, maxp), np.int32)
    for b in range(slots):
        for j in range(maxp):
            table[b, j] = free.pop()
    positions = np.full(slots, live_len - 1, np.int32)
    q = rng.randn(slots, Hkv * G, D).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(positions))


def step_fn(impl: str):
    if impl == "stream":
        def f(q, pool_k, pool_v, table, positions):
            return kops.paged_attention(q, pool_k, pool_v, table, positions,
                                        0)
    else:
        def f(q, pool_k, pool_v, table, positions):
            k = kops.paged_gather(pool_k, table, 0)
            v = kops.paged_gather(pool_v, table, 0)
            idx = jnp.arange(k.shape[1])[None, :]
            kvp = jnp.where(idx <= positions[:, None], idx, -1)
            o = chunked_attention(q[:, None], k, v,
                                  q_pos0=jnp.maximum(positions, 0),
                                  kv_positions=kvp)
            return o[:, 0]
    return f


def bench_impl(impl: str, args_dev, iters: int) -> dict:
    fn = jax.jit(step_fn(impl))
    compiled = fn.lower(*args_dev).compile()
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    jax.block_until_ready(fn(*args_dev))          # warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args_dev))
        best = min(best, time.perf_counter() - t0)
    slots = args_dev[0].shape[0]
    return {
        "step_ms_best": best * 1e3,
        "tokens_per_s": slots / best,
        "peak_temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0) or 0),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=12,
                    help="decode slots (oversubscribed vs a batch-4 engine)")
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--group", type=int, default=4,
                    help="GQA group (Hq = kv_heads * group)")
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--live-len", type=int, default=48,
                    help="live positions per slot (short vs the max_seq "
                         "reservation: the oversubscribed regime)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI shapes (seconds)")
    ap.add_argument("--out", default="BENCH_paged_attention.json")
    args = ap.parse_args(argv)

    if args.smoke:
        args.slots, args.max_seq, args.page_size = 4, 64, 8
        args.kv_heads, args.group, args.head_dim = 2, 2, 16
        args.live_len, args.iters = 20, 5

    case = make_case(slots=args.slots, max_seq=args.max_seq,
                     page=args.page_size, Hkv=args.kv_heads, G=args.group,
                     D=args.head_dim, live_len=args.live_len)
    rows = {}
    for impl in ("gather", "stream"):
        rows[impl] = bench_impl(impl, case, args.iters)
        r = rows[impl]
        print(f"[bench_paged_attention] {impl:>7}: "
              f"{r['tokens_per_s']:9.1f} tok/s  "
              f"temp {r['peak_temp_bytes'] / 1e6:7.2f}MB  "
              f"accessed {r['bytes_accessed'] / 1e6:8.2f}MB", flush=True)

    result = {
        "slots": args.slots,
        "max_seq": args.max_seq,
        "page_size": args.page_size,
        "kv_heads": args.kv_heads,
        "group": args.group,
        "head_dim": args.head_dim,
        "live_len": args.live_len,
        "backend": jax.default_backend(),
        "impls": rows,
        "speedup_stream_vs_gather": (rows["stream"]["tokens_per_s"]
                                     / rows["gather"]["tokens_per_s"]),
        "peak_bytes_gather_over_stream": (
            rows["gather"]["peak_temp_bytes"]
            / max(rows["stream"]["peak_temp_bytes"], 1)),
        "bytes_accessed_gather_over_stream": (
            rows["gather"]["bytes_accessed"]
            / max(rows["stream"]["bytes_accessed"], 1.0)),
    }
    print(f"[bench_paged_attention] stream/gather = "
          f"{result['speedup_stream_vs_gather']:.2f}x tok/s, peak temp "
          f"gather/stream = {result['peak_bytes_gather_over_stream']:.1f}x, "
          f"bytes accessed gather/stream = "
          f"{result['bytes_accessed_gather_over_stream']:.1f}x")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        print("wrote", args.out)
    return result


if __name__ == "__main__":
    main()
