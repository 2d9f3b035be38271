"""Find a cell's knee once: set the cell up once, then offer its mix at
each of several rates for a short window and print what each rate gives.

    python benchmarks/chip/sweep.py --workload qwen3-4b.chat --seed 1 \
        --seconds 20 --rates 4,6,8,10,12

One line per rate: requests, tokens/s, TTFT p50/p95, TPOT p95, queue wait
p95 and how long serving what arrived took after the window closed.  The
knee is the highest rate at which the queue does not grow over the window:
TTFT p95 stays within about twice its low-rate value, and the tokens
delivered in the window keep pace with the tokens offered.  The drain is
no guide: the longest outputs alone take tens of seconds to decode.  The
cell's rate (``cells/<workload>.json``) is 0.8 of the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run
import traffic as traffic_lib
from stats import percentile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax

    import program
    cell = run.load_cell(args.workload)
    device = jax.devices()[0]
    if device.platform != "tpu":
        run.log(f"no TPU: JAX found {device.platform} devices")
        return 1
    program.enable_compile_cache(str(run.CACHE_DIR))
    clock = program.CompileClock()
    _, engine, _, times = run.set_up(cell, args.seed, device, clock)
    run.log(f"set-up {time.perf_counter() - run.T_PROCESS:.2f}s {times}")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        arrivals = traffic_lib.generate(cell.mix, rate, args.seconds,
                                        args.seed + i, cell.conf["vocab_size"])
        t = time.perf_counter()
        with jax.default_device(device):
            served, _ = run.serve_window(engine, program, arrivals,
                                         args.seconds)
        total = time.perf_counter() - t
        e2e = run.end_to_end(cell, served, args.seconds, 0.0)
        ttft = [s.ttft_s for s in served]
        mem = device.memory_stats() or {}
        print(json.dumps({
            "rate_per_s": rate, "requests": len(served),
            "failed": sum(not s.finished for s in served),
            "tokens_per_s": e2e["tokens_per_s"]["value"],
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p95_s": e2e["ttft_p95_s"]["value"],
            "tpot_p95_s": e2e["tpot_p95_s"]["value"],
            "queue_p95_s": percentile([s.queue_s for s in served], 95),
            "drain_s": total - args.seconds,
            "peak_bytes": mem.get("peak_bytes_in_use"),
            "bytes_limit": mem.get("bytes_limit")}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
