"""Readings that a cell's check limit is set from, on the chip, at the
cell's own size and load.  For each seed one window is served and judged
twice by ``check.check``: as the benchmark judges the program, and with
the control (the reference at fp8) in the program's place, at the cell's
own limits.  One process serves every seed in turn, so set-up compiles
once.

    python benchmarks/chip/control.py --workload qwen3-4b.chat \\
        --seconds 20 --seeds 11,12,13

One JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import check
import run
import traffic as traffic_lib


def readings(cell, seed: int, seconds: float, device, clock,
             control: bool = True) -> dict:
    import jax

    import program
    params, engine, _, _ = run.set_up(cell, seed, device, clock)
    arrivals = traffic_lib.generate(cell.mix, cell.params["rate_per_s"],
                                    seconds, seed, cell.conf["vocab_size"])
    with jax.default_device(device):
        served, _ = run.serve_window(engine, program, arrivals, seconds)
    del engine
    gc.collect()
    out = {"seed": seed, "requests": len(served)}
    with jax.default_device(device):
        for name, precision in (("program", "f32"), ("control", "fp8")):
            if name == "control" and not control:
                continue
            v = check.check(cell, params, served, seed, precision)
            out[name] = {"correct": v.correct,
                         "checked_tokens": v.checked_tokens,
                         **{n: x["value"] for n, x in v.numbers().items()}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-control", action="store_true",
                    help="read the program alone (the lower reading)")
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
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, device, clock,
                                  control=not args.no_control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
