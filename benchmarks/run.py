"""Run every benchmark (one per paper table/figure).  CSV to stdout.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run compression throughput

Smoke-scale JSON outputs land under ``results/`` (gitignored) — only the
full runs' checked-in BENCH_*.json live at the repo root, as the perf
baselines ``benchmarks/gate.py`` judges against.
"""
from __future__ import annotations

import os
import sys
import time

from repro.launch.cache import enable_compile_cache

from . import (bench_accuracy_tradeoff, bench_complexity, bench_compression,
               bench_decoupling, bench_equiv_ops, bench_fleet,
               bench_paged_attention, bench_quant, bench_serving,
               bench_throughput)

RESULTS_DIR = "results"


def _smoke_out(name: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, name)


ALL = {
    "compression": bench_compression.main,        # paper Fig. 3
    "throughput": bench_throughput.main,          # paper Table 1
    "equiv_ops": bench_equiv_ops.main,            # paper Fig. 6
    "complexity": bench_complexity.main,          # O(n log n) claim
    "decoupling": bench_decoupling.main,          # FFT/IFFT decoupling
    "accuracy_tradeoff": bench_accuracy_tradeoff.main,  # k-vs-quality
    # serving suite (smoke-scale here; the full runs write the checked-in
    # BENCH_*.json files — see each bench's module docstring)
    "serving": lambda: bench_serving.main(
        ["--smoke", "--out", _smoke_out("BENCH_serving_smoke.json")]),
    "paged_attention": lambda: bench_paged_attention.main(
        ["--smoke", "--out",
         _smoke_out("BENCH_paged_attention_smoke.json")]),
    "quant": lambda: bench_quant.main(
        ["--smoke", "--out", _smoke_out("BENCH_quant_smoke.json")]),
    "fleet": lambda: bench_fleet.main(
        ["--smoke", "--out", _smoke_out("BENCH_fleet_smoke.json")]),
}


def main():
    names = sys.argv[1:] or list(ALL)
    enable_compile_cache()
    for name in names:
        t0 = time.time()
        ALL[name]()
        print(f"[{name}: {time.time() - t0:.1f}s]\n", flush=True)


if __name__ == "__main__":
    main()
