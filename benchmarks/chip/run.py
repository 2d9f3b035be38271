"""Run one benchmark cell once, on the accelerator this process finds.

    python benchmarks/chip/run.py --workload qwen3-4b.chat --seed 7 \
        --seconds 40 --trace 0

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); its rate, slots and pool are in
``cells/<workload>.json``; the configuration's architecture is served by
its module under ``models/`` (``arch.py``); each per-layer metric is read
by ``metrics/<metric>.py``.  A run:

1. makes the weights from the seed on the device, builds the program's
   continuous engine and warms every prefill bucket the mix can reach and
   the decode program (set-up: ``setup_s`` ends at the first arrival);
2. offers the mix's requests open loop for ``--seconds``, each timed from
   its scheduled arrival, then serves every request that arrived to its end;
3. with ``--trace 1``, traces part of the window with the profiler and
   prints the per-layer metrics instead of the end-to-end ones;
4. frees the engine and checks a sample of the served tokens against the
   plain reference (``reference.py``), and prints one JSON line.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
# JAX's persistent compile cache lives in the checkout, at a fixed path,
# whatever the environment names: only a cell's first run there compiles,
# and two checkouts never share programs.
CACHE_DIR = ROOT / ".jax_cache"

import numpy as np  # noqa: E402

import arch  # noqa: E402
import traffic as traffic_lib  # noqa: E402
from stats import percentile  # noqa: E402
import work  # noqa: E402

TRACE_SECONDS = 5.0        # traced part: the window's last 5 s ...
TRACE_AT = 0.5             # ... or its second half, if that is shorter
DRAIN_LIMIT_S = 120.0      # serving what arrived may take this long at most


# ---------------------------------------------------------------------------
# The cell, from the data files
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict                 # configs/<config>.json
    mix: dict                  # traffic/<traffic>.json
    params: dict               # cells/<workload>.json
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def max_seq(self) -> int:
        return traffic_lib.max_seq(self.mix)


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = _load_json(root / confs[w["config"]]["file"])
    arch.module(conf)
    mix = _load_json(HERE / "traffic" / f"{w['traffic']}.json")
    params = _load_json(HERE / "cells" / f"{name}.json")
    cell = Cell(name, int(w["chips"]), conf, mix, params,
                bench["end_to_end"], bench["per_layer"])
    longest = conf.get("max_position_embeddings")
    if longest is not None and cell.max_seq > longest:
        raise ValueError(f"{name}: sequences of {cell.max_seq} positions, "
                         f"the configuration has {longest}")
    return cell


def load_metric(name: str):
    """``metrics/<name>.py``: a module with ``read(ctx) -> float | None``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# What a run hands the metric readers
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    index: int
    arrival_s: float
    prompt: np.ndarray
    max_new_tokens: int
    status: str = "UNFINISHED"
    tokens: List[int] = dataclasses.field(default_factory=list)
    queue_s: Optional[float] = None
    ttft_s: Optional[float] = None
    decode_s: Optional[float] = None
    # (seconds on the window's clock, tokens) per delivery: the prefill's
    # first token, then each decode chunk's
    delivered: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return self.status.startswith("FINISHED")

    @property
    def tpot_s(self) -> Optional[float]:
        n = len(self.tokens)
        if not self.finished or n < 2 or self.decode_s is None:
            return None
        return self.decode_s / (n - 1)


@dataclasses.dataclass
class Registry:
    """The program's metrics over the window, by their flat names
    (``name{label=value}``): each histogram's values observed in it and
    each counter's increase."""
    histograms: Dict[str, List[float]]
    counters: Dict[str, float]


@dataclasses.dataclass
class Context:
    cell: Cell
    seconds: float
    requests: List[Served]
    registry: Registry
    peaks: object = None
    trace: object = None                   # trace_reduce.Reduction
    # leaf-op device seconds by named scope, per traced program
    # (``repro.obs.scopes.device_seconds``: {"jit_decode_loop": {"runs",
    # "leaf_s", "top", "under", "unscoped_ops"}, ...})
    scope_s: Optional[Dict[str, dict]] = None
    traced_work: Optional[Dict[str, float]] = None

    def hist(self, name: str) -> List[float]:
        return self.registry.histograms.get(name, [])

    @property
    def traced_from(self) -> float:
        """Start of the traced part, on the window's clock."""
        return traced_part(self.seconds)[0]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def warm_up(engine, cell: Cell, program) -> List[int]:
    """Serve one request per prefill bucket the mix can reach (a prompt of
    the bucket's full length, two tokens each, so the decode program runs
    too).  Returns the buckets warmed."""
    page = cell.conf["served"]["page_size"]
    buckets = traffic_lib.prefill_buckets(cell.mix, page)
    rng = np.random.default_rng(0)
    vocab = cell.conf["vocab_size"]
    reqs = [program.request(-1 - i, rng.integers(1, vocab, size=b * page)
                            .astype(np.int32), 2)
            for i, b in enumerate(buckets)]
    results = engine.generate(reqs)
    bad = [r["status"] for r in results if not r["status"].startswith(
        "FINISHED")]
    if bad:
        raise RuntimeError(f"warm-up requests ended {bad}")
    missing = set(buckets) - set(engine.stats()["prefill_buckets"])
    if missing:
        raise RuntimeError(f"warm-up left prefill buckets {sorted(missing)} "
                           f"uncompiled")
    return buckets


def serve_window(engine, program, arrivals, seconds: float, trace_dir=None):
    """Offer ``arrivals`` open loop; serve everything that arrived.  With
    ``trace_dir``, profile the window's end (``traced_part``), starting
    and stopping between engine steps: the profiler's stop, which holds
    the host for seconds, then falls after the window.
    Returns (served requests, traced interval on the obs clock or None)."""
    import jax
    obs = engine.obs
    engine.reset_serve_clock()
    obs.traces.clear()
    served = [Served(a.index, a.arrival_s, a.prompt, a.max_new_tokens)
              for a in arrivals]
    orders = {}
    t_trace = traced_part(seconds)
    traced = None
    tracing = False
    sched = engine.scheduler
    window = None
    # the engine's serve clock starts at the first submit, microseconds
    # after this: the window's clock
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.submit"):
        for s in served:
            orders[engine.submit(program.request(s.index, s.prompt,
                                                 s.max_new_tokens),
                                 s.arrival_s)] = s
    deadline = seconds + DRAIN_LIMIT_S
    while not sched.idle:
        now = time.perf_counter() - t0
        if trace_dir is not None:
            if not tracing and traced is None and now >= t_trace[0]:
                _start_trace(trace_dir)
                window = jax.profiler.TraceAnnotation("bench.window")
                window.__enter__()
                tracing = True
                traced = [obs.now(), None]
            elif tracing and now >= t_trace[1]:
                window.__exit__(None, None, None)
                traced[1] = obs.now()
                jax.profiler.stop_trace()
                tracing = False
        if now > deadline:
            raise RuntimeError(f"requests still in flight {DRAIN_LIMIT_S}s "
                               f"after the window closed")
        if not sched.running and sched.queue and sched.queue[0].arrival_s > now:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, sched.queue[0].arrival_s - now))
            continue
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
    if tracing:
        window.__exit__(None, None, None)
        traced[1] = obs.now()
        jax.profiler.stop_trace()
    t_obs0 = obs.rebase(t0)
    for order, s in orders.items():
        res = engine.result(order, pop=True)
        s.status = res["status"]
        s.tokens = list(res["tokens"])
        if res.get("queue_s") is not None and res.get("prefill_s") is not None:
            s.queue_s = res["queue_s"]
            s.ttft_s = res["queue_s"] + res["prefill_s"]
            s.decode_s = res["decode_s"]
    for tr in obs.traces.completed:
        s = orders.get(tr.order)
        if s is not None and tr.first_token_s is not None:
            s.delivered = [(tr.first_token_s - t_obs0, 1)] + [
                (t - t_obs0, n) for t, n in tr.chunks]
    return served, (tuple(traced) if traced else None)


def traced_part(seconds: float):
    """The traced part of a window, on the window's clock."""
    return max(TRACE_AT * seconds, seconds - TRACE_SECONDS), seconds


def _start_trace(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def traced_work(engine, conf: dict, interval) -> Dict[str, float]:
    """Operations and bytes of the prefills and decode steps that ran in
    the traced interval, rebuilt from each request's own timeline: a
    prefill ran there if its request was admitted and got its first token
    inside it; a decode chunk did if it ended inside it (the interval
    opens and closes between engine steps).  The slots of one decode
    dispatch share its end; its steps are the most tokens a slot took."""
    lo, hi = interval
    out = {"prefill_flops": 0.0, "prefills": 0, "decode_flops": 0.0,
           "decode_tokens": 0, "kv_bytes": 0.0, "kernel_flops": 0.0}
    steps: Dict[float, int] = {}
    for tr in engine.obs.traces.completed:
        if tr.first_token_s is None:
            continue
        S = tr.prompt_len
        if lo <= tr.admit_s and tr.first_token_s <= hi:
            out["prefill_flops"] += work.prefill_flops(conf, S)
            out["prefills"] += 1
        emitted = 1
        for t_end, n in tr.chunks:
            if lo <= t_end <= hi:
                for j in range(n):
                    ctx = S + emitted + j       # positions the query attends
                    out["decode_flops"] += work.decode_flops(conf, ctx)
                    out["kv_bytes"] += work.kv_read_bytes(conf, ctx)
                    out["kernel_flops"] += work.kernel_flops(conf, ctx)
                out["decode_tokens"] += n
                steps[t_end] = max(steps.get(t_end, 0), n)
            emitted += n
    out["decode_steps"] = sum(steps.values())
    return out


def device_info(devices, chips: int, trace=None) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    info = {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}
    if trace is not None:
        info["busy_s"] = trace.busy_s
        info["window_s"] = trace.window_s
    return info


def end_to_end(cell: Cell, served: List[Served], seconds: float,
               setup_s: float) -> Dict[str, dict]:
    arrived = [s for s in served if s.arrival_s < seconds]
    ttft = [s.ttft_s if (s.finished and s.ttft_s is not None) else math.inf
            for s in arrived]
    tpot = [s.tpot_s for s in arrived if s.tpot_s is not None]
    values = {
        # every output token delivered inside the window, of requests
        # finished or still in flight when it closed
        "tokens_per_s": sum(n for s in served for t, n in s.delivered
                            if t <= seconds) / seconds,
        "ttft_p95_s": percentile(ttft, 95),
        "tpot_p95_s": percentile(tpot, 95),
        "setup_s": setup_s,
    }
    out = {}
    for m in cell.end_to_end:
        v = values[m["name"]]
        if v is None or not math.isfinite(v):
            raise RuntimeError(f"{m['name']} is {v}: requests failed")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def set_up(cell: Cell, seed: int, device, clock, fault=None):
    """Weights from the seed, the engine, and its warm-up.  Returns
    (weights, engine, compiles before warm-up, seconds of each part)."""
    import jax

    import program
    import weights
    times = {}
    with jax.default_device(device):
        t = time.perf_counter()
        params = weights.make_params(cell.conf, seed)
        jax.block_until_ready(params)
        times["weights"] = time.perf_counter() - t
        t = time.perf_counter()
        engine = program.build_engine(cell.conf, cell.params, params,
                                      cell.max_seq, device)
        jax.block_until_ready(engine.params)
        times["engine"] = time.perf_counter() - t
        if fault is not None:
            fault(engine)
        t = time.perf_counter()
        c_before = clock.compiles
        times["buckets"] = len(warm_up(engine, cell, program))
        times["warm_up"] = time.perf_counter() - t
    return params, engine, c_before, times


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log("the program (src/repro) is not in this checkout")
        return 2
    cell = load_cell(args.workload)
    return run(cell, args.seed, args.seconds, bool(args.trace))


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, fault=None) -> int:
    """One run of ``cell``; prints the result line.  ``require_tpu=False``
    and ``fault`` (called with the engine before warm-up) exist for the
    tests, which drive a run on the CPU, without the persistent compile
    cache, with the timed path broken underneath."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform} devices")
        return 1
    if len(devices) < cell.chips:
        log(f"the cell needs {cell.chips} chips, JAX found {len(devices)}")
        return 1
    if cell.chips != 1:
        log("only one-chip cells are built")
        return 1

    import check
    import program
    from peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind) if require_tpu else None
    cache_dir = (program.enable_compile_cache(str(CACHE_DIR))
                 if require_tpu else None)
    clock = program.CompileClock()
    device = devices[0]

    params, engine, c_before, times = set_up(cell, seed, device, clock,
                                             fault)
    arrivals = traffic_lib.generate(cell.mix, cell.params["rate_per_s"],
                                    seconds, seed,
                                    cell.conf["vocab_size"])
    marks = program.metric_marks(engine)
    compiles_setup = clock.compiles
    setup_s = time.perf_counter() - T_PROCESS
    log(f"set-up {setup_s:.2f}s: weights {times['weights']:.2f}s, engine "
        f"{times['engine']:.2f}s, warm-up {times['warm_up']:.2f}s "
        f"({times['buckets']} prefill buckets, {clock.compiles - c_before} "
        f"compiles); backend compiles "
        f"{clock.compiles} ({clock.seconds:.2f}s), cache hits "
        f"{clock.cache_hits} ({clock.cache_load_s:.2f}s), cache {cache_dir}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        with jax.default_device(device):
            served, interval = serve_window(engine, program, arrivals,
                                            seconds, trace_dir)
        compiles_window = clock.compiles - compiles_setup
        registry = Registry(*program.metrics_since(engine, marks))
        reduction = None
        scope_s = None
        tw = None
        if trace:
            import trace_reduce
            xplane = trace_reduce.find_xplane(trace_dir)
            reduction = trace_reduce.reduce_file(xplane)
            scope_s = program.scope_seconds(xplane,
                                            program.op_scopes(engine))
            if interval is None:
                raise RuntimeError("the window ended before its traced part")
            tw = traced_work(engine, cell.conf, interval)
        info = device_info(devices, cell.chips, reduction)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    failed = sum(1 for s in served if not s.finished)
    ctx = Context(cell, seconds, served, registry, peaks, reduction,
                  scope_s, tw)
    log(f"window: {len(served)} requests, {failed} not finished, compiles "
        f"in window {compiles_window}")

    del engine
    gc.collect()
    with jax.default_device(device):
        verdict = check.check(cell, params, served, seed)
    for line in verdict.lines():
        log(line)
    result = {"correct": verdict.correct, "attempted": len(served),
              "failed": failed}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = info
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduction.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in reduction.idle_gaps[:10]]}
    else:
        result["metrics"] = end_to_end(cell, served, seconds, setup_s)
        result["device"] = info
    result["compiles_in_window"] = compiles_window
    result["check"] = verdict.numbers()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
