"""repro.obs.prof + chrometrace + benchmarks/gate.py: dispatch-level
roofline attribution invariants, Chrome-trace schema + slice accounting,
the --trace-out round-trip through a real serve, Prometheus exposition,
and the perf-regression gate's direction-aware rules."""
import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models.registry import build_model
from repro.obs import Obs, aot_compile, prometheus_text, resolve_hardware
from repro.obs.chrometrace import (PID_ENGINE, PID_REQUESTS, build_trace,
                                   request_events, validate_trace,
                                   write_trace)
from repro.obs.metrics import Gauge, Registry
from repro.obs.prof import DispatchCost, Profiler
from repro.roofline.analysis import (HARDWARE_PRESETS, HOST_CPU, TPU_V4,
                                     TPU_V5E, HardwareSpec, detect_hardware)
from repro.serve.engine import ContinuousEngine, Engine, Request

_GATE_PATH = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                          "gate.py")
_spec = importlib.util.spec_from_file_location("bench_gate", _GATE_PATH)
gate = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gate)


# ---------------------------------------------------------------------------
# HardwareSpec + Profiler core
# ---------------------------------------------------------------------------
def test_hardware_presets():
    assert set(HARDWARE_PRESETS) >= {"tpu-v5e", "tpu-v4", "host-cpu",
                                     "gpu-generic"}
    for spec in HARDWARE_PRESETS.values():
        assert spec.peak_flops > 0 and spec.hbm_bw > 0
        assert spec.ridge_flops_per_byte == pytest.approx(
            spec.peak_flops / spec.hbm_bw)
    assert resolve_hardware("tpu-v5e") is TPU_V5E
    assert resolve_hardware("auto") is detect_hardware()
    with pytest.raises(ValueError):
        resolve_hardware("abacus")


def test_detect_hardware_keys_on_device_kind():
    """Peaks come from the device kind JAX reports; the host preset covers
    platform cpu only, and an accelerator with no preset is an error."""
    from types import SimpleNamespace as Dev
    assert detect_hardware() is HOST_CPU                 # this CPU backend
    assert detect_hardware(Dev(platform="cpu", device_kind="cpu")) \
        is HOST_CPU
    assert detect_hardware(Dev(platform="tpu",
                               device_kind="TPU v5 lite")) is TPU_V5E
    assert detect_hardware(Dev(platform="tpu", device_kind="TPU v4")) \
        is TPU_V4
    with pytest.raises(ValueError, match="TPU v9"):
        detect_hardware(Dev(platform="tpu", device_kind="TPU v9"))
    with pytest.raises(ValueError, match="H100"):
        detect_hardware(Dev(platform="gpu", device_kind="H100"))


def test_aot_compile_raises_on_compile_error():
    """A program the backend refuses fails loudly instead of falling back
    to an uncosted jit wrapper: here the Pallas TPU kernel, lowered without
    interpret mode, on the CPU."""
    from repro.kernels.paged_attention import paged_attention_kernel
    pool = jnp.ones((1, 2, 4, 1, 8))
    args = (jnp.ones((1, 2, 8)), pool, pool, jnp.ones((1, 1), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.int32(0))
    prof = Profiler(Registry(), hardware=HOST_CPU)
    with pytest.raises(ValueError, match="interpret"):
        aot_compile(jax.jit(paged_attention_kernel), args, prof, "pa")
    assert "pa" not in prof.costs


def test_dispatch_cost_bound_sides():
    spec = HardwareSpec("toy", peak_flops=100.0, hbm_bw=10.0)
    # intensity above the ridge (10 FLOP/byte) -> compute-bound
    c = DispatchCost("k", flops=1000.0, bytes_accessed=10.0,
                     t_compute_s=10.0, t_memory_s=1.0)
    assert c.bound == "compute" and c.bound_s == 10.0
    c = DispatchCost("k", flops=10.0, bytes_accessed=1000.0,
                     t_compute_s=0.1, t_memory_s=100.0)
    assert c.bound == "memory" and c.bound_s == 100.0
    assert c.intensity == pytest.approx(0.01)
    del spec


def test_profiler_register_and_dispatch():
    reg = Registry()
    prof = Profiler(reg, hardware=HOST_CPU)
    fn = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64), jnp.float32)
    compiled, cost = aot_compile(fn, (x,), prof, "matmul")
    assert cost is not None and cost.kind == "matmul"
    assert cost.flops > 0 and cost.bytes_accessed > 0
    # the compiled executable is callable and agrees with the jit wrapper
    assert float(compiled(x)) == pytest.approx(float(fn(x)))
    prof.on_dispatch(cost, 0.0, 0.5)
    prof.on_dispatch(cost, 0.5, 0.6)
    s = prof.summary()["matmul"]
    assert s["dispatches"] == 2
    # achieved rates are flops/dt means: (f/0.5 + f/0.1)/2
    want = (cost.flops / 0.5 + cost.flops / 0.1) / 2
    assert s["achieved_flops_per_s"] == pytest.approx(want)
    assert s["achieved_bytes_per_s"] > 0
    assert s["roofline_frac"] > 0
    assert s["roofline_frac_max"] >= s["roofline_frac_p50"]
    # events logged on the obs clock for the chrome exporter
    assert [e[0] for e in prof.events] == ["matmul", "matmul"]
    # histograms landed in the registry under dispatch labels
    snap = reg.snapshot()
    assert "prof.roofline_frac{dispatch=matmul}" in snap["histograms"]
    assert "prof.flops_per_s{dispatch=matmul}" in snap["histograms"]


def test_profiler_disabled_is_noop():
    reg = Registry()
    prof = Profiler(reg, hardware=HOST_CPU, enabled=False)
    fn = jax.jit(lambda x: x * 2)
    compiled, cost = aot_compile(fn, (jnp.ones(4),), prof, "x2")
    prof.on_dispatch(cost, 0.0, 1.0)
    prof.watch("some.gauge")
    assert len(prof.events) == 0 and prof.samples == {}
    assert prof.summary()["x2"]["dispatches"] == 0


def test_profiler_watch_samples_gauges():
    reg = Registry()
    prof = Profiler(reg, hardware=HOST_CPU)
    g = reg.gauge("pool.free_pages")
    prof.watch("pool.free_pages")
    prof.watch("pool.free_pages")            # idempotent
    fn = jax.jit(lambda x: x + 1)
    _, cost = aot_compile(fn, (jnp.ones(2),), prof, "inc")
    g.set(7)
    prof.on_dispatch(cost, 0.0, 0.1)
    g.set(3)
    prof.on_dispatch(cost, 0.1, 0.2)
    assert prof.samples["pool.free_pages"] == [(0.1, 7.0), (0.2, 3.0)]


# ---------------------------------------------------------------------------
# Gauge low-water mark
# ---------------------------------------------------------------------------
def test_gauge_min_seen():
    g = Gauge()
    assert g.min_seen is None                # no sample yet != 0 headroom
    for v, lo in [(5, 5), (9, 5), (2, 2), (4, 2)]:
        g.set(v)
        assert g.min_seen == lo
    assert g.max_seen == 9


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------
def test_prometheus_text_sections():
    reg = Registry()
    reg.counter("sched.admitted").inc(3)
    reg.gauge("pool.free_pages", pool="kv").set(11)
    h = reg.histogram("trace.ttft_s", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE sched_admitted_total counter" in lines
    assert "sched_admitted_total 3.0" in lines
    assert 'pool_free_pages{pool="kv"} 11.0' in lines
    # cumulative buckets + +Inf + sum/count
    assert 'trace_ttft_s_bucket{le="0.1"} 1' in lines
    assert 'trace_ttft_s_bucket{le="1.0"} 2' in lines
    assert 'trace_ttft_s_bucket{le="+Inf"} 3' in lines
    assert "trace_ttft_s_count 3" in lines
    assert any(l.startswith("trace_ttft_s_sum ") for l in lines)
    # snapshot round-trip gives the identical rendering
    assert prometheus_text(reg.snapshot()) == text


def test_prometheus_cli_reads_last_snapshot(tmp_path):
    from repro.obs.emit import main as emit_main
    path = tmp_path / "m.jsonl"
    reg = Registry()
    reg.counter("tokens").inc(5)
    lines = [{"type": "snapshot", "seq": 0, "t_s": 0.0,
              "counters": {"tokens": 1.0}, "gauges": {}, "histograms": {}},
             {"type": "snapshot", "seq": 1, "t_s": 1.0,
              **reg.snapshot()}]
    path.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
    assert emit_main(["--to-prom", str(path)]) == 0


# ---------------------------------------------------------------------------
# Chrome-trace exporter (unit level)
# ---------------------------------------------------------------------------
def _trace_obs():
    """An Obs with two finished requests + profiled dispatches."""
    obs = Obs()
    prof = obs.profiler
    fn = jax.jit(lambda x: x * 2)
    _, cost = aot_compile(fn, (jnp.ones(3),), prof, "decode_chunk")
    prof.on_dispatch(cost, 0.01, 0.02)
    prof.on_dispatch(cost, 0.03, 0.05)
    for order, (enq, adm, ft, ret) in enumerate(
            [(0.0, 0.01, 0.02, 0.05), (0.005, 0.02, 0.03, 0.06)]):
        tr = obs.trace_start(order, order, 4, enq)
        tr.mark_admit(adm)
        tr.mark_first_token(ft)
        tr.mark_chunk(ret, 2)
        tr.mark_retire(ret)
        obs.trace_finish(tr)
    return obs


def test_chrome_trace_schema_and_monotone_ts(tmp_path):
    obs = _trace_obs()
    path = tmp_path / "trace.json"
    trace = write_trace(obs, str(path))
    validate_trace(trace)                    # monotone non-negative ts
    on_disk = json.loads(path.read_text())   # valid JSON round-trip
    validate_trace(on_disk)
    assert on_disk == trace
    ts = [e["ts"] for e in trace["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts) and all(t >= 0 for t in ts)


def test_chrome_trace_request_slices_exact():
    # a served 4-mark trace contributes EXACTLY queue/prefill/decode
    obs = _trace_obs()
    trace = build_trace(obs)
    for order in (0, 1):
        slices = [e for e in trace["traceEvents"]
                  if e.get("pid") == PID_REQUESTS and e["ph"] == "X"
                  and e.get("tid") == order]
        assert [s["name"] for s in slices] == ["queue", "prefill", "decode"]
        for s in slices:
            assert s["args"]["status"] == "FINISHED"
            assert s["args"]["order"] == order
    kinds = {e["name"] for e in trace["traceEvents"]
             if e.get("pid") == PID_ENGINE and e["ph"] == "X"}
    assert kinds == {"decode_chunk"}


def test_chrome_trace_unserved_and_preempted_slices():
    from repro.obs.trace import RequestTrace
    # cancelled in queue: enqueue + retire only -> one "queue" slice
    tr = RequestTrace(id=0, order=0, prompt_len=4, enqueue_s=0.0)
    tr.status = "CANCELLED"
    tr.mark_retire(0.5)
    ev = request_events(tr)
    assert [e["name"] for e in ev if e["ph"] == "X"] == ["queue"]
    assert ev[0]["args"]["status"] == "CANCELLED"
    # preemptions render as thread-scoped instants
    tr2 = RequestTrace(id=1, order=1, prompt_len=4, enqueue_s=0.0)
    tr2.mark_admit(0.1)
    tr2.mark_first_token(0.2)
    tr2.mark_preempt(0.3, 2)
    tr2.mark_retire(0.4)
    tr2.status = "FINISHED_BUDGET"
    ev2 = request_events(tr2)
    inst = [e for e in ev2 if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "preempt"
    assert inst[0]["args"]["recompute_tokens"] == 2


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace({"events": []})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [
            {"ph": "X", "pid": 1, "name": "a", "ts": -1.0, "dur": 1.0}]})
    with pytest.raises(ValueError):           # unsorted
        validate_trace({"traceEvents": [
            {"ph": "X", "pid": 1, "name": "a", "ts": 5.0, "dur": 1.0},
            {"ph": "X", "pid": 1, "name": "b", "ts": 1.0, "dur": 1.0}]})


# ---------------------------------------------------------------------------
# Engine integration (smoke model, module-scoped)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("tinyllama-1.1b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _reqs(n, new=5):
    rng = np.random.RandomState(0)
    return [Request(prompt=rng.randint(0, 512, size=rng.randint(3, 12))
                    .astype(np.int32), max_new_tokens=new, id=i)
            for i in range(n)]


@pytest.mark.parametrize("engine_cls", [Engine, ContinuousEngine],
                         ids=["batch", "continuous"])
def test_engine_roofline_stats(setup, engine_cls):
    cfg, params = setup
    kw = (dict(max_batch=2) if engine_cls is Engine
          else dict(max_slots=2, page_size=8))
    eng = engine_cls(cfg, params, max_seq=32, precompute=False, **kw)
    eng.generate(_reqs(3))
    st = eng.stats()
    assert st["hardware"] in HARDWARE_PRESETS
    roof = st["roofline"]
    assert roof, "no dispatch kinds profiled"
    # both engines: every kind reports roofline fraction + achieved bytes/s
    prefill_kinds = [k for k in roof if k.startswith("prefill")]
    decode_kinds = [k for k in roof if "decode" in k]
    assert prefill_kinds and decode_kinds
    for kind, r in roof.items():
        assert r["dispatches"] >= 1, kind
        assert r["flops"] > 0 and r["bytes_accessed"] > 0
        assert r["achieved_flops_per_s"] > 0
        assert r["achieved_bytes_per_s"] > 0
        assert r["roofline_frac"] > 0
        assert r["bound"] in ("compute", "memory")


def test_continuous_min_free_pages(setup):
    cfg, params = setup
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32,
                           page_size=8, precompute=False)
    eng.generate(_reqs(3))
    st = eng.stats()
    # the pool drained below its resting level and refilled at retire
    assert 0 <= st["min_free_pages"] < st["free_pages"]
    # everything returned (num_pages includes the reserved trash page)
    assert st["free_pages"] == eng.num_pages - 1


def test_trace_out_round_trip_real_serve(setup, tmp_path):
    """--trace-out through a real 2-request continuous serve: the file is
    Perfetto-loadable, has one lane per request, engine dispatch lanes,
    and counter tracks."""
    cfg, params = setup
    obs = Obs()
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32,
                           page_size=8, precompute=False, obs=obs)
    results = eng.generate(_reqs(2))
    assert all(r["status"].startswith("FINISHED") for r in results)
    path = tmp_path / "serve_trace.json"
    trace = write_trace(obs, str(path))
    validate_trace(json.loads(path.read_text()))
    req_lanes = {e["tid"] for e in trace["traceEvents"]
                 if e.get("pid") == PID_REQUESTS and e["ph"] == "X"}
    assert req_lanes == {0, 1}
    # every finished request contributes exactly its trace's slices
    for order in req_lanes:
        names = [e["name"] for e in trace["traceEvents"]
                 if e.get("pid") == PID_REQUESTS and e["ph"] == "X"
                 and e["tid"] == order]
        assert names == ["queue", "prefill", "decode"]
    kinds = {e["name"] for e in trace["traceEvents"]
             if e.get("pid") == PID_ENGINE and e["ph"] == "X"}
    assert "decode_chunk" in kinds
    assert any(k.startswith("prefill_") for k in kinds)
    counters = {e["name"] for e in trace["traceEvents"] if e["ph"] == "C"}
    assert {"pool.free_pages", "sched.queue_depth"} <= counters


# ---------------------------------------------------------------------------
# benchmarks/gate.py
# ---------------------------------------------------------------------------
_BENCH = {
    "arch": "tiny", "requests": 4,
    "modes": {
        "poisson": {"continuous": {"tokens": 100, "tokens_per_s": 1000.0,
                                   "p99_latency_s": 0.5,
                                   "makespan_s": 2.0}},
        "obs_overhead": {"overhead_frac": 0.005},
    },
    "speedup_continuous_vs_batch": 2.0,
    "lost_requests": 0,
    "some_new_metric": 42.0,
}


def _gate_rc(baseline, candidate, **kw):
    res = gate.compare(baseline, candidate, **kw)
    return 1 if res["failed"] else 0, res


def test_gate_pass_on_identical():
    rc, res = _gate_rc(_BENCH, copy.deepcopy(_BENCH))
    assert rc == 0
    assert all(r["verdict"] in ("PASS", "INFO") for r in res["rows"])


def test_gate_fails_on_throughput_drop():
    bad = copy.deepcopy(_BENCH)
    bad["modes"]["poisson"]["continuous"]["tokens_per_s"] = 800.0  # -20%
    rc, res = _gate_rc(_BENCH, bad)
    assert rc == 1
    failed = {r["metric"] for r in res["failed"]}
    assert failed == {"modes.poisson.continuous.tokens_per_s"}
    # a throughput RISE never fails
    good = copy.deepcopy(_BENCH)
    good["modes"]["poisson"]["continuous"]["tokens_per_s"] = 2000.0
    assert _gate_rc(_BENCH, good)[0] == 0


def test_gate_fails_on_latency_rise():
    bad = copy.deepcopy(_BENCH)
    bad["modes"]["poisson"]["continuous"]["p99_latency_s"] = 0.6  # +20%
    rc, res = _gate_rc(_BENCH, bad)
    assert rc == 1
    assert res["failed"][0]["metric"] == \
        "modes.poisson.continuous.p99_latency_s"
    # a latency DROP never fails
    good = copy.deepcopy(_BENCH)
    good["modes"]["poisson"]["continuous"]["p99_latency_s"] = 0.1
    assert _gate_rc(_BENCH, good)[0] == 0
    # tol-scale widens the band: +20% passes at scale 3 (45% tolerance)
    assert _gate_rc(_BENCH, bad, tol_scale=3.0)[0] == 0


def test_gate_exact_parity_and_unknown_default():
    bad = copy.deepcopy(_BENCH)
    bad["modes"]["poisson"]["continuous"]["tokens"] = 101   # parity break
    rc, res = _gate_rc(_BENCH, bad)
    assert rc == 1
    assert res["failed"][0]["rule"] == "exact"
    # unknown metrics default to informational: huge swing, no gate
    weird = copy.deepcopy(_BENCH)
    weird["some_new_metric"] = 42000.0
    rc, res = _gate_rc(_BENCH, weird)
    assert rc == 0
    row = next(r for r in res["rows"] if r["metric"] == "some_new_metric")
    assert row["verdict"] == "INFO" and row["pattern"] == "<unknown>"
    # schema drift is surfaced, not gated
    dropped = copy.deepcopy(_BENCH)
    del dropped["some_new_metric"]
    rc, res = _gate_rc(_BENCH, dropped)
    assert rc == 0 and res["only_baseline"] == ["some_new_metric"]


def test_gate_cli_and_markdown(tmp_path):
    b = tmp_path / "base.json"
    c = tmp_path / "cand.json"
    b.write_text(json.dumps(_BENCH))
    bad = copy.deepcopy(_BENCH)
    bad["modes"]["poisson"]["continuous"]["tokens_per_s"] = 700.0
    c.write_text(json.dumps(bad))
    out = tmp_path / "delta.md"
    rc = gate.main(["--baseline", str(b), "--candidate", str(c),
                    "--out", str(out)])
    assert rc == 1
    md = out.read_text()
    assert "| metric |" in md and "**FAIL**" in md
    assert "modes.poisson.continuous.tokens_per_s" in md
    # identical -> rc 0
    rc = gate.main(["--baseline", str(b), "--candidate", str(b)])
    assert rc == 0
