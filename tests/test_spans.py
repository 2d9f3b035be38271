"""Spans and named scopes on the profiler's clock: the engine's host spans
in a CPU trace, its host time per step, its transfer counters, the op->scope
map of its compiled programs (also when loaded from the persistent compile
cache), and the names of the Pallas calls."""
import glob
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models.registry import build_model
from repro.obs import Obs
from repro.obs import scopes as scopes_lib
from repro.obs.metrics import Histogram
from repro.serve.engine import ContinuousEngine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine():
    cfg = get_smoke_config("qwen3-4b").replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return ContinuousEngine(cfg, params, max_slots=2, max_seq=64,
                            page_size=16, decode_chunk=4)


def _reqs():
    rng = np.random.RandomState(0)
    return [Request(prompt=rng.randint(1, 500, size=s).astype(np.int32),
                    max_new_tokens=n, id=i)
            for i, (s, n) in enumerate([(20, 9), (12, 6), (30, 7)])]


@pytest.fixture(scope="module")
def traced_serve(tmp_path_factory):
    """A cold tiny serve recorded with the JAX profiler: (engine, host
    events of the serving thread as (start, end, name, stats))."""
    engine = _engine()
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        engine.generate(_reqs())
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    from jax.profiler import ProfileData
    pdata = ProfileData.from_file(path)
    host = next(p for p in pdata.planes if p.name == "/host:CPU")
    for line in host.lines:
        evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                dict(ev.stats) if ev.name == "engine.prefill" else {})
               for ev in line.events]
        if any(name == "engine.step" for _, _, name, _ in evs):
            return engine, evs
    raise AssertionError("no engine.step span in the trace")


def _parent(ev, events):
    """Innermost other event that encloses ``ev``."""
    s, e = ev[0], ev[1]
    outer = [o for o in events if o is not ev and o[0] <= s and e <= o[1]
             and (o[1] - o[0]) >= (e - s)]
    return min(outer, key=lambda o: o[1] - o[0])[2] if outer else None


def test_engine_spans_nest_as_named(traced_serve):
    _, events = traced_serve
    spans = [ev for ev in events if ev[2].split(".")[0] in
             ("engine", "sched", "health", "obs")]
    parents = {}
    for ev in spans:
        parents.setdefault(ev[2], set()).add(_parent(ev, spans))
    assert parents["engine.step"] == {None}
    for name in ("sched.admit", "engine.prefill", "sched.grow",
                 "engine.decode.launch", "engine.decode.fence",
                 "engine.decode.fetch", "engine.decode.emit", "obs.tick"):
        assert parents[name] == {"engine.step"}, name
    for name in ("launch", "fence", "fetch"):
        assert parents[f"engine.prefill.{name}"] == {"engine.prefill"}
    assert parents["health.fold"] <= {"engine.decode.emit",
                                      "engine.prefill"}
    assert "engine.decode.emit" in parents["health.fold"]
    assert "engine.decode.emit" in parents["sched.retire"]
    # a cold engine compiles inside the launch that first needs a program
    assert parents["engine.compile"] == {"engine.prefill.launch",
                                         "engine.decode.launch"}


def test_prefill_span_carries_the_request_order(traced_serve):
    _, events = traced_serve
    orders = sorted(int(st["order"]) for _, _, name, st in events
                    if name == "engine.prefill")
    assert orders == [0, 1, 2]


def test_step_host_time_once_per_decode_step(traced_serve):
    engine, events = traced_serve
    h = engine.obs.registry.histogram("engine.step_host_s")
    steps = [ev for ev in events if ev[2] == "engine.step"]
    decoding = [s for s in steps if any(
        ev[2] == "engine.decode.launch" and s[0] <= ev[0] and ev[1] <= s[1]
        for ev in events)]
    assert h.count == len(decoding) == engine.stats()["dispatches"]
    assert len(steps) >= len(decoding)
    # host time excludes the fences: never more than the step's span
    longest = max((s[1] - s[0]) * 1e-9 for s in decoding)
    assert 0 < h.max <= longest + 1e-3


def test_host_transfers_counted_where_they_happen(traced_serve):
    engine, _ = traced_serve
    v = engine.obs.registry.value
    d2h = v("engine.host_transfers", dir="d2h")
    h2d = v("engine.host_transfers", dir="h2d")
    chunks = engine.stats()["dispatches"]
    prefills = engine.obs.registry.histogram(
        "engine.prefill_dispatch_s").count
    # a chunk reads buf, cur, pos, rem, done, anom and the health vector;
    # a prefill reads its token, the guard's flag and its health vector
    assert d2h == 7 * chunks + 3 * prefills
    # a chunk uploads cur, pos, rem and, when it changed, the block table;
    # a prefill uploads its tokens, pages and true length
    assert 3 * (chunks + prefills) < h2d <= 4 * chunks + 3 * prefills


def test_span_is_a_trace_annotation():
    span = Obs.span("engine.prefill", order=3)
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:                       # inert without a profiler running
        pass


def test_op_scopes_name_the_layer_kinds(traced_serve):
    engine, _ = traced_serve
    maps = engine.op_scopes()
    assert "decode_chunk" in maps
    prefills = [k for k in maps if k.startswith("prefill_")]
    assert prefills
    for kind in ["decode_chunk"] + prefills:
        prog = maps[kind]
        found = {k for path in prog["ops"].values()
                 for k in scopes_lib.kinds(path)}
        assert {"spectral", "lm_head", "attention", "layers", "mlp",
                "embed", "final_norm", "sample", "health",
                "kv_write"} <= found, (kind, found)
    assert maps["decode_chunk"]["module"] == "jit_decode_loop"
    assert maps[prefills[0]]["module"] == "jit_prefill_pack"
    # the spectral projections run inside the layers, the head outside
    paths = maps["decode_chunk"]["ops"].values()
    assert any("layers" in p and "attention" in p and "spectral" in p
               for p in paths)
    assert all(scopes_lib.top_scope(p) == "lm_head"
               for p in paths if "lm_head" in scopes_lib.kinds(p))


def test_op_scopes_from_the_persistent_compile_cache(tmp_path):
    """A program loaded from JAX's persistent cache keeps its op_name
    metadata: the second process compiles nothing and reads the same
    map."""
    script = textwrap.dedent("""
        import json, sys
        import jax, jax.numpy as jnp
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        hits = []
        jax.monitoring.register_event_listener(
            lambda e, **_: hits.append(e)
            if e == "/jax/compilation_cache/cache_hits" else None)
        from repro.obs.scopes import program_scopes

        def f(x):
            with jax.named_scope("lm_head"):
                y = x @ x.T
            with jax.named_scope("sample"):
                return jnp.argmax(y, axis=-1)

        c = jax.jit(f).lower(jnp.ones((8, 8))).compile()
        print(json.dumps({"hits": len(hits), "scopes": program_scopes(c)}))
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    import json
    outs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    assert outs[0]["hits"] == 0 and outs[1]["hits"] > 0
    assert outs[0]["scopes"] == outs[1]["scopes"]
    kinds = {k for path in outs[1]["scopes"]["ops"].values()
             for k in scopes_lib.kinds(path)}
    assert kinds == {"lm_head", "sample"}


def _pallas_names(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    names = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                names.append(getattr(name, "name", name))
            for v in eqn.params.values():       # jit, scan, cond bodies
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)
    walk(jaxpr.jaxpr)
    return names


def test_pallas_calls_carry_their_names():
    from repro.kernels import bc_fused, flash_attention, spectral_matmul
    from repro.kernels import ops as kops
    f32 = lambda *s: np.ones(s, np.float32)      # noqa: E731
    table = np.array([[1, 2], [3, 4]], np.int32)
    pos = np.array([10, 3], np.int32)
    pool = f32(2, 5, 8, 2, 32)
    cases = {
        "paged_attention": (lambda *a: kops.paged_attention(
            *a, 1, mode="interpret"), (f32(2, 4, 32), pool, pool, table,
                                       pos)),
        "paged_gather": (lambda p, t: kops.paged_gather(
            p, t, 1, mode="interpret"), (pool, table)),
        "flash_attention": (lambda *a: flash_attention.flash_attention(
            *a, interpret=True), (f32(1, 2, 8, 32), f32(1, 1, 8, 32),
                                  f32(1, 1, 8, 32))),
        "spectral_matmul": (lambda *a: spectral_matmul.spectral_matmul(
            *a, interpret=True), (f32(3, 8, 4), f32(3, 8, 4),
                                  f32(3, 4, 8), f32(3, 4, 8),
                                  f32(3, 4, 8))),
        "bc_fused": (lambda *a: bc_fused.bc_fused_matmul(
            *a, k=4, interpret=True), (f32(8, 2, 4), f32(2, 2, 3),
                                       f32(2, 2, 3), f32(2, 2, 3))),
    }
    for name, (fn, args) in cases.items():
        assert _pallas_names(fn, *args) == [name]
    # no scope may read as the kernel: its roofline finds it by name
    assert not any("paged_attention" in s for s in scopes_lib.SCOPES)


def test_parse_hlo_and_top_scope():
    text = textwrap.dedent("""\
        HloModule jit_decode_loop, is_scheduled=true

        ENTRY %main.1 (x.1: f32[8]) -> f32[8] {
          %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
          %fusion.3 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%c, metadata={op_type="dot_general" op_name="jit(decode_loop)/while/body/layers/while/body/attention/spectral/dot_general" stack_frame_id=3}
          %copy.2 = f32[8]{0} copy(%fusion.3)
          ROOT %dot.9 = f32[8]{0} dot(%copy.2, %x.1), metadata={op_name="jit(decode_loop)/lm_head/dot_general"}
        }
        """)
    prog = scopes_lib.parse_hlo(text)
    ops = prog["ops"]
    assert prog["module"] == "jit_decode_loop"
    assert ops["fusion.3"].endswith("attention/spectral/dot_general")
    assert "copy.2" not in ops
    assert prog["types"]["copy.2"] == "f32[8]{0}"
    assert prog["types"]["dot.9"] == "f32[8]{0}"
    assert scopes_lib.kinds(ops["fusion.3"]) == ("layers", "attention",
                                                  "spectral")
    assert scopes_lib.top_scope(ops["dot.9"]) == "lm_head"
    assert scopes_lib.top_scope(ops["x.1"]) == scopes_lib.UNSCOPED
    assert scopes_lib.top_scope(None) == scopes_lib.UNSCOPED


def test_histogram_mark_and_values_since():
    h = Histogram(keep=5)
    for v in (1.0, 2.0):
        h.observe(v)
    m = h.mark()
    assert h.values_since(m) == []
    for v in (3.0, 4.0, 5.0, 6.0):
        h.observe(v)
    assert h.values_since(m) == [3.0, 4.0, 5.0]     # keep=5 retained
    assert h.values_since(0) == [1.0, 2.0, 3.0, 4.0, 5.0]
    assert h.count == 6


def test_device_seconds_by_hand():
    """Leaf ops go under the module event that encloses them, looked up in
    the map of that module's program: of two programs of one name, the
    one whose instructions have the ops' result types."""
    from types import SimpleNamespace as NS

    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur)

    small = {"module": "jit_prefill_pack",
             "ops": {"fusion.1": "jit(prefill_pack)/layers/while/body/"
                                 "attention/spectral/dot_general",
                     "dot.2": "jit(prefill_pack)/lm_head/dot_general"},
             "types": {"fusion.1": "f32[1,128]{1,0}",
                       "dot.2": "f32[1,512]{1,0}", "copy.3": "f32[4]{0}",
                       "while.4": "(s32[], f32[1,128]{1,0})"}}
    big = {"module": "jit_prefill_pack",
           "ops": {"fusion.1": "jit(prefill_pack)/lm_head/dot_general"},
           "types": {"fusion.1": "f32[1,256]{1,0}"}}
    ops = [ev("%while.4 = (s32[], f32[1,128]{1,0}) while(%t), body=%b",
              100, 700),
           ev("%fusion.1 = f32[1,128]{1,0} fusion(%a), kind=kLoop",
              150, 300),
           ev("%dot.2 = f32[1,512]{1,0} dot(%x, %y)", 500, 200),
           ev("%copy.3 = f32[4]{0} copy(%z)", 820, 50),
           ev("%fusion.9 = f32[2]{0} fusion(%q)", 2000, 10)]   # no module
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules",
           events=[ev("jit_prefill_pack(42)", 100, 800)]),
        NS(name="XLA Ops", events=ops)])
    out = scopes_lib.device_seconds([dev, NS(name="/host:CPU", lines=[])],
                                    [big, small])
    d = out["jit_prefill_pack"]
    assert d["runs"] == 1
    assert d["leaf_s"] == pytest.approx(550e-9)          # while left out
    assert d["top"] == pytest.approx({"layers": 300e-9, "lm_head": 200e-9,
                                      "unscoped": 50e-9})
    assert d["under"]["spectral"] == pytest.approx(300e-9)
    assert d["unscoped_ops"] == pytest.approx({"copy.3": 50e-9})
