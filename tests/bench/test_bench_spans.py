"""The recorded engine trace with program spans (``testdata/
spans.xplane.pb``, a 2-layer qwen3-4b-width serve on a TPU v5 lite, and
``spans.scopes.json``, its op->scope maps): its idle gaps fall in the
engine's spans, and its device time falls under the named scopes.  Also
pins every field the trace reduction gives on ``small.xplane.pb``."""
import json
import os

import pytest

import trace_reduce as tr
from repro.obs import scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                    "benchmarks", "chip", "testdata")
SPANS = os.path.join(DATA, "spans.xplane.pb")
SCOPES = os.path.join(DATA, "spans.scopes.json")
PROGRAM_SPANS = ("engine.", "sched.", "health.", "obs.")


@pytest.fixture(scope="module")
def programs():
    with open(SCOPES) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def by_scope(programs):
    return scopes.device_seconds_file(SPANS, list(programs.values()))


def test_idle_gaps_fall_in_program_spans():
    """Under the innermost host span, each of the ten longest idle gaps is
    one of JAX's own spans (``np.asarray(jax.Array)`` in the fetch) or a
    program span; among the program spans alone, each is named by one of
    the engine's."""
    r = tr.reduce_file(SPANS)
    assert len(r.idle_gaps) == 10
    assert not any(name == "bench.step" for name, _ in r.idle_gaps)
    assert r.program_count("decode_loop") > 0
    assert r.program_count("prefill_pack") == 4
    innermost = tr._host_span_at
    try:
        tr._host_span_at = lambda t, spans: innermost(
            t, [s for s in spans if s[2].startswith(PROGRAM_SPANS)])
        by_program = tr.reduce_file(SPANS)
    finally:
        tr._host_span_at = innermost
    assert [s for _, s in by_program.idle_gaps] == [
        s for _, s in r.idle_gaps]
    for name, seconds in by_program.idle_gaps:
        assert name.startswith(PROGRAM_SPANS), (name, seconds)


def test_maps_cover_the_traced_programs(programs):
    assert {p["module"] for p in programs.values()} == {
        "jit_decode_loop", "jit_prefill_pack"}
    assert "decode_chunk" in programs


def test_device_time_by_scope(by_scope):
    dec = by_scope["jit_decode_loop"]
    pre = by_scope["jit_prefill_pack"]
    for d in (dec, pre):
        assert d["runs"] > 0
        assert sum(d["top"].values()) == pytest.approx(d["leaf_s"])
        for kind in ("layers", "attention", "mlp", "spectral", "lm_head"):
            assert d["under"][kind] > 0, kind
        # every layer kind lies inside the scanned stack
        assert d["under"]["attention"] <= d["under"]["layers"]
        assert d["under"]["spectral"] <= d["under"]["layers"]
    # what carries no scope: in decode, the copies XLA puts in for the
    # loop's carried pool (no op_name), in prefill a small rest
    unscoped = dec["top"][scopes.UNSCOPED]
    assert sum(s for n, s in dec["unscoped_ops"].items()
               if n.startswith("copy")) >= 0.8 * unscoped
    assert pre["top"].get(scopes.UNSCOPED, 0.0) < 0.05 * pre["leaf_s"]
    assert dec["under"]["sample"] > 0 and dec["under"]["health"] > 0
    assert pre["under"]["kv_write"] > 0


def test_leaf_time_fits_in_the_programs(by_scope):
    r = tr.reduce_file(SPANS)
    for mod, part in (("jit_decode_loop", "decode_loop"),
                      ("jit_prefill_pack", "prefill_pack")):
        assert 0 < by_scope[mod]["leaf_s"] <= r.program_s(part) * 1.001
        assert by_scope[mod]["runs"] == r.program_count(part)


def test_kernel_is_named_and_runs_once_per_layer_and_step():
    r = tr.reduce_file(SPANS)
    kernel = [n for d in r.devices.values() for n in d.op_s
              if "tpu_custom_call" in n]
    assert kernel and all("paged_attention" in n for n in kernel)


def test_small_trace_reduction_is_unchanged():
    """Every field of the reduction of ``small.xplane.pb``, as the
    benchmark read it when the trace was recorded."""
    r = tr.reduce_file(os.path.join(DATA, "small.xplane.pb"))
    assert r.window_s == pytest.approx(0.055679786, abs=1e-12)
    assert r.busy_s == pytest.approx(0.000196957, abs=1e-12)
    assert list(r.devices) == [0]
    d = r.devices[0]
    assert d.module_s == pytest.approx({"jit_prefill_pack": 0.000167511,
                                        "jit_decode_loop": 0.0000295})
    assert d.module_n == {"jit_prefill_pack": 3, "jit_decode_loop": 2}
    assert d.op_s == pytest.approx({
        "%copy-start: copy-start": 4e-08,
        "%copy-done: copy-done": 5.936e-06,
        "%convolution_tanh_fusion.3: fusion": 5.2521e-05,
        "%convolution_tanh_fusion.2: fusion": 3.4585e-05,
        "%convolution_tanh_fusion.1: fusion": 3.4584e-05,
        "%convolution_tanh_fusion: fusion": 3.9804e-05,
        "%decode_loop.1: custom-call tpu_custom_call": 2.9487e-05})
    assert set(d.op_text) == set(d.op_s)
    assert d.gaps[:3] == [(53747029.0, 105458318.0),
                          (51810817.0, 52823930.0),
                          (105472980.0, 106456050.0)]
    assert [n for n, _ in r.idle_gaps] == [
        "bench.wait", "bench.step", "bench.step", "bench.step",
        "bench.step", "PjitFunction(prefill_pack)",
        "PjitFunction(prefill_pack)", "PjitFunction(prefill_pack)",
        "bench.step", "PjitFunction(prefill_pack)"]
    assert [s for _, s in r.idle_gaps[:5]] == pytest.approx(
        [0.051711289, 0.001013113, 0.00098307, 0.000815398, 0.000602047])


@pytest.mark.parametrize("name,program,scope", [
    ("decode.lm_head_s_per_step", "jit_decode_loop", "lm_head"),
    ("decode.spectral_s_per_step", "jit_decode_loop", "spectral")])
def test_scope_seconds_per_decode_step(by_scope, name, program, scope):
    """The readers of the decode programs' named scopes, on the recorded
    trace: the scope's leaf seconds over the traced decode steps; silent
    without a trace, a step, or the scope."""
    import types

    import run
    metric = run.load_metric(name)
    ctx = types.SimpleNamespace(scope_s=by_scope,
                                traced_work={"decode_steps": 40})
    assert metric.read(ctx) == by_scope[program]["under"][scope] / 40
    assert metric.read(ctx) > 0
    ctx.traced_work = {"decode_steps": 0}
    assert metric.read(ctx) is None
    assert metric.read(types.SimpleNamespace(
        scope_s=None, traced_work={"decode_steps": 40})) is None
    bare = {program: dict(by_scope[program], under={})}
    assert metric.read(types.SimpleNamespace(
        scope_s=bare, traced_work={"decode_steps": 40})) is None


def test_prefill_head_share(by_scope):
    import types

    import run
    metric = run.load_metric("prefill.lm_head_share")
    pre = by_scope["jit_prefill_pack"]
    share = metric.read(types.SimpleNamespace(scope_s=by_scope))
    assert share == 100.0 * pre["under"]["lm_head"] / pre["leaf_s"]
    assert 0 < share < 100
    assert metric.read(types.SimpleNamespace(scope_s={})) is None
