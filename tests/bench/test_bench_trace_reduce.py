"""The reduction from a profiler trace to busy time, program and kernel
time and idle gaps: on hand-made planes whose answer is known, and on a
small trace recorded on a TPU v5 lite (``benchmarks/chip/testdata``)."""
import os
from types import SimpleNamespace as NS

import importlib.util

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                    "benchmarks", "chip", "testdata", "small.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def hand_made():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 10000),
        ev("bench.step", 1000, 4000),
        ev("bench.wait", 5000, 3000),
        ev("bench.step", 8000, 3000)])])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("fusion.1", 500, 1000),     # clipped at 1000
                         ev("paged_attention_kernel", 1500, 2000),
                         ev("fusion.2", 3000, 1000),     # overlaps the above
                         ev("fusion.3", 9000, 1000),
                         ev("fusion.4", 12000, 500)],    # after the window
                XLA_Modules=[ev("jit_prefill_pack(12)", 1000, 3000),
                             ev("jit_decode_loop(7)", 9000, 1000)])
    return [host, dev, plane("/device:TPU:1")]


def test_busy_program_kernel_and_gaps_by_hand():
    r = tr.reduce_planes(hand_made())
    assert r.window_s == pytest.approx(10000e-9)
    # busy: [1000, 1500] + [1500, 3500] + [3000, 4000] + [9000, 10000]
    # = [1000, 4000] + [9000, 10000] = 4000 ns
    assert r.devices[0].busy_s == pytest.approx(4000e-9)
    assert r.busy_s == pytest.approx(4000e-9)       # TPU:1 ran nothing
    assert r.program_s("prefill_pack") == pytest.approx(3000e-9)
    assert r.program_count("decode_loop") == 1
    assert r.op_s("paged_attention") == pytest.approx(2000e-9)
    # gaps: [4000, 9000] (5000 ns, its middle in bench.wait) and
    # [10000, 11000] (1000 ns, in the second bench.step)
    assert r.idle_gaps == [("bench.wait", pytest.approx(5000e-9)),
                           ("bench.step", pytest.approx(1000e-9))]
    assert r.top_ops(1) == [("paged_attention_kernel",
                             pytest.approx(2000e-9))]


def test_a_stalled_enqueue_does_not_shift_the_device_clock():
    """The device clock runs 1000 ns behind the host's.  The second
    program's enqueue stalls for 5000 ns after the device has started it:
    aligning by the enqueue's end would shift the window by the stall and
    cut the second kernel off; its start bounds the shift."""
    def enq(start, dur, run):
        return NS(name="DoEnqueueProgram", start_ns=start, duration_ns=dur,
                  stats=[("run_id", run)])

    def mod(name, start, dur, run):
        return NS(name=name, start_ns=start, duration_ns=dur,
                  stats=[("run_id", run)])

    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 10000, 10000), enq(10300, 50, 1),
        enq(18850, 5000, 2)])])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("kernel", 9500, 1000), ev("kernel", 18000, 900)],
                XLA_Modules=[mod("jit_decode_loop(1)", 9400, 1200, 1),
                             mod("jit_decode_loop(1)", 17900, 1050, 2)])
    r = tr.reduce_planes([host, dev])
    assert r.op_s("kernel") == pytest.approx(1900e-9)
    assert r.program_s("decode_loop") == pytest.approx(2250e-9)
    assert r.busy_s == pytest.approx(1900e-9)


def test_no_window_span_is_an_error():
    planes = hand_made()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        tr.reduce_planes(planes)


def test_recorded_trace():
    r = tr.reduce_file(DATA)
    assert 0.05 < r.window_s < 5.0
    assert 0.0 < r.busy_s < r.window_s
    assert r.program_count("prefill_pack") == 3
    assert r.program_count("decode_loop") == 2
    assert 0.0 < r.op_s("tpu_custom_call") <= r.program_s("decode_loop")
    assert r.program_s("prefill_pack") + r.program_s("decode_loop") <= \
        r.window_s
    # the 50 ms sleep is the longest stretch with nothing on the device
    name, seconds = r.idle_gaps[0]
    assert name == "bench.wait" and 0.045 < seconds < r.window_s


# The paged attention call as a TPU v5 lite trace names it in the served
# decode program of qwen3-4b: pool K (operand 3) placed in VMEM, pool V in HBM.
SERVED_KERNEL = (
    "%closed_call.12 = bf16[16,32,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call("
    "s32[16,28]{1,0:T(8,128)S(1)} %get-tuple-element.2101, "
    "s32[16]{0:T(128)S(1)} %get-tuple-element.2100, "
    "bf16[16,32,128]{2,1,0:T(8,128)(2,1)S(1)} %pad_maximum_fusion.3, "
    "f32[152,128,8,128]{3,2,1,0:T(8,128)S(1)} %fusion.590, "
    "f32[152,128,8,128]{3,2,1,0:T(8,128)} %fusion.596), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={'
    "s32[16,28]{1,0}, s32[16]{0}, bf16[16,32,128]{2,1,0}, "
    "f32[152,128,8,128]{3,2,1,0}, f32[152,128,8,128]{3,2,1,0}}")


def _roofline_metric():
    path = os.path.join(os.path.dirname(DATA), "..", "metrics",
                        "paged_attention_roofline.py")
    spec = importlib.util.spec_from_file_location("pa_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_operand_memory_spaces():
    assert tr.operand_spaces(SERVED_KERNEL) == [1, 1, 1, 1, 0]
    assert tr.op_name(SERVED_KERNEL) == \
        "%closed_call.12: custom-call tpu_custom_call"
    assert tr.operand_spaces(
        "%t = (f32[2]{0}, s32[]) custom-call(f32[2]{0} %a, "
        "f32[2]{0:T(2)S(1)} %b), custom_call_target=\"x\"") == [0, 1]
    assert tr.operand_spaces("fusion.1") == []


def test_roofline_counts_only_the_pool_operands_read_from_hbm():
    """The recorded kernel reads both pools from HBM; the served one reads
    K from VMEM, so only V's bytes bound it."""
    m = _roofline_metric()
    recorded = tr.reduce_file(DATA).op_texts("tpu_custom_call")
    assert m.hbm_share(recorded) == 1.0
    assert m.hbm_share([SERVED_KERNEL]) == 0.5
    assert m.hbm_share([SERVED_KERNEL] + recorded) == 0.5
    trace = NS(op_s=lambda name: 0.05 if name == m.PALLAS else 0.0,
               op_texts=lambda name: [SERVED_KERNEL])
    ctx = NS(trace=trace, peaks=NS(hbm_bytes_per_s=800e9,
                                   bf16_flops_per_s=200e12),
             traced_work={"decode_tokens": 8, "kv_bytes": 40e9,
                          "kernel_flops": 1e9})
    # 20 GB from HBM at 800 GB/s: 25 ms of the kernel's 50
    assert m.read(ctx) == pytest.approx(50.0)
