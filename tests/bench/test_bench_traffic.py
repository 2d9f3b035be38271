"""The traffic generator and the shape of a run's result line."""
import io
import json
import os
import contextlib

import numpy as np
import pytest

import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(HERE, "..", "..", "benchmarks", "chip", "traffic")


def load(name):
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_same_seed_same_requests(mix):
    m = load(mix)
    a = traffic.generate(m, 5.0, 20.0, 2**35 + 3, 151936)
    b = traffic.generate(m, 5.0, 20.0, 2**35 + 3, 151936)
    assert len(a) == len(b) == 100
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_seeds_reorder_one_set_of_work(mix):
    m = load(mix)
    a = traffic.generate(m, 5.0, 20.0, 1, 151936)
    b = traffic.generate(m, 5.0, 20.0, 2, 151936)
    key = lambda reqs: sorted((len(r.prompt), r.max_new_tokens) for r in reqs)
    assert key(a) == key(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    for reqs in (a, b):
        t = [r.arrival_s for r in reqs]
        assert t == sorted(t) and 0.0 <= t[0] and t[-1] <= 20.0


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_seeds_offer_the_same_work_by_each_block_end(mix):
    m = load(mix)
    sh = traffic.shape(m, 2.0, 50.0)
    block = np.floor(sh["times"] / traffic.REORDER_BLOCK_S)
    ends = [sh["times"][block == b].max() for b in np.unique(block)]
    assert len(ends) == 44          # the seconds that hold an arrival
    for seed in (1, 2, 2**33 + 1):
        reqs = traffic.generate(m, 2.0, 50.0, seed, 151936)
        for end in ends:
            got = sorted((len(r.prompt), r.max_new_tokens) for r in reqs
                         if r.arrival_s <= end + 1e-9)
            want = sorted(zip(sh["prompts"][sh["times"] <= end].tolist(),
                              sh["outputs"][sh["times"] <= end].tolist()))
            assert got == want


@pytest.mark.parametrize("mix,page", [("chat", 128), ("longdoc", 128)])
def test_bucket_set_does_not_depend_on_the_seed(mix, page):
    m = load(mix)
    buckets = traffic.prefill_buckets(m, page)
    assert buckets == list(range(buckets[0], buckets[-1] + 1))
    for seed in (0, 7, 2**33 + 1):
        reqs = traffic.generate(m, 6.0, 30.0, seed, 151936)
        used = {-(-len(r.prompt) // page) for r in reqs}
        assert used <= set(buckets)
        for r in reqs:
            assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
            assert m["output"]["min"] <= r.max_new_tokens <= \
                m["output"]["max"]
            assert r.prompt.min() >= 1 and r.prompt.max() < 151936
    assert traffic.prefill_buckets(m, page) == buckets


def test_chat_and_longdoc_bucket_counts():
    assert len(traffic.prefill_buckets(load("chat"), 128)) == 24
    assert len(traffic.prefill_buckets(load("longdoc"), 128)) == 33


CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "compiles_in_window", "check"]


def test_last_line_has_the_contract_keys(cell_factory):
    import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.run(cell_factory(), seed=2**34 + 5, seconds=1.5,
                     trace=False, require_tpu=False)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line) == CONTRACT_KEYS
    assert line["correct"] is True
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_p95_s",
                                    "tpot_p95_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert line["compiles_in_window"] == 0
    # the compared numbers close standard error, each beside its limit
    tail = err.getvalue().strip().splitlines()[-3:]
    assert [t.split(":")[0] for t in tail] == [
        "[bench] check unfinished_requests",
        "[bench] check wrong_length_requests",
        "[bench] check served_logit_gap"]
    assert all("limit" in t for t in tail)


def test_run_without_a_tpu_prints_nothing(cell_factory, capsys):
    import run
    assert run.run(cell_factory(), seed=1, seconds=1.0, trace=False) == 1
    assert capsys.readouterr().out == ""


def test_an_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError, match="arrival process"):
        traffic.generate(dict(load("chat"), arrivals="bursty"), 5.0, 10.0,
                         1, 151936)


def test_traced_run_line(cell_factory):
    """The ``--trace 1`` path end to end on the CPU: the CPU has no TPU
    plane, so the device metrics (the named scopes' among them) are silent
    and busy time is 0, but the line, its ``breakdown`` and the
    program-span metrics are there."""
    import run
    cell = cell_factory()
    cell.per_layer = [{"name": n, "unit": u} for n, u in (
        ("sched.queue_wait_p95_s", "s"), ("engine.prefill_s_p50", "s"),
        ("engine.decode_chunk_s_p50", "s"), ("prefill.mfu", "%"),
        ("decode.mfu", "%"), ("paged_attention_roofline", "%"),
        ("device.idle_share", "%"), ("engine.step_host_s_p50", "s"),
        ("prefill.lm_head_share", "%"), ("decode.lm_head_s_per_step", "s"),
        ("decode.spectral_s_per_step", "s"))]
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = run.run(cell, seed=21, seconds=3.0, trace=True,
                     require_tpu=False)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compiles_in_window",
                          "check"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"sched.queue_wait_p95_s",
                                    "engine.prefill_s_p50",
                                    "engine.decode_chunk_s_p50",
                                    "engine.step_host_s_p50",
                                    "device.idle_share"}
    assert line["metrics"]["engine.step_host_s_p50"]["value"] > 0
    assert line["metrics"]["device.idle_share"]["value"] == 100.0
    assert line["device"]["window_s"] > 0 and line["device"]["busy_s"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_traced_part_ends_the_window():
    import run
    assert run.traced_part(50.0) == (45.0, 50.0)
    assert run.traced_part(3.0) == (1.5, 3.0)


def test_queue_wait_leaves_out_the_traced_part():
    """The profiler holds the host while it traces and stops: requests
    that arrive in the traced part do not count."""
    import types
    import run
    waits = [0.1, 0.2, 0.3, 0.4, 9.0, 12.0]
    reqs = [types.SimpleNamespace(arrival_s=t, queue_s=w)
            for t, w in zip([1.0, 10.0, 20.0, 30.0, 46.0, 49.0], waits)]
    ctx = types.SimpleNamespace(requests=reqs, traced_from=45.0)
    value = run.load_metric("sched.queue_wait_p95_s").read(ctx)
    assert value == pytest.approx(0.385)
