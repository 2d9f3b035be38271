"""The Qwen configurations read the same through ``models/qwen.py`` as
they did when the harness held the Qwen architecture in its own files.
``recorded_readings.json`` was recorded from that harness with these
inputs: for both tiny configurations, a digest of every weight leaf, of
the reference's logit rows (f32 and the fp8 control) and the check's
verdicts on a fixed set of served requests; and the work counts of the
tiny and the two full configurations over a grid.  Equality is bitwise."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import check
import reference
import run
import weights
import work
from bench_tiny import tiny_cell, tiny_conf

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(HERE, "..", "..", "benchmarks", "chip", "configs")
PARAM_SEED = 2**33 + 17
CHECK_SEED = 2**32 + 5
PROMPTS = (1, 24, 56, 3000)
CONTEXTS = (1, 7, 100, 1000, 4097)
TINY = {"qwen3": (True, False), "qwen2.5": (False, True)}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_readings.json")) as f:
        return json.load(f)


def digest(x):
    a = np.ascontiguousarray(np.asarray(x))
    return (f"{a.dtype}{list(a.shape)}:"
            + hashlib.sha256(a.tobytes()).hexdigest())


def ref_inputs():
    rng = np.random.default_rng(11)
    tokens = rng.integers(1, 1024, size=(2, 40)).astype(np.int32)
    pos = np.array([[3, 10, 20, 39, 39, 0], [0, 5, 6, 7, 30, 38]], np.int32)
    ids = rng.integers(0, 1024, size=(2, 6, 3)).astype(np.int32)
    return tokens, pos, ids


def served_list():
    rng = np.random.default_rng(12)
    out = []
    for i in range(5):
        S = int(rng.integers(8, 57))
        n = int(rng.integers(2, 13))
        prompt = rng.integers(1, 1024, size=S).astype(np.int32)
        toks = rng.integers(0, 1024, size=n).tolist()
        out.append(run.Served(i, 0.1 * i, prompt, n,
                              status="FINISHED_BUDGET", tokens=toks))
    return out


@pytest.fixture(scope="module", params=sorted(TINY))
def tiny(request):
    qk, bias = TINY[request.param]
    conf = tiny_conf(qk, bias)
    return request.param, conf, weights.make_params(conf, PARAM_SEED)


def test_weights_unmoved(tiny, recorded):
    name, _, params = tiny
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert {jax.tree_util.keystr(p): digest(v) for p, v in leaves} == \
        recorded[name]["leaves"]


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_reference_unmoved(tiny, recorded, precision):
    name, conf, params = tiny
    rows = reference.logit_rows(params, conf, *ref_inputs(),
                                precision=precision)
    assert [digest(x) for x in rows] == recorded[name][f"reference_{precision}"]


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_check_readings_unmoved(tiny, recorded, precision):
    """The chat cell's readings, by name, order and value."""
    name, conf, params = tiny
    cell = tiny_cell(*TINY[name])
    v = check.check(cell, params, served_list(), CHECK_SEED, precision)
    want = recorded[name][f"check_{precision}"]
    assert list(v.numbers()) == ["unfinished_requests",
                                 "wrong_length_requests", "served_logit_gap"]
    assert v.numbers() == want["numbers"]
    assert (v.checked_tokens, v.checked_requests) == (
        want["checked_tokens"], want["checked_requests"])


@pytest.mark.parametrize("name", ["qwen3", "qwen2.5", "qwen3-4b",
                                  "qwen2.5-3b"])
def test_work_unmoved(recorded, name):
    if name in TINY:
        conf = tiny_conf(*TINY[name])
    else:
        with open(os.path.join(CONFIGS, f"{name}.json")) as f:
            conf = json.load(f)
    assert {"prefill_flops": [work.prefill_flops(conf, n) for n in PROMPTS],
            "decode_flops": [work.decode_flops(conf, c) for c in CONTEXTS],
            "kv_read_bytes": [work.kv_read_bytes(conf, c) for c in CONTEXTS],
            "kernel_flops": [work.kernel_flops(conf, c) for c in CONTEXTS]} \
        == recorded["work"][name]
