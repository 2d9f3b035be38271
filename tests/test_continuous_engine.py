"""ContinuousEngine vs the batch-engine oracle: greedy token identity for
every servable registry arch, slot recycling, EOS page-freeing, telemetry,
and sampling reproducibility.

The oracle is the batch engine under a single-admission schedule (one
request, B=1): prefill runs at the request's own positions and decode at
its own cache length, so its greedy tokens are the ground truth the
continuous engine must reproduce while serving many requests at once.

The parity sweep runs the smoke configs at float32: with bfloat16
activations, XLA CPU reassociates batched GEMMs across batch widths at
bf16-ulp scale, which flips greedy argmax on near-tied random-init logits
— a dtype artifact, not a control-plane property (the bf16 case is pinned
separately on tinyllama, where logits are well-separated).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_smoke_config
from repro.models.registry import build_model
from repro.serve.engine import ContinuousEngine, Engine, Request
from repro.serve.kvcache import servable_reasons

SERVABLE = [a for a in ARCH_IDS if not servable_reasons(get_smoke_config(a))]


def _reqs(specs, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(1, 500, size=s).astype(np.int32),
                    max_new_tokens=n, id=i)
            for i, (s, n) in enumerate(specs)]


def test_servable_set():
    """Exactly the linear-cache decoder LMs are continuous-servable."""
    assert set(SERVABLE) == {"tinyllama-1.1b", "qwen2.5-3b", "qwen3-4b",
                             "llama4-maverick-400b-a17b",
                             "phi-3-vision-4.2b"}
    for arch in set(ARCH_IDS) - set(SERVABLE):
        with pytest.raises(ValueError, match="not continuous-servable"):
            cfg = get_smoke_config(arch)
            params = build_model(cfg).init(jax.random.PRNGKey(0))
            ContinuousEngine(cfg, params)


@pytest.fixture(scope="module", params=SERVABLE)
def arch_setup(request):
    cfg = get_smoke_config(request.param).replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def test_matches_oracle_with_recycling(arch_setup):
    """More requests than slots, mixed unaligned prompt lengths and
    budgets: every request's greedy tokens equal its B=1 oracle run."""
    cfg, params = arch_setup
    reqs = _reqs([(20, 13), (12, 21), (16, 17), (9, 10), (23, 6)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32,
                           page_size=4, decode_chunk=5)
    got = eng.generate(reqs)
    assert [g["tokens"] for g in got] == want
    st = eng.stats()
    assert st["pages_in_use"] == 0          # free list fully restored
    assert st["retired"] == len(reqs)
    # the decode program's temporaries, read from its executable
    assert isinstance(st["decode_temp_bytes"], int)
    assert st["decode_temp_bytes"] > 0


def test_matches_oracle_bf16_tinyllama():
    """Default-dtype pin on the arch whose logits are tie-free."""
    cfg = get_smoke_config("tinyllama-1.1b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    reqs = _reqs([(20, 13), (16, 17), (8, 25), (12, 21)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32,
                           page_size=4, decode_chunk=6)
    assert [g["tokens"] for g in eng.generate(reqs)] == want


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_smoke_config("tinyllama-1.1b").replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def test_eos_frees_pages_early_and_matches_oracle(tiny_setup):
    cfg, params = tiny_setup
    reqs = _reqs([(16, 12), (12, 12)])
    ref = Engine(cfg, params, max_batch=1, max_seq=32)
    base = ref.generate([reqs[0]])[0]["tokens"]
    eos = base[3]                           # a token the model emits mid-way
    oracle = Engine(cfg, params, max_batch=1, max_seq=32, eos_id=eos)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                           decode_chunk=4, eos_id=eos)
    got = eng.generate(reqs)
    assert [g["tokens"] for g in got] == want
    toks = got[0]["tokens"]
    assert toks[-1] == eos and eos not in toks[:-1]
    assert got[0]["decode_len"] < 12        # stopped early
    assert eng.stats()["pages_in_use"] == 0


def test_budget_clamp_matches_batch_engine(tiny_setup):
    """A prompt near max_seq clamps the decode budget like the oracle."""
    cfg, params = tiny_setup
    reqs = _reqs([(20, 16)])                # budget clamps to 24-20+1=5
    oracle = Engine(cfg, params, max_batch=1, max_seq=24)
    want = oracle.generate(reqs)[0]
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=24, page_size=4)
    got = eng.generate(reqs)[0]
    assert want["decode_len"] == got["decode_len"] == 5
    assert got["tokens"] == want["tokens"]


def test_prompt_longer_than_max_seq_raises(tiny_setup):
    cfg, params = tiny_setup
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=16, page_size=4)
    with pytest.raises(ValueError, match="max_seq"):
        eng.generate(_reqs([(20, 4)]))
    # the raise happens BEFORE anything is admitted: the engine stays usable
    # and no pages leaked
    with pytest.raises(ValueError, match="max_seq"):
        eng.generate(_reqs([(8, 4), (20, 4)]))
    assert eng.stats()["pages_in_use"] == 0
    out = eng.generate(_reqs([(8, 4)]))
    assert out[0]["decode_len"] == 4


def test_unsorted_arrival_times(tiny_setup):
    """FIFO admission with out-of-order arrival times must wait for the
    head, not stall (regression: spurious 'scheduler stall' RuntimeError)."""
    cfg, params = tiny_setup
    reqs = _reqs([(12, 4), (12, 4)])
    eng = ContinuousEngine(cfg, params, max_slots=1, max_seq=32, page_size=4)
    out = eng.generate(reqs, arrival_times=[0.3, 0.0])
    assert [r["decode_len"] for r in out] == [4, 4]


def test_arrival_times_and_latency_fields(tiny_setup):
    cfg, params = tiny_setup
    reqs = _reqs([(12, 6), (12, 6), (16, 4)])
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4)
    out = eng.generate(reqs, arrival_times=[0.0, 0.0, 0.2])
    assert [r["id"] for r in out] == [0, 1, 2]
    for r in out:
        assert r["decode_len"] == len(r["tokens"])
        assert r["latency_s"] >= r["queue_s"] >= 0.0
        assert r["tokens_per_s"] > 0
    # the late request cannot complete before it arrived
    assert out[2]["latency_s"] > 0


def test_telemetry(tiny_setup):
    cfg, params = tiny_setup
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4)
    assert eng.stats()["decode_temp_bytes"] is None   # nothing compiled yet
    eng.generate(_reqs([(12, 6), (8, 10), (16, 4)]))
    st = eng.stats()
    assert st["requests"] == st["retired"] == 3
    assert st["tokens"] == 6 + 10 + 4
    assert st["queue_depth"] == 0 and st["tokens_in_flight"] == 0
    assert st["peak_pages_in_use"] > 0 and st["pages_in_use"] == 0
    assert st["prefill_s"] > 0 and st["decode_s"] > 0
    assert st["pool_bytes"] > 0
    assert st["prefill_buckets"]            # page-aligned compile buckets
    assert st["prompt_pad_waste"] >= 0


def test_result_status_fields(tiny_setup):
    cfg, params = tiny_setup
    reqs = _reqs([(16, 12), (12, 12)])
    ref = Engine(cfg, params, max_batch=1, max_seq=32)
    eos = ref.generate([reqs[0]])[0]["tokens"][3]
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                           eos_id=eos)
    got = eng.generate(reqs)
    assert got[0]["status"] == "FINISHED_EOS"
    statuses = {g["status"] for g in got}
    assert statuses <= {"FINISHED_EOS", "FINISHED_BUDGET"}
    assert all(g["preemptions"] == 0 for g in got)


def test_preemption_parity_small_pool(tiny_setup):
    """Optimistic admission over an undersized pool: decode-time growth
    preempts, preempted requests recompute-prefill — and every request's
    greedy tokens still equal its B=1 oracle run."""
    cfg, params = tiny_setup
    reqs = _reqs([(16, 12), (14, 12), (15, 10)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    # 8 usable pages: two 4-page prefills fill the pool; first growth must
    # preempt the younger slot (worst case is 7 pages each)
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                           num_pages=9, decode_chunk=4)
    got = eng.generate(reqs)
    assert [g["tokens"] for g in got] == want
    st = eng.stats()
    assert st["preempted"] > 0
    assert any(g["preemptions"] > 0 for g in got)
    assert st["pages_in_use"] == 0 and st["tokens_in_flight"] == 0
    assert sum(st["statuses"].values()) == len(reqs)


def test_deadline_expires_in_queue(tiny_setup):
    cfg, params = tiny_setup
    reqs = _reqs([(12, 10), (12, 10)])
    reqs[1] = dataclasses.replace(reqs[1], deadline_s=1e-4)
    eng = ContinuousEngine(cfg, params, max_slots=1, max_seq=32, page_size=4)
    out = eng.generate(reqs)
    assert out[0]["status"] == "FINISHED_BUDGET"
    assert out[1]["status"] == "TIMEOUT" and out[1]["decode_len"] == 0
    assert eng.stats()["pages_in_use"] == 0


def test_deadline_expires_in_flight(tiny_setup):
    cfg, params = tiny_setup
    reqs = _reqs([(12, 20)])
    reqs[0] = dataclasses.replace(reqs[0], deadline_s=0.05)
    eng = ContinuousEngine(cfg, params, max_slots=1, max_seq=32, page_size=4,
                           decode_chunk=1)
    out = eng.generate(reqs)                # compile alone blows the budget
    assert out[0]["status"] == "TIMEOUT"
    assert out[0]["decode_len"] < 20
    assert eng.stats()["pages_in_use"] == 0


def test_cancel_and_drain(tiny_setup):
    cfg, params = tiny_setup
    reqs = _reqs([(12, 8), (12, 8), (12, 8)])
    eng = ContinuousEngine(cfg, params, max_slots=1, max_seq=32, page_size=4,
                           decode_chunk=1)
    orders = [eng.submit(r) for r in reqs]
    eng.step()                              # admits + prefills request 0
    assert eng.cancel(reqs[1].id)           # still queued: result now
    assert eng.result(orders[1])["status"] == "CANCELLED"
    assert eng.cancel(reqs[0].id)           # running: retired next boundary
    assert not eng.cancel(999)              # unknown id
    eng.drain()                             # sheds request 2 as REJECTED
    assert eng.result(orders[0])["status"] == "CANCELLED"
    assert eng.result(orders[2])["status"] == "REJECTED"
    st = eng.stats()
    assert st["pages_in_use"] == 0 and st["queue_depth"] == 0
    assert sum(st["statuses"].values()) == 3


def test_drain_idempotent(tiny_setup):
    """drain() is safe to call twice: the second call finds a closed
    intake and an idle scheduler, returns nothing, and leaves the results
    poppable exactly once."""
    cfg, params = tiny_setup
    reqs = _reqs([(12, 6), (12, 6)])
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                           decode_chunk=2)
    orders = [eng.submit(r) for r in reqs]
    eng.step()                              # both admitted + prefilled
    first = eng.drain()
    assert sorted(r["status"] for r in first) == ["FINISHED_BUDGET"] * 2
    assert eng.drain() == []                # idempotent: nothing new, no raise
    for o in orders:
        assert eng.result(o, pop=True)["status"] == "FINISHED_BUDGET"
        assert eng.result(o) is None        # popped exactly once
    st = eng.stats()
    assert st["pages_in_use"] == 0 and st["queue_depth"] == 0
    # a drained engine refuses new work instead of losing it
    o2 = eng.submit(_reqs([(8, 4)], seed=3)[0])
    assert eng.result(o2)["status"] == "REJECTED"


def test_cancel_preempted_resume_entry(tiny_setup):
    """Cancel a request while it sits in the queue as a RESUME entry
    (preempted mid-decode, waiting to recompute-prefill): it settles
    CANCELLED carrying the oracle-prefix tokens it had already generated,
    and the survivor still matches its oracle run."""
    cfg, params = tiny_setup
    reqs = _reqs([(16, 12), (14, 12), (15, 10)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    # same undersized pool as test_preemption_parity_small_pool: decode-time
    # growth must preempt the younger slot back to the queue
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                           num_pages=9, decode_chunk=4)
    orders = [eng.submit(r) for r in reqs]
    victim = None
    for _ in range(200):
        eng.step()
        resumed = [e for e in eng.scheduler.queue if e.resume_tokens]
        if resumed:
            victim = resumed[0]
            break
    assert victim is not None, "pool never preempted a request to the queue"
    vid = victim.request.id
    assert eng.cancel(vid)
    res = eng.result(orders[vid])
    assert res["status"] == "CANCELLED"
    assert res["preemptions"] >= 1
    assert res["tokens"] == want[vid][:len(res["tokens"])]   # oracle prefix
    assert 0 < len(res["tokens"]) < len(want[vid])
    # run the survivors to terminal before draining: drain() sheds
    # still-fresh queue entries as REJECTED, and whether the last request
    # was admitted yet when the preemption fired is scheduling-dependent
    while eng.step():
        pass
    eng.drain()
    for i, o in enumerate(orders):
        if i == vid:
            continue
        out = eng.result(o)
        assert out["status"] in ("FINISHED_BUDGET", "FINISHED_EOS")
        assert out["tokens"] == want[i]
    st = eng.stats()
    assert st["pages_in_use"] == 0 and st["tokens_in_flight"] == 0
    assert sum(st["statuses"].values()) == len(reqs)


def test_bounded_queue_rejects_at_submit(tiny_setup):
    cfg, params = tiny_setup
    reqs = _reqs([(12, 4), (12, 4)])
    eng = ContinuousEngine(cfg, params, max_slots=1, max_seq=32, page_size=4,
                           max_queue=1)
    o0 = eng.submit(reqs[0])
    o1 = eng.submit(reqs[1])                # queue full (nothing stepped yet)
    assert eng.result(o1)["status"] == "REJECTED"
    while eng.step():                       # request 0 runs to completion
        pass
    eng.drain()
    assert eng.result(o0)["status"] == "FINISHED_BUDGET"
    assert eng.stats()["queue_depth"] == 0


def test_sampling_reproducible_and_seed_distinct(tiny_setup):
    cfg, params = tiny_setup
    reqs = _reqs([(12, 12), (16, 12)])
    def run(seed):
        eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32,
                               page_size=4, sample=True, seed=seed)
        return [r["tokens"] for r in eng.generate(reqs)]
    a, b, c = run(1), run(1), run(2)
    assert a == b                           # reproducible per seed
    assert a != c                           # distinct across seeds
