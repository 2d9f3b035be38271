"""Fused paged flash-decode attention: the streamed online-softmax paths
(off-scan and interpret-mode Pallas kernel) against the gather-then-attend
oracle, over random pools, unaligned lengths, idle (trash-page) slots, and
GQA ratios — plus the engine-level stream/gather token identity and the
decode head-sharding spec.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_smoke_config
from repro.dist import sharding as sh
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models.registry import build_model
from repro.serve.engine import ContinuousEngine, Engine, Request

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Oracle: paged_gather + attention_ref per slot (independent of the scan)
# ---------------------------------------------------------------------------
def _pool_case(rng, *, num_pages, page, Hkv, G, D, positions, softcap=0.0):
    """Build a random pool + per-slot tables for the given positions (-1 =
    idle slot); owned pages are distinct, unowned entries hold trash 0."""
    B = len(positions)
    Hq = Hkv * G
    maxp = max([p // page + 1 for p in positions if p >= 0], default=1)
    pool_k = rng.randn(num_pages, page, Hkv, D).astype(np.float32)
    pool_v = rng.randn(num_pages, page, Hkv, D).astype(np.float32)
    free = list(range(1, num_pages))
    rng.shuffle(free)
    table = np.zeros((B, maxp), np.int32)
    for b, pos in enumerate(positions):
        need = 0 if pos < 0 else pos // page + 1
        for j in range(need):
            table[b, j] = free.pop()
    q = rng.randn(B, Hq, D).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(np.asarray(positions, np.int32)),
            softcap)


def _oracle(q, pool_k, pool_v, table, positions, softcap):
    """Gathered view + attention_ref, one slot at a time."""
    gk = np.asarray(kops.paged_gather(pool_k, table, mode="off"))
    gv = np.asarray(kops.paged_gather(pool_v, table, mode="off"))
    B, Hq, D = q.shape
    out = np.zeros((B, Hq, D), np.float32)
    for b in range(int(B)):
        L = int(positions[b]) + 1
        if L <= 0:
            continue                         # idle slot: all-masked -> zero
        out[b] = np.asarray(kref.attention_ref(
            q[b:b + 1, :, None],
            jnp.asarray(gk[b:b + 1, :L].transpose(0, 2, 1, 3)),
            jnp.asarray(gv[b:b + 1, :L].transpose(0, 2, 1, 3)),
            causal=True, softcap=softcap, kv_offset=L - 1))[0, :, 0]
    return out


def _check(case, tol=2e-5):
    q, pool_k, pool_v, table, positions, softcap = case
    want = _oracle(q, pool_k, pool_v, table, positions, softcap)
    off = kops.paged_attention(q, pool_k, pool_v, table, positions,
                               softcap=softcap, mode="off")
    interp = kops.paged_attention(q, pool_k, pool_v, table, positions,
                                  softcap=softcap, mode="interpret")
    np.testing.assert_allclose(np.asarray(off), want, rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(interp), want, rtol=tol, atol=tol)
    # idle slots are exactly zero in every lowering
    for b, pos in enumerate(np.asarray(positions)):
        if pos < 0:
            assert not np.asarray(off)[b].any()
            assert not np.asarray(interp)[b].any()


# ---------------------------------------------------------------------------
# Deterministic sweep (always runs): GQA ratios, unaligned lengths, idle
# slots, partial last pages, softcap
# ---------------------------------------------------------------------------
CASES = [
    dict(page=4, Hkv=2, G=2, D=8, positions=[5, -1, 15]),     # mixed + idle
    dict(page=8, Hkv=1, G=4, D=16, positions=[0, 7, 8]),      # MQA, edges
    dict(page=4, Hkv=4, G=1, D=8, positions=[3, 3, 2, 11]),   # MHA, dup len
    dict(page=16, Hkv=2, G=4, D=4, positions=[30, 1]),        # big page
    dict(page=4, Hkv=2, G=2, D=8, positions=[-1, -1]),        # all idle
    dict(page=4, Hkv=2, G=3, D=8, positions=[9, 2], softcap=20.0),
    # table wider than the scan's BLOCK_PAGES: multi-block while_loop with
    # a non-block-aligned maxp (exercises the table-padding branch)
    dict(page=4, Hkv=2, G=2, D=8, positions=[27, 5]),         # maxp=7
    dict(page=2, Hkv=1, G=2, D=4, positions=[19, -1]),        # maxp=10
]


@pytest.mark.parametrize("case", CASES)
def test_streamed_matches_gather_oracle(case):
    rng = np.random.RandomState(0)
    kw = dict(case)
    positions = kw.pop("positions")
    need = sum(p // kw["page"] + 1 for p in positions if p >= 0) + 1
    _check(_pool_case(rng, num_pages=need + 2, positions=positions, **kw))


def test_dispatch_env_default(monkeypatch):
    """The platform drives the default dispatch like every other kernel:
    the XLA stream on the CPU, the kernel wherever kernel_mode says so."""
    rng = np.random.RandomState(1)
    case = _pool_case(rng, num_pages=6, page=4, Hkv=2, G=2, D=8,
                      positions=[5, 9])
    q, pk, pv, tab, pos, _ = case
    assert kops.kernel_mode() == "off"
    off = kops.paged_attention(q, pk, pv, tab, pos)
    monkeypatch.setattr(kops, "kernel_mode", lambda: "interpret")
    interp = kops.paged_attention(q, pk, pv, tab, pos)
    np.testing.assert_allclose(np.asarray(off), np.asarray(interp),
                               rtol=2e-5, atol=2e-5)


def test_output_dtype_follows_query():
    rng = np.random.RandomState(2)
    q, pk, pv, tab, pos, _ = _pool_case(rng, num_pages=6, page=4, Hkv=2,
                                        G=2, D=8, positions=[5, 9])
    out = kops.paged_attention(q.astype(jnp.bfloat16), pk, pv, tab, pos,
                               mode="off")
    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Hypothesis property sweep (when available; deterministic sweep above is
# the container fallback)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(st.data())
    def test_streamed_property_sweep(data):
        page = data.draw(st.sampled_from([2, 4, 8]), label="page")
        Hkv = data.draw(st.sampled_from([1, 2, 4]), label="Hkv")
        G = data.draw(st.sampled_from([1, 2, 4]), label="G")
        D = data.draw(st.sampled_from([4, 8]), label="D")
        B = data.draw(st.integers(1, 4), label="B")
        positions = [
            data.draw(st.one_of(st.just(-1), st.integers(0, 8 * page - 1)),
                      label=f"pos{b}") for b in range(B)]
        softcap = data.draw(st.sampled_from([0.0, 30.0]), label="softcap")
        need = sum(p // page + 1 for p in positions if p >= 0) + 1
        seed = data.draw(st.integers(0, 2 ** 16), label="seed")
        rng = np.random.RandomState(seed)
        _check(_pool_case(rng, num_pages=need + 2, page=page, Hkv=Hkv, G=G,
                          D=D, positions=positions, softcap=softcap))


# ---------------------------------------------------------------------------
# Engine level: stream vs gather token identity + telemetry
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_smoke_config("tinyllama-1.1b").replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _reqs(specs, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(1, 500, size=s).astype(np.int32),
                    max_new_tokens=n, id=i)
            for i, (s, n) in enumerate(specs)]


def test_engine_stream_matches_gather_and_oracle(tiny_setup):
    """The new default (stream) and the legacy gather path emit identical
    greedy tokens — both equal to the B=1 batch-engine oracle — including
    slot recycling over more requests than slots."""
    cfg, params = tiny_setup
    reqs = _reqs([(20, 13), (12, 21), (16, 17), (9, 10)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    kw = dict(max_slots=2, max_seq=32, page_size=4, decode_chunk=5)
    stream = ContinuousEngine(cfg, params, **kw)
    gather = ContinuousEngine(cfg, params, paged_attn="gather", **kw)
    assert [g["tokens"] for g in stream.generate(reqs)] == want
    assert [g["tokens"] for g in gather.generate(reqs)] == want


def test_engine_interpret_mode_matches_oracle(tiny_setup, monkeypatch):
    """A kernel_mode of 'interpret' runs the Pallas flash-decode kernel inside
    the real decode loop (slot recycling included) and still emits the
    oracle's greedy tokens."""
    cfg, params = tiny_setup
    reqs = _reqs([(20, 13), (12, 21), (16, 17)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    monkeypatch.setattr(kops, "kernel_mode", lambda: "interpret")
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32,
                           page_size=4, decode_chunk=5)
    assert [g["tokens"] for g in eng.generate(reqs)] == want


def test_engine_memory_telemetry_and_budget_default(tiny_setup):
    """Streamed decode raises the default admission budget to the slot
    ceiling and reports the attention-memory estimates; the gather oracle
    keeps a conservative budget and a maxp*page-times-wider peak."""
    cfg, params = tiny_setup
    kw = dict(max_slots=2, max_seq=32, page_size=4)
    stream = ContinuousEngine(cfg, params, **kw)
    gather = ContinuousEngine(cfg, params, paged_attn="gather", **kw)
    assert stream.scheduler.max_tokens_in_flight == 2 * 33
    assert gather.scheduler.max_tokens_in_flight == 33
    st_s, st_g = stream.stats(), gather.stats()
    assert st_s["attention_impl"] == "stream"
    assert st_g["attention_impl"] == "gather"
    # gather pays 3x the per-token traffic; its peak buffer spans the full
    # maxp*page reservation vs the scan's BLOCK_PAGES-page working set
    from repro.kernels.paged_attention import BLOCK_PAGES
    assert st_g["attention_bytes_per_token"] == \
        3 * st_s["attention_bytes_per_token"]
    bp = min(BLOCK_PAGES, stream.max_pages_per_slot)
    assert st_g["peak_attention_bytes"] * bp == \
        stream.max_pages_per_slot * st_s["peak_attention_bytes"]
    assert st_s["decode_peak_bytes_est"] == \
        st_s["pool_bytes"] + st_s["peak_attention_bytes"]


# ---------------------------------------------------------------------------
# Sharding: the streamed op's q/out head spec mirrors the pool's placement
# ---------------------------------------------------------------------------
class FakeMesh:
    def __init__(self, shape, names):
        self.devices = np.zeros(shape)
        self.axis_names = names


def test_decode_head_spec():
    mesh = FakeMesh((4, 8), ("data", "model"))
    # slots over DP, heads over model
    assert sh.decode_head_spec((8, 16, 64), mesh) == \
        P(("data",), "model", None)
    # GQA fallback: too few heads -> head_dim carries "model"
    assert sh.decode_head_spec((8, 2, 64), mesh) == \
        P(("data",), None, "model")
    # indivisible everywhere -> replicate (never wrong)
    assert sh.decode_head_spec((3, 2, 3), mesh) == P(None, None, None)
    # head placement agrees with the pool leaf it contracts against
    pool = sh.page_pool_spec((128, 16, 16, 64), mesh)
    q = sh.decode_head_spec((8, 16, 64), mesh)
    assert pool[-2] == q[1] == "model"
