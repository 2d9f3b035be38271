"""Fused paged flash-decode attention: the streamed online-softmax paths
(off-scan and interpret-mode Pallas kernel) against the gather-then-attend
oracle, over random pools, unaligned lengths, idle (trash-page) slots, and
GQA ratios — plus the engine-level stream/gather token identity and the
decode head-sharding spec.

Every lowering reads one layer of the stacked ``(n, P, page, Hkv, D)``
pool in place: the cases put the pool under test at a layer other than 0
of a stack whose other layers hold other values.
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
from repro.layers import attention as attn_lib
from repro.models.registry import build_model
from repro.serve import kvcache as kvc
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


LAYER = 1                                  # the pool's index in its stack


def _stacked(rng, pool, n=3, layer=LAYER):
    """``pool`` at ``layer`` of an n-layer stack; the other layers random."""
    stack = rng.randn(n, *pool.shape).astype(np.float32)
    stack[layer] = np.asarray(pool)
    return jnp.asarray(stack)


def _oracle(q, pool_k, pool_v, table, positions, softcap):
    """Gathered view (plain numpy indexing of one layer's own pool) +
    attention_ref, one slot at a time."""
    B, Hq, D = q.shape
    maxp = table.shape[1]
    _, page, Hkv, _ = pool_k.shape
    tab = np.asarray(table)
    gk = np.asarray(pool_k)[tab].reshape(B, maxp * page, Hkv, D)
    gv = np.asarray(pool_v)[tab].reshape(B, maxp * page, Hkv, D)
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
    rng = np.random.RandomState(7)
    sk, sv = _stacked(rng, pool_k), _stacked(rng, pool_v)
    off = kops.paged_attention(q, sk, sv, table, positions, LAYER,
                               softcap=softcap, mode="off")
    interp = kops.paged_attention(q, sk, sv, table, positions, LAYER,
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
    pk, pv = _stacked(rng, pk), _stacked(rng, pv)
    assert kops.kernel_mode() == "off"
    off = kops.paged_attention(q, pk, pv, tab, pos, LAYER)
    monkeypatch.setattr(kops, "kernel_mode", lambda: "interpret")
    interp = kops.paged_attention(q, pk, pv, tab, pos, LAYER)
    np.testing.assert_allclose(np.asarray(off), np.asarray(interp),
                               rtol=2e-5, atol=2e-5)


def test_output_dtype_follows_query():
    rng = np.random.RandomState(2)
    q, pk, pv, tab, pos, _ = _pool_case(rng, num_pages=6, page=4, Hkv=2,
                                        G=2, D=8, positions=[5, 9])
    out = kops.paged_attention(q.astype(jnp.bfloat16), pk[None], pv[None],
                               tab, pos, 0, mode="off")
    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# The stacked pool: a layer read in place equals the call on its own slice,
# and a decode write into one layer leaves every other layer untouched
# ---------------------------------------------------------------------------
def _lane_pool(rng, lane, shape):
    """(pool K, pool V, scale kwargs) of a lane: f32 values, or int8 codes
    with positive per-(layer, page, head) scales."""
    if lane == "f32":
        return (jnp.asarray(rng.randn(*shape).astype(np.float32)),
                jnp.asarray(rng.randn(*shape).astype(np.float32)), {})
    codes = lambda: jnp.asarray(                        # noqa: E731
        rng.randint(-127, 128, shape).astype(np.int8))
    sshape = (*shape[:2], shape[3])
    scales = lambda: jnp.asarray(                       # noqa: E731
        rng.uniform(0.5, 1.5, sshape).astype(np.float32) / 127)
    return codes(), codes(), {"k_scale": scales(), "v_scale": scales()}


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("lane", ["f32", "int8"])
def test_stacked_layer_matches_its_own_slice(lane, mode):
    """Layer 2 of a 3-layer pool, read in place by the stream lowering and
    by the kernel, gives the same bits as the same call on that layer's
    own (P, page, Hkv, D) slice."""
    rng = np.random.RandomState(4)
    n, layer, P_, page, Hkv, G, D = 3, 2, 9, 4, 2, 2, 8
    pk, pv, sc = _lane_pool(rng, lane, (n, P_, page, Hkv, D))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]],
                        jnp.int32)
    pos = jnp.asarray([13, 5, -1], jnp.int32)
    q = jnp.asarray(rng.randn(3, Hkv * G, D).astype(np.float32))
    got = kops.paged_attention(q, pk, pv, table, pos, layer, mode=mode, **sc)
    own = kops.paged_attention(
        q, pk[layer][None], pv[layer][None], table, pos, 0, mode=mode,
        **{k: v[layer][None] for k, v in sc.items()})
    np.testing.assert_array_equal(np.asarray(got), np.asarray(own))
    assert np.asarray(got)[:2].any() and not np.asarray(got)[2].any()


@pytest.mark.parametrize("lane", ["f32", "int8"])
def test_decode_write_leaves_other_layers_untouched(lane):
    """One paged decode step of an attention block at layer g writes each
    live slot's row at (g, page, offset) of the stacked leaves: every
    other layer (and, at layer g, every page no slot writes) keeps its
    bits, scales included."""
    cfg = get_smoke_config("tinyllama-1.1b").replace(dtype="float32")
    a = cfg.attention
    n, P_, page, g = 3, 7, 4, 1
    rng = np.random.RandomState(5)
    pk, pv, sc = _lane_pool(rng, lane,
                            (n, P_, page, a.num_kv_heads, a.head_dim))
    cache = {"k": pk, "v": pv, **sc}
    before = {k: np.asarray(v) for k, v in cache.items()}
    table = jnp.asarray([[1, 2], [3, 4], [0, 0]], jnp.int32)
    pos = jnp.asarray([5, 2, -1], jnp.int32)      # pages 2 and 3 written
    params = attn_lib.init_attention(jax.random.PRNGKey(0), cfg,
                                     cfg.d_model, cfg.compression)
    x = jnp.asarray(rng.randn(3, 1, cfg.d_model).astype(np.float32))
    _, new = attn_lib.attention_block(
        params, x, cfg=cfg, cache=cache, cache_pos=pos, mode="serve",
        block_table=table, layer=jnp.int32(g))
    written = [2, 3, 0]                          # trash page 0: idle slot
    for key, old in before.items():
        got = np.asarray(new[key])
        others = [i for i in range(n) if i != g]
        np.testing.assert_array_equal(got[others], old[others])
        keep = [p for p in range(P_) if p not in written]
        np.testing.assert_array_equal(got[g, keep], old[g, keep])
    # the live slots' rows hold their new values
    assert (np.asarray(new["k"])[g, 2, 1] != before["k"][g, 2, 1]).any()
    assert (np.asarray(new["k"])[g, 3, 2] != before["k"][g, 3, 2]).any()


def test_unrolled_paged_decode_matches_scan():
    """The unrolled layer loop serves paged decode from the same carried
    pool, each group at its static index: logits and pool match the
    scanned step's."""
    cfg = get_smoke_config("llama4-maverick-400b-a17b").replace(
        dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(6)
    pool = jax.tree.map(
        lambda a: jnp.asarray(rng.randn(*a.shape).astype(np.float32)),
        kvc.build_pool(cfg, num_pages=7, page_size=4))
    table = jnp.asarray([[1, 2], [3, 4], [0, 0]], jnp.int32)
    pos = jnp.asarray([5, 2, -1], jnp.int32)
    tok = jnp.asarray([[3], [7], [0]], jnp.int32)
    outs = []
    for unroll in (False, True):
        m = build_model(cfg.replace(unroll_scan=unroll))
        outs.append(jax.jit(lambda p, t, c: m.decode_step(
            p, t, c, pos, block_table=table))(params, tok, pool))
    (lg_s, pool_s), (lg_u, pool_u) = outs
    np.testing.assert_allclose(np.asarray(lg_u), np.asarray(lg_s),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(pool_u), jax.tree.leaves(pool_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


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
