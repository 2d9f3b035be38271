"""repro.quant: codec round-trip bounds (hypothesis-guarded), int4
packing, per-page-scale invariants of the scatter path, quantized
paged-attention off/interpret agreement on the dequantized values,
scale sharding rules, pool dtype plumbing, and engine-level greedy
parity of the int8 KV pool against the f32 oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_smoke_config
from repro.core import circulant as cc
from repro.dist import sharding
from repro.kernels import ops as kops
from repro.models.registry import build_model
from repro.quant import QuantPolicy, calibrate
from repro.quant import codec as qc
from repro.serve import kvcache as kvc
from repro.serve.engine import ContinuousEngine, Engine, Request
from repro.serve.params import precompute_serving_params

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Codec round trip
# ---------------------------------------------------------------------------
def _roundtrip(x: np.ndarray, qmax: float):
    xs = jnp.asarray(x)
    scale = qc.absmax_scale(xs, axes=-1, qmax=qmax)[..., None]
    q = qc.quantize(xs, scale, qmax)
    dq = qc.dequantize(q, scale)
    err = np.abs(x - np.asarray(dq))
    bound = np.asarray(scale) / 2 + 1e-7 * (np.abs(x) + 1)
    assert (err <= bound).all(), f"max err {err.max()} > scale/2"
    assert np.abs(np.asarray(q)).max() <= qmax


def test_roundtrip_bound_deterministic():
    rng = np.random.RandomState(0)
    for scale in (1e-3, 1.0, 37.0):
        _roundtrip(rng.randn(4, 33).astype(np.float32) * scale, 127.0)
        _roundtrip(rng.randn(4, 33).astype(np.float32) * scale, 7.0)


def test_zero_block_encodes_and_decodes_zero():
    x = jnp.zeros((2, 8))
    s = qc.absmax_scale(x, axes=-1)[..., None]
    assert (np.asarray(s) == 0).all()
    assert (np.asarray(qc.dequantize(qc.quantize(x, s), s)) == 0).all()


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(st.integers(0, 2 ** 16), st.integers(1, 40),
           st.floats(1e-4, 1e4), st.sampled_from([127.0, 7.0]))
    def test_roundtrip_bound_property(seed, n, scale, qmax):
        rng = np.random.RandomState(seed)
        _roundtrip(rng.randn(3, n).astype(np.float32) * scale, qmax)


def test_int4_pack_unpack_exact_inverse():
    rng = np.random.RandomState(1)
    for n in (1, 2, 5, 8, 33):
        q = jnp.asarray(rng.randint(-7, 8, size=(3, 4, n)).astype(np.int8))
        packed = qc.pack_int4(q)
        assert packed.dtype == jnp.uint8
        assert packed.shape[-1] == (n + 1) // 2
        assert (np.asarray(qc.unpack_int4(packed, n)) == np.asarray(q)).all()


# ---------------------------------------------------------------------------
# Per-page scale invariants (the decode scatter path)
# ---------------------------------------------------------------------------
def test_page_scatter_invariants():
    """Scales only grow, always cover the page's live content, written
    values round-trip within the codec bound (+ one half-step per scale
    growth for earlier residents), and untouched pages stay untouched —
    the other layers of the stacked pool too."""
    rng = np.random.RandomState(0)
    page, H, D = 4, 2, 3
    n, g = 3, 1                                  # writes go to layer g
    stack = jnp.zeros((n, 5, page, H, D), jnp.int8)
    stack_sc = jnp.zeros((n, 5, H), jnp.float32)
    pool, scales = stack[g], stack_sc[g]         # layer g's view, for reads
    pid = jnp.asarray([1, 3], jnp.int32)
    written = np.zeros((2, page, H, D), np.float32)
    grows = np.zeros((2, page, H), np.int32)     # growth events AFTER write
    prev = np.zeros((2, H), np.float32)
    for i in range(page):
        x = rng.randn(2, H, D).astype(np.float32) * (i + 1)   # forces growth
        stack, stack_sc = qc.page_scatter(stack, stack_sc, g, pid,
                                          jnp.asarray([i, i], jnp.int32),
                                          jnp.asarray(x))
        pool, scales = stack[g], stack_sc[g]
        s = np.asarray(scales)[np.asarray(pid)]               # (2, H)
        assert (s >= prev - 1e-12).all(), "scale shrank"
        grows[:, :i] += (s > prev + 1e-12)[:, None, :]
        prev = s
        written[:, i] = x
        # scale covers everything currently resident
        content = np.abs(written[:, :i + 1]).max(axis=(1, 3)) / 127.0
        assert (s >= content - 1e-6).all()
    deq = (np.asarray(pool, np.float32)[np.asarray(pid)]
           * prev[:, None, :, None])
    bound = (prev[:, None, :] * (1 + grows) / 2 + 1e-6)[..., None]
    assert (np.abs(deq - written) <= bound).all()
    # pages not in pid untouched
    others = np.asarray([0, 2, 4])
    assert (np.asarray(pool)[others] == 0).all()
    assert (np.asarray(scales)[others] == 0).all()
    # steady state (no growth): the fast path writes ONLY the token row —
    # scales and every other resident row bit-unchanged
    before_pool, before_scales = np.asarray(pool), np.asarray(scales)
    small = rng.randn(2, H, D).astype(np.float32) * 1e-3
    stack, stack_sc = qc.page_scatter(stack, stack_sc, g, pid,
                                      jnp.asarray([1, 2], jnp.int32),
                                      jnp.asarray(small))
    pool, scales = stack[g], stack_sc[g]
    assert (np.asarray(scales) == before_scales).all()
    after = np.asarray(pool)
    rows = np.ones((5, page), bool)
    rows[np.asarray(pid)[0], 1] = rows[np.asarray(pid)[1], 2] = False
    assert (after[rows] == before_pool[rows]).all()
    want = np.clip(np.round(small / prev[:, :, None]), -127, 127)
    got = after[np.asarray(pid), np.asarray([1, 2])]
    assert (got == want).all()
    # the other layers of the stack were never written
    others = [i for i in range(n) if i != g]
    assert not np.asarray(stack)[others].any()
    assert not np.asarray(stack_sc)[others].any()


def test_pack_prefill_quantizes_per_page_per_head():
    cfg = get_smoke_config("tinyllama-1.1b")
    policy = QuantPolicy(kv_dtype="int8")
    pool = kvc.build_pool(cfg, num_pages=9, page_size=4, policy=policy)
    dense = jax.tree.map(
        lambda s: jnp.asarray(np.random.RandomState(0).randn(
            *s.shape).astype(np.float32)),
        jax.eval_shape(lambda: build_model(cfg).init_cache(
            1, 8, dtype=jnp.float32)))
    pages = jnp.asarray([3, 5], jnp.int32)
    packed = kvc.pack_prefill_cache(pool, dense, pages, page_size=4)

    def check(pnode, dnode):
        if kvc._is_kv_leaf(pnode):
            for key in ("k", "v"):
                n, _, _, h, d = dnode[key].shape
                want = np.asarray(dnode[key]).reshape(n, 2, 4, h, d)
                sc = np.asarray(pnode[key + "_scale"])[:, np.asarray(pages)]
                np.testing.assert_allclose(
                    sc, np.abs(want).max(axis=(2, 4)) / 127.0, rtol=1e-6)
                got = (np.asarray(pnode[key][:, np.asarray(pages)],
                                  np.float32) * sc[:, :, None, :, None])
                assert (np.abs(got - want) <= sc.max() / 2 + 1e-6).all()
        elif isinstance(pnode, (list, tuple)):
            for p_, d_ in zip(pnode, dnode):
                check(p_, d_)
    check(packed, dense)


# ---------------------------------------------------------------------------
# Pool dtype plumbing (QuantPolicy is the single source of truth)
# ---------------------------------------------------------------------------
def test_build_pool_policy_dtypes():
    cfg = get_smoke_config("tinyllama-1.1b")
    a = cfg.attention
    for policy, dtype, scaled in ((None, jnp.float32, False),
                                  (QuantPolicy("bf16"), jnp.bfloat16, False),
                                  (QuantPolicy("int8"), jnp.int8, True)):
        pool = kvc.build_pool(cfg, num_pages=9, page_size=4, policy=policy)

        def walk(node):
            if kvc._is_kv_leaf(node):
                assert node["k"].dtype == dtype
                assert ("k_scale" in node) == scaled
                if scaled:
                    n = node["k"].shape[0]
                    assert node["k_scale"].shape == (n, 9, a.num_kv_heads)
                    assert node["k_scale"].dtype == jnp.float32
            elif isinstance(node, (list, tuple)):
                for v in node:
                    walk(v)
        walk(pool)
    with pytest.raises(ValueError, match="kv_dtype"):
        QuantPolicy(kv_dtype="fp4")
    with pytest.raises(ValueError, match="weight_bits"):
        QuantPolicy(weight_bits=2)
    # int8 pool ~4x smaller than f32 at equal pages (scales cost < 2%)
    f32 = kvc.page_bytes(cfg, 16)
    i8 = kvc.page_bytes(cfg, 16, QuantPolicy("int8"))
    assert 3.5 < f32 / i8 <= 4.0


# ---------------------------------------------------------------------------
# Quantized spectral weight planes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_plane_contraction_close(bits):
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(3, 2, 16).astype(np.float32))
    x = jnp.asarray(rng.randn(5, 32).astype(np.float32))
    exact = cc.bc_matmul_spectral(x, cc.spectral_cache(w), 16, 44)
    qcache = qc.quantize_plane_cache(cc.spectral_cache(w), bits)
    got = cc.bc_matmul_spectral(x, qcache, 16, 44)
    # error budget: per-row absmax scale x contraction width
    tol = 0.02 if bits == 8 else 0.4
    assert float(jnp.abs(got - exact).max()) < tol * float(
        jnp.abs(exact).max() + 1)
    # idempotent
    again = qc.quantize_plane_cache(qcache, bits)
    assert set(again) == set(qcache)
    # gauss vs naive quantized lowering agree on the same quantized planes
    xr, xi = cc.rfft_planes(cc._blockify(x, 2, 16), 16)
    g = cc._gauss_contract(xr, xi, qcache, "...qf,pqf->...pf")
    n = cc._naive_complex_contract(xr, xi, qcache, "...qf,pqf->...pf")
    # (not identical: gauss contracts the quantized combo planes; both must
    # stay within the same quantization band of the exact contraction)
    ref = cc._naive_complex_contract(xr, xi, cc.spectral_cache(w),
                                     "...qf,pqf->...pf")
    for approx in (g, n):
        for got_p, ref_p in zip(approx, ref):
            assert float(jnp.abs(got_p - ref_p).max()) < tol * float(
                jnp.abs(ref_p).max() + 1)


def test_quantize_serving_params_walks_all_caches():
    cfg = get_smoke_config("llama4-maverick-400b-a17b").replace(
        dtype="float32")                       # MoE: expert caches too
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    baked = precompute_serving_params(params, cfg)
    quant = precompute_serving_params(
        params, cfg, QuantPolicy(quant_weights=True))
    n_caches, n_scaled = 0, 0

    def walk(node):
        nonlocal n_caches, n_scaled
        if isinstance(node, dict):
            for key, v in node.items():
                if key.endswith("_cache") and isinstance(v, dict):
                    n_caches += 1
                    if "wr_s" in v:
                        n_scaled += 1
                        assert v["wr"].dtype == jnp.int8
                        assert v["wr_s"].shape[-1] == 1
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)
    walk(quant)
    assert n_caches > 0 and n_scaled == n_caches
    # baked (unquantized) tree untouched by comparison
    n_caches = n_scaled = 0
    walk(baked)
    assert n_scaled == 0 and n_caches > 0


# ---------------------------------------------------------------------------
# Quantized paged attention: off == interpret on the dequantized values
# ---------------------------------------------------------------------------
def test_quantized_paged_attention_modes_agree():
    rng = np.random.RandomState(0)
    P_, page, Hkv, G, D = 9, 4, 2, 2, 8
    qk, sk = qc.quantize_page_block(jnp.asarray(
        rng.randn(P_, page, Hkv, D).astype(np.float32)))
    qv, sv = qc.quantize_page_block(jnp.asarray(
        rng.randn(P_, page, Hkv, D).astype(np.float32)))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]],
                        jnp.int32)
    pos = jnp.asarray([13, 5, -1], jnp.int32)
    q = jnp.asarray(rng.randn(3, Hkv * G, D).astype(np.float32))
    kw = dict(k_scale=sk[None], v_scale=sv[None])       # a 1-layer stack
    off = kops.paged_attention(q, qk[None], qv[None], table, pos, 0,
                               mode="off", **kw)
    interp = kops.paged_attention(q, qk[None], qv[None], table, pos, 0,
                                  mode="interpret", **kw)
    # both lanes read the SAME dequantized values: the f32 lane run on the
    # explicitly dequantized pool is the bit-level reference for 'off'
    dqk = qc.dequantize(qk, sk[:, None, :, None])
    dqv = qc.dequantize(qv, sv[:, None, :, None])
    ref = kops.paged_attention(q, dqk[None], dqv[None], table, pos, 0,
                               mode="off")
    np.testing.assert_array_equal(np.asarray(off), np.asarray(ref))
    np.testing.assert_allclose(np.asarray(interp), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)
    assert not np.asarray(off)[2].any()        # idle slot exactly zero


# ---------------------------------------------------------------------------
# Engine-level greedy parity: int8 KV vs the f32 oracle
# ---------------------------------------------------------------------------
def _reqs(specs, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(1, 500, size=s).astype(np.int32),
                    max_new_tokens=n, id=i)
            for i, (s, n) in enumerate(specs)]


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen3-4b"])
def test_engine_int8_kv_greedy_parity(arch):
    """int8 KV pool vs the f32 oracle on tinyllama + a GQA arch: the
    teacher-forced sweep must clear the 99% agreement bar (acceptance
    criterion), and the free-running engine must agree with the f32
    continuous engine on >= 80% of emitted positions (free-running
    divergence compounds after one near-tie flip — methodology in
    docs/quantization.md)."""
    cfg = get_smoke_config(arch).replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rep = calibrate.parity_report(cfg, params,
                                  policy=QuantPolicy(kv_dtype="int8"),
                                  prompt_len=20, new_tokens=16)
    assert rep["greedy_agreement"] >= 0.99
    assert rep["max_logit_drift"] < 1.0

    reqs = _reqs([(20, 8), (12, 10), (16, 6)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                           decode_chunk=5, quant=QuantPolicy("int8"))
    got = [g["tokens"] for g in eng.generate(reqs)]
    agree = sum(int(a == b) for g, w in zip(got, want)
                for a, b in zip(g, w))
    total = sum(len(w) for w in want)
    assert agree / total >= 0.8, f"{agree}/{total}"
    assert eng.stats()["pages_in_use"] == 0    # lifecycle invariants intact


def test_engine_bf16_pool_matches_f32_oracle():
    """bf16 pool storage stays within a stated logit tolerance of the f32
    dense-cache oracle (the no-regression guard for the non-quantized
    dtypes).  Logits, not greedy tokens: a bf16 round-off may flip a
    near-tie, which is a legitimate difference and not a regression.

    Tolerance: 0.05 absolute on the teacher-forced logits — about 4x the
    measured bf16 drift of this model (~0.013) and the drift of the int8
    pool; an f32 pool drifts ~2e-6."""
    cfg = get_smoke_config("tinyllama-1.1b").replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    for seed in (0, 1):
        rep = calibrate.parity_report(cfg, params,
                                      policy=QuantPolicy("bf16"),
                                      prompt_len=20, new_tokens=16,
                                      seed=seed)
        assert rep["max_logit_drift"] < 0.05, rep
    # the engine serves the bf16 pool end to end, first tokens (f32
    # prefill logits in both) identical to the oracle's
    reqs = _reqs([(20, 8), (12, 10)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                           quant=QuantPolicy("bf16"))
    got = [g["tokens"] for g in eng.generate(reqs)]
    assert [len(g) for g in got] == [len(w) for w in want]
    assert [g[0] for g in got] == [w[0] for w in want]


def test_engine_int8_pool_recycled_pages_serve_like_fresh():
    """A decode-grown page keeps no scale from its previous owner: serving
    the same requests again on the same int8 engine (every page now
    recycled) emits the same tokens as on the fresh pool."""
    cfg = get_smoke_config("tinyllama-1.1b").replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                           decode_chunk=5, quant=QuantPolicy("int8"))
    reqs = _reqs([(20, 8), (12, 10), (16, 6), (9, 12)])
    first = [g["tokens"] for g in eng.generate(reqs)]
    second = [g["tokens"] for g in eng.generate(list(reversed(reqs)))]
    assert list(reversed(second)) == first


def test_engine_quant_telemetry():
    cfg = get_smoke_config("tinyllama-1.1b").replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    f32 = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4)
    i8 = ContinuousEngine(cfg, params, max_slots=2, max_seq=32, page_size=4,
                          quant=QuantPolicy("int8"))
    st_f, st_i = f32.stats(), i8.stats()
    assert st_f["quant_policy"]["kv_dtype"] == "f32"
    assert st_i["quant_policy"]["kv_dtype"] == "int8"
    assert st_i["kv_pool_bytes"] * 3.5 < st_f["kv_pool_bytes"]
    # attention-byte telemetry recomputed for int8 page traffic
    assert st_i["attention_bytes_per_token"] * 3.9 < \
        st_f["attention_bytes_per_token"]


# ---------------------------------------------------------------------------
# Sharding rules for the scale tensors
# ---------------------------------------------------------------------------
class _FakeMesh:
    def __init__(self, shape, axes):
        self.devices = np.empty(shape, dtype=object)
        self.axis_names = axes


def test_page_scale_spec_rules():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    # (n, P, Hkv): pages over DP like the payload, heads indivisible ->
    # replicated (a scale has no head_dim to fall back to)
    assert sharding.page_scale_spec((2, 64, 4), mesh) == P(None, ("data",),
                                                          None)
    assert sharding.page_scale_spec((2, 64, 16), mesh) == P(None, ("data",),
                                                            "model")
    # indivisible page count replicates; never an in-page-offset dim
    assert sharding.page_scale_spec((2, 63, 4), mesh) == P(None, None, None)


def test_pool_specs_route_scales_and_int8_payloads():
    cfg = get_smoke_config("tinyllama-1.1b")
    mesh = _FakeMesh((4, 2), ("data", "model"))
    pool = jax.eval_shape(lambda: kvc.build_pool(
        cfg, num_pages=8, page_size=4, policy=QuantPolicy("int8")))
    specs = sharding.pool_specs(pool, mesh)

    def walk(snode, pnode):
        if isinstance(snode, dict) and "k" in snode:
            # int8 payloads still shard: pages over DP, offset unsharded
            dp = P(("data",))[0]                  # as PartitionSpec keeps it
            assert snode["k"][1] == dp and snode["k"][2] is None
            assert snode["k_scale"][1] == dp
            assert len(snode["k_scale"]) == 3     # no in-page-offset dim
        elif isinstance(snode, (list, tuple)):
            for s, p_ in zip(snode, pnode):
                walk(s, p_)
    walk(specs, pool)


def test_plane_scale_param_specs():
    mesh = _FakeMesh((4, 4), ("data", "model"))
    # column projection: block-row dim carries "model" like its payload
    assert sharding.param_spec(("segments", "attn", "q", "wc_cache", "wr_s"),
                               (3, 8, 1), mesh) == P(None, "model", None)
    # row projection (o/down/out): payload model-shards q, which the scale
    # does not have -> replicated
    assert sharding.param_spec(("segments", "attn", "o", "wc_cache", "wr_s"),
                               (3, 8, 1), mesh) == P(None, None, None)
    # expert scales (E, p, 1): EP-first like the expert planes
    assert sharding.param_spec(
        ("segments", "moe", "experts", "up_cache", "ws1_s"),
        (3, 4, 8, 1), mesh) == P(None, "model", None, None)
    # E indivisible by the model axis: column scales fall back to the
    # block-row dim like their payload; row scales replicate (their
    # payload model-shards q, which a scale does not have) — regression
    # for the experts branch previously shadowing the scale rule
    assert sharding.param_spec(("moe", "experts", "up_cache", "wr_s"),
                               (3, 8, 1), mesh) == P(None, "model", None)
    assert sharding.param_spec(("moe", "experts", "down_cache", "wr_s"),
                               (3, 8, 1), mesh) == P(None, None, None)
    # never a DP axis on a scale
    spec = sharding.param_spec(("attn", "q", "qkv_cache", "ws2_s"),
                               (16, 1), mesh)
    assert "data" not in jax.tree.leaves(tuple(spec))


# ---------------------------------------------------------------------------
# Calibration report
# ---------------------------------------------------------------------------
def test_weight_absmax_report():
    cfg = get_smoke_config("tinyllama-1.1b").replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    baked = precompute_serving_params(params, cfg)
    rep = calibrate.weight_absmax_report(baked)
    assert rep, "no serving caches found"
    for entry in rep.values():
        for stats in entry.values():
            assert stats["absmax"] > 0
            assert 0 <= stats["scale_min"] <= stats["scale_max"]
            assert stats["scale_max"] == pytest.approx(
                stats["absmax"] / 127.0)
    # the quantized tree reports consistent scales (read back, not derived)
    qrep = calibrate.weight_absmax_report(
        precompute_serving_params(params, cfg, QuantPolicy(
            quant_weights=True)))
    assert set(qrep) == set(rep)
    for path in rep:
        got = qrep[path]["wr"]["scale_max"]
        assert got == pytest.approx(rep[path]["wr"]["scale_max"], rel=1e-5)
    # int4-packed trees read back with qmax=7: absmax stays the true
    # absmax, not 127/7x it (regression)
    q4rep = calibrate.weight_absmax_report(
        precompute_serving_params(params, cfg, QuantPolicy(
            quant_weights=True, weight_bits=4)))
    for path in rep:
        assert q4rep[path]["wr"]["absmax"] == pytest.approx(
            rep[path]["wr"]["absmax"], rel=1e-5)
        # nibble packing halves the int8 payload (round up on odd kf:
        # ceil(kf/2)/kf <= 3/4 for kf >= 2)
        b8, b4 = qrep[path]["wr"]["bytes"], q4rep[path]["wr"]["bytes"]
        assert b8 / 2 <= b4 <= b8 * 0.75
