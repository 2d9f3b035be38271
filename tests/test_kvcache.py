"""Paged KV-cache pool: allocator/block-table invariants (hypothesis
sweeps), pool construction, the paged-gather kernel dispatch, and the
pool sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.dist import sharding
from repro.kernels import ops as kops
from repro.models.registry import build_model
from repro.serve import kvcache as kvc

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# PageAllocator
# ---------------------------------------------------------------------------
def test_allocator_basics():
    a = kvc.PageAllocator(8)
    assert a.available == 7                 # page 0 reserved (trash)
    pages = a.alloc(3)
    assert len(pages) == 3 and kvc.TRASH_PAGE not in pages
    assert a.available == 4 and a.in_use == 3
    assert a.alloc(5) is None               # exhausted: None, state unchanged
    assert a.available == 4
    a.free(pages)
    assert a.available == 7 and a.in_use == 0
    with pytest.raises(ValueError):
        a.free(pages)                       # double free


def test_allocator_free_rejects_corruption():
    a = kvc.PageAllocator(8)
    pages = a.alloc(2)
    with pytest.raises(ValueError, match="trash page"):
        a.free([kvc.TRASH_PAGE])
    with pytest.raises(ValueError, match="double free"):
        a.free([pages[0], pages[0]])            # second hit within one call
    with pytest.raises(ValueError, match="foreign page"):
        a.free([99])
    with pytest.raises(ValueError, match="foreign page"):
        a.free([-1])


def test_allocator_fault_hook_fails_alloc():
    calls = []

    def fault(n):
        calls.append(n)
        return len(calls) == 1                  # first alloc only

    a = kvc.PageAllocator(8, fault=fault)
    assert a.alloc(2) is None                   # injected failure
    assert a.available == 7 and a.in_use == 0   # state untouched
    assert a.alloc(2) is not None
    assert calls == [2, 2]


def _allocator_schedule(num_pages, sizes):
    """No page is ever held twice; free fully restores the pool."""
    a = kvc.PageAllocator(num_pages)
    held = []
    seen = set()
    for n in sizes:
        pages = a.alloc(n)
        if pages is None:
            assert n > a.available
            continue
        assert not seen.intersection(pages), "page handed out twice"
        assert kvc.TRASH_PAGE not in pages
        seen.update(pages)
        held.append(pages)
        if len(held) > 2:                   # free the oldest now and then
            old = held.pop(0)
            a.free(old)
            seen.difference_update(old)
    for pages in held:
        a.free(pages)
    assert a.available == num_pages - 1 and a.in_use == 0


def test_allocator_random_schedules():
    """Deterministic randomized sweep (runs with or without hypothesis)."""
    rng = np.random.RandomState(0)
    for _ in range(100):
        num_pages = int(rng.randint(2, 40))
        sizes = rng.randint(0, 7, size=rng.randint(0, 40)).tolist()
        _allocator_schedule(num_pages, sizes)


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(st.integers(2, 40), st.lists(st.integers(0, 6), max_size=40))
    def test_allocator_never_double_hands_out(num_pages, sizes):
        _allocator_schedule(num_pages, sizes)


# ---------------------------------------------------------------------------
# BlockTable
# ---------------------------------------------------------------------------
def test_block_table_reserve_release():
    a = kvc.PageAllocator(8)                # 7 usable pages (+ trash)
    t = kvc.BlockTable(a, max_slots=2, page_size=4, max_pages_per_slot=4)
    assert t.reserve(0, 9)                  # 3 pages
    assert len(t.pages(0)) == 3
    assert t.reserve(0, 5)                  # shrink request: no-op
    assert len(t.pages(0)) == 3
    assert t.reserve(1, 16)                 # 4 pages -> pool now empty
    assert not t.reserve(0, 16)             # exhausted -> False, no change
    assert len(t.pages(0)) == 3
    row = t.table[0]
    assert all(p != kvc.TRASH_PAGE for p in row[:3]) and row[3] == 0
    assert not set(t.pages(0)) & set(t.pages(1))
    t.release(0)
    t.release(1)
    assert a.available == 7
    assert (t.table == kvc.TRASH_PAGE).all()


def test_block_table_release_idempotent():
    a = kvc.PageAllocator(8)
    t = kvc.BlockTable(a, max_slots=2, page_size=4, max_pages_per_slot=4)
    t.reserve(0, 9)
    t.release(0)
    assert a.available == 7
    t.release(0)                                # second release: no-op
    t.release(1)                                # never-reserved slot: no-op
    assert a.available == 7 and a.in_use == 0


def test_block_table_version_tracks_mutations():
    a = kvc.PageAllocator(8)
    t = kvc.BlockTable(a, max_slots=2, page_size=4, max_pages_per_slot=4)
    v0 = t.version
    assert t.reserve(0, 9)                      # grows: version moves
    v1 = t.version
    assert v1 > v0
    assert t.reserve(0, 5)                      # no growth: version still
    assert t.version == v1
    assert t.reserve(1, 16)
    assert not t.reserve(0, 16)                 # failed reserve: no change
    v2 = t.version
    t.release(0)
    assert t.version > v2
    v3 = t.version
    t.release(0)                                # idempotent: version still
    assert t.version == v3


def test_block_table_overflow_raises():
    t = kvc.BlockTable(kvc.PageAllocator(10), 1, 4, 2)
    with pytest.raises(ValueError, match="max_pages_per_slot"):
        t.reserve(0, 100)


def _table_schedule(slots, page, maxp, num_pages, ops):
    """Randomized reserve/release schedule: no page in two rows at once,
    free list fully restored after all rows release."""
    t = kvc.BlockTable(kvc.PageAllocator(num_pages), slots, page, maxp)
    for s, do_reserve, n in ops:
        if do_reserve:
            t.reserve(s, n)
        else:
            t.release(s)
        owned = [set(t.pages(i)) for i in range(slots)]
        for i in range(slots):
            for j in range(i + 1, slots):
                assert not owned[i] & owned[j], "page owned by two slots"
        assert kvc.TRASH_PAGE not in set().union(*owned)
    for s in range(slots):
        t.release(s)
    assert t.allocator.available == num_pages - 1


def test_block_table_random_schedules():
    """Deterministic randomized sweep (runs with or without hypothesis)."""
    rng = np.random.RandomState(1)
    for _ in range(60):
        slots = int(rng.randint(1, 6))
        page = int(rng.choice([2, 4, 8]))
        maxp = int(rng.randint(1, 7))
        num_pages = int(rng.randint(2, slots * maxp + 2))
        ops = [(int(rng.randint(0, slots)), bool(rng.randint(0, 2)),
                int(rng.randint(1, maxp * page + 1)))
               for _ in range(rng.randint(1, 30))]
        _table_schedule(slots, page, maxp, num_pages, ops)


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(st.data())
    def test_block_table_no_shared_ownership(data):
        slots = data.draw(st.integers(1, 5))
        page = data.draw(st.sampled_from([2, 4, 8]))
        maxp = data.draw(st.integers(1, 6))
        num_pages = data.draw(st.integers(2, slots * maxp + 1))
        ops = data.draw(st.lists(st.tuples(
            st.integers(0, slots - 1), st.booleans(),
            st.integers(1, maxp * page)), min_size=1, max_size=30))
        _table_schedule(slots, page, maxp, num_pages, ops)


# ---------------------------------------------------------------------------
# Pool construction + gather dispatch
# ---------------------------------------------------------------------------
def test_build_pool_shapes():
    cfg = get_smoke_config("tinyllama-1.1b")
    pool = kvc.build_pool(cfg, num_pages=9, page_size=4)
    leaves = jax.tree.leaves(pool)
    a = cfg.attention
    for leaf in leaves:
        assert leaf.shape[1:] == (9, 4, a.num_kv_heads, a.head_dim)
    assert kvc.pool_bytes(pool) == sum(
        leaf.size * 4 for leaf in leaves)


def test_build_pool_rejects_unservable():
    for arch in ("mixtral-8x7b", "whisper-large-v3", "xlstm-125m",
                 "recurrentgemma-2b", "gemma2-9b"):
        cfg = get_smoke_config(arch)
        assert kvc.servable_reasons(cfg)
        with pytest.raises(ValueError, match="not paged-servable"):
            kvc.build_pool(cfg, num_pages=5, page_size=4)


def test_paged_gather_modes_agree():
    """Both lowerings gather one layer's pages out of the stacked pool."""
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(3, 9, 4, 2, 8).astype(np.float32))
    table = jnp.asarray(rng.randint(0, 9, size=(3, 5)).astype(np.int32))
    layer = 2
    off = kops.paged_gather(pool, table, layer, mode="off")
    ref = np.asarray(pool)[layer][np.asarray(table).reshape(-1)].reshape(
        3, 20, 2, 8)
    np.testing.assert_array_equal(np.asarray(off), ref)
    interp = kops.paged_gather(pool, table, layer, mode="interpret")
    np.testing.assert_array_equal(np.asarray(interp), ref)


def test_pack_prefill_cache_places_pages():
    cfg = get_smoke_config("tinyllama-1.1b")
    pool = kvc.build_pool(cfg, num_pages=9, page_size=4)
    model_cache = jax.tree.map(
        lambda s: jnp.arange(np.prod(s.shape), dtype=jnp.float32).reshape(
            s.shape),
        jax.eval_shape(lambda: build_model(cfg).init_cache(
            1, 8, dtype=jnp.float32)))
    pages = jnp.asarray([3, 5], jnp.int32)
    packed = kvc.pack_prefill_cache(pool, model_cache, pages, page_size=4)

    def check(pnode, dnode):
        if kvc._is_kv_leaf(pnode):
            for key in ("k", "v"):
                got = np.asarray(pnode[key][:, np.asarray(pages)])
                n, _, _, h, d = dnode[key].shape
                want = np.asarray(dnode[key]).reshape(n, 2, 4, h, d)
                np.testing.assert_array_equal(got, want)
        elif isinstance(pnode, (list, tuple)):
            for p, d in zip(pnode, dnode):
                check(p, d)
    check(packed, model_cache)


# ---------------------------------------------------------------------------
# Sharding rules for the pool
# ---------------------------------------------------------------------------
class _FakeMesh:
    def __init__(self, shape, axes):
        self.devices = np.empty(shape, dtype=object)
        self.axis_names = axes


# a spec entry sharded over the one DP axis, as PartitionSpec normalises it
DP_ENTRY = jax.sharding.PartitionSpec(("data",))[0]


def test_page_pool_spec_rules():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    # (n, P, page, Hkv, D): pages over DP, heads indivisible -> head_dim
    spec = sharding.page_pool_spec((2, 64, 16, 4, 32), mesh)
    assert spec == jax.sharding.PartitionSpec(None, ("data",), None, None,
                                              "model")
    # divisible heads take the model axis
    spec = sharding.page_pool_spec((2, 64, 16, 16, 32), mesh)
    assert spec == jax.sharding.PartitionSpec(None, ("data",), None, "model",
                                              None)
    # indivisible page count replicates, page dim NEVER sharded
    spec = sharding.page_pool_spec((2, 63, 16, 4, 32), mesh)
    assert spec[1] is None and spec[2] is None


def test_dp_round_up_keeps_page_dim_shardable():
    """The engine's default pool (slots * maxp + 1 trash) is indivisible by
    any DP product >= 2; dp_round_up restores divisibility so the page dim
    shards instead of silently replicating."""
    mesh = _FakeMesh((16, 16), ("data", "model"))
    n = sharding.dp_round_up(32 * 16 + 1, mesh)        # 513 -> 528
    assert n % 16 == 0 and n >= 513
    spec = sharding.page_pool_spec((2, n, 16, 16, 32), mesh)
    assert spec[1] == DP_ENTRY
    # no DP axes (or size-1): identity
    assert sharding.dp_round_up(7, _FakeMesh((1, 4), ("data", "model"))) == 7


def test_pool_specs_match_dense_cache_story():
    """Pages shard like the dense cache they replace: batch->DP becomes
    page->DP, heads->model unchanged; block tables replicate."""
    cfg = get_smoke_config("tinyllama-1.1b")
    mesh = _FakeMesh((4, 2), ("data", "model"))
    pool = jax.eval_shape(lambda: kvc.build_pool(cfg, num_pages=8,
                                                 page_size=4))
    specs = sharding.pool_specs(pool, mesh)
    for spec in jax.tree.leaves(specs,
                                is_leaf=lambda x: isinstance(
                                    x, jax.sharding.PartitionSpec)):
        assert spec[1] == DP_ENTRY           # page-id dim over DP
        assert spec[2] is None               # in-page offset never sharded
    table = jnp.zeros((4, 8), jnp.int32)
    assert sharding.pool_specs(table, mesh) == jax.sharding.PartitionSpec()
