"""Adaptive multi-tier edge cache (DESIGN.md §8): tier transitions,
byte-accounting invariants (property-style), warm() admission control, and
engine equivalence with the tiered policies enabled."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # deterministic fallback, see _hypothesis_compat
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.cache import TIER_LADDER, EdgeCache
from repro.graphio import formats

# The property tests can't take pytest fixtures (the hypothesis fallback
# shim's wrapper hides the signature), so they share one module-level store.
_PROP_STORE = None


def _prop_store():
    global _PROP_STORE
    if _PROP_STORE is None:
        import tempfile

        from repro.graphio import spe
        from repro.graphio.formats import TileStore

        rng = np.random.default_rng(11)
        nv, ne = 200, 1200
        src = rng.integers(0, nv, ne)
        dst = rng.integers(0, nv, ne)
        key = src * nv + dst
        _, idx = np.unique(key, return_index=True)
        store = TileStore(tempfile.mkdtemp(prefix="cache_prop_"))
        plan = spe.preprocess_arrays(src[idx], dst[idx], None, nv, store,
                                     tile_size=64)
        _PROP_STORE = (store, plan)
    return _PROP_STORE


def _warm_blob_size(store, tile_id=0):
    """Size of a tile's blob at the tiered admission mode (warm, zstd-1)."""
    raw = formats.decompress_blob(store.read_tile_blob(tile_id),
                                  store.disk_mode)
    return len(formats.compress_blob(raw, TIER_LADDER[1]))


# --------------------------- tier transitions ------------------------------

def test_unknown_policy_rejected(small_store):
    store, _, _ = small_store
    with pytest.raises(ValueError, match="policy"):
        EdgeCache(store, 1 << 20, policy="mru")


def test_admission_lands_in_warm_tier(small_store):
    store, _, _ = small_store
    cache = EdgeCache(store, 1 << 30, policy="tiered")
    cache.get(0)
    snap = cache.tier_snapshot()
    assert snap["warm"]["tiles"] == 1
    assert "hot" not in snap or snap["hot"]["tiles"] == 0


def test_repeated_hits_promote_to_hot(small_store):
    store, _, _ = small_store
    cache = EdgeCache(store, 1 << 30, policy="tiered", promote_hits=2)
    cache.get(0)            # miss -> warm
    cache.get(0)            # hit 1: below promote threshold
    assert cache.tier_snapshot()["warm"]["tiles"] == 1
    cache.get(0)            # hit 2: promoted warm -> hot
    snap = cache.tier_snapshot()
    assert snap["hot"]["tiles"] == 1
    assert cache.stats.promotions == 1
    # hot entries decode without a codec pass; content identical
    t = cache.get(0)
    np.testing.assert_array_equal(t.src, store.read_tile(0).src)


def test_pressure_demotes_reused_tiles_instead_of_evicting(small_store):
    """Tiles with demonstrated reuse are demoted (kept, compressed colder)
    under pressure, never evicted while zero-reuse churn is around; the
    streaming tiles are the ones that get evicted."""
    store, plan, _ = small_store
    reused = (0, 1, 2)
    cap = sum(_warm_blob_size(store, t) for t in reused) + 64
    tiered = EdgeCache(store, cap, policy="tiered", promote_hits=100)
    for t in reused:
        tiered.get(t)
    for t in reused:
        tiered.get(t)           # reuse: these earn demote-not-evict
    for t in range(3, min(12, plan.num_tiles)):
        tiered.get(t)           # streaming churn under full cache
    assert tiered.stats.demotions > 0      # reused tiles were recompressed,
    assert tiered.stats.evictions > 0      # the zero-reuse stream evicted
    assert tiered.resident_bytes() <= cap
    # reused tiles outlive the streaming churn (demoted colder, evicted only
    # once already cold and no zero-reuse victim remains)
    assert any(tiered.contains(t) for t in reused)
    assert tiered.tier_snapshot().get("cold", {}).get("tiles", 0) > 0


def test_streaming_scan_evicts_without_recompress(small_store):
    """A pure streaming scan (no tile ever re-hit) must not pay demotion
    codec work — zero-reuse entries are evicted directly."""
    store, plan, _ = small_store
    sizes = [store.tile_disk_bytes(t) for t in range(plan.num_tiles)]
    cache = EdgeCache(store, sum(sizes[:3]) // 2, policy="tiered")
    for t in range(plan.num_tiles):
        cache.get(t)
    assert cache.stats.demotions == 0
    assert cache.stats.evictions > 0
    assert cache.resident_bytes() <= cache.capacity_bytes


def test_promotion_suppressed_under_pressure_resumes_on_resize(small_store):
    """Hit credit accumulates while capacity is tight; growing the budget
    (memory pressure change) lets maintain()/resize() promote."""
    store, _, _ = small_store
    w = _warm_blob_size(store)
    cache = EdgeCache(store, int(w * 1.2), policy="tiered", promote_hits=2)
    for _ in range(5):
        cache.get(0)        # pressure ~0.83 > watermark: no inline promotion
    assert cache.stats.promotions == 0
    assert cache.tier_snapshot()["warm"]["tiles"] == 1
    out = cache.resize(1 << 30)
    assert out["promoted"] == 1
    assert cache.tier_snapshot()["hot"]["tiles"] == 1


def test_resize_shrink_walks_demote_ladder(small_store):
    store, plan, _ = small_store
    cache = EdgeCache(store, 1 << 30, policy="tiered", promote_hits=100)
    for t in range(plan.num_tiles):
        cache.get(t)
    for t in range(plan.num_tiles):
        cache.get(t)            # reuse: shrink must demote, not just evict
    before = sum(d["tiles"] for d in cache.tier_snapshot().values())
    w = _warm_blob_size(store)
    cache.resize(3 * w)
    assert cache.resident_bytes() <= 3 * w
    assert cache.stats.demotions > 0
    assert sum(d["tiles"] for d in cache.tier_snapshot().values()) <= before


def test_maintain_predemotes_at_high_pressure(small_store):
    store, _, _ = small_store
    need = sum(_warm_blob_size(store, t) for t in (0, 1, 2))
    cache = EdgeCache(store, need + 8, policy="tiered", promote_hits=100)
    assert cache.warm([0, 1, 2]) == 3       # pressure ~0.99
    for t in (0, 1, 2):
        cache.get(t)        # reused: eligible for pre-demotion
    out = cache.maintain()
    assert out["demoted"] >= 1
    assert cache.tier_snapshot().get("cold", {}).get("tiles", 0) >= 1


def test_cost_aware_keeps_high_value_tile(small_store):
    """The cost-aware victim is the least decompress-seconds-saved per
    byte; a heavily reused tile must survive a streaming scan."""
    store, plan, _ = small_store
    sizes = [store.tile_disk_bytes(t) for t in range(plan.num_tiles)]
    # promote_hits high: tile 0 stays warm (small blob), so its
    # decompress-seconds-saved per byte dwarfs the single-use tiles'
    cache = EdgeCache(store, sum(sizes[:3]), policy="cost-aware",
                      promote_hits=100)
    for _ in range(10):
        cache.get(0)                        # tile 0: high reuse
    for t in range(1, plan.num_tiles):      # streaming churn
        cache.get(t)
    assert cache.contains(0)


def test_background_retier_thread_starts_and_stops(small_store):
    store, _, _ = small_store
    cache = EdgeCache(store, 1 << 30, policy="tiered")
    cache.get(0)
    cache.start_background(interval_s=0.01)
    try:
        import time
        time.sleep(0.05)
    finally:
        cache.stop_background()
    assert cache._bg_thread is None


# --------------------------- warm() admission control ----------------------

def test_warm_stops_at_capacity_no_thrash(small_store):
    """Warming a working set larger than capacity must stop instead of
    LRU-thrashing: no evictions, and the first tiles stay resident."""
    store, plan, _ = small_store
    sizes = [store.tile_disk_bytes(t) for t in range(plan.num_tiles)]
    cache = EdgeCache(store, sum(sizes[:2]) + 32, mode=1)
    admitted = cache.warm(range(plan.num_tiles))
    assert admitted == 2
    assert cache.stats.evictions == 0
    assert cache.contains(0) and cache.contains(1)
    assert cache.resident_bytes() <= cache.capacity_bytes
    # the admitted prefix now hits
    h0 = cache.stats.hits
    cache.get(0)
    assert cache.stats.hits == h0 + 1


def test_warm_counts_resident_tiles(small_store):
    store, plan, _ = small_store
    cache = EdgeCache(store, 1 << 30, mode=2)
    assert cache.warm(range(plan.num_tiles)) == plan.num_tiles
    # warming again is all hits, nothing re-read
    b0 = store.bytes_read
    assert cache.warm(range(plan.num_tiles)) == plan.num_tiles
    assert store.bytes_read == b0


# --------------------------- accounting invariants -------------------------

@given(st.sampled_from(["lru", "tiered", "cost-aware"]),
       st.integers(2, 6),
       st.lists(st.integers(0, 3 * 8 - 1), min_size=1, max_size=40))
@settings(max_examples=12, deadline=None)
def test_cache_accounting_invariants(policy, cap_tiles, ops):
    """After ANY get/warm/maintain sequence: resident_bytes() <=
    capacity_bytes, resident bytes match the tier snapshot exactly, and
    hits + misses == number of lookups performed."""
    store, plan = _prop_store()
    P = plan.num_tiles
    sizes = [store.tile_disk_bytes(t) for t in range(P)]
    cache = EdgeCache(store, cap_tiles * (sum(sizes) // P), policy=policy)
    lookups = 0
    for op in ops:
        kind, tid = divmod(op, 8)
        tid = tid % P
        if kind == 0:
            cache.get(tid)
            lookups += 1
        elif kind == 1:
            cache.warm([tid])      # single tile: exactly one lookup
            lookups += 1
        else:
            cache.maintain()
        assert cache.resident_bytes() <= cache.capacity_bytes
        snap_bytes = sum(d.get("bytes", 0)
                         for d in cache.tier_snapshot().values())
        assert snap_bytes == cache.resident_bytes()
        assert cache.stats.hits + cache.stats.misses == lookups


@given(st.sampled_from(["tiered", "cost-aware"]),
       st.lists(st.integers(0, 7), min_size=4, max_size=24))
@settings(max_examples=8, deadline=None)
def test_retier_preserves_content_and_budget(policy, ops):
    """Promotion/demotion churn never corrupts a tile or the byte budget."""
    store, plan = _prop_store()
    P = plan.num_tiles
    sizes = [store.tile_disk_bytes(t) for t in range(P)]
    cache = EdgeCache(store, sum(sizes[:3]), policy=policy, promote_hits=1)
    for tid in ops:
        t = cache.get(tid % P)
        ref = store.read_tile(tid % P)
        np.testing.assert_array_equal(t.src, ref.src)
        np.testing.assert_array_equal(t.dst_local, ref.dst_local)
        assert cache.resident_bytes() <= cache.capacity_bytes
    cache.maintain()
    assert cache.resident_bytes() <= cache.capacity_bytes


# --------------------------- engine equivalence ----------------------------

def _engine_run(store, prog, **kw):
    from repro.core.engine import EngineConfig, OutOfCoreEngine

    kw.setdefault("max_supersteps", 200)
    # the per-tile paths, where the edge cache serves every superstep
    kw.setdefault("engine_mode", "tiled")
    cfg = EngineConfig(num_servers=3, **kw)
    return OutOfCoreEngine(store, cfg).run(prog)


@pytest.mark.parametrize("policy", ["tiered", "cost-aware"])
def test_tiered_engine_bit_identical_pagerank_wcc(small_store, policy):
    from repro.core.apps import WCC, PageRank

    store, plan, _ = small_store
    sizes = [store.tile_disk_bytes(t) for t in range(plan.num_tiles)]
    cap = sum(sizes) // 3     # eviction/demotion pressure every superstep
    for prog_factory in (lambda: PageRank(update_tol=1e-10), WCC):
        ref = _engine_run(store, prog_factory())
        res = _engine_run(store, prog_factory(), cache_policy=policy,
                          cache_capacity_bytes=cap)
        assert ref.supersteps == res.supersteps
        assert np.array_equal(ref.values, res.values)


def test_tiered_engine_bit_identical_sssp_pipelined(tmp_path, small_graph):
    from repro.core.apps import SSSP
    from repro.graphio import spe
    from repro.graphio.formats import TileStore

    nv, src, dst = small_graph
    rng = np.random.default_rng(3)
    val = rng.uniform(0.5, 2.0, len(src)).astype(np.float32)
    store = TileStore(str(tmp_path / "w"))
    spe.preprocess_arrays(src, dst, val, nv, store, tile_size=100)
    ref = _engine_run(store, SSSP(source=0))
    res = _engine_run(store, SSSP(source=0), cache_policy="tiered",
                      pipeline=True, prefetch_depth=3, prefetch_workers=2,
                      stack_size=2)
    assert np.array_equal(ref.values, res.values)


def test_cache_aware_order_resident_first(small_store):
    """Cache-hit-first scheduling: resident tiles lead the visit order and
    the result/stat stream is unaffected."""
    from repro.core.apps import PageRank
    from repro.core.engine import EngineConfig, OutOfCoreEngine

    store, plan, _ = small_store
    eng = OutOfCoreEngine(store, EngineConfig(num_servers=1))
    tids = list(range(plan.num_tiles))
    eng.caches[0].warm(tids[::2])         # every other tile resident
    ordered = eng._order_cache_first(0, tids)
    assert sorted(ordered) == tids
    assert ordered[: len(tids[::2])] == tids[::2]
    assert ordered[len(tids[::2]):] == tids[1::2]

    ref = _engine_run(store, PageRank(update_tol=1e-10),
                      cache_aware_order=False)
    res = _engine_run(store, PageRank(update_tol=1e-10),
                      cache_aware_order=True)
    assert np.array_equal(ref.values, res.values)


def test_superstep_report_carries_tier_stats(small_store):
    from repro.core.apps import PageRank

    store, plan, _ = small_store
    sizes = [store.tile_disk_bytes(t) for t in range(plan.num_tiles)]
    res = _engine_run(store, PageRank(), cache_policy="tiered",
                      cache_capacity_bytes=sum(sizes) // 8, max_supersteps=4)
    h = res.history[-1]
    assert h.cache_tiers                      # per-tier residency present
    assert sum(d["tiles"] for d in h.cache_tiers.values()) > 0
    # the working set exceeds the warm-tier budget, so re-tiering must
    # have moved tiles (demotions under pressure, or promotions after)
    assert (sum(x.cache_demotions for x in res.history)
            + sum(x.cache_promotions for x in res.history)) > 0


def test_second_run_stats_rebaselined(small_store):
    """Regression: the cumulative-counter baselines (_io_busy_cum /
    _promo_cum / _demo_cum / _disk_cum) were only set in __init__, so cache
    activity between runs (warm()/maintain()/direct get()s) leaked into the
    next run's first-superstep deltas.  run() must re-baseline: every
    per-superstep delta of run 2 sums exactly to what run 2 itself moved."""
    from repro.core.apps import PageRank
    from repro.core.engine import EngineConfig, OutOfCoreEngine

    store, plan, _ = small_store
    sizes = [store.tile_disk_bytes(t) for t in range(plan.num_tiles)]
    eng = OutOfCoreEngine(store, EngineConfig(
        num_servers=2, cache_capacity_bytes=sum(sizes) // 3, cache_mode=2,
        tile_skipping=False, max_supersteps=3, engine_mode="tiled"))
    eng.run(PageRank())
    # external cache traffic between the runs: clear + touch tiles directly
    for c in eng.caches.values():
        c.clear()
        c.get(eng.assignment[0][0])
    external = sum(c.stats.disk_bytes_read for c in eng.caches.values())
    res2 = eng.run(PageRank())
    total_after = sum(c.stats.disk_bytes_read for c in eng.caches.values())
    per_ss = [h.disk_bytes_read for h in res2.history]
    assert all(b >= 0 for b in per_ss)
    # run 2's deltas cover exactly run 2's disk traffic — the external
    # reads between runs are excluded (pre-fix they landed in superstep 0)
    assert sum(per_ss) == total_after - external
    assert all(h.io_busy_seconds >= 0 for h in res2.history)
    assert all(h.cache_promotions >= 0 and h.cache_demotions >= 0
               for h in res2.history)
