"""Pipelined superstep engine (DESIGN.md §7): prefetch iterator contract,
serial/pipelined equivalence, and stacked-batch padding correctness."""
import threading
import time

import numpy as np
import pytest

from repro.core.apps import SSSP, WCC, PageRank
from repro.core.cache import EdgeCache
from repro.core.engine import EngineConfig, OutOfCoreEngine


# --------------------------- prefetch iterator -----------------------------

def test_prefetch_iter_order_and_content(small_store):
    store, plan, _ = small_store
    ids = list(range(plan.num_tiles))[::-1]  # arbitrary (reverse) order
    got = list(store.prefetch_iter(ids, depth=2))
    assert [tid for tid, _ in got] == ids
    for tid, tile in got:
        ref = store.read_tile(tid)
        np.testing.assert_array_equal(tile.src, ref.src)
        np.testing.assert_array_equal(tile.dst_local, ref.dst_local)
        np.testing.assert_array_equal(tile.row_ptr, ref.row_ptr)


def test_prefetch_iter_empty_and_single(small_store):
    store, plan, _ = small_store
    assert list(store.prefetch_iter([], depth=3)) == []
    [(tid, tile)] = list(store.prefetch_iter([0], depth=3))
    assert tid == 0 and tile.meta.tile_id == 0


def test_prefetch_iter_bounded_depth(small_store):
    """Readahead must never exceed ``depth`` undelivered tiles, no matter
    how slow the consumer is."""
    store, plan, _ = small_store
    depth = 2
    reads = []
    lock = threading.Lock()
    orig = store.read_tile

    def counting_read(tid):
        with lock:
            reads.append(tid)
        return orig(tid)

    store.read_tile = counting_read
    try:
        consumed = 0
        max_ahead = 0
        for _tid, _tile in store.prefetch_iter(range(plan.num_tiles),
                                               depth=depth, workers=2):
            consumed += 1
            time.sleep(0.02)  # slow consumer: give workers time to run ahead
            with lock:
                max_ahead = max(max_ahead, len(reads) - consumed)
        assert consumed == plan.num_tiles
        # at most `depth` tiles may be claimed/decoded but not yet consumed
        assert max_ahead <= depth
    finally:
        store.read_tile = orig


def test_prefetch_iter_early_close_stops_workers(small_store):
    store, plan, _ = small_store
    it = store.prefetch_iter(range(plan.num_tiles), depth=2)
    next(it)
    it.close()  # must not hang or leak a blocked worker
    alive = [t for t in threading.enumerate()
             if t.name.startswith("graphh-prefetch")]
    assert not alive


def test_prefetch_iter_through_cache_hits(small_store):
    store, plan, _ = small_store
    cache = EdgeCache(store, capacity_bytes=1 << 30, mode=2)
    cache.warm(range(plan.num_tiles))
    misses0 = cache.stats.misses
    bytes0 = store.bytes_read
    out = list(store.prefetch_iter(range(plan.num_tiles), depth=3,
                                   cache=cache))
    assert len(out) == plan.num_tiles
    assert cache.stats.misses == misses0          # all hits
    assert cache.stats.hits >= plan.num_tiles
    assert store.bytes_read == bytes0             # disk never touched


def test_prefetch_iter_inflight_dedup_single_read_per_tile(small_store):
    """Regression: two prefetch workers claiming the same tile id both
    missed the cache (get_if_resident consulted, but nothing marked the
    read in flight) and read the tile from disk twice.  With in-flight
    deduplication the follower waits for the leader's read and serves the
    duplicate from the cache — exactly one disk read per distinct tile."""
    store, plan, _ = small_store
    cache = EdgeCache(store, capacity_bytes=1 << 30, mode=2)
    reads = []
    lock = threading.Lock()
    orig = store.read_tile_blob

    def slow_counting_read(tid):
        with lock:
            reads.append(tid)
        time.sleep(0.05)   # hold the read open so workers overlap on it
        return orig(tid)

    store.read_tile_blob = slow_counting_read
    try:
        # duplicate ids back to back: both workers pick up the same tile
        ids = [t for t in range(min(4, plan.num_tiles)) for _ in range(2)]
        got = list(store.prefetch_iter(ids, depth=4, workers=2, cache=cache))
        assert [tid for tid, _ in got] == ids
        for tid, tile in got:
            assert tile.meta.tile_id == tid
        with lock:
            assert sorted(reads) == sorted(set(ids))   # one read per tile
    finally:
        store.read_tile_blob = orig


def test_prefetch_iter_propagates_errors(small_store):
    store, plan, _ = small_store
    with pytest.raises(FileNotFoundError):
        list(store.prefetch_iter([0, 99999], depth=2))


# --------------------------- stacked-batch padding -------------------------

def test_run_tile_stack_padding_is_inert(small_store):
    from repro.core.distributed import pad_stack_to
    from repro.core.gab import run_tile_stack
    from repro.core.tiles import stack_tiles

    store, plan, _ = small_store
    import jax.numpy as jnp

    tiles = [store.read_tile(t) for t in range(min(3, plan.num_tiles))]
    nv = plan.num_vertices
    prog = PageRank()
    state = prog.init(nv, np.ones(nv), np.ones(nv))
    values = jnp.asarray(state.pop("value"))
    aux = {k: jnp.asarray(v) for k, v in state.items()}

    plain = stack_tiles(tiles, plan.row_cap)
    padded = pad_stack_to(stack_tiles(tiles, plan.row_cap), len(tiles) + 3)
    assert len(padded["row_start"]) == len(tiles) + 3

    m1, u1 = run_tile_stack(prog, values, aux, plain, plan.row_cap)
    m2, u2 = run_tile_stack(prog, values, aux, padded, plan.row_cap)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


def test_run_tile_stack_matches_run_tile(small_store):
    """One batched dispatch == per-tile dispatches, bit for bit."""
    from repro.core.gab import run_tile, run_tile_stack
    from repro.core.tiles import stack_tiles, tile_edge_values

    store, plan, _ = small_store
    import jax.numpy as jnp

    tiles = [store.read_tile(t) for t in range(plan.num_tiles)]
    nv = plan.num_vertices
    prog = PageRank()
    state = prog.init(nv, np.ones(nv), np.ones(nv))
    values = jnp.asarray(state.pop("value"))
    aux = {k: jnp.asarray(v) for k, v in state.items()}

    masked, upd = run_tile_stack(prog, values, aux,
                                 stack_tiles(tiles, plan.row_cap),
                                 plan.row_cap)
    masked, upd = np.asarray(masked), np.asarray(upd)

    ref_masked = np.zeros(nv, np.float32)
    ref_upd = np.zeros(nv, bool)
    for t in tiles:
        rows, new, u = run_tile(
            prog, values, aux, (t.src, t.dst_local, tile_edge_values(t)),
            t.meta.row_start, t.meta.num_rows, plan.row_cap)
        rows, new, u = np.asarray(rows), np.asarray(new), np.asarray(u)
        ref_masked[rows[u]] = new[u]
        ref_upd[rows[u]] = True

    np.testing.assert_array_equal(upd, ref_upd)
    np.testing.assert_array_equal(masked[ref_upd], ref_masked[ref_upd])


# --------------------------- engine equivalence ----------------------------

def _run(store, prog, pipeline, **kw):
    # the per-tile paths, serial and pipelined, not the resident stacks
    cfg = EngineConfig(num_servers=3, max_supersteps=200, pipeline=pipeline,
                       prefetch_depth=3, prefetch_workers=2, stack_size=2,
                       engine_mode="tiled", **kw)
    return OutOfCoreEngine(store, cfg).run(prog)


@pytest.mark.parametrize("prog_factory", [
    lambda: PageRank(update_tol=1e-10),
    lambda: WCC(),
], ids=["pagerank", "wcc"])
def test_pipelined_bit_identical_unweighted(small_store, prog_factory):
    store, plan, _ = small_store
    ser = _run(store, prog_factory(), pipeline=False)
    pip = _run(store, prog_factory(), pipeline=True)
    assert ser.supersteps == pip.supersteps
    assert np.array_equal(ser.values, pip.values)  # bit-identical


def test_pipelined_bit_identical_sssp(tmp_path, small_graph):
    from repro.graphio import spe
    from repro.graphio.formats import TileStore

    nv, src, dst = small_graph
    rng = np.random.default_rng(3)
    val = rng.uniform(0.5, 2.0, len(src)).astype(np.float32)
    store = TileStore(str(tmp_path / "w"))
    spe.preprocess_arrays(src, dst, val, nv, store, tile_size=100)
    ser = _run(store, SSSP(source=0), pipeline=False)
    pip = _run(store, SSSP(source=0), pipeline=True)
    assert ser.supersteps == pip.supersteps
    assert np.array_equal(ser.values, pip.values)


def test_pipelined_with_tile_skipping(tmp_path, small_graph):
    """Skip filters and the pipelined path must compose: the survivor list
    is prefetched, skipped tiles are never read."""
    from repro.graphio import spe
    from repro.graphio.formats import TileStore

    nv, src, dst = small_graph
    rng = np.random.default_rng(3)
    val = rng.uniform(0.5, 2.0, len(src)).astype(np.float32)
    store = TileStore(str(tmp_path / "w2"))
    spe.preprocess_arrays(src, dst, val, nv, store, tile_size=64)
    kw = dict(tile_skipping=True, skip_density_threshold=0.9, block_shift=2)
    ser = _run(store, SSSP(source=0), pipeline=False, **kw)
    pip = _run(store, SSSP(source=0), pipeline=True, **kw)
    assert np.array_equal(ser.values, pip.values)
    assert sum(h.tiles_skipped for h in pip.history) > 0
    assert (sum(h.tiles_skipped for h in ser.history)
            == sum(h.tiles_skipped for h in pip.history))


def test_pipelined_small_cache_and_stall_accounting(small_store):
    """Under eviction pressure results stay exact and the stall/io-busy
    accounting stays sane (stall <= superstep wall time)."""
    store, plan, _ = small_store
    sizes = [store.tile_disk_bytes(t) for t in range(plan.num_tiles)]
    cap = sum(sizes) // 3
    ser = _run(store, PageRank(update_tol=1e-10), pipeline=False,
               cache_capacity_bytes=cap, cache_mode=2)
    pip = _run(store, PageRank(update_tol=1e-10), pipeline=True,
               cache_capacity_bytes=cap, cache_mode=2)
    assert np.array_equal(ser.values, pip.values)
    for h in pip.history:
        assert 0.0 <= h.stall_seconds <= h.seconds + 1e-6
        assert h.io_busy_seconds >= 0.0
    # the serial engine never hides I/O behind compute
    assert all(h.io_hidden_seconds == 0.0 for h in ser.history)


def test_pipelined_stack_size_one(small_store):
    """stack_size=1 degenerates to per-tile dispatch but stays correct."""
    store, plan, _ = small_store
    ser = _run(store, PageRank(update_tol=1e-10), pipeline=False)
    cfg = EngineConfig(num_servers=2, max_supersteps=200, pipeline=True,
                       prefetch_depth=1, prefetch_workers=1, stack_size=1,
                       engine_mode="tiled")
    pip = OutOfCoreEngine(store, cfg).run(PageRank(update_tol=1e-10))
    assert np.array_equal(ser.values, pip.values)
