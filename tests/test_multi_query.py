"""Multi-query GAB (DESIGN.md §9): differential battery + retirement.

The contract under test: a Q-query batched run is *bit-identical*, column
for column, to Q independent single-query runs — across engine modes
(serial/pipelined, looped/stacked), all three cache policies, and both
segment-reduce implementations — while streaming each tile once per
superstep regardless of Q (the ~Qx I/O amortization that motivates the
whole layer), and retiring converged query columns so late stragglers
stop paying for finished queries.
"""
import numpy as np
import pytest

from repro.core.apps import (LandmarkDistances, MultiSourceBFS, PageRank,
                             PersonalizedPageRank)
from repro.core.engine import EngineConfig, OutOfCoreEngine

SEEDS = (0, 5, 17, 111)


def run(store, prog, servers=3, **kw):
    eng = OutOfCoreEngine(store, EngineConfig(num_servers=servers,
                                              max_supersteps=200, **kw))
    return eng.run(prog)


@pytest.fixture(scope="module")
def weighted_store(small_graph, tmp_path_factory):
    from repro.graphio import spe
    from repro.graphio.formats import TileStore

    nv, src, dst = small_graph
    rng = np.random.default_rng(3)
    val = rng.uniform(0.5, 2.0, len(src)).astype(np.float32)
    store = TileStore(str(tmp_path_factory.mktemp("wstore")))
    spe.preprocess_arrays(src, dst, val, nv, store, tile_size=100)
    return store


@pytest.fixture(scope="module")
def solo_ppr(small_store):
    store, _, _ = small_store
    return {s: run(store, PersonalizedPageRank(seeds=(s,))) for s in SEEDS}


@pytest.fixture(scope="module")
def solo_msbfs(small_store):
    store, _, _ = small_store
    return {s: run(store, MultiSourceBFS(sources=(s,))) for s in SEEDS}


# ---------------------------------------------------------------------------
# differential battery: batched == Q independent runs, bit for bit
# ---------------------------------------------------------------------------

def test_ppr_batched_bit_identical_to_solo(small_store, solo_ppr):
    store, _, _ = small_store
    rb = run(store, PersonalizedPageRank(seeds=SEEDS))
    assert rb.converged
    assert rb.values.shape == (store.load_plan().num_vertices, len(SEEDS))
    for q, s in enumerate(SEEDS):
        np.testing.assert_array_equal(rb.values[:, q], solo_ppr[s].values[:, 0])
        # a column retires exactly when its solo run would converge
        assert rb.per_query_supersteps[q] == solo_ppr[s].supersteps


def test_msbfs_batched_bit_identical_to_solo(small_store, solo_msbfs):
    store, _, _ = small_store
    rb = run(store, MultiSourceBFS(sources=SEEDS))
    assert rb.converged
    for q, s in enumerate(SEEDS):
        np.testing.assert_array_equal(rb.values[:, q],
                                      solo_msbfs[s].values[:, 0])
        assert rb.per_query_supersteps[q] == solo_msbfs[s].supersteps


def test_landmark_sssp_batched_bit_identical_to_solo(weighted_store):
    rb = run(weighted_store, LandmarkDistances(landmarks=SEEDS))
    assert rb.converged
    for q, s in enumerate(SEEDS):
        rs = run(weighted_store, LandmarkDistances(landmarks=(s,)))
        np.testing.assert_array_equal(rb.values[:, q], rs.values[:, 0])


@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("engine_mode", ["tiled", "stacked"])
@pytest.mark.parametrize("cache_policy", ["lru", "tiered", "cost-aware"])
def test_mode_matrix_bit_identical(small_store, solo_msbfs, pipeline,
                                   engine_mode, cache_policy):
    """Serial/pipelined x looped/stacked x all cache policies must all
    produce the exact solo results per column."""
    store, _, _ = small_store
    rb = run(store, MultiSourceBFS(sources=SEEDS), pipeline=pipeline,
             engine_mode=engine_mode, cache_policy=cache_policy)
    for q, s in enumerate(SEEDS):
        np.testing.assert_array_equal(rb.values[:, q],
                                      solo_msbfs[s].values[:, 0])


@pytest.mark.parametrize("skip_filter", ["bitmap", "bloom"])
def test_tile_skipping_with_batched_queries(weighted_store, skip_filter):
    """Tile skipping keys on the *union* of active vertices across live
    query columns — results must match a no-skip run exactly, and tiles
    must actually be skipped once the joint frontier thins."""
    prog = LandmarkDistances(landmarks=SEEDS)
    r_skip = run(weighted_store, prog, tile_skipping=True,
                 skip_density_threshold=0.9, block_shift=2,
                 skip_filter=skip_filter)
    r_ref = run(weighted_store, LandmarkDistances(landmarks=SEEDS),
                tile_skipping=False)
    np.testing.assert_array_equal(r_skip.values, r_ref.values)
    if skip_filter == "bloom":
        # 2^16 bits over 300 vertices is near-exact per-vertex membership,
        # so the thinning multi-query frontier must skip something; the
        # 4-vertex-block bitmap is coarser and may legitimately skip nothing
        # against a 4-query union frontier
        assert sum(h.tiles_skipped for h in r_skip.history) > 0


def test_pallas_seg_impl_matches_jnp(small_store, weighted_store):
    """Both monoids through the Pallas kernels at Q>1: sum (MXU one-hot
    GEMM, PPR) and min (masked VPU reduction, landmark distances)."""
    store, _, _ = small_store
    a = run(store, PersonalizedPageRank(seeds=SEEDS), seg_impl="pallas_onehot")
    b = run(store, PersonalizedPageRank(seeds=SEEDS), seg_impl="jnp")
    np.testing.assert_array_equal(a.values, b.values)
    c = run(weighted_store, LandmarkDistances(landmarks=SEEDS),
            seg_impl="pallas_onehot")
    d = run(weighted_store, LandmarkDistances(landmarks=SEEDS), seg_impl="jnp")
    np.testing.assert_array_equal(c.values, d.values)


# ---------------------------------------------------------------------------
# I/O amortization: one edge pass serves all Q queries
# ---------------------------------------------------------------------------

def test_q32_ppr_streams_tiles_once(small_store):
    """Acceptance: a Q=32 PPR batch must stream each tile once per
    superstep — io_bytes within 5% of a single-query run (i.e. ~32x
    amortization vs 32 independent runs) — with per-query results
    bit-identical to the corresponding single-query runs."""
    store, plan, _ = small_store
    rng = np.random.default_rng(0)
    seeds = tuple(int(v) for v in rng.choice(plan.num_vertices, 32,
                                             replace=False))
    # 1-byte cache: every tile visit is a real disk read, so disk_bytes_read
    # counts tile streaming exactly (per tile: resident tiles are read once)
    kw = dict(cache_capacity_bytes=1, tile_skipping=False,
              engine_mode="tiled")
    rb = run(store, PersonalizedPageRank(seeds=seeds), **kw)
    assert rb.converged

    # the batch runs as long as its slowest query; compare tile I/O against
    # that query's solo run
    slowest = int(np.argmax(rb.per_query_supersteps))
    rs = run(store, PersonalizedPageRank(seeds=(seeds[slowest],)), **kw)
    io_b = sum(h.disk_bytes_read for h in rb.history)
    io_s = sum(h.disk_bytes_read for h in rs.history)
    assert abs(io_b - io_s) <= 0.05 * io_s, (io_b, io_s)

    np.testing.assert_array_equal(rb.values[:, slowest], rs.values[:, 0])
    for q in (0, 7, 19, 31):   # spot-check more columns
        r1 = run(store, PersonalizedPageRank(seeds=(seeds[q],)), **kw)
        np.testing.assert_array_equal(rb.values[:, q], r1.values[:, 0])
        assert rb.per_query_supersteps[q] == r1.supersteps


# ---------------------------------------------------------------------------
# query retirement: converged columns leave compute, broadcast, accounting
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chain_store(tmp_path_factory):
    """A 50-vertex path 0->1->...->40 plus isolated vertices 41..49: BFS
    from 0 needs 40 supersteps, BFS from the isolated 45 converges
    immediately."""
    from repro.graphio import spe
    from repro.graphio.formats import TileStore

    nv = 50
    src = np.arange(0, 40)
    dst = np.arange(1, 41)
    store = TileStore(str(tmp_path_factory.mktemp("chain")))
    spe.preprocess_arrays(src, dst, None, nv, store, tile_size=16)
    return store, nv


def test_query_retirement_excludes_converged_columns(chain_store):
    store, nv = chain_store
    rb = run(store, MultiSourceBFS(sources=(0, 45)), servers=2)
    assert rb.converged
    # the isolated-source query produces zero updates in superstep 0 and
    # retires there; the chain query runs on alone
    assert rb.history[0].active_queries == 2
    assert rb.history[0].retired_queries == (1,)
    assert rb.history[0].updated_per_query[1] == 0
    assert rb.per_query_supersteps[1] == 1
    for h in rb.history[1:]:
        assert h.active_queries == 1
        assert set(h.updated_per_query) == {0}
        assert h.retired_queries in ((), (0,))
        assert h.updated_pairs == h.updated_vertices  # one live column

    # after retirement the broadcast payload must be byte-identical to a
    # run that never had the retired query at all
    rs = run(store, MultiSourceBFS(sources=(0,)), servers=2)
    assert rs.supersteps == rb.supersteps
    for hb, hs in zip(rb.history[1:], rs.history[1:]):
        assert hb.raw_bytes == hs.raw_bytes
        assert hb.wire_bytes == hs.wire_bytes

    np.testing.assert_array_equal(rb.values[:, 0], rs.values[:, 0])
    assert rb.values[45, 1] == 0.0 and np.isinf(rb.values[0, 1])

    # dense comm ships whole columns: while both queries are live the
    # payload is strictly larger, and drops to the solo size the superstep
    # after retirement
    rbd = run(store, MultiSourceBFS(sources=(0, 45)), servers=2,
              comm_mode="dense")
    rsd = run(store, MultiSourceBFS(sources=(0,)), servers=2,
              comm_mode="dense")
    assert rbd.history[0].raw_bytes > rsd.history[0].raw_bytes
    for hb, hs in zip(rbd.history[1:], rsd.history[1:]):
        assert hb.raw_bytes == hs.raw_bytes


def test_single_query_stats_unchanged(small_store):
    """Classic 1-D programs keep their stats semantics."""
    store, _, _ = small_store
    r = run(store, PageRank(update_tol=1e-10))
    for h in r.history:
        assert h.active_queries == 1
        assert h.updated_pairs == h.updated_vertices
        assert h.updated_per_query == {}
        assert h.retired_queries == ()
    assert r.per_query_supersteps is None


# ---------------------------------------------------------------------------
# 2-D broadcast payloads (host accounting + device collectives)
# ---------------------------------------------------------------------------

def test_multi_query_payload_accounting():
    from repro.core import comm

    nv, nq = 256, 3
    values = np.arange(nv * nq, dtype=np.float32).reshape(nv, nq)
    updated = np.zeros((nv, nq), dtype=bool)
    updated[:, 0] = True           # dense column (density 1.0)
    updated[:10, 1] = True         # sparse column (10 updates)
    # column 2: converged — no updates at all
    rec = comm.plan_broadcast(values, updated, compressor="none")
    assert rec.mode == "mixed"
    assert rec.query_modes == ("dense", "sparse", "sparse")
    # dense col: ceil(V/8) bitvector + V f32; sparse cols: 10 pairs of
    # (uint32 vertex, uint32 query) + 10 f32 values, zero for column 2
    want = ((nv + 7) // 8 + 4 * nv) + 10 * (8 + 4)
    assert rec.raw_bytes == want
    assert rec.wire_bytes == want  # compressor "none"

    dense = comm.plan_broadcast(values, updated, compressor="none",
                                mode="dense")
    assert dense.query_modes == ("dense",) * 3
    assert dense.raw_bytes == 3 * ((nv + 7) // 8 + 4 * nv)
    sparse = comm.plan_broadcast(values, updated, compressor="none",
                                 mode="sparse")
    assert sparse.query_modes == ("sparse",) * 3
    assert sparse.raw_bytes == (nv + 10) * (8 + 4)


def test_sampled_accounting_multi_query(small_store, solo_msbfs):
    """comm_accounting="sampled" must stay bit-identical and estimate
    2-D sparse payloads at 12 bytes/cell ((u32, u32) pair + f32), not the
    1-D 8 bytes/update."""
    from repro.core import comm

    store, _, _ = small_store
    rb = run(store, MultiSourceBFS(sources=SEEDS), comm_accounting="sampled")
    for q, s in enumerate(SEEDS):
        np.testing.assert_array_equal(rb.values[:, q],
                                      solo_msbfs[s].values[:, 0])
    # unit check of the pair-overhead estimate
    assert comm.wire_bytes_estimate(1000, 0.01, index_bytes=8) == 10 * 12
    assert comm.wire_bytes_estimate(1000, 0.01) == 10 * 8


def test_hybrid_broadcast_2d_single_host():
    """Device-side 2-D broadcast on a 1-shard mesh: flatten to (vertex,
    query) cells, results must round-trip exactly for every mode."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.compat import shard_map_unchecked
    from repro.core import comm

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    rng = np.random.default_rng(0)
    nv, nq = 64, 4
    old = rng.normal(size=(nv, nq)).astype(np.float32)
    upd = rng.random((nv, nq)) < 0.1
    new = np.where(upd, rng.normal(size=(nv, nq)).astype(np.float32), 0.0)
    want = np.where(upd, new, old)

    rep = P()
    for mode in ("dense", "sparse", "hybrid"):
        fn = shard_map_unchecked(
            lambda o, m, u: comm.hybrid_broadcast(o, m, u, "x", mode=mode)[0],
            mesh=mesh, in_specs=(rep, rep, rep), out_specs=rep)
        got = np.asarray(fn(old, new, upd))
        np.testing.assert_array_equal(got, want, err_msg=mode)
