"""Multi-process cluster runtime (DESIGN.md §11).

The acceptance property: an N-server cluster run is bit-identical to the
single-process engine for every app at N in {1, 2, 4}.  Covered two ways:

  * in-process "clusters" — each rank is a thread with its own engine +
    ClusterExchange over a real transport (fast; also what gives coverage
    visibility into the cluster code paths), and
  * real spawned clusters through launch.cluster.run_cluster (slower; one
    launch per (N, store) amortizes process startup over all apps).
"""
import os
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.core import transport as T
from repro.core.apps import (LandmarkDistances, MultiSourceBFS, PageRank,
                             PersonalizedPageRank, SSSP, WCC)
from repro.core.distributed import ClusterExchange
from repro.core.engine import EngineConfig, OutOfCoreEngine
from repro.graphio import spe
from repro.graphio.formats import TileStore
from repro.launch.cluster import ClusterConfig, ClusterFailure, run_cluster
from repro.runtime.faults import FaultPlan, FaultSpec

SS = 12   # superstep cap: keep runs cheap; parity must hold at any cap


def _make_store(weighted, seed=7, nv=220, ne=1400, tile_size=96):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, ne)
    dst = rng.integers(0, nv, ne)
    key = src * nv + dst
    _, i = np.unique(key, return_index=True)
    src, dst = src[i], dst[i]
    val = (rng.uniform(0.1, 10.0, len(src)).astype(np.float32)
           if weighted else None)
    root = tempfile.mkdtemp(prefix=f"cluster_store_{int(weighted)}_")
    spe.preprocess_arrays(src, dst, val, nv, TileStore(root), tile_size)
    return root


@pytest.fixture(scope="module")
def stores():
    """(unweighted root, weighted root) shared by every test here."""
    return _make_store(False), _make_store(True)


def _apps_for(weighted):
    if weighted:
        return [SSSP(source=0), LandmarkDistances(landmarks=(0, 9, 33))]
    return [PageRank(), WCC(), PersonalizedPageRank(seeds=(1, 7, 50)),
            MultiSourceBFS(sources=(2, 11, 60))]


def _reference(root, prog, n, **cfg_kw):
    eng = OutOfCoreEngine(TileStore(root), EngineConfig(
        num_servers=n, max_supersteps=SS, **cfg_kw))
    return eng.run(prog)


def _thread_cluster(root, prog_factory, n, **cfg_kw):
    """Run one app on an in-process n-rank cluster (threads + shm rings)."""
    run_dir = tempfile.mkdtemp(prefix="cluster_rings_")
    T.create_ring_files(run_dir, n)
    outs = [None] * n
    errs = [None] * n

    def worker(r):
        try:
            store = TileStore(root)
            store.load_meta()
            eng = OutOfCoreEngine(store, EngineConfig(
                num_servers=n, server_rank=r, max_supersteps=SS, **cfg_kw))
            tr = T.RingTransport(r, n, run_dir)
            ex = ClusterExchange(tr, assignment=eng.assignment,
                                 edges_per_tile=eng.plan.edges_per_tile,
                                 timeout=60.0)
            eng.exchange = ex
            try:
                outs[r] = eng.run(prog_factory())
            finally:
                ex.close()
                tr.close()
        except BaseException as exc:   # pragma: no cover - surfaced below
            errs[r] = exc

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    for r, e in enumerate(errs):
        assert e is None, f"rank {r}: {e!r}"
    return outs


@pytest.mark.parametrize("n", [1, 2, 4])
def test_inprocess_cluster_bit_identical(stores, n):
    unweighted, _ = stores
    ref = _reference(unweighted, PageRank(), n)
    outs = _thread_cluster(unweighted, PageRank, n)
    for r in range(n):
        assert np.array_equal(outs[r].values, ref.values)
        assert outs[r].supersteps == ref.supersteps
        assert outs[r].converged == ref.converged
    # every rank derived the same merged wire accounting
    for h_ref, *h_ranks in zip(*(o.history for o in outs)):
        assert all(h.wire_bytes == h_ref.wire_bytes for h in h_ranks)
        assert all(h.updated_vertices == h_ref.updated_vertices
                   for h in h_ranks)


def test_inprocess_cluster_multi_query_retirement(stores):
    unweighted, _ = stores
    prog = lambda: PersonalizedPageRank(seeds=(1, 7, 50))  # noqa: E731
    ref = _reference(unweighted, prog(), 2)
    outs = _thread_cluster(unweighted, prog, 2)
    for r in range(2):
        assert np.array_equal(outs[r].values, ref.values)
        assert np.array_equal(outs[r].per_query_supersteps,
                              ref.per_query_supersteps)
        # column retirement is cluster-wide: same columns, same supersteps
        assert [h.retired_queries for h in outs[r].history] == \
               [h.retired_queries for h in ref.history]


def test_inprocess_cluster_ooc_vstate(stores):
    unweighted, _ = stores
    ref = _reference(unweighted, PageRank(), 2, vertex_memory_budget=2000)
    outs = _thread_cluster(unweighted, PageRank, 2,
                           vertex_memory_budget=2000)
    assert np.array_equal(outs[0].values, ref.values)
    assert np.array_equal(outs[1].values, ref.values)


def test_inprocess_cluster_pipelined(stores):
    unweighted, _ = stores
    ref = _reference(unweighted, PageRank(), 2)
    outs = _thread_cluster(unweighted, PageRank, 2, engine_mode="tiled",
                           pipeline=True)
    assert np.array_equal(outs[0].values, ref.values)


def test_exchange_steal_rebalances_deterministically(stores):
    """Both ranks must derive the same post-steal assignment from the
    same replicated timings, and results stay identical (tiles are
    idempotent — ownership never changes values)."""
    unweighted, _ = stores
    store = TileStore(unweighted)
    store.load_meta()
    eng = OutOfCoreEngine(store, EngineConfig(num_servers=2))
    run_dir = tempfile.mkdtemp(prefix="steal_rings_")
    T.create_ring_files(run_dir, 2)
    nv = eng.plan.num_vertices
    rng = np.random.default_rng(0)
    idx = np.sort(rng.choice(nv, size=40, replace=False)).astype(np.int64)
    vals = rng.normal(size=40).astype(np.float32)
    results = [None, None]

    def worker(r):
        tr = T.RingTransport(r, 2, run_dir)
        ex = ClusterExchange(tr, assignment=eng.assignment,
                             edges_per_tile=eng.plan.edges_per_tile,
                             steal=True, straggler_factor=1.5, timeout=60.0)
        try:
            half = idx[r::2]
            out = ex.exchange(idx=half, vals=vals[r::2], mask=None, nv=nv,
                              compute_seconds=10.0 if r == 0 else 1.0)
            results[r] = (out, [list(a) for a in ex.assignment])
        finally:
            ex.close()
            tr.close()

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120.0)
    (out0, asg0), (out1, asg1) = results
    # identical merged updates on both ranks (rank order)
    assert np.array_equal(out0.idx, out1.idx)
    assert np.array_equal(out0.vals, out1.vals)
    # rank 0 straggled 10x -> it must shed tiles; both agree on the result
    assert out0.assignment is not None
    assert asg0 == asg1
    before = len(eng.assignment[0])
    assert len(asg0[0]) < before
    assert sorted(t for a in asg0 for t in a) == \
           sorted(t for a in eng.assignment for t in a)


# ---------------------------------------------------------------------------
# Real spawned clusters (launch.cluster)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("n", [1, 2, 4])
def test_spawned_cluster_all_apps_bit_identical(stores, n):
    """The acceptance sweep: all six apps, real server processes."""
    for root, weighted in zip(stores, (False, True)):
        progs = _apps_for(weighted)
        refs = [_reference(root, p, n) for p in progs]
        out = run_cluster(root, progs, ClusterConfig(
            num_servers=n, engine=EngineConfig(max_supersteps=SS)))
        assert out.verified   # driver-side cross-rank equality
        for a, p in enumerate(progs):
            assert np.array_equal(out.results[a].values, refs[a].values), p
            assert out.results[a].supersteps == refs[a].supersteps


@pytest.mark.slow
def test_spawned_cluster_tcp_and_steal(stores):
    unweighted, _ = stores
    ref = _reference(unweighted, PageRank(), 2)
    out = run_cluster(unweighted, [PageRank()], ClusterConfig(
        num_servers=2, transport="tcp", steal=True,
        engine=EngineConfig(max_supersteps=SS)))
    assert out.verified
    assert np.array_equal(out.results[0].values, ref.values)


# ---------------------------------------------------------------------------
# Fault drills on real spawned clusters (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _assert_no_live_children(pids, grace=10.0):
    """Every pid must be gone (teardown neither hangs nor leaks)."""
    deadline = time.monotonic() + grace
    for pid in pids:
        while True:
            try:
                os.kill(pid, 0)
            except (ProcessLookupError, PermissionError):
                break       # dead (or reaped); PermissionError = not ours
            assert time.monotonic() < deadline, f"child {pid} leaked"
            time.sleep(0.1)


@pytest.mark.slow
@pytest.mark.parametrize("n", [2, 4])
def test_spawned_cluster_sigkill_fail_fast(stores, n):
    """SIGKILL a rank mid-superstep: the parent must notice within the
    poll loop (not the transport timeout), raise ClusterFailure, and
    reap every child in bounded time."""
    unweighted, _ = stores
    plan = FaultPlan(specs=(FaultSpec(site="superstep", superstep=2,
                                      rank=1, kind="sigkill"),))
    cfg = ClusterConfig(
        num_servers=n, on_failure="fail",
        engine=EngineConfig(max_supersteps=SS, fault_plan=plan),
        timeout_seconds=60, launch_timeout_seconds=240)
    t0 = time.monotonic()
    with pytest.raises(ClusterFailure) as ei:
        run_cluster(unweighted, [PageRank()], cfg)
    assert time.monotonic() - t0 < 120          # bounded, not a hang
    assert ei.value.dead_ranks == [1]
    assert not ei.value.preempted
    assert len(ei.value.pids) == n
    _assert_no_live_children(ei.value.pids)


@pytest.mark.slow
def test_spawned_cluster_kill_restart_resume_bit_identical(stores, tmp_path):
    """The tentpole acceptance drill: hard-kill rank 1 at superstep 4,
    supervised restart resumes from the boundary checkpoint, and all six
    apps still answer byte-for-byte like the uninterrupted run."""
    for root, weighted in zip(stores, (False, True)):
        progs = _apps_for(weighted)
        refs = [_reference(root, p, 2) for p in progs]
        ck = str(tmp_path / f"ck_{int(weighted)}")
        # killing at superstep 4 guarantees the step-2 boundary published:
        # rank 1 only reaches 4 after rank 0's superstep-3 frames, which
        # are sent strictly after rank 0's boundary-2 save
        plan = FaultPlan(
            specs=(FaultSpec(site="superstep", superstep=4, rank=1,
                             kind="kill"),),
            marker_dir=str(tmp_path / f"mk_{int(weighted)}"))
        cfg = ClusterConfig(
            num_servers=2, on_failure="restart", max_restarts=2,
            engine=EngineConfig(max_supersteps=SS, checkpoint_dir=ck,
                                checkpoint_every=2, fault_plan=plan),
            timeout_seconds=60, launch_timeout_seconds=600)
        out = run_cluster(root, progs, cfg)
        assert out.restarts == 1
        assert out.final_servers == 2
        assert out.verified
        # prog 0 resumed mid-stream (its post-restart history is shorter
        # than the global superstep count)
        assert len(out.results[0].history) < out.results[0].supersteps
        for a, p in enumerate(progs):
            assert np.array_equal(out.results[a].values, refs[a].values), p
            assert out.results[a].supersteps == refs[a].supersteps
            assert out.results[a].converged == refs[a].converged


@pytest.mark.slow
def test_spawned_cluster_shrink_resize(stores, tmp_path):
    """Elastic mid-run resize: kill a rank at N=4, supervision resumes
    with the 3 survivors (remapped assignment), same answers."""
    unweighted, _ = stores
    ref = _reference(unweighted, PageRank(), 4)
    plan = FaultPlan(
        specs=(FaultSpec(site="superstep", superstep=4, rank=2,
                         kind="kill"),),
        marker_dir=str(tmp_path / "mk"))
    cfg = ClusterConfig(
        num_servers=4, on_failure="shrink", max_restarts=2,
        engine=EngineConfig(max_supersteps=SS,
                            checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=2, fault_plan=plan),
        timeout_seconds=60, launch_timeout_seconds=600)
    out = run_cluster(unweighted, [PageRank()], cfg)
    assert out.restarts == 1
    assert out.final_servers == 3
    assert np.array_equal(out.results[0].values, ref.values)
    assert out.results[0].supersteps == ref.supersteps


@pytest.mark.slow
def test_spawned_cluster_preemption_saves_and_resumes(stores, tmp_path):
    """Spot-reclaim drill: a SIGTERM'd (preemptible) rank checkpoints at
    the barrier and exits cleanly; the restart resumes bit-identically —
    no periodic checkpoints needed, the preemption save is the resume
    point."""
    unweighted, _ = stores
    ref = _reference(unweighted, PageRank(), 2)
    plan = FaultPlan(
        specs=(FaultSpec(site="superstep", superstep=4, rank=0,
                         kind="preempt"),),
        marker_dir=str(tmp_path / "mk"))
    cfg = ClusterConfig(
        num_servers=2, on_failure="restart", max_restarts=2,
        engine=EngineConfig(max_supersteps=SS,
                            checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=0, preemptible=True,
                            fault_plan=plan),
        timeout_seconds=60, launch_timeout_seconds=600)
    out = run_cluster(unweighted, [PageRank()], cfg)
    assert out.restarts == 1
    # resumed exactly at the preemption boundary (superstep 5)
    assert len(out.results[0].history) == out.results[0].supersteps - 5
    assert np.array_equal(out.results[0].values, ref.values)
    assert out.results[0].supersteps == ref.supersteps


@pytest.mark.slow
def test_spawned_cluster_fail_fast_exceeding_restart_budget(stores):
    """A not-once fault that kills every attempt must exhaust
    max_restarts and surface the ClusterFailure (never loop forever)."""
    unweighted, _ = stores
    plan = FaultPlan(specs=(FaultSpec(site="superstep", superstep=1,
                                      rank=0, kind="kill", once=False),))
    cfg = ClusterConfig(
        num_servers=2, on_failure="restart", max_restarts=1,
        engine=EngineConfig(max_supersteps=SS, fault_plan=plan),
        timeout_seconds=60, launch_timeout_seconds=240)
    with pytest.raises(ClusterFailure):
        run_cluster(unweighted, [PageRank()], cfg)


# ---------------------------------------------------------------------------
# Scheduler / elastic units backing the cluster runtime
# ---------------------------------------------------------------------------

def test_rebalance_assignment_noop_when_balanced():
    from repro.runtime.scheduler import rebalance_assignment

    asg = [[0, 2], [1, 3]]
    edges = np.array([10, 10, 10, 10])
    assert rebalance_assignment(asg, edges, [1.0, 1.1]) is None
    assert rebalance_assignment([[0], [1]], edges[:2], [0.0, 0.0]) is None


def test_rebalance_assignment_moves_off_straggler():
    from repro.runtime.scheduler import rebalance_assignment

    asg = [[0, 1, 2, 3], [4, 5, 6, 7]]
    edges = np.array([100, 90, 80, 70, 10, 10, 10, 10])
    out = rebalance_assignment(asg, edges, [10.0, 1.0])
    assert out is not None
    new, moved = out
    assert moved > 0
    assert len(new[0]) < 4
    # partition stays complete and disjoint
    flat = sorted(t for a in new for t in a)
    assert flat == list(range(8))
    # deterministic: same inputs, same output
    again, _ = rebalance_assignment(asg, edges, [10.0, 1.0])
    assert again == new


def test_make_cluster_mesh_requires_devices():
    from repro.launch.mesh import make_cluster_mesh

    # single-CPU test env: a 1-server mesh works, a wide one explains how
    mesh = make_cluster_mesh(1)
    assert mesh.axis_names == ("server",)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_cluster_mesh(99)


def test_remap_assignment_shrink_and_grow():
    from repro.runtime.elastic import remap_assignment

    edges = np.array([50, 40, 30, 20, 10, 5])
    old = [[0, 3], [1, 4], [2, 5]]
    shrunk = remap_assignment(old, 2, edges)
    assert sorted(t for a in shrunk for t in a) == list(range(6))
    # survivors keep their original tiles (cache warmth): the orphans from
    # removed rank 2 land on the least-loaded survivors without displacing
    # the survivors' own tiles in this balanced case
    assert set(old[0]) <= set(shrunk[0])
    assert set(old[1]) <= set(shrunk[1])
    grown = remap_assignment(shrunk, 3, edges)
    assert sorted(t for a in grown for t in a) == list(range(6))
    assert all(len(a) > 0 for a in grown)
    # deterministic
    assert remap_assignment(old, 2, edges) == shrunk


def test_cluster_refuses_more_ranks_than_tpu_chips(monkeypatch):
    """On a TPU platform one chip belongs to one process: surplus ranks are
    refused with a clear error before any rank is spawned."""
    from repro.launch import cluster

    monkeypatch.setattr(cluster, "local_devices", lambda: ("tpu", 1))

    def no_spawn(*a, **k):
        raise AssertionError("a rank was spawned")

    monkeypatch.setattr(cluster, "_run_attempt", no_spawn)
    with pytest.raises(ValueError, match="only 1 local TPU chip"):
        run_cluster("/nonexistent-store", [PageRank()],
                    ClusterConfig(num_servers=2))


def test_local_devices_needs_no_jax_off_tpu(monkeypatch):
    """A JAX_PLATFORMS without the TPU (what ranks inherit here) answers
    without spawning a probe, so CPU launches pay nothing for the check."""
    from repro.launch import cluster

    def no_probe(*a, **k):
        raise AssertionError("probe process spawned")

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(cluster.mp, "get_context", no_probe)
    assert cluster.local_devices() == ("cpu", 0)
    cluster.check_ranks_fit(8)
