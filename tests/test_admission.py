"""Mid-run query admission (DESIGN.md §13).

The acceptance invariant: a query admitted into a retired ``[V, Q]``
slot at superstep k is **bit-identical** to a fresh single-query run —
per-column math is independent of batch context, the admitted column
runs one forced all-dirty superstep, and its per-query superstep count
is measured from its own admission.  Covered across serial / pipelined
/ ooc-vstate engines and an in-process N=2 cluster, plus the session
API properties: slot reuse never leaks prior column state, drains
freeze partial values, and a session with zero live columns keeps
stepping until scheduled admissions arrive.
"""
import dataclasses
import tempfile
import threading

import numpy as np
import pytest

from repro.core import transport as T
from repro.core.apps import APPS
from repro.core.distributed import ClusterExchange
from repro.core.engine import EngineConfig, OutOfCoreEngine
from repro.graphio import spe
from repro.graphio.formats import TileStore

SS = 120   # enough for every app here to converge on the test graphs


def _make_store(weighted, seed=7, nv=220, ne=1400, tile_size=96):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, ne)
    dst = rng.integers(0, nv, ne)
    key = src * nv + dst
    _, i = np.unique(key, return_index=True)
    src, dst = src[i], dst[i]
    val = (rng.uniform(0.1, 10.0, len(src)).astype(np.float32)
           if weighted else None)
    root = tempfile.mkdtemp(prefix=f"admit_store_{int(weighted)}_")
    spe.preprocess_arrays(src, dst, val, nv, TileStore(root), tile_size)
    return root


@pytest.fixture(scope="module")
def stores():
    return _make_store(False), _make_store(True)


# (app, initial seeds, admitted seed, admission superstep)
CASES = [
    ("ppr", (1, 7, 50), 77, 2),
    ("msbfs", (2, 11, 60), 77, 1),
    ("landmarks", (0, 9, 33), 77, 1),
]

MODES = {
    "serial": {},
    "pipelined": dict(engine_mode="tiled", pipeline=True),
    "ooc": dict(vertex_memory_budget=48 * 1024, num_intervals=4),
}


def _root(stores, app):
    return stores[1] if app == "landmarks" else stores[0]


def _cfg(**kw):
    return EngineConfig(num_servers=2, max_supersteps=SS, **kw)


def _run(root, prog, **kw):
    eng = OutOfCoreEngine(TileStore(root), _cfg(**kw))
    return eng.run(prog)


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("app,init,seed,at", CASES,
                         ids=[c[0] for c in CASES])
def test_admitted_query_bit_identical(stores, app, init, seed, at, mode):
    root = _root(stores, app)
    kw = MODES[mode]
    fresh = _run(root, APPS[app]().with_queries((seed,)), **kw)
    assert fresh.converged
    batch = _run(root, APPS[app]().with_queries(init),
                 admit_plan=((at, (seed,)),), **kw)
    gq = len(init)           # admitted query renumbers after the batch
    assert np.array_equal(batch.values[:, gq], fresh.values[:, 0])
    # superstep accounting is relative to its own admission: same count
    # as the fresh run even though it started mid-stream
    assert batch.per_query_supersteps[gq] == fresh.per_query_supersteps[0]
    # the original batch is untouched by the splice
    ref = _run(root, APPS[app]().with_queries(init), **kw)
    assert np.array_equal(batch.values[:, :gq], ref.values)


def test_admission_cluster_n2(stores):
    """Rank 0 ships the admission record in its frame header; both ranks
    splice identically and match the fresh single-query run."""
    root = stores[0]
    fresh = _run(root, APPS["msbfs"]().with_queries((77,)))
    n = 2
    run_dir = tempfile.mkdtemp(prefix="admit_rings_")
    T.create_ring_files(run_dir, n)
    outs = [None] * n
    errs = [None] * n

    def worker(r):
        try:
            store = TileStore(root)
            store.load_meta()
            eng = OutOfCoreEngine(store, _cfg(
                server_rank=r, admit_plan=((1, (77,)),)))
            tr = T.RingTransport(r, n, run_dir)
            ex = ClusterExchange(tr, assignment=eng.assignment,
                                 edges_per_tile=eng.plan.edges_per_tile,
                                 timeout=60.0)
            eng.exchange = ex
            try:
                outs[r] = eng.run(APPS["msbfs"]().with_queries((2, 11)))
            finally:
                ex.close()
                tr.close()
        except BaseException as exc:    # pragma: no cover - surfaced below
            errs[r] = exc

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    for r, e in enumerate(errs):
        assert e is None, f"rank {r}: {e!r}"
    for r in range(n):
        assert np.array_equal(outs[r].values[:, 2], fresh.values[:, 0])
        assert (outs[r].per_query_supersteps[2]
                == fresh.per_query_supersteps[0])
        # the admission barrier is cluster-wide and deterministic
        assert [h.admitted_queries for h in outs[r].history] == \
               [h.admitted_queries for h in outs[0].history]
    assert np.array_equal(outs[0].values, outs[1].values)


# ---------------------------------------------------------------------------
# session API properties


def _session(root, prog, *, q_slots=None, **kw):
    eng = OutOfCoreEngine(TileStore(root), _cfg(**kw))
    return eng.open_session(prog, q_slots=q_slots)


@pytest.mark.parametrize("ooc", [False, True], ids=["mem", "ooc"])
def test_slot_reuse_never_leaks(stores, ooc):
    """admit -> retire -> admit reusing the same physical slot: the new
    column must match a fresh run exactly (no residue from the prior
    occupant's values, aux, or convergence state)."""
    kw = (dict(vertex_memory_budget=48 * 1024, num_intervals=4)
          if ooc else {})
    root = stores[0]
    seeds = [3, 41, 77, 105, 9]
    fresh = {s: _run(root, APPS["msbfs"]().with_queries((s,)), **kw)
             for s in seeds}
    sess = _session(root, APPS["msbfs"]().with_queries((seeds[0],)),
                    q_slots=1, **kw)
    for s in seeds[1:]:
        sess.admit([s])
    while not sess.finished:
        stats = sess.step()
        # one live column max: each admission reuses the freed slot
        assert stats.active_queries <= 1
    res = sess.result()
    assert res.converged
    for gq, s in enumerate(seeds):
        assert np.array_equal(res.values[:, gq], fresh[s].values[:, 0]), s
        assert (res.per_query_supersteps[gq]
                == fresh[s].per_query_supersteps[0]), s


def test_drain_freezes_partial_column(stores):
    root = stores[0]
    prog = APPS["ppr"]().with_queries((1, 7))
    sess = _session(root, prog)
    sess.step()
    sess.step()
    sess.drain([1])
    stats = sess.step()
    assert stats.drained_queries == (1,)
    assert sess.active_queries == (0,)
    # a drained query never reports a convergence superstep count
    assert sess.query_supersteps(1) == -1
    partial = sess.query_result(1)
    while not sess.finished:
        sess.step()
    res = sess.result()
    # the frozen partial column is what the result carries for qid 1
    assert np.array_equal(res.values[:, 1], partial)
    # ...and qid 0 still converged to the batch-run answer
    ref = _run(root, APPS["ppr"]().with_queries((1, 7)))
    assert np.array_equal(res.values[:, 0], ref.values[:, 0])


def test_zero_live_columns_waits_for_scheduled_admission(stores):
    """A session whose columns all retired keeps stepping (no compute,
    barrier only) until a scheduled admission refills it — and the late
    query still matches a fresh run bit-for-bit."""
    root = stores[0]
    fresh = _run(root, APPS["msbfs"]().with_queries((77,)))
    gap_at = 20      # well after the 3-ish supersteps msbfs needs
    res = _run(root, APPS["msbfs"]().with_queries((2,)),
               admit_plan=((gap_at, (77,)),))
    assert res.converged
    gap = [h for h in res.history if h.active_queries == 0]
    assert gap, "expected idle supersteps between retirement and admission"
    assert all(h.tiles_processed == 0 and h.updated_pairs == 0
               for h in gap)
    assert np.array_equal(res.values[:, 1], fresh.values[:, 0])
    assert res.per_query_supersteps[1] == fresh.per_query_supersteps[0]


def test_admit_respects_slot_cap(stores):
    """Live admissions beyond q_slots queue until retirement frees a
    slot; scheduled plan entries ride along; nothing is lost."""
    root = stores[0]
    sess = _session(root, APPS["msbfs"]().with_queries((2, 11)),
                    q_slots=2)
    gqs = sess.admit([77, 105, 9])
    assert gqs == [2, 3, 4]
    assert sess.free_slots == 0
    seen = set()
    while not sess.finished:
        stats = sess.step()
        assert stats.active_queries <= 2
        seen.update(stats.admitted_queries)
    assert seen == {2, 3, 4}
    res = sess.result()
    assert res.converged
    fresh = _run(root, APPS["msbfs"]().with_queries((77,)))
    assert np.array_equal(res.values[:, 2], fresh.values[:, 0])


def test_checkpoint_resume_preserves_admission_lineage(stores, tmp_path):
    """A session checkpointed mid-serve resumes with query lineage,
    renumbering, and per-query accounting intact (manifest ``queries`` /
    ``admitted_at`` / ``next_qid``)."""
    root = stores[0]
    ck = str(tmp_path / "ck")
    cfg = _cfg(checkpoint_dir=ck, admit_plan=((1, (77,)),))
    eng = OutOfCoreEngine(TileStore(root), cfg)
    sess = eng.open_session(APPS["ppr"]().with_queries((1, 7)))
    for _ in range(4):
        sess.step()
    sess.checkpoint()
    sess.close()
    loaded = eng.ckpt.load_graph()
    assert loaded.live_queries().keys() == {0, 1, 2}
    assert loaded.live_queries()[2] == 77
    # resume and run to completion: identical to the uninterrupted run
    cfg2 = dataclasses.replace(cfg, resume=True)
    eng2 = OutOfCoreEngine(TileStore(root), cfg2)
    sess2 = eng2.open_session(APPS["ppr"]().with_queries((1, 7)))
    assert sess2.superstep == 4
    assert sess2.query_seeds[2] == 77
    while not sess2.finished:
        sess2.step()
    res = sess2.result()
    clean = _run(root, APPS["ppr"]().with_queries((1, 7)),
                 admit_plan=((1, (77,)),))
    assert np.array_equal(res.values, clean.values)
    assert np.array_equal(res.per_query_supersteps,
                          clean.per_query_supersteps)
