"""The resident tile store: with ``engine_mode="auto"`` the engine keeps every
tile in device memory and runs a server's dense superstep as one device
program when the padded tiles fit ``device_budget_bytes``, and runs tile by
tile otherwise; the values are those of the per-tile path either way.

Graph: Graph500 R-MAT at scale 10 (1,024 vertices, 16,384 arcs drawn,
duplicates removed) in 1,024-edge tiles.
"""
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gab
from repro.core.apps import SSSP, PageRank, PersonalizedPageRank
from repro.core.engine import (STACK_SLOT_BYTES, EngineConfig,
                               OutOfCoreEngine, resident_vertex_bytes)

SCALE = 10
TILE_EDGES = 1024
SUPERSTEPS = 4


@pytest.fixture(scope="module")
def arcs():
    """(src, dst) of the graph, duplicates removed."""
    from repro.graphio import synth

    nv = 1 << SCALE
    src, dst, _ = next(synth.rmat_edges(nv, 16 * nv, seed=3))
    key = np.unique(src * nv + dst)
    return key // nv, key % nv


@pytest.fixture(scope="module")
def store(arcs, tmp_path_factory):
    from repro.graphio import spe
    from repro.graphio.formats import TileStore

    st = TileStore(str(tmp_path_factory.mktemp("resident")))
    spe.preprocess_arrays(*arcs, None, 1 << SCALE, st, tile_size=TILE_EDGES)
    return st


@pytest.fixture(scope="module")
def sparse_root(store, arcs):
    """A root whose one out-neighbour has the fewest out-edges: superstep 1
    updates one vertex, so superstep 2 skips tiles (per-vertex filters)."""
    src, dst = arcs
    deg = OutOfCoreEngine(store, EngineConfig()).out_degree
    ones = np.flatnonzero(deg == 1)
    head = {int(s): int(d) for s, d in zip(src, dst) if deg[s] == 1}
    return int(min(ones, key=lambda v: (deg[head[int(v)]] or 1 << 30, v)))


def _stack_bytes(plan) -> int:
    return plan.num_tiles * plan.edge_cap * STACK_SLOT_BYTES


def _session_bytes(store, prog) -> int:
    """The stacks and the vertex arrays of a resident superstep."""
    plan = store.load_plan()
    in_degree, out_degree = store.load_degrees()
    state = prog.init(plan.num_vertices, out_degree.astype(np.float64),
                      in_degree.astype(np.float64))
    values = np.asarray(state.pop("value"))
    aux = {k: np.asarray(v) for k, v in state.items()}
    return _stack_bytes(plan) + resident_vertex_bytes(
        plan.num_vertices, plan.row_cap, plan.edge_cap, values, aux)


#: (config, budget against the session's bytes, stealing, resolved mode)
SELECTION = {
    "auto-fits": ({}, 0, False, "stacked"),
    "auto-one-byte-under": ({}, -1, False, "tiled"),
    "auto-stacks-alone-fit": ({}, "stacks", False, "tiled"),
    "auto-out-of-core-vertex-state": (dict(vertex_memory_budget=4096), 0,
                                      False, "tiled"),
    "auto-stealing": ({}, 0, True, "tiled"),
    "explicit-tiled": (dict(engine_mode="tiled"), 0, False, "tiled"),
    "explicit-stacked": (dict(engine_mode="stacked"), -1, False, "stacked"),
    "explicit-merged": (dict(engine_mode="merged"), -1, False, "merged"),
    "explicit-stacked-out-of-core": (
        dict(engine_mode="stacked", vertex_memory_budget=4096), 0, False,
        "tiled"),
}


@pytest.mark.parametrize("case", sorted(SELECTION))
@pytest.mark.parametrize("prog", [
    lambda: PageRank(),
    lambda: PersonalizedPageRank(seeds=(1, 5, 9, 200)),
], ids=["pagerank", "ppr4"])
def test_auto_selects_the_mode_from_bytes(store, case, prog):
    kw, delta, steal, expected = SELECTION[case]
    # the budget counts the vertex arrays of the session's program: [V] or
    # [V, Q] values, aux, and the scan's padded buffers and results
    budget = (_stack_bytes(store.load_plan()) if delta == "stacks"
              else _session_bytes(store, prog()) + delta)
    eng = OutOfCoreEngine(store, EngineConfig(device_budget_bytes=budget,
                                              **kw))
    if steal:
        # stealing moves tiles between servers, so they stay on the host
        eng.exchange = SimpleNamespace(rank=0, steal=True)
    session = eng.open_session(prog())
    assert session.engine_mode == expected
    session.close()


@pytest.mark.parametrize("mode", ["stacked", "merged"])
def test_stealing_refuses_the_modes_that_pin_tiles(store, mode):
    eng = OutOfCoreEngine(store, EngineConfig(engine_mode=mode))
    eng.exchange = SimpleNamespace(rank=0, steal=True)
    with pytest.raises(ValueError, match="stealing"):
        eng.open_session(PageRank())


@pytest.mark.parametrize("prog", [
    lambda: PageRank(),
    lambda: PersonalizedPageRank(seeds=(1, 5, 9, 200)),
    lambda: SSSP(source=0),
], ids=["pagerank", "ppr4", "sssp"])
def test_vertex_bytes_hold_the_resident_program(prog):
    """The fit test's vertex bytes bound what the compiled resident program
    holds besides the stacks, plus the next superstep's values and aux."""
    nv, tiles, edges, rows = 5_000, 6, 2_048, 1_500
    p = prog()
    deg = np.full(nv, 3.0)
    state = p.init(nv, deg, deg)
    values = np.asarray(state.pop("value"))
    aux = {k: np.asarray(v) for k, v in state.items()}
    stk = {"src": jnp.zeros((tiles, edges), jnp.int32),
           "dst_local": jnp.zeros((tiles, edges), jnp.int32),
           "val": jnp.ones((tiles, edges)),
           "row_start": jnp.zeros(tiles, jnp.int32),
           "num_rows": jnp.full(tiles, rows, jnp.int32)}
    mem = gab._jit_run_tile_stack.lower(
        p, jnp.asarray(values), {k: jnp.asarray(v) for k, v in aux.items()},
        stk, rows, "jnp", None).compile().memory_analysis()
    stacks = sum(a.nbytes for a in stk.values())
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - stacks
            + values.nbytes + sum(a.nbytes for a in aux.values()))
    assert resident_vertex_bytes(nv, rows, edges, values, aux) >= held


def _steps(store, prog, **kw):
    session = OutOfCoreEngine(store, EngineConfig(**kw)).open_session(prog)
    hist = [session.step() for _ in range(SUPERSTEPS)]
    return session, np.array(session.values, copy=True), hist


def test_sssp_matches_tiled_with_a_sparse_superstep(store, sparse_root):
    kw = dict(block_shift=0)
    _, tiled, _ = _steps(store, SSSP(source=sparse_root),
                         engine_mode="tiled", **kw)
    session, auto, hist = _steps(store, SSSP(source=sparse_root), **kw)
    eng = session.eng
    assert session.engine_mode == "stacked"
    assert auto.tobytes() == tiled.tobytes()
    assert any(h.tiles_skipped for h in hist)
    threshold = eng.cfg.skip_density_threshold * eng.plan.num_vertices
    for k, h in enumerate(hist):
        # a sparse superstep falls back to the per-tile loop with filters
        sparse = k > 0 and hist[k - 1].updated_vertices < threshold
        assert h.tiles_resident == (0 if sparse else h.tiles_processed), k
    assert hist[0].tiles_resident == eng.plan.num_tiles


@pytest.mark.parametrize("prog", [
    lambda: PageRank(),
    lambda: PersonalizedPageRank(seeds=(1, 5, 9, 200)),
], ids=["pagerank", "ppr4"])
def test_float_programs_match_tiled(store, prog):
    _, tiled, _ = _steps(store, prog(), engine_mode="tiled")
    session, auto, hist = _steps(store, prog())
    assert session.engine_mode == "stacked"
    assert all(h.tiles_resident == session.eng.plan.num_tiles for h in hist)
    np.testing.assert_allclose(auto, tiled, rtol=1e-6)
    # the same float32 operations in the same order: identical here
    assert auto.tobytes() == tiled.tobytes()


def test_resident_supersteps_move_the_stacks_once(store, monkeypatch):
    calls = []
    program = gab._jit_run_tile_stack

    def counted(*args, **kwargs):
        calls.append(1)
        return program(*args, **kwargs)

    monkeypatch.setattr(gab, "_jit_run_tile_stack", counted)
    plan = store.load_plan()
    eng = OutOfCoreEngine(store, EngineConfig())
    session = eng.open_session(PageRank())
    values = session.values.copy()
    hist = [session.step() for _ in range(SUPERSTEPS)]
    # one device program per superstep of the one server
    assert len(calls) == SUPERSTEPS
    # superstep 0: the [V] values and the stacks (src, dst_local and edge
    # values per slot, row_start and num_rows per tile); then the values
    assert hist[0].h2d_bytes == (values.nbytes + _stack_bytes(plan)
                                 + plan.num_tiles * 2 * 4)
    assert all(h.h2d_bytes == values.nbytes for h in hist[1:])
    # one [V] float32 value array and one [V] update mask back
    assert all(h.d2h_bytes == plan.num_vertices * (4 + 1) for h in hist)
    assert all(h.tiles_processed == h.tiles_resident == plan.num_tiles
               for h in hist)


@pytest.mark.parametrize("seg_impl", ["jnp", "pallas_fused"])
def test_the_resident_program_carries_its_named_scopes(seg_impl):
    nv, edges, rows, tiles = 64, 128, 16, 3
    stk = {"src": jnp.zeros((tiles, edges), jnp.int32),
           "dst_local": jnp.zeros((tiles, edges), jnp.int32),
           "val": jnp.ones((tiles, edges)),
           "row_start": jnp.zeros(tiles, jnp.int32),
           "num_rows": jnp.full(tiles, rows, jnp.int32)}
    compiled = gab._jit_run_tile_stack.lower(
        PageRank(), jnp.ones(nv), {"inv_out_degree": jnp.ones(nv)}, stk,
        rows, seg_impl, None).compile().as_text()
    # the benchmark's device time of the tile step reads programs by name
    assert "tile_stack" in compiled.splitlines()[0]
    for scope in ("graphh.gather", "graphh.combine", "graphh.apply"):
        assert f"/{scope}/" in compiled, scope
