"""The engine's spans and counters (core/obs.py): the ``graphh.*`` spans of a
superstep in a ``jax.profiler`` trace, nested as the work is, and the
per-superstep counters against what the tile store says they must be.

Graph: Graph500 R-MAT at scale 10 (1,024 vertices, 16,384 arcs drawn,
duplicates removed) in 1,024-edge tiles.
"""
import pathlib

import jax
import numpy as np
import pytest

from repro.core import obs
from repro.core.apps import SSSP, PageRank
from repro.core.engine import EngineConfig, OutOfCoreEngine

SCALE = 10
TILE_EDGES = 1024

#: the spans of the serial tiled path, each with the span it nests in
SERIAL_SPANS = {
    obs.VALUES_PUT: obs.SUPERSTEP,
    obs.SKIP: obs.SUPERSTEP,
    obs.TILE_LOAD: obs.SUPERSTEP,
    obs.TILE_DISPATCH: obs.SUPERSTEP,
    obs.TILE_FETCH: obs.SUPERSTEP,
    obs.TILE_SPLIT: obs.SUPERSTEP,
    obs.BARRIER: obs.SUPERSTEP,
    obs.BARRIER_MEASURE: obs.BARRIER,
    obs.BARRIER_APPLY: obs.BARRIER,
    obs.BARRIER_CACHE: obs.BARRIER,
}
ALL_SPANS = set(SERIAL_SPANS) | {obs.SUPERSTEP}


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

    st = TileStore(str(tmp_path_factory.mktemp("spans")))
    spe.preprocess_arrays(*arcs, None, 1 << SCALE, st, tile_size=TILE_EDGES)
    return st


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; return its result and the spans of
    the trace as (start_ns, end_ns, name, stats) on the engine's thread."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    [path] = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    space = jax.profiler.ProfileData.from_file(str(path))
    lines = [[(e.start_ns, e.end_ns, e.name,
               dict(e.stats) if e.name == obs.SUPERSTEP else {})
              for e in ln.events]
             for pl in space.planes if pl.name == "/host:CPU"
             for ln in pl.lines]
    [line] = [ln for ln in lines if any(e[2] == obs.SUPERSTEP for e in ln)]
    return out, [e for e in line if e[2].startswith("graphh.")]


def _inside(child, parents):
    return any(p[0] <= child[0] and child[1] <= p[1] for p in parents)


def test_a_traced_superstep_holds_every_serial_span_nested(store, arcs,
                                                          tmp_path):
    # per-vertex skip filters, and a root whose one out-neighbour has the
    # fewest out-edges: superstep 1 runs that neighbour's tiles, skips others
    eng = OutOfCoreEngine(store, EngineConfig(block_shift=0,
                                              engine_mode="tiled"))
    src, dst = arcs
    deg = eng.out_degree
    ones = np.flatnonzero(deg == 1)
    head = {int(s): int(d) for s, d in zip(src, dst) if deg[s] == 1}
    root = int(min(ones, key=lambda v: (deg[head[int(v)]] or 1 << 30, v)))
    session = eng.open_session(SSSP(source=root))
    session.step()          # superstep 0 runs every tile and builds filters
    stats, spans = _traced(tmp_path, session.step)
    assert stats.tiles_skipped > 0 and stats.tiles_processed > 0
    by_name = {}
    for ev in spans:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == ALL_SPANS
    [step] = by_name[obs.SUPERSTEP]
    assert step[3] == {"superstep": 1}
    for name, parent in SERIAL_SPANS.items():
        for ev in by_name[name]:
            assert _inside(ev, by_name[parent]), (name, parent)
    for name in (obs.TILE_LOAD, obs.TILE_DISPATCH, obs.TILE_FETCH,
                 obs.TILE_SPLIT):
        assert len(by_name[name]) == stats.tiles_processed
    # the timed spans and their SuperstepStats sums read the same clock
    dispatch_ns = sum(e - s for s, e, _, _ in by_name[obs.TILE_DISPATCH])
    assert dispatch_ns * 1e-9 >= stats.dispatch_seconds


def test_counters_of_a_full_superstep(store):
    plan = store.load_plan()
    eng = OutOfCoreEngine(store, EngineConfig(engine_mode="tiled"))
    session = eng.open_session(PageRank())
    values = session.values.copy()
    stats = session.step()      # superstep 0: every tile runs
    assert stats.tiles_processed == plan.num_tiles
    assert stats.edges_real == plan.num_edges
    assert stats.edges_padded == plan.num_tiles * plan.edge_cap
    # per tile src, dst_local and edge values (int32, int32, float32) and
    # two int32 scalars; before them the [V] float32 values
    assert stats.h2d_bytes == (values.nbytes + plan.num_tiles
                               * (3 * 4 * plan.edge_cap + 2 * 4))
    # per tile rows (int32), new values (float32) and the update mask
    assert stats.d2h_bytes == plan.num_tiles * plan.row_cap * (4 + 4 + 1)


CONFIGS = {
    "serial": dict(engine_mode="tiled"),
    "pipelined": dict(engine_mode="tiled", pipeline=True, stack_size=2),
    "stacked": dict(engine_mode="stacked"),
    "merged": dict(engine_mode="merged"),
    "ooc-vstate": dict(vertex_memory_budget=4096),
    "ooc-vstate-pipelined": dict(vertex_memory_budget=4096, pipeline=True),
}


@pytest.mark.parametrize("mode", sorted(CONFIGS))
def test_tile_phases_add_up_to_compute(store, mode, tmp_path):
    plan = store.load_plan()
    eng = OutOfCoreEngine(store, EngineConfig(**CONFIGS[mode]))
    session = eng.open_session(PageRank())
    session.step()
    stats, spans = _traced(tmp_path, session.step)
    assert {e[2] for e in spans} <= ALL_SPANS
    assert {obs.TILE_DISPATCH, obs.TILE_FETCH, obs.TILE_SPLIT} <= \
        {e[2] for e in spans}
    phases = (stats.dispatch_seconds + stats.fetch_seconds
              + stats.split_seconds)
    assert 0 < phases <= stats.compute_seconds
    assert stats.load_seconds + stats.compute_seconds <= stats.seconds
    assert stats.edges_real == plan.num_edges
    assert stats.edges_real <= stats.edges_padded
    assert stats.h2d_bytes > 0 and stats.d2h_bytes > 0


def test_values_are_the_same_with_the_profiler_on(store, tmp_path):
    def run():
        eng = OutOfCoreEngine(store, EngineConfig())
        session = eng.open_session(PageRank())
        for _ in range(3):
            session.step()
        return session.values.copy()

    plain = run()
    traced, spans = _traced(tmp_path, run)
    assert spans
    assert np.array_equal(plain, traced)
    assert plain.tobytes() == traced.tobytes()


@pytest.mark.parametrize("seg_impl", ["jnp", "pallas_fused"])
def test_the_tile_step_carries_its_named_scopes(seg_impl):
    import jax.numpy as jnp

    from repro.core import gab

    nv, edges, rows = 64, 128, 16
    compiled = gab._jit_tile_step.lower(
        PageRank(), jnp.ones(nv), {"inv_out_degree": jnp.ones(nv)},
        jnp.zeros(edges, jnp.int32), jnp.zeros(edges, jnp.int32),
        jnp.ones(edges), (jnp.int32(0), jnp.int32(rows)), rows, seg_impl,
        None).compile().as_text()
    for scope in ("graphh.gather", "graphh.combine", "graphh.apply"):
        assert f"/{scope}/" in compiled, scope
