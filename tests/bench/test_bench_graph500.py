"""The harness's Graph500 generator: seeded, deterministic, a simple undirected
graph skewed as Graph500's, giving every seed the same tile shapes, and with
kernel 3's weights where the configuration asks for them."""
import hashlib

import numpy as np
import pytest

from bench import graph500
from repro.core.partition import plan_partition
from repro.graphio import spe
from repro.graphio.formats import TileStore

SCALE = 10
NV, NE = 1 << SCALE, 16 << SCALE


@pytest.fixture(scope="module")
def graphs():
    return {seed: graph500.generate(SCALE, 16, 7, seed)
            for seed in (1, 2, 2**31 + 9)}


def test_same_seed_same_graph(graphs):
    again = graph500.generate(SCALE, 16, 7, 1)
    assert again.num_vertices == graphs[1].num_vertices
    for a, b in zip(graphs[1][1:], again[1:]):
        np.testing.assert_array_equal(a, b)


def test_seeds_relabel_one_graph(graphs):
    """Every seed gives the same graph up to a relabelling that keeps each
    vertex id's degree, so SPE cuts the same tiles."""
    nv = graphs[1].num_vertices
    plans, base = [], []
    for g in graphs.values():
        assert g.num_vertices == nv
        in_deg = np.bincount(g.dst, minlength=nv)
        plans.append(plan_partition(in_deg, 1024, 128, 8))
        back = np.argsort(g.relabel)       # vertex id -> base id
        base.append(sorted(zip(back[g.src].tolist(), back[g.dst].tolist())))
        assert np.array_equal(in_deg[g.relabel],
                              np.bincount(back[g.dst], minlength=nv))
    for p in plans[1:]:
        assert (p.edge_cap, p.row_cap) == (plans[0].edge_cap, plans[0].row_cap)
        np.testing.assert_array_equal(p.splitter, plans[0].splitter)
        np.testing.assert_array_equal(p.edges_per_tile,
                                      plans[0].edges_per_tile)
    assert all(b == base[0] for b in base[1:])
    assert not np.array_equal(graphs[1].src, graphs[2].src)


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_the_graph_is_simple_and_undirected(graphs, seed):
    g = graphs[seed]
    assert g.src.dtype == g.dst.dtype == np.int32
    assert 0 <= g.src.min() and max(g.src.max(), g.dst.max()) < g.num_vertices
    arcs = set(zip(g.src.tolist(), g.dst.tolist()))
    assert len(arcs) == len(g.src)                     # no duplicate arc
    assert all(s != d for s, d in arcs)                # no self-loop
    assert all((d, s) in arcs for s, d in arcs)        # both directions
    # every kept vertex has an edge, and the numbering has no gap
    assert np.bincount(g.src, minlength=g.num_vertices).min() >= 1
    assert sorted(g.relabel.tolist()) == list(range(g.num_vertices))


def test_graph500_degree_skew(graphs):
    g = graphs[1]
    deg = np.bincount(g.src, minlength=g.num_vertices)
    # duplicates and self-loops go: at scale 10 about a third of the drawn
    # edges repeat, and some ids have no edge
    assert 0.4 * NE < len(g.src) / 2 < 0.9 * NE
    assert g.num_vertices < 0.95 * NV
    # power-law tail: the top 1% of vertices hold over a tenth of the arcs,
    # and the hub has over 15 times the mean degree
    top = np.sort(deg)[::-1][:g.num_vertices // 100].sum()
    assert top > len(g.src) / 10
    assert deg.max() > 15 * deg.mean()


@pytest.mark.parametrize("seed", [-1, 1 << 64])
def test_seed_out_of_range_is_refused(seed):
    with pytest.raises(ValueError):
        graph500.prng_key(seed)


def test_large_seeds_do_not_wrap():
    a = graph500.prng_key(5)
    b = graph500.prng_key(5 + (1 << 32))
    import jax
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))


#: sha256 of src, dst and relabel of ``generate(10, 16, 7, seed)`` as the
#: generator made them before it could draw weights
UNWEIGHTED_DIGESTS = {
    1: "5734da4d7e6e8972cf4531a369de93bff98e57f92645ffc9f66858d577b5b034",
    2**31 + 9:
        "2e4ba37f7dbc234b8c9ddbeebb4ce78ccddf9a8364a4ae2464ccdbef0f267752",
}


@pytest.fixture(scope="module")
def weighted():
    return {seed: graph500.generate(SCALE, 16, 7, seed, weights="uniform01")
            for seed in (1, 2**31 + 9)}


@pytest.mark.parametrize("seed", sorted(UNWEIGHTED_DIGESTS))
def test_an_unweighted_graph_is_as_before(graphs, weighted, seed):
    g = graphs[seed]
    assert g.weight is None
    h = hashlib.sha256()
    for a in (g.src, g.dst, g.relabel):
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == UNWEIGHTED_DIGESTS[seed]
    # drawing the weights moves no arc
    for a, b in zip(g[1:4], weighted[seed][1:4]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_weights_are_uniform01_float32_and_shared_by_both_arcs(weighted,
                                                               seed):
    g = weighted[seed]
    w = g.weight
    assert w.dtype == np.float32 and w.shape == g.src.shape
    assert 0.0 <= w.min() and w.max() < 1.0
    assert 0.4 < w.mean() < 0.6
    arc = {(s, d): x for s, d, x in zip(g.src.tolist(), g.dst.tolist(),
                                        w.tolist())}
    assert all(arc[d, s] == x for (s, d), x in arc.items())


def test_a_graph_seed_gives_the_same_weights(weighted):
    again = graph500.generate(SCALE, 16, 7, 1, weights="uniform01")
    np.testing.assert_array_equal(again.weight, weighted[1].weight)
    other = graph500.generate(SCALE, 16, 8, 1, weights="uniform01")
    assert not np.array_equal(other.weight[:100], weighted[1].weight[:100])


def test_seeds_keep_each_weight_on_its_edge(weighted):
    """Two seeds give the same weighted edges in base ids: the same multiset
    of weights, each on the same edge of the isomorphic graphs."""
    out = []
    for g in weighted.values():
        back = np.argsort(g.relabel)       # vertex id -> base id
        out.append(sorted(zip(back[g.src].tolist(), back[g.dst].tolist(),
                              g.weight.tolist())))
    assert out[0] == out[1]


def test_unknown_weights_are_refused():
    with pytest.raises(ValueError):
        graph500.generate(SCALE, 16, 7, 1, weights="normal")


def test_a_zero_weight_survives_spe_into_the_tile(tmp_path):
    """SSSP relaxes across a weight of 0.0 (a [0, 1) draw over 2**28 edges
    makes some), so SPE must keep it as the tile's ``val``: only the sink
    row marks padding there, never ``val == 0``."""
    src = np.array([0, 1, 1, 2, 3, 0], np.int32)
    dst = np.array([1, 0, 2, 1, 0, 3], np.int32)
    val = np.array([0.0, 0.0, 0.25, 0.25, 0.0, 0.0], np.float32)
    store = TileStore(str(tmp_path / "store"))
    plan = spe.preprocess_arrays(src, dst, val, 4, store, tile_size=4)
    got = set()
    for t in range(plan.num_tiles):
        tile = store.read_tile(t)
        n = tile.meta.num_edges
        assert tile.val is not None
        got |= set(zip(tile.src[:n].tolist(),
                       (tile.dst_local[:n] + tile.meta.row_start).tolist(),
                       tile.val[:n].tolist()))
    assert got == set(zip(src.tolist(), dst.tolist(), val.tolist()))
