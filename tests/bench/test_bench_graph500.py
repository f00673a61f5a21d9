"""The harness's Graph500 generator: seeded, deterministic, a simple undirected
graph skewed as Graph500's, and giving every seed the same tile shapes."""
import numpy as np
import pytest

from bench import graph500
from repro.core.partition import plan_partition

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
