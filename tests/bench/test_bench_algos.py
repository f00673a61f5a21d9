"""The algorithms' plain references, the teps numerator and the
lower-precision control."""
import numpy as np
import pytest

from bench import cells, control
from bench.graph500 import Graph

PR = cells.algorithm("pagerank")


def _graph(nv, src, dst):
    return Graph(nv, np.asarray(src, np.int32), np.asarray(dst, np.int32),
                 np.arange(nv, dtype=np.int32))


def test_teps_numerator_of_pagerank_on_a_hand_built_graph():
    # 5 -> 0 -> 1 -> 2 -> 0, 0 -> 3, 4 isolated
    g = _graph(6, [5, 0, 1, 2, 0], [0, 1, 2, 0, 3])
    out_deg = np.bincount(g.src, minlength=6)
    _, edges = PR.reference(g, out_deg, None, 5)
    # superstep 0: every arc. Superstep 0 leaves vertex 2 at 1.0 (its one
    # in-arc carries 1.0), so its arc is not traversed at 1; vertex 5 has no
    # in-arc and stays at 0.15 from superstep 1 on, so its arc drops from 2.
    assert edges == [5, 4, 4, 4, 4]


def test_teps_numerator_counts_both_arcs_of_an_undirected_edge():
    # the path 0 - 1 - 2 as arcs both ways: every vertex is active at
    # superstep 0; the ends then hold one value and the middle another,
    # which stay put from superstep 2 on
    g = _graph(3, [0, 1, 1, 2], [1, 0, 2, 1])
    out_deg = np.bincount(g.src, minlength=3)
    _, edges = PR.reference(g, out_deg, None, 3)
    assert edges[0] == 4 and edges[1] == 4


def _random_graph(seed, nv=200, ne=1500):
    rng = np.random.default_rng(seed)
    return _graph(nv, rng.integers(0, nv, ne), rng.integers(0, nv, ne))


@pytest.mark.parametrize("seed", [0, 1])
def test_pagerank_reference_is_the_damped_recurrence(seed):
    g = _random_graph(seed)
    nv = g.num_vertices
    out_deg = np.bincount(g.src, minlength=nv)
    v = np.ones(nv)
    for k in range(1, 6):
        acc = np.zeros(nv)
        for s, t in zip(g.src, g.dst):
            acc[t] += v[s] / out_deg[s]
        v = 0.15 + 0.85 * acc
        ref, _ = PR.reference(g, out_deg, None, k)
        np.testing.assert_allclose(ref, v, rtol=1e-12)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_bfloat16_control_fails_the_limits(bench_copy, seed):
    cell = cells.cell("small.pr", bench_copy)
    out = control.control_readings(cell, seed, supersteps=6)
    assert any(out["control"][k] > lim for k, lim in out["limits"].items())
    assert out["traversed_edges"] > 0


def test_the_reference_passes_its_own_limits():
    g = _random_graph(5)
    out_deg = np.bincount(g.src, minlength=g.num_vertices)
    ref, _ = PR.reference(g, out_deg, None, 4)
    assert all(v == 0 for v in PR.compare(ref.copy(), ref).values())
