"""End-to-end behaviour tests for the whole system.

1. GraphH pipeline: synthetic graph -> SPE -> tile store -> out-of-core
   engine (cache + hybrid comm + skipping) -> PageRank == networkx; engine
   accounting is self-consistent.
2. LM pipeline: train a tiny model for a few steps (driver code path),
   checkpoint, then serve completions from the trained weights.
"""
import numpy as np
import pytest


def test_graphh_end_to_end(tmp_path):
    import networkx as nx

    from repro.core.apps import PageRank
    from repro.core.engine import EngineConfig, OutOfCoreEngine
    from repro.graphio import spe, synth
    from repro.graphio.formats import TileStore

    nv, ne = 2000, 16000
    store = TileStore(str(tmp_path / "g"), disk_mode=2)    # compressed at rest
    spe.preprocess(lambda: synth.rmat_edges(nv, ne, seed=5),
                   nv, store, tile_size=1024)
    plan = store.load_plan()
    assert plan.num_tiles > 4

    eng = OutOfCoreEngine(store, EngineConfig(
        num_servers=4, cache_capacity_bytes=1 << 22, cache_mode="auto",
        comm_mode="hybrid", max_supersteps=100,
        # the edge cache serves every superstep on the per-tile path
        engine_mode="tiled"))
    res = eng.run(PageRank(update_tol=1e-9))
    assert res.converged

    # oracle
    tiles_edges = []
    for t in range(plan.num_tiles):
        tile = store.read_tile(t)
        n = tile.meta.num_edges
        tiles_edges.append((tile.src[:n], tile.dst_local[:n] + tile.meta.row_start))
    src = np.concatenate([e[0] for e in tiles_edges])
    dst = np.concatenate([e[1] for e in tiles_edges])
    # RMAT emits parallel edges; GraphH keeps multiplicity (paper semantics),
    # so the oracle uses multiplicity as edge weight.
    key = src.astype(np.int64) * nv + dst
    uniq, counts = np.unique(key, return_counts=True)
    G = nx.DiGraph()
    G.add_nodes_from(range(nv))
    G.add_weighted_edges_from(
        zip((uniq // nv).tolist(), (uniq % nv).tolist(), counts.tolist()))
    pr = nx.pagerank(G, alpha=0.85, tol=1e-12, max_iter=500, weight="weight")
    ref = np.array([pr[i] for i in range(nv)])
    ours = res.values / res.values.sum()
    assert np.abs(ours - ref).max() < 1e-6

    # accounting self-consistency
    h0 = res.history[0]
    assert h0.tiles_processed == plan.num_tiles
    assert h0.raw_bytes > 0 and h0.wire_bytes > 0
    assert 0 <= h0.cache_hit_ratio <= 1
    # warm cache by superstep 2 (capacity is generous)
    assert res.history[2].disk_bytes_read <= res.history[0].disk_bytes_read


def test_lm_train_then_serve(tmp_path):
    from repro.launch import serve as serve_cli
    from repro.launch import train as train_cli

    losses = train_cli.main([
        "--arch", "granite-moe-1b-a400m", "--reduced",
        "--steps", "12", "--batch", "4", "--seq", "32",
        "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "6",
        "--log-every", "6",
    ])
    assert losses[-1] < losses[0]
    outs = serve_cli.main([
        "--arch", "granite-moe-1b-a400m", "--reduced",
        "--requests", "4", "--slots", "2", "--max-new", "4",
        "--max-len", "48", "--prompt-len", "6",
        "--ckpt-dir", str(tmp_path / "ck"),
    ])
    assert len(outs) == 4
    assert all(len(o.tokens) == 4 for o in outs)


def test_graph_cli(tmp_path):
    from repro.launch import graph as graph_cli

    res = graph_cli.main([
        "--app", "pagerank", "--vertices", "500", "--edges", "3000",
        "--tile-size", "256", "--servers", "2", "--supersteps", "30",
        "--store", str(tmp_path / "s"),
    ])
    assert res.supersteps > 1
