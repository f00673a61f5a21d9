"""CPU rehearsal of ``chip_smoke.py``'s phases at scale 10.

The script itself refuses to run off a TPU; the phase functions are called
here directly (kernels interpreted on the CPU) so a wrong path, argument
or check is found before any chip time is spent.
"""
import importlib.util
from pathlib import Path

import pytest


@pytest.fixture(scope="module")
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def built(smoke, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("smoke_store"))
    # a smaller tile than the script's default gives the scale-10 graph
    # several tiles, so tile order, skipping and tile checks are exercised
    store = smoke.build_graph(root, scale=10, seed=0, tile_size=2048)
    return store, smoke.reference_graph(10, 0)


def test_build_phase(built):
    store, g = built
    plan = store.load_plan()
    assert plan.num_vertices == g["nv"] == 1024
    assert plan.num_tiles > 1


def test_analytics_phase(smoke, built, capsys):
    store, g = built
    smoke.run_analytics(store, g)
    out = capsys.readouterr().out
    for label in ("pagerank/jnp", "pagerank/fused", "sssp/jnp",
                  "sssp/fused", "sssp/fused-vs-onehot tiles"):
        assert f"] {label}:" in out, label


def test_serve_phase(smoke, built, capsys):
    store, _ = built
    smoke.serve_queries(store, seed=0, ppr_rtol=smoke.FUSED_ULP_RTOL)
    out = capsys.readouterr().out
    assert "] serve:" in out and "] serve/offline-check:" in out


def test_main_refuses_off_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_reference_matches_engine_semantics(smoke, built):
    """The numpy reference is the engine's Jacobi superstep: SSSP after
    one relaxation from vertex 0 reaches exactly its out-neighbours."""
    import numpy as np

    _, g = built
    d1 = smoke.reference_sssp(g, 1)
    reached = np.flatnonzero(np.isfinite(d1))
    src_sorted = g["src"]
    assert set(reached) >= {0}
    heads = np.repeat(g["heads"], np.diff(np.r_[g["starts"], len(src_sorted)]))
    assert set(reached) == {0} | set(heads[src_sorted == 0].tolist())
