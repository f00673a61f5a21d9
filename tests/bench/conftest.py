"""Fixtures for the benchmark harness's CPU tests: the repository root on
``sys.path`` (the harness is the ``bench`` package there) and a copy of the
benchmark with small cells added, which is how a later change adds one."""
import json
import shutil
import sys
from pathlib import Path
from typing import Optional

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

#: small stand-in of the cell: scale 10 (at most 1,024 vertices and 16,384
#: edges drawn)
SMALL_SCALE = 10
SMALL_TILE_EDGES = 1024


def _add_cell(root: Path, name: str, like: str, config: str,
             scale: int = SMALL_SCALE,
             tile_edges: int = SMALL_TILE_EDGES,
             weights: Optional[str] = None, **workload) -> str:
    """Add cell ``name`` (configuration ``config``) to the benchmark copy at
    ``root``, shaped like cell ``like`` at ``scale``, with the graph's
    ``weights`` and the cell file's keys in ``workload`` set where given: two
    new files and one BENCHMARK.json entry, nothing else."""
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    base = next(w for w in bench["workloads"] if w["name"] == like)
    cfg = json.loads((root / "configs" / f"{base['config']}.json").read_text())
    cfg["graph"]["scale"] = scale
    if weights is not None:
        cfg["graph"]["weights"] = weights
    cfg["store"]["tile_edges"] = tile_edges
    (root / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    wl = json.loads((root / "workloads" / f"{like}.json").read_text())
    wl.update(workload, config=config)
    (root / "workloads" / f"{name}.json").write_text(json.dumps(wl))
    bench["workloads"].append(dict(base, name=name, config=config,
                                   traffic=wl["algorithm"]))
    (root.parent / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of bench/ and BENCHMARK.json with the small cell ``small.pr``
    added; returns the copy's bench directory."""
    root = tmp_path / "bench"
    shutil.copytree(REPO / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    _add_cell(root, "small.pr", "g500-22.pr", "small")
    return root


@pytest.fixture
def add_cell():
    """The helper that adds a small cell to a benchmark copy."""
    return _add_cell
