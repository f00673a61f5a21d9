"""Finds the benchmark's pieces by name.

Everything that belongs to one configuration, one cell, one algorithm or one
metric sits in a file of its own, and a later change adds a piece by adding a
file:

    BENCHMARK.json              cells, metrics, units and bounds
    bench/configs/<config>.json the deployment: graph, store, memory, servers
    bench/workloads/<cell>.json the cell: configuration, algorithm, roots
    bench/algos/<algorithm>.py  program, plain reference, compared numbers
    bench/metrics/<metric>.py   ``reduce(run)``: one metric from a run record

A configuration's ``graph`` may carry ``"weights"``: absent or ``null`` for
an unweighted graph, ``"uniform01"`` for Graph500 kernel 3's float32 weights,
uniform on [0, 1) (bench/graph500.py). The store then holds them as edge
values, and an algorithm's reference reads them as ``graph.weight``.

``root`` is the benchmark's directory (this one, or a copy in a test);
``BENCHMARK.json`` lies beside it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Optional

BENCH_DIR = Path(__file__).resolve().parent

#: a name as BENCHMARK.json allows one: no slash, so no path leads out
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


@dataclasses.dataclass(frozen=True)
class Metric:
    """One metric of BENCHMARK.json with the reducer that computes it."""

    name: str
    unit: str
    reducer: ModuleType


@dataclasses.dataclass(frozen=True)
class Cell:
    """One cell: its workload file, its configuration and its algorithm."""

    name: str
    chips: int
    config: dict
    workload: dict
    algorithm: ModuleType


def _checked(name: str) -> str:
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _file(root: Path, kind: str, name: str, suffix: str) -> Path:
    path = Path(root) / kind / f"{_checked(name)}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return path


def _module(path: Path, kind: str) -> ModuleType:
    tag = re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(f"graphh_bench_{kind}_{tag}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = BENCH_DIR) -> dict:
    """The parsed BENCHMARK.json beside ``root``."""
    with open(Path(root).parent / "BENCHMARK.json") as f:
        return json.load(f)


def config(name: str, root: Path = BENCH_DIR) -> dict:
    """The configuration file ``configs/<name>.json``."""
    with open(_file(root, "configs", name, ".json")) as f:
        return json.load(f)


def workload(name: str, root: Path = BENCH_DIR) -> dict:
    """The cell file ``workloads/<name>.json``."""
    with open(_file(root, "workloads", name, ".json")) as f:
        return json.load(f)


def algorithm(name: str, root: Path = BENCH_DIR) -> ModuleType:
    """The algorithm module ``algos/<name>.py``."""
    return _module(_file(root, "algos", name, ".py"), "algos")


def reducer(name: str, root: Path = BENCH_DIR) -> ModuleType:
    """The metric reducer ``metrics/<name>.py``."""
    return _module(_file(root, "metrics", name, ".py"), "metrics")


def cell(name: str, root: Path = BENCH_DIR) -> Cell:
    """Cell ``name`` as BENCHMARK.json declares it, with its files loaded."""
    entry = _entry(benchmark(root)["workloads"], name)
    wl = workload(name, root)
    if wl["config"] != entry["config"]:
        raise ValueError(f"cell {name}: workload file names configuration "
                         f"{wl['config']!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    return Cell(name=name, chips=int(entry["chips"]),
                config=config(wl["config"], root), workload=wl,
                algorithm=algorithm(wl["algorithm"], root))


def metrics(cell_name: str, trace: bool,
            root: Path = BENCH_DIR) -> list[Metric]:
    """The metrics a run of ``cell_name`` reports: the end-to-end ones, or
    with ``trace`` the per-layer ones; a metric with a ``workloads`` list
    only in the cells it names."""
    bench = benchmark(root)
    _entry(bench["workloads"], cell_name)
    out = []
    for m in bench["per_layer" if trace else "end_to_end"]:
        if cell_name in m.get("workloads", [cell_name]):
            out.append(Metric(m["name"], m["unit"], reducer(m["name"], root)))
    return out


def _entry(entries: list, name: str) -> dict:
    _checked(name)
    found: Optional[dict] = next((e for e in entries if e["name"] == name),
                                 None)
    if found is None:
        raise KeyError(f"BENCHMARK.json has no cell named {name!r}")
    return found
