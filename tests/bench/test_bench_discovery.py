"""The harness finds configurations, cells, algorithms and metrics by name,
and BENCHMARK.json agrees with the files it names."""
import json
import re
from pathlib import Path

import pytest

from bench import cells

REPO = Path(__file__).resolve().parents[2]

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_every_cell_loads_by_name(entry):
    cell = cells.cell(entry["name"])
    assert cell.workload["config"] == entry["config"]
    assert cell.chips == entry["chips"] == cell.config["chips"]
    assert set(cell.algorithm.LIMITS) and callable(cell.algorithm.reference)
    for trace in (False, True):
        assert cells.metrics(entry["name"], trace)
    # set-up and at least one more end-to-end metric in every cell
    names = {m.name for m in cells.metrics(entry["name"], False)}
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_every_config_file_is_its_own(entry):
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    cfg = cells.config(entry["name"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert cfg["source"] == entry["source"]


def test_every_metric_has_a_reducer_and_moves_an_end_to_end_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(cells.reducer(m["name"]).reduce)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"]


def test_benchmark_json_keeps_to_its_schema_limits():
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(name.fullmatch(n) for n in names)
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir()


def test_adding_a_cell_is_adding_files(bench_copy, add_cell):
    add_cell(bench_copy, "tiny.pr", "g500-22.pr", "tiny", scale=8)
    cell = cells.cell("tiny.pr", bench_copy)
    assert cell.config["graph"]["scale"] == 8
    assert cell.algorithm.LIMITS == cells.cell("g500-22.pr").algorithm.LIMITS
    names = [m.name for m in cells.metrics("tiny.pr", False, bench_copy)]
    assert names == ["teps", "setup_s"]
    traced = [m.name for m in cells.metrics("tiny.pr", True, bench_copy)]
    assert "gab_roofline" not in traced       # it names its cells
    assert "device_idle_share" in traced


def test_adding_a_metric_is_adding_a_file(bench_copy):
    bench_json = bench_copy.parent / "BENCHMARK.json"
    bench = json.loads(bench_json.read_text())
    bench["per_layer"].append({"name": "sessions", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "superstep loop", "moves": "teps"})
    bench_json.write_text(json.dumps(bench))
    (bench_copy / "metrics" / "sessions.py").write_text(
        "def reduce(run):\n    return run['sessions']\n")
    found = {m.name: m for m in cells.metrics("small.pr", True, bench_copy)}
    assert found["sessions"].reducer.reduce({"sessions": 3}) == 3


@pytest.mark.parametrize("bad", ["../configs/g500-22", "a/b", "", ".hidden",
                                 "x" * 65])
def test_names_that_are_not_names_are_refused(bad):
    with pytest.raises(ValueError):
        cells.workload(bad)


def test_unknown_names_are_errors():
    with pytest.raises(FileNotFoundError):
        cells.config("no-such-config")
    with pytest.raises(KeyError):
        cells.cell("no-such.cell")
