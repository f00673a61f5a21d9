"""The harness's run on the CPU at scale 10: the engine against the harness's
references through the window code, the faults the check must catch, the
configurations' guarantees, and the refusals."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import cells, run
import repro.core.engine as engine_mod

REPO = Path(__file__).resolve().parents[2]
PEAKS = {"hbm_bytes_per_s": 819e9}


def _run(root, name, seconds=0.3, trace=False):
    record = run.run_cell(cells.cell(name, root), 2**31 + 11, seconds, trace,
                          peak_table=PEAKS)
    return record, run.result_line(record, cells.metrics(name, trace, root),
                                   jax.devices())


def test_engine_matches_reference_through_the_window(bench_copy):
    record, line = _run(bench_copy, "small.pr")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == record["sessions"] >= 1
    assert record["stats"] and record["traversed_edges"] > 0
    assert set(line["metrics"]) == {"teps", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "compared"
    limits = {k: v["limit"] for k, v in line["compared"].items()}
    assert {"edge_cache_bytes", "device_peak_bytes"} <= set(limits)
    # the in-memory configuration's cache holds every tile raw
    assert record["cache_mode"] == 1
    assert limits["edge_cache_bytes"] == 8 << 30
    # the window's supersteps each carry the host's usage over them
    assert len(record["usage"]) == len(record["stats"])


def test_traced_run_reports_the_host_layers_on_the_cpu(bench_copy):
    _, line = _run(bench_copy, "small.pr", trace=True)
    # no device trace on the CPU: those readers find nothing and stay silent
    assert set(line["metrics"]) == {"spe_s", "warmup_s", "tile_load_s",
                                    "tile_step_ms", "barrier_s"}
    assert line["correct"] is True


def _frozen_state(monkeypatch):
    real = engine_mod.EngineSession.step

    def step(self):
        before = self.values.copy()
        stats = real(self)
        self.values[:] = before
        return stats
    monkeypatch.setattr(engine_mod.EngineSession, "step", step)


def _half_the_tiles(monkeypatch):
    real = engine_mod.run_tile

    def run_tile(prog, values, aux, arrays, row_start, *rest):
        rows, new, upd = real(prog, values, aux, arrays, row_start, *rest)
        if row_start % 2:
            upd = jnp.zeros_like(upd)
        return rows, new, upd
    monkeypatch.setattr(engine_mod, "run_tile", run_tile)


def _altered_answer(monkeypatch):
    real = engine_mod.run_tile

    def run_tile(prog, values, aux, arrays, row_start, *rest):
        rows, new, upd = real(prog, values, aux, arrays, row_start, *rest)
        if row_start == 0:
            v = new[0]
            new = new.at[0].set(jnp.where(jnp.isfinite(v), v * 1.01 + 0.01,
                                          0.5))
            upd = upd.at[0].set(True)
        return rows, new, upd
    monkeypatch.setattr(engine_mod, "run_tile", run_tile)


@pytest.mark.parametrize("fault", [_frozen_state, _half_the_tiles,
                                   _altered_answer])
def test_a_broken_timed_path_is_not_correct(bench_copy, monkeypatch, fault):
    fault(monkeypatch)
    _, line = _run(bench_copy, "small.pr")
    assert line["correct"] is False
    assert line["failed"] >= 1


def test_edge_cache_over_capacity_is_not_correct(bench_copy, monkeypatch):
    monkeypatch.setattr(engine_mod.EdgeCache, "resident_bytes",
                        lambda self: self.capacity_bytes + 1)
    _, line = _run(bench_copy, "small.pr")
    c = line["compared"]["edge_cache_bytes"]
    assert c["value"] > c["limit"] and line["correct"] is False


def test_device_peak_over_budget_is_not_correct(bench_copy, monkeypatch):
    monkeypatch.setattr(run, "_memory_peak", lambda: 1 << 50)
    _, line = _run(bench_copy, "small.pr")
    assert line["compared"]["device_peak_bytes"]["value"] == 1 << 50
    assert line["correct"] is False


def test_unknown_device_kind_is_an_error():
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.ChipError):
        run.peaks("TPU v99")


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-22.pr",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_off_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _cli(REPO)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "runs only on a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for path in bench["paths"]:
        shutil.copytree(REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout == ""


def test_window_counts_the_running_superstep_by_its_share_of_time():
    edges, times = [10, 20, 40], [(0.0, 1.0), (1.0, 3.0), (3.5, 7.5)]
    assert run._in_window(edges, times, 5.5) == 10 + 20 + 40 * 0.5
    assert run._in_window(edges, times, 7.5) == 70
    assert run._in_window(edges, times, 0.25) == 2.5
    # a session opening between supersteps traverses nothing
    assert run._in_window(edges, times, 3.25) == 30
