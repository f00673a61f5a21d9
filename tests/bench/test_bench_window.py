"""The harness's run on the CPU at scale 10: the engine against the harness's
references through the window code, the faults the check must catch, the
configurations' guarantees, and the refusals."""
import dataclasses
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


def _half_the_tiles(monkeypatch, paths=("tile", "stack")):
    """Rows of every other tile keep their old values: per tile (sparse
    supersteps, the tiled path) and in a resident stack (dense supersteps
    since the store fits the device)."""
    if "tile" in paths:
        real = engine_mod.run_tile

        def run_tile(prog, values, aux, arrays, row_start, *rest):
            rows, new, upd = real(prog, values, aux, arrays, row_start, *rest)
            if row_start % 2:
                upd = jnp.zeros_like(upd)
            return rows, new, upd
        monkeypatch.setattr(engine_mod, "run_tile", run_tile)
    if "stack" in paths:
        real_stack = engine_mod.run_tile_stack

        def run_tile_stack(prog, values, aux, stk, *rest):
            num_rows = jnp.asarray(stk["num_rows"]).at[1::2].set(0)
            return real_stack(prog, values, aux, dict(stk, num_rows=num_rows),
                              *rest)
        monkeypatch.setattr(engine_mod, "run_tile_stack", run_tile_stack)


def _alter_first_row(new, upd):
    v = new[0]
    return (new.at[0].set(jnp.where(jnp.isfinite(v), v * 1.01 + 0.01, 0.5)),
            upd.at[0].set(True))


def _altered_answer(monkeypatch, paths=("tile", "stack")):
    """The first row's new value is altered where it is produced: in the
    tile that starts at row 0, and in a resident stack's result."""
    if "tile" in paths:
        real = engine_mod.run_tile

        def run_tile(prog, values, aux, arrays, row_start, *rest):
            rows, new, upd = real(prog, values, aux, arrays, row_start, *rest)
            if row_start == 0:
                new, upd = _alter_first_row(new, upd)
            return rows, new, upd
        monkeypatch.setattr(engine_mod, "run_tile", run_tile)
    if "stack" in paths:
        real_stack = engine_mod.run_tile_stack

        def run_tile_stack(*args):
            return _alter_first_row(*real_stack(*args))
        monkeypatch.setattr(engine_mod, "run_tile_stack", run_tile_stack)


def _assert_fails_its_comparison(line):
    c = line["compared"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert line["correct"] is False
    assert line["failed"] >= 1


@pytest.mark.parametrize("fault", [_frozen_state, _half_the_tiles,
                                   _altered_answer])
def test_a_broken_timed_path_is_not_correct(bench_copy, monkeypatch, fault):
    fault(monkeypatch)
    _, line = _run(bench_copy, "small.pr")
    _assert_fails_its_comparison(line)


@pytest.mark.parametrize("path,mode", [("stack", "stacked"),
                                       ("tile", "tiled")])
@pytest.mark.parametrize("fault", [_half_the_tiles, _altered_answer])
def test_a_fault_on_one_path_alone_is_not_correct(bench_copy, monkeypatch,
                                                  fault, path, mode):
    """Each path's fault alone turns the run incorrect, the other path left
    as it is: the resident stack in the cell's own engine mode, the per-tile
    entry with the engine pinned to its tiled path."""
    if mode == "tiled":
        real = run._engine_config
        monkeypatch.setattr(run, "_engine_config", lambda cfg, trace: (
            dataclasses.replace(real(cfg, trace), engine_mode="tiled")))
    modes = []
    real_open = engine_mod.OutOfCoreEngine.open_session

    def open_session(self, *args, **kw):
        session = real_open(self, *args, **kw)
        modes.append(session.engine_mode)
        return session
    monkeypatch.setattr(engine_mod.OutOfCoreEngine, "open_session",
                        open_session)
    fault(monkeypatch, paths=(path,))
    _, line = _run(bench_copy, "small.pr")
    assert set(modes) == {mode}
    _assert_fails_its_comparison(line)


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
