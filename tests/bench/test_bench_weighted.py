"""A weighted cell is added by files alone: a scale-10 configuration with
Graph500 kernel 3's weights, a cell file and an SSSP algorithm module
(``data/sssp.py``) written into a copy of the benchmark, run through the
harness's own ``run_cell`` on the CPU."""
import shutil
from pathlib import Path

import jax
import pytest

from bench import cells, control, run

DATA = Path(__file__).parent / "data"
PEAKS = {"hbm_bytes_per_s": 819e9}
#: a window that holds the first search whole on a loaded CPU: alone, it
#: ends about 0.2 s into the window, after 10 supersteps
WHOLE_SEARCH_SECONDS = 4.0


@pytest.fixture
def sssp_copy(bench_copy, add_cell):
    """The benchmark copy with the weighted cell ``small-w.sssp``: four
    search keys on the weighted scale-10 graph."""
    shutil.copy(DATA / "sssp.py", bench_copy / "algos" / "sssp.py")
    add_cell(bench_copy, "small-w.sssp", "g500-22.pr", "small-w",
             weights="uniform01", algorithm="sssp",
             roots={"count": 4, "seed": 3})
    return bench_copy


def _run(root, seed, seconds=0.5):
    record = run.run_cell(cells.cell("small-w.sssp", root), seed, seconds,
                          False, peak_table=PEAKS)
    return record, run.result_line(
        record, cells.metrics("small-w.sssp", False, root), jax.devices())


@pytest.mark.parametrize("seed", [2**31 + 11, 2**32 + 77])
def test_a_weighted_sssp_cell_runs_correct_from_files_alone(sssp_copy, seed):
    record, line = _run(sssp_copy, seed, WHOLE_SEARCH_SECONDS)
    assert record["weighted"] is True
    assert line["correct"] is True and line["failed"] == 0
    # whole searches: the window moves on to the next search key
    assert record["sessions"] >= 2 and record["traversed_edges"] > 0
    assert set(line["metrics"]) == {"teps", "setup_s"}


def test_unit_weights_in_the_store_are_not_correct(sssp_copy, monkeypatch):
    """The store given 1.0 for every weight runs hop counts, which the
    reference over the graph's weights must refuse."""
    real = run.spe.preprocess_arrays

    def unit_weights(src, dst, val, *rest, **kw):
        return real(src, dst, val * 0 + 1, *rest, **kw)
    monkeypatch.setattr(run.spe, "preprocess_arrays", unit_weights)
    _, line = _run(sssp_copy, 2**31 + 11)
    c = line["compared"]["max_abs_err"]
    assert c["value"] > c["limit"]
    assert line["correct"] is False and line["failed"] >= 1


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_bfloat16_control_of_weighted_sssp_fails_the_limit(sssp_copy, seed):
    """Weighted distances need float32: the bfloat16 control reads above
    the limit, as the hop counts of unit weights never could."""
    cell = cells.cell("small-w.sssp", sssp_copy)
    out = control.control_readings(cell, seed, supersteps=8)
    assert out["control"]["max_abs_err"] > 10 * out["limits"]["max_abs_err"]
    assert out["traversed_edges"] > 0
