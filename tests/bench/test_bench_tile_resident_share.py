"""tile_resident_share: the share of the window's processed tiles that ran
from device-resident edges, exact on a hand-made run record, and nothing
without a trace, from a program without the counter, or without tiles."""
from types import SimpleNamespace

import pytest

from bench import cells
from repro.core.engine import SuperstepStats

NAME = "tile_resident_share"


def _stats(superstep, processed, skipped, resident):
    return SuperstepStats(
        superstep=superstep, seconds=1.0, load_seconds=0.0,
        compute_seconds=0.5, updated_vertices=0, density=0.0,
        tiles_processed=processed, tiles_skipped=skipped, raw_bytes=0,
        wire_bytes=0, network_bytes=0, cache_hit_ratio=1.0,
        disk_bytes_read=0, tiles_resident=resident)


def _run(stats, trace=True):
    return {"stats": stats,
            "trace": {"busy_s": 1.0, "window_s": 2.0} if trace else None}


@pytest.mark.parametrize("stats,expected", [
    ([_stats(1, 40, 0, 40), _stats(2, 40, 0, 40)], 100.0),
    # a sparse superstep runs its few tiles one at a time, from the host
    ([_stats(1, 40, 0, 40), _stats(2, 10, 30, 0)], 100.0 * 40 / 50),
    ([_stats(1, 40, 0, 0)], 0.0),
], ids=["resident", "one-sparse-superstep", "tiled"])
def test_share_of_a_hand_made_run(stats, expected):
    assert cells.reducer(NAME).reduce(_run(stats)) == pytest.approx(expected)


def test_nothing_without_a_trace():
    run = _run([_stats(1, 40, 0, 40)], trace=False)
    assert cells.reducer(NAME).reduce(run) is None


def test_nothing_from_a_program_without_the_counter():
    old = SimpleNamespace(superstep=1, seconds=1.0, load_seconds=0.1,
                          compute_seconds=0.5, tiles_processed=10)
    assert cells.reducer(NAME).reduce(_run([old])) is None


def test_nothing_from_a_window_without_tiles():
    assert cells.reducer(NAME).reduce(_run([_stats(1, 0, 40, 0)])) is None
    assert cells.reducer(NAME).reduce(_run([])) is None


def test_the_metric_is_declared_for_the_traced_cell():
    assert NAME in {m.name for m in cells.metrics("g500-22.pr", True)}
    assert NAME not in {m.name for m in cells.metrics("g500-22.pr", False)}
