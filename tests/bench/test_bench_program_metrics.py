"""The metrics that read the engine's tile-step spans and counters: exact
numbers on a hand-made run record, and nothing without a trace or from a
program that lacks the counters."""
from types import SimpleNamespace

import pytest

from bench import cells
from repro.core.engine import SuperstepStats

NAMES = ("tile_dispatch_ms", "tile_fetch_ms", "tile_split_ms", "h2d_gb",
         "edge_fill")


def _stats(superstep, tiles, dispatch, fetch, split, h2d, real, padded):
    return SuperstepStats(
        superstep=superstep, seconds=1.0, load_seconds=0.1,
        compute_seconds=dispatch + fetch + split, updated_vertices=0,
        density=0.0, tiles_processed=tiles, tiles_skipped=0, raw_bytes=0,
        wire_bytes=0, network_bytes=0, cache_hit_ratio=1.0,
        disk_bytes_read=0, dispatch_seconds=dispatch, fetch_seconds=fetch,
        split_seconds=split, h2d_bytes=h2d, d2h_bytes=9 * tiles,
        edges_real=real, edges_padded=padded)


def _run(stats, trace=True):
    return {"stats": stats,
            "trace": {"busy_s": 1.0, "window_s": 2.0} if trace else None}


RUN = _run([_stats(1, 10, 0.020, 0.050, 0.010, 3_000_000_000, 300, 1000),
            _stats(2, 30, 0.040, 0.150, 0.030, 7_000_000_000, 900, 3000)])


@pytest.mark.parametrize("name,expected", [
    ("tile_dispatch_ms", 1e3 * 0.060 / 40),
    ("tile_fetch_ms", 1e3 * 0.200 / 40),
    ("tile_split_ms", 1e3 * 0.040 / 40),
    ("h2d_gb", 10.0 / 2),
    ("edge_fill", 100.0 * 1200 / 4000),
])
def test_reducer_on_a_hand_made_run(name, expected):
    assert cells.reducer(name).reduce(RUN) == pytest.approx(expected)


def test_the_tile_phases_add_up_to_the_tile_step():
    step = cells.reducer("tile_step_ms").reduce(RUN)
    parts = sum(cells.reducer(n).reduce(RUN)
                for n in ("tile_dispatch_ms", "tile_fetch_ms",
                          "tile_split_ms"))
    assert parts == pytest.approx(step)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_without_a_trace(name):
    assert cells.reducer(name).reduce(_run(RUN["stats"], trace=False)) \
        is None


@pytest.mark.parametrize("name", NAMES)
def test_nothing_from_a_program_without_the_counters(name):
    old = SimpleNamespace(superstep=1, seconds=1.0, load_seconds=0.1,
                          compute_seconds=0.5, tiles_processed=10)
    assert cells.reducer(name).reduce(_run([old])) is None


@pytest.mark.parametrize("name", NAMES)
def test_nothing_from_a_window_without_tiles(name):
    empty = _stats(1, 0, 0.0, 0.0, 0.0, 0, 0, 0)
    value = cells.reducer(name).reduce(_run([empty]))
    # bytes per superstep are a number even with no tile; the rest divide
    # by tiles or slots
    assert value == (0.0 if name == "h2d_gb" else None)
    assert cells.reducer(name).reduce(_run([])) is None


def test_the_metrics_are_declared_for_the_traced_cell():
    traced = {m.name for m in cells.metrics("g500-22.pr", True)}
    assert set(NAMES) <= traced
    untraced = {m.name for m in cells.metrics("g500-22.pr", False)}
    assert not set(NAMES) & untraced
