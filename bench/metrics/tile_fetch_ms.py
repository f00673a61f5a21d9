"""tile_fetch_ms (ms/tile): the engine's ``fetch_seconds`` (its
``graphh.tile.fetch`` span: waiting for the tile step and copying its rows,
new values and update mask to the host) per processed tile over the window.
Reported in traced runs; a program without the counter reports nothing."""


def reduce(run):
    """Mean fetch milliseconds per processed tile, or None."""
    stats = run["stats"]
    if run.get("trace") is None or not stats:
        return None
    if not all(hasattr(s, "fetch_seconds") for s in stats):
        return None
    tiles = sum(s.tiles_processed for s in stats)
    if not tiles:
        return None
    return 1e3 * sum(s.fetch_seconds for s in stats) / tiles
