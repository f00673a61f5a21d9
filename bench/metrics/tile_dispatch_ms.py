"""tile_dispatch_ms (ms/tile): the engine's ``dispatch_seconds`` (its
``graphh.tile.dispatch`` span: the tile's edge-value array built, the tile
step's inputs handed to the device and the step enqueued) per processed tile
over the window. Reported in traced runs; a program without the counter
reports nothing."""


def reduce(run):
    """Mean dispatch milliseconds per processed tile, or None."""
    stats = run["stats"]
    if run.get("trace") is None or not stats:
        return None
    if not all(hasattr(s, "dispatch_seconds") for s in stats):
        return None
    tiles = sum(s.tiles_processed for s in stats)
    if not tiles:
        return None
    return 1e3 * sum(s.dispatch_seconds for s in stats) / tiles
