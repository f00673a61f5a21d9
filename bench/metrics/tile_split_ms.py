"""tile_split_ms (ms/tile): the engine's ``split_seconds`` (its
``graphh.tile.split`` span: picking the updated rows out of the fetched
result on the host) per processed tile over the window. Reported in traced
runs; a program without the counter reports nothing."""


def reduce(run):
    """Mean split milliseconds per processed tile, or None."""
    stats = run["stats"]
    if run.get("trace") is None or not stats:
        return None
    if not all(hasattr(s, "split_seconds") for s in stats):
        return None
    tiles = sum(s.tiles_processed for s in stats)
    if not tiles:
        return None
    return 1e3 * sum(s.split_seconds for s in stats) / tiles
