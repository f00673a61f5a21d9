"""tile_step_ms (ms/tile): the engine's ``compute_seconds`` (the jitted tile
step, its host-device copies and the split of its result) per processed
tile over the window."""


def reduce(run):
    """Mean tile-step milliseconds per processed tile."""
    tiles = sum(s.tiles_processed for s in run["stats"])
    if not tiles:
        return None
    return 1e3 * sum(s.compute_seconds for s in run["stats"]) / tiles
