"""tile_resident_share (%): processed tiles that ran from edges the device
held across supersteps (the engine's ``tiles_resident``) over all processed
tiles of the window (``tiles_processed``). Reported in traced runs; a program
without the counter reports nothing."""


def reduce(run):
    """Percentage of the window's processed tiles that were resident, or
    None."""
    stats = run["stats"]
    if run.get("trace") is None or not stats:
        return None
    if not all(hasattr(s, "tiles_resident") for s in stats):
        return None
    processed = sum(s.tiles_processed for s in stats)
    if not processed:
        return None
    return 100.0 * sum(s.tiles_resident for s in stats) / processed
