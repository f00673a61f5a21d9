"""tile_load_s (s/superstep): the engine's ``load_seconds`` (edge-cache
lookups, decompression and tile-store reads) per superstep of the window."""


def reduce(run):
    """Mean tile-load seconds per window superstep."""
    stats = run["stats"]
    if not stats:
        return None
    return sum(s.load_seconds for s in stats) / len(stats)
