"""edge_fill (%): real edges over padded edge slots of the tiles the window
processed (the engine's ``edges_real`` and ``edges_padded``): the share of
the tile step's edge work and of its host-to-device edge bytes that is not
padding. Reported in traced runs; a program without the counters reports
nothing."""


def reduce(run):
    """Percentage of processed edge slots holding a real edge, or None."""
    stats = run["stats"]
    if run.get("trace") is None or not stats:
        return None
    if not all(hasattr(s, "edges_padded") for s in stats):
        return None
    padded = sum(s.edges_padded for s in stats)
    if not padded:
        return None
    return 100.0 * sum(s.edges_real for s in stats) / padded
