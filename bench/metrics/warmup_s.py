"""warmup_s (s): the harness's clock around the warm-up session, which
compiles the tile step and fills the edge cache from the tile store."""


def reduce(run):
    """Seconds of the warm-up session."""
    return run["warmup_s"]
