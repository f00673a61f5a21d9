"""spe_s (s): the harness's clock around ``spe.preprocess`` (degree pass,
splitter, shuffle to per-tile spill files, tile build and write)."""


def reduce(run):
    """Seconds of graph pre-processing."""
    return run["spe_s"]
