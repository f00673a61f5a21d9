"""setup_s (s): process start to the first superstep of the window.

Covers the graph's generation on the device, SPE into the tile store, opening
the engine and the warm-up session (compilation and the edge-cache fill).
"""


def reduce(run):
    """Seconds from process start to the window."""
    return run["setup_s"]
