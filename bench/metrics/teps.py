"""teps (edges/s): traversed edges over the window's wall time.

Graph500's sense: for each superstep, the out-arcs of the vertices active at
its start, taken from the harness's reference trajectory and its own degree
arrays (never from the engine's counters), over all sessions of the window,
divided by the window's ``--seconds``. An undirected edge is stored as two
arcs, and a superstep that traverses it both ways counts both. The superstep running when the window
ends counts with the share of its edges that its share of time inside the
window gives: a whole-superstep count jumps by a superstep's edges when timing
noise moves the last boundary across the window's end.
"""


def reduce(run):
    """Traversed edges per second of the window."""
    if run["window_s"] <= 0:
        return None
    return run["traversed_edges"] / run["window_s"]
