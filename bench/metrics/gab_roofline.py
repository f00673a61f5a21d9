"""gab_roofline (%): the tile step's share of its roofline.

The tile step (gather, combine, apply of one tile) is bound by memory
bandwidth: it does a few operations per byte. Its roofline time is the least
bytes any implementation must move for the tiles the traced supersteps
processed, over the chip's peak HBM bandwidth; the share is that time over the
device time of the tile-step programs in the trace.

The least bytes count real edges and rows, never the padded shapes:

- per real edge: its source index and the source value of each query, and
  in a weighted run its weight;
- per real row: its old value read and its new value written, per query.

A layout that moves fewer bytes needs a change of this count, in a benchmark
change of its own.
"""

INDEX_BYTES = 4
VALUE_BYTES = 4          # float32 vertex values and edge weights, as the
                         # configurations state
TILE_STEP_PROGRAMS = ("tile_step", "tile_stack")


def least_bytes(edges: int, rows: int, queries: int,
                weighted: bool = False) -> int:
    """Bytes a tile step must move for ``edges`` real edges, each with a
    weight where ``weighted``, into ``rows`` real rows."""
    per_edge = INDEX_BYTES + VALUE_BYTES * queries
    if weighted:
        per_edge += VALUE_BYTES
    return per_edge * edges + 2 * VALUE_BYTES * queries * rows


def reduce(run):
    """Roofline percentage of the traced tile steps, or None without them."""
    tr = run.get("trace")
    if not tr:
        return None
    device_s = sum(v for k, v in tr["kernel_s"].items()
                   if any(p in k for p in TILE_STEP_PROGRAMS))
    if device_s <= 0 or not run["traced_edges"]:
        return None
    need = least_bytes(run["traced_edges"], run["traced_rows"],
                       run["queries"], run.get("weighted", False))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / device_s
