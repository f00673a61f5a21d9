"""The lower-precision control of a cell's correctness check.

    python3 bench/control.py --workload g500-22.pr --seeds 1,2,3 --supersteps 6

For each seed it makes the cell's graph as a run does, computes the
algorithm's plain reference in the precision the configuration states, and
the same reference with the vertex state in bfloat16 (the step below float32)
put in the program's place, for the first root's ``--supersteps`` supersteps.
It prints the compared numbers of the control beside the cell's limits, one
JSON line per seed: each limit has to be below what the control reads, or the
check could not tell a bfloat16 program from a float32 one. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import cells, graph500  # noqa: E402


def control_readings(cell: cells.Cell, seed: int, supersteps: int) -> dict:
    """The control's compared numbers for ``seed`` (and the reference's own
    traversed edges), at ``supersteps`` supersteps from the first root."""
    algo = cell.algorithm
    graph = graph500.of_config(cell.config["graph"], seed)
    out_deg = np.bincount(graph.src, minlength=graph.num_vertices)
    root = algo.roots(graph, out_deg, cell.workload)[0]
    ref, edges = algo.reference(graph, out_deg, root, supersteps)
    low, _ = algo.reference(graph, out_deg, root, supersteps,
                            precision="bfloat16")
    return dict(seed=seed, supersteps=supersteps, traversed_edges=sum(edges),
                control=algo.compare(low.astype(ref.dtype), ref),
                limits=dict(algo.LIMITS))


def main(argv=None) -> int:
    """Command-line entry point."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--supersteps", type=int, required=True)
    args = ap.parse_args(argv)
    cell = cells.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = control_readings(cell, seed, args.supersteps)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
