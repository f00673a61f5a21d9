"""Single-source shortest paths (paper Algorithm 7; Graph500 kernel 3) over
the edge weights of a weighted configuration.

The tests copy this module into a benchmark copy as ``algos/sssp.py``: the
harness takes a weighted SSSP cell from files alone.

The plain reference is Bellman-Ford in float64 over the harness's own arcs
and ``graph.weight``, synchronous by superstep, with no tiles, cache or
kernel:

    d_0 = inf except d_0[root] = 0,  d_{k+1}[v] = min(d_k[v],
                                     min_{(u, v)} d_k[u] + w(u, v))

``precision="bfloat16"`` is the control: the same recurrence with the
distances rounded to bfloat16 after every superstep, the step below the
float32 the configuration states.

A weight of 0.0 is a real edge (a draw uniform on [0, 1) makes some): in the
program's tiles only the sink row, never ``val == 0``, marks padding for
SSSP.

Compared numbers: ``max_abs_err``, the largest |program - reference| over the
vertices both reach, and ``reach_mismatch``, the vertices that one side
reaches and the other does not (exact, limit 0).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

#: compared number -> limit. At scale 10 on the CPU the program's float32
#: reads 0 on four seeds (the weights are multiples of 2**-23, so distances
#: under 2 add up exactly), the bfloat16 control 3.7e-3 on three
LIMITS = {"max_abs_err": 1e-4, "reach_mismatch": 0}


def roots(graph, out_deg, workload) -> list:
    """The workload's search keys: ``roots.count`` base vertices drawn from
    ``roots.seed``, in the run's labels, so every ``--seed`` searches from
    the same vertices of the one data set."""
    spec = workload["roots"]
    rng = np.random.default_rng(int(spec["seed"]))
    base = rng.choice(graph.num_vertices, int(spec["count"]), replace=False)
    return [int(r) for r in graph.relabel[base]]


def program(apps, root):
    """The program's SSSP from ``root``."""
    return apps["sssp"](source=root)


def reference(graph, out_deg, root, supersteps: int,
              precision: str = "float64"):
    """Distances after ``supersteps`` and, per superstep, the traversed
    edges: out-arcs of the vertices active at its start (the root at
    superstep 0, whose distance is not the min's identity inf; then those
    whose distance fell in the previous superstep)."""
    if precision not in ("float64", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    nv = graph.num_vertices
    order = np.argsort(graph.dst, kind="stable")
    src = graph.src[order]
    weight = graph.weight[order].astype(np.float64)
    dst = graph.dst[order]
    starts = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
    d = np.full(nv, np.inf)
    d[root] = 0.0
    active = np.isfinite(d)
    edges = []
    for _ in range(supersteps):
        edges.append(int(out_deg[active].sum()))
        acc = np.full(nv, np.inf)
        acc[dst[starts]] = np.minimum.reduceat(d[src] + weight, starts)
        new = np.minimum(d, acc)
        if precision == "bfloat16":
            new = new.astype(ml_dtypes.bfloat16).astype(np.float64)
        active = new != d
        d = new
    return d, edges


def compare(values: np.ndarray, ref: np.ndarray) -> dict:
    """The compared numbers of one session's distances against the
    reference."""
    v = values.astype(np.float64)
    both = np.isfinite(v) & np.isfinite(ref)
    err = np.abs(v[both] - ref[both])
    return {"max_abs_err": float(err.max(initial=0.0)),
            "reach_mismatch": int((np.isfinite(v) != np.isfinite(ref)).sum())}
