"""PageRank (paper Algorithm 6: Graphalytics PR without the redistribution of
dangling vertices' mass).

The plain reference is unnormalised damped PageRank over the harness's own
arcs, in float64, with no tiles, cache or kernel:

    v_0 = 1,  v_{k+1}[d] = 0.15 + 0.85 * sum_{(s, d)} v_k[s] / out(s)

``precision="bfloat16"`` is the control: the
same recurrence with the vertex state rounded to bfloat16 after every
superstep, the step below the float32 the configuration states.

Compared number: ``max_rel_err``, the largest |program - reference| /
reference over all vertices (the reference is at least 0.15 everywhere). Its
limit is set from the readings in PERF.md: the program's float32 over a dozen
seeds and more (lower) and this control (upper).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

DAMPING = 0.85

#: compared number -> limit (PERF.md, "Correctness": readings and limit)
LIMITS = {"max_rel_err": 1e-3}


def roots(graph, out_deg, workload) -> list:
    """PageRank has no root: every session is the same whole-graph run."""
    return [None]


def program(apps, root):
    """The program's PageRank with its defaults."""
    return apps["pagerank"]()


def reference(graph, out_deg, root, supersteps: int,
              precision: str = "float64"):
    """Values after ``supersteps`` and, per superstep, the traversed edges:
    out-arcs of the vertices active at its start (all at superstep 0, whose
    values differ from the sum's identity 0; then those whose value changed
    in the previous superstep)."""
    nv = graph.num_vertices
    inv = np.zeros(nv)
    nz = out_deg > 0
    inv[nz] = 1.0 / out_deg[nz]
    v = np.ones(nv)
    active = v != 0.0
    edges = []
    for _ in range(supersteps):
        edges.append(int(out_deg[active].sum()))
        msg = (v * inv)[graph.src]
        acc = np.bincount(graph.dst, weights=msg, minlength=nv)
        new = (1.0 - DAMPING) + DAMPING * acc
        if precision == "bfloat16":
            new = new.astype(ml_dtypes.bfloat16).astype(np.float64)
        elif precision != "float64":
            raise ValueError(f"unknown precision {precision!r}")
        active = new != v
        v = new
    return v, edges


def compare(values: np.ndarray, ref: np.ndarray) -> dict:
    """The compared numbers of one session's values against the reference."""
    err = np.abs(values.astype(np.float64) - ref) / np.abs(ref)
    return {"max_rel_err": float(err.max())}
