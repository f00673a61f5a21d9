"""Bring-up check of GraphH's main path on one TPU chip, at Graph500 scale.

    python chip_smoke.py [--scale 22] [--seed 0]

Every phase is fatal: a failed check raises and the script exits non-zero.

  (a) build: a Graph500-style R-MAT graph (edge factor 16, weighted edges)
      generated from ``--seed`` goes through ``launch.graph.build_store``
      (SPE) into a TileStore at the default tile size of 65,536 edges.
      Scale 22 is 4,194,304 vertices and 67,108,864 edges, about the size
      of soc-LiveJournal1.
  (b) analytics: PageRank (sum monoid) and SSSP (min monoid), 5 supersteps
      each, through the ``launch.graph`` CLI — once on the default path
      (XLA scatter, ``seg_impl="jnp"``) and once with ``--kernel-autotune``
      (the fused Pallas kernel, compiled by Mosaic).  Every run is compared
      with a plain numpy reference at the same superstep count: SSSP
      exactly, PageRank within rtol 1e-5 of a float64 reference.  Two real
      tiles also go through the fused kernel and the unfused one-hot kernel
      at the same blocks, which DESIGN.md §14 bounds to the last ulp of the
      apply (SSSP: bit-equal).
  (c) serving: a GraphService with 8 query slots answers 16 mixed PPR and
      MS-BFS queries (seeds from ``--seed``, 2 supersteps each); every
      answer must be bit-identical to an offline single-query run.

Each phase prints its wall seconds, the first superstep's seconds (compile
and first tile reads included), the steady ms per superstep and the
device's peak bytes in use, all labelled with the device they were
measured on.  The last line of stdout is one JSON object naming the
device.  There is no CPU mode: without a TPU the script exits non-zero
before doing anything.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import types
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import gab  # noqa: E402
from repro.core.apps import APPS, PageRank, SSSP  # noqa: E402
from repro.core.engine import EngineConfig, OutOfCoreEngine  # noqa: E402
from repro.core.tiles import tile_edge_values  # noqa: E402
from repro.graphio import synth  # noqa: E402
from repro.graphio.formats import TileStore  # noqa: E402
from repro.launch import graph as graph_cli  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.roofline import kernel_tune  # noqa: E402
from repro.serve.graph_service import GraphService  # noqa: E402

EDGE_FACTOR = 16          # Graph500
MIN_SCALE = 22
TILE_SIZE = 65536         # launch.graph's default
SUPERSTEPS = 5
CACHE_MB = 4000           # holds every raw tile of a scale-22 store
Q_SLOTS = 8
SERVE_QUERIES = 16
SERVE_SUPERSTEPS = 2
PAGERANK_RTOL = 1e-5
FUSED_ULP_RTOL = 1e-6     # DESIGN.md §14: fused vs unfused, last ulp


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _report(phase: str, **fields) -> None:
    """One labelled measurement line: every number here was measured on the
    device named at the start of the line."""
    kind = jax.devices()[0].device_kind
    body = ", ".join(f"{k} {v}" for k, v in fields.items())
    print(f"[measured on {kind}] {phase}: {body}", flush=True)


def _superstep_fields(history) -> dict:
    secs = [h.seconds for h in history]
    steady = secs[1:] or secs
    return dict(first_superstep_s=secs[0],
                steady_ms_per_superstep=1e3 * float(np.mean(steady)))


# --------------------------------------------------------------------------
# (a) build
# --------------------------------------------------------------------------

def build_graph(root: str, scale: int, seed: int,
                tile_size: int = TILE_SIZE) -> TileStore:
    """SPE-preprocess the weighted R-MAT graph of ``scale`` into a TileStore
    under ``root`` through the CLI's ``build_store``."""
    nv, ne = 1 << scale, EDGE_FACTOR << scale
    t0 = time.perf_counter()
    store = graph_cli.build_store(types.SimpleNamespace(
        store=root, disk_mode=1, graph="rmat", app="sssp", vertices=nv,
        edges=ne, seed=seed, tile_size=tile_size))
    dt = time.perf_counter() - t0
    plan = store.load_plan()
    _require(plan.num_vertices == nv and plan.num_edges == ne,
             f"store holds {plan.num_vertices} vertices / {plan.num_edges} "
             f"edges, expected {nv} / {ne}")
    nbytes = sum(store.tile_disk_bytes(t) for t in range(plan.num_tiles))
    _report("build", scale=scale, vertices=nv, edges=ne,
            tiles=plan.num_tiles, edge_cap=plan.edge_cap,
            row_cap=plan.row_cap, store_bytes=nbytes, wall_s=dt)
    return store


# --------------------------------------------------------------------------
# plain numpy reference (independent of the engine: regenerated edges)
# --------------------------------------------------------------------------

def reference_graph(scale: int, seed: int) -> dict:
    """The same edge stream the store was built from, held as dst-sorted
    arrays with per-dst segment starts for ``reduceat``."""
    nv, ne = 1 << scale, EDGE_FACTOR << scale
    src = np.empty(ne, np.int32)
    dst = np.empty(ne, np.int32)
    w = np.empty(ne, np.float32)
    off = 0
    for s, d, v in synth.rmat_edges(nv, ne, seed=seed, weighted=True):
        n = len(s)
        src[off:off + n], dst[off:off + n], w[off:off + n] = s, d, v
        off += n
    out_deg = np.bincount(src, minlength=nv).astype(np.float64)
    order = np.argsort(dst, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
    starts = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
    return dict(nv=nv, src=src, w=w, heads=dst[starts], starts=starts,
                out_deg=out_deg)


def reference_pagerank(g: dict, supersteps: int,
                       damping: float = 0.85) -> np.ndarray:
    """Unnormalized damped PageRank in float64, weighted by edge values
    (the engine's ``src · (inv_out_degree · w)``)."""
    inv = np.zeros(g["nv"])
    nz = g["out_deg"] > 0
    inv[nz] = 1.0 / g["out_deg"][nz]
    scale = inv[g["src"]] * g["w"]
    v = np.ones(g["nv"])
    for _ in range(supersteps):
        acc = np.zeros(g["nv"])
        acc[g["heads"]] = np.add.reduceat(v[g["src"]] * scale, g["starts"])
        v = (1.0 - damping) + damping * acc
    return v


def reference_sssp(g: dict, supersteps: int, source: int = 0) -> np.ndarray:
    """Jacobi Bellman-Ford relaxations in float32 (exact: adds and mins)."""
    d = np.full(g["nv"], np.inf, np.float32)
    d[source] = 0.0
    for _ in range(supersteps):
        best = np.minimum.reduceat(d[g["src"]] + g["w"], g["starts"])
        d = d.copy()
        d[g["heads"]] = np.minimum(d[g["heads"]], best)
    return d


# --------------------------------------------------------------------------
# (b) analytics through the CLI
# --------------------------------------------------------------------------

def _cli_run(store: TileStore, app: str, fused: bool,
             supersteps: int):
    argv = ["--app", app, "--store", store.root, "--reuse",
            "--servers", "1", "--supersteps", str(supersteps),
            "--cache-mb", str(CACHE_MB)]
    if fused:
        argv.append("--kernel-autotune")
    t0 = time.perf_counter()
    res = graph_cli.main(argv)
    return res, time.perf_counter() - t0


def _check_fused_tiles(store: TileStore, prog, values: np.ndarray) -> None:
    """The fused kernel against the unfused one-hot kernel at the same
    blocks on the first and last tile (the hub tile and the sparsest)."""
    plan = store.load_plan()
    nv = plan.num_vertices
    in_deg, out_deg = store.load_degrees()
    state = prog.init(nv, out_deg.astype(np.float64),
                      in_deg.astype(np.float64))
    state.pop("value")
    aux = {k: jax.numpy.asarray(v) for k, v in state.items()}
    vdev = jax.numpy.asarray(values)
    blocks = kernel_tune.pick_blocks(prog.combine, 1, plan.edge_cap,
                                     plan.row_cap).blocks
    for tid in (0, plan.num_tiles - 1):
        tile = store.read_tile(tid)
        m = tile.meta
        arrays = (tile.src, tile.dst_local, tile_edge_values(tile))
        out = {}
        for impl in ("pallas_fused", "pallas_onehot"):
            _, new, upd = gab.run_tile(prog, vdev, aux, arrays, m.row_start,
                                       m.num_rows, plan.row_cap, impl,
                                       blocks)
            out[impl] = (np.asarray(new), np.asarray(upd))
        (nf, uf), (no, uo) = out["pallas_fused"], out["pallas_onehot"]
        if prog.combine == "min":
            _require(np.array_equal(nf, no) and np.array_equal(uf, uo),
                     f"tile {tid}: fused min kernel != one-hot kernel")
        else:
            np.testing.assert_allclose(
                nf, no, rtol=FUSED_ULP_RTOL, atol=0,
                err_msg=f"tile {tid}: fused sum kernel vs one-hot kernel")


def run_analytics(store: TileStore, g: dict,
                  supersteps: int = SUPERSTEPS) -> None:
    """PageRank and SSSP on the jnp and fused paths, each checked against
    the numpy reference at the superstep count it ran."""
    refs = {}
    for app, prog in (("pagerank", PageRank()), ("sssp", SSSP())):
        for fused in (False, True):
            res, dt = _cli_run(store, app, fused, supersteps)
            _require(res.supersteps == supersteps or res.converged,
                     f"{app}: stopped after {res.supersteps} of "
                     f"{supersteps} supersteps without converging")
            key = (app, res.supersteps)
            if key not in refs:
                refs[key] = (reference_pagerank(g, res.supersteps)
                             if app == "pagerank"
                             else reference_sssp(g, res.supersteps))
            ref = refs[key]
            path = "fused" if fused else "jnp"
            if app == "sssp":
                np.testing.assert_array_equal(
                    res.values, ref, err_msg=f"sssp/{path} vs numpy")
                err = 0.0
            else:
                np.testing.assert_allclose(
                    res.values, ref, rtol=PAGERANK_RTOL, atol=0,
                    err_msg=f"pagerank/{path} vs numpy float64")
                err = float(np.max(np.abs(res.values - ref) / ref))
            _report(f"{app}/{path}", supersteps=res.supersteps, wall_s=dt,
                    **_superstep_fields(res.history),
                    peak_bytes_in_use=_peak_bytes(), max_rel_err=err)
            if fused:
                _check_fused_tiles(store, prog, res.values)
                _report(f"{app}/fused-vs-onehot tiles", status="equal"
                        if app == "sssp" else f"within rtol {FUSED_ULP_RTOL}")


# --------------------------------------------------------------------------
# (c) serving
# --------------------------------------------------------------------------

def serve_queries(store: TileStore, seed: int,
                  n_queries: int = SERVE_QUERIES,
                  supersteps: int = SERVE_SUPERSTEPS,
                  ppr_rtol: float = 0.0) -> None:
    """Serve mixed PPR / MS-BFS queries, then re-run each one alone offline
    and require bit-identical answers (and superstep counts when done).

    ``ppr_rtol`` > 0 accepts PPR answers within that relative tolerance
    instead: XLA:CPU contracts PPR's ``(1-d)·seed + d·accum`` into an FMA
    or not depending on Q (DESIGN.md §14), so the CPU rehearsal allows the
    last ulp there; on the chip the answers must be equal."""
    _, out_deg = store.load_degrees()
    rng = np.random.default_rng(seed)
    seeds = rng.choice(np.flatnonzero(out_deg > 0), size=n_queries,
                       replace=False)
    cfg = EngineConfig(num_servers=1,
                       cache_capacity_bytes=int(CACHE_MB * 1e6))
    svc = GraphService(store, cfg, q_slots=Q_SLOTS,
                       max_supersteps=supersteps)
    tickets = [svc.submit("ppr" if i % 2 == 0 else "msbfs", int(s))
               for i, s in enumerate(seeds)]
    t0 = time.perf_counter()
    thread = svc.start()
    for t in tickets:
        _require(t.wait(timeout=900.0), f"query {t.rid} did not finish")
    svc.request_drain()
    svc.join(timeout=300.0)
    dt = time.perf_counter() - t0
    _require(not thread.is_alive(), "serve loop did not drain")
    steps = svc.stats["supersteps"]
    _report("serve", queries=n_queries, q_slots=Q_SLOTS, supersteps=steps,
            wall_s=dt,
            first_result_s=min(t.finished_s for t in tickets) - t0,
            mean_ms_per_superstep=1e3 * dt / max(steps, 1),
            peak_bytes_in_use=_peak_bytes())

    engines = {app: OutOfCoreEngine(store, cfg) for app in ("ppr", "msbfs")}
    t0 = time.perf_counter()
    history = []
    for t in tickets:
        _require(t.status in ("done", "timeout"),
                 f"query {t.rid} ended {t.status}")
        prog = APPS[t.app]().with_queries((t.seed,))
        res = engines[t.app].run(prog, max_supersteps=supersteps)
        history += res.history
        offline = res.values[:, 0]
        what = f"query {t.rid} ({t.app}, seed {t.seed}) vs its offline run"
        if t.app == "ppr" and ppr_rtol > 0:
            np.testing.assert_allclose(t.result, offline,
                                       rtol=ppr_rtol, atol=0, err_msg=what)
        else:
            _require(np.array_equal(t.result, offline),
                     f"{what}: {np.sum(t.result != offline)} values differ")
        if t.status == "done":
            _require(t.supersteps == int(res.per_query_supersteps[0]),
                     f"query {t.rid}: {t.supersteps} supersteps served, "
                     f"{res.per_query_supersteps[0]} offline")
    _report("serve/offline-check", queries=n_queries,
            done=sum(t.status == "done" for t in tickets),
            wall_s=time.perf_counter() - t0, supersteps=len(history),
            **_superstep_fields(history), peak_bytes_in_use=_peak_bytes())


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=int, default=MIN_SCALE,
                    help="Graph500 scale: 2^scale vertices, 16x edges "
                         f"(at least {MIN_SCALE})")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.scale < MIN_SCALE:
        ap.error(f"--scale must be at least {MIN_SCALE}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's default device is {dev.platform} "
              f"({dev.device_kind}), not a TPU; this check runs only on "
              f"the chip", file=sys.stderr)
        return 1
    with tempfile.TemporaryDirectory(prefix="graphh_smoke_") as root:
        store = build_graph(root, args.scale, args.seed)
        run_analytics(store, reference_graph(args.scale, args.seed))
        serve_queries(store, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
