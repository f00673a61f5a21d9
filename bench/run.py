"""Run one benchmark cell of GraphH on the chip and print its result line.

    python3 bench/run.py --workload g500-22.pr --seed 7 --seconds 51 --trace 0

A run:

1. set-up: makes the cell's Graph500 graph on the device from ``--seed``
   (bench/graph500.py), hands the arcs, and their weights where the
   configuration has them, to ``spe.preprocess`` into a tile
   store under ``$TMPDIR``, opens ``OutOfCoreEngine`` with the
   configuration's memory budgets, opens a session of the cell's program
   (``engine.open_session``) and steps it through the workload's
   ``warmup_supersteps`` (compilation, edge-cache fill);
2. window: with what set-up left frozen out of the garbage collector's scans,
   keeps calling ``session.step()`` on that session for
   ``--seconds``, and runs the superstep under way then to its end. A session
   that finishes is followed by the next one (the next root) inside the
   window. The traversed edges count up to ``--seconds``, the superstep
   running at that moment by its share of time (bench/metrics/teps.py);
3. check: once the window has closed and the device's peak memory has been
   read, runs the algorithm's plain reference (bench/algos/) over the
   harness's own arcs for every session of the window, at the superstep count
   it reached, and holds the compared numbers and the configuration's
   guarantees to their limits.

With ``--trace 1`` the profiler records whole supersteps of the window from
its second one on, for about :data:`TRACE_SECONDS`, and the result carries the
per-layer metrics, the device's busy and window seconds and a breakdown. The
last line of stdout is the result; the last lines of stderr are the compared
numbers with their limits. Off a TPU, or with fewer chips than the cell asks
for, it exits 2 before measuring and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()   # set-up is timed from the process's start

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation as span  # noqa: E402

from bench import cells, devtrace, graph500  # noqa: E402
from repro.core.apps import APPS  # noqa: E402
from repro.core.engine import EngineConfig, OutOfCoreEngine  # noqa: E402
from repro.graphio import spe  # noqa: E402
from repro.graphio.formats import TileStore  # noqa: E402

TRACE_FROM = 1          # first traced superstep of the window
TRACE_SECONDS = 10.0    # trace whole supersteps until this much has passed


class ChipError(RuntimeError):
    """No TPU, too few chips, or a chip the peaks table does not know."""


def require_chip(chips: int) -> list:
    """The devices of this process: a TPU with at least ``chips`` chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise ChipError(f"JAX's devices are {devs[0].platform} "
                        f"({devs[0].device_kind}); the benchmark runs only "
                        f"on a TPU")
    if len(devs) < chips:
        raise ChipError(f"the cell asks for {chips} chips, JAX finds "
                        f"{len(devs)}")
    return devs


def peaks(kind: str, root: Path = cells.BENCH_DIR) -> dict:
    """Published peaks of device kind ``kind`` (bench/peaks.json); an
    unknown kind is an error, never a default."""
    with open(Path(root) / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise ChipError(f"bench/peaks.json has no peaks for device kind "
                        f"{kind!r}")
    return table[kind]


def _engine_config(cfg: dict, trace: bool) -> EngineConfig:
    mem = cfg["memory"]
    return EngineConfig(
        num_servers=int(cfg["servers"]),
        cache_capacity_bytes=int(mem["host_edge_cache_bytes"]),
        device_budget_bytes=int(mem["device_edge_budget_bytes"]),
        # which tiles the skip pre-pass ran, for the roofline's byte count
        debug_skip_log=trace,
    )


def _host_usage() -> tuple:
    """This process's CPU seconds, major page faults and involuntary context
    switches so far: a superstep that stalls shows in one of them when the
    stall is the process's own work, paging, or another process's."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime, r.ru_majflt, r.ru_nivcsw


def _processed_tiles(eng, log_from: int) -> list[int]:
    """Tiles the last superstep processed: the skip log's run lists when the
    pre-pass ran (it logs one entry per server), else every tile."""
    entries = eng.skip_log[log_from:]
    if not entries:
        return [t for s in eng.exec_servers for t in eng.assignment[s]]
    return [t for e in entries for t in e["run"]]


def _window(eng, algo, roots, prog, session, seconds, tracer):
    """Step ``session``, then the next roots' sessions, for ``seconds``;
    returns the window's record."""
    plan = eng.plan
    rows = np.diff(plan.splitter)
    sessions = [(roots[0], session)]
    stats = []
    times = []   # (start, end) of each superstep from the window's start
    usage = []   # _host_usage() differences over each superstep
    traced_edges = traced_rows = 0
    cache_peak = 0
    n_root = 0
    # what set-up left behind is never scanned by a collection in the window
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    while True:
        if session.finished:
            n_root += 1
            root = roots[n_root % len(roots)]
            if root != sessions[-1][0]:
                prog = algo.program(APPS, root)
            with span("bench.session_open"):
                session = eng.open_session(prog)
            sessions.append((root, session))
        if tracer is not None and len(stats) == TRACE_FROM:
            tracer.start(time.perf_counter())
        log_from = len(eng.skip_log)
        before = _host_usage()
        started = time.perf_counter() - t0
        with span("bench.superstep"):
            stats.append(session.step())
        now = time.perf_counter()
        times.append((started, now - t0))
        usage.append(tuple(x - y for x, y in zip(_host_usage(), before)))
        cache_peak = max(cache_peak, *(c.resident_bytes()
                                       for c in eng.caches.values()))
        if tracer is not None and tracer.active:
            done = _processed_tiles(eng, log_from)
            traced_edges += int(plan.edges_per_tile[done].sum())
            traced_rows += int(rows[done].sum())
            if tracer.elapsed(now) >= TRACE_SECONDS or now - t0 >= seconds:
                tracer.stop()
        if now - t0 >= seconds:
            break
    run_s = time.perf_counter() - t0
    gc.unfreeze()
    if tracer is not None:
        tracer.stop()
    results = [(root, len(s.history), np.array(s.values, copy=True))
               for root, s in sessions]
    for _, s in sessions:
        s.close()
    return dict(run_s=run_s, times=times, usage=usage, stats=stats,
                sessions=results, cache_peak=cache_peak,
                traced_edges=traced_edges, traced_rows=traced_rows)


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
             peak_table: dict, t_start: float = _T_START) -> dict:
    """Set up, measure and check one run of ``cell``; returns the run record
    the metric reducers read (see bench/metrics/)."""
    cfg, wl, algo = cell.config, cell.workload, cell.algorithm
    t = time.perf_counter()
    with span("bench.generate"):
        graph = graph500.of_config(cfg["graph"], seed)
    out_deg = np.bincount(graph.src, minlength=graph.num_vertices)
    generate_s = time.perf_counter() - t
    work = tempfile.mkdtemp(prefix="graphh_bench_")
    try:
        store = TileStore(os.path.join(work, "store"))
        t = time.perf_counter()
        with span("bench.spe"):
            spe.preprocess_arrays(graph.src, graph.dst, graph.weight,
                                  graph.num_vertices, store,
                                  tile_size=int(cfg["store"]["tile_edges"]))
        spe_s = time.perf_counter() - t
        plan = store.load_plan()
        raw_bytes = sum(store.tile_disk_bytes(i)
                        for i in range(plan.num_tiles))
        ecfg = _engine_config(cfg, trace)
        t = time.perf_counter()
        warm = int(wl["warmup_supersteps"])
        with span("bench.warmup"):
            eng = OutOfCoreEngine(store, ecfg)
            roots = algo.roots(graph, out_deg, wl)
            prog = algo.program(APPS, roots[0])
            session = eng.open_session(prog)
            for _ in range(warm):
                if not session.finished:
                    session.step()
        warmup_s = time.perf_counter() - t
        setup_s = time.perf_counter() - t_start
        tracer = (devtrace.Tracer(os.path.join(work, "trace"))
                  if trace else None)
        win = _window(eng, algo, roots, prog, session, seconds, tracer)
        memory_peak = _memory_peak()
        cache_mode = eng.cache_mode
        del eng, prog, session
        gc.collect()
        summary = None
        if trace:
            xplane = devtrace.find_xplane(tracer.log_dir)
            summary = devtrace.reduce(xplane) if xplane else None
    finally:
        shutil.rmtree(work, ignore_errors=True)

    t = time.perf_counter()
    edges = []
    compared = {}
    failed = 0
    for i, (root, n_ss, values) in enumerate(win["sessions"]):
        ref, per_step = algo.reference(graph, out_deg, root, n_ss)
        edges += per_step[warm:] if i == 0 else per_step
        numbers = algo.compare(values, ref)
        failed += any(numbers[k] > lim for k, lim in algo.LIMITS.items())
        for k, v in numbers.items():
            compared[k] = max(compared.get(k, v), v)
    limits = dict(algo.LIMITS)
    if "edge_cache_within_capacity" in cfg["guarantees"]:
        compared["edge_cache_bytes"] = win["cache_peak"]
        limits["edge_cache_bytes"] = ecfg.cache_capacity_bytes
    if "device_peak_within_budget" in cfg["guarantees"]:
        compared["device_peak_bytes"] = memory_peak
        limits["device_peak_bytes"] = ecfg.device_budget_bytes
    reference_s = time.perf_counter() - t
    return dict(
        setup_s=setup_s, generate_s=generate_s, spe_s=spe_s,
        warmup_s=warmup_s, reference_s=reference_s,
        window_s=float(seconds), run_s=win["run_s"], stats=win["stats"],
        usage=win["usage"],
        traversed_edges=_in_window(edges, win["times"], seconds),
        sessions=len(win["sessions"]),
        failed=failed, compared=compared, limits=limits,
        memory_peak_bytes=memory_peak, cache_mode=cache_mode,
        num_vertices=graph.num_vertices, num_arcs=len(graph.src),
        weighted=graph.weight is not None,
        raw_tile_bytes=raw_bytes, num_tiles=plan.num_tiles,
        edge_cap=plan.edge_cap, row_cap=plan.row_cap,
        trace=summary, traced_edges=win["traced_edges"],
        traced_rows=win["traced_rows"], queries=1, peaks=peak_table)


def _in_window(edges: list, times: list, seconds: float) -> float:
    """Traversed edges inside the first ``seconds`` of the window: every
    superstep that ended by then, and of the one running at that moment the
    share of its edges that its elapsed share of time gives (the window's
    loop runs that superstep to its end, and the check compares there)."""
    total = 0.0
    for n, (s, e) in zip(edges, times):
        if e <= seconds:
            total += n
        elif s < seconds:
            total += n * (seconds - s) / (e - s)
    return total


def _memory_peak() -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def result_line(run: dict, metrics: list, devs: list) -> dict:
    """The result object: correct, attempted, failed, metrics, device, and
    with a trace the breakdown; ``compared`` comes last."""
    correct = all(run["compared"][k] <= lim
                  for k, lim in run["limits"].items())
    values = {}
    for m in metrics:
        v = m.reducer.reduce(run)
        if v is not None:
            values[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": run["sessions"],
           "failed": run["failed"], "metrics": values, "device": device}
    tr = run["trace"]
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["top_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["compared"] = {k: {"value": run["compared"][k], "limit": lim}
                       for k, lim in run["limits"].items()}
    return out


def _describe(run: dict) -> str:
    stats = run["stats"]
    lines = [
        f"set-up {run['setup_s']:.3f} s: generate {run['generate_s']:.3f}, "
        f"spe {run['spe_s']:.3f}, warm-up {run['warmup_s']:.3f}",
        f"graph: {run['num_vertices']} vertices, {run['num_arcs']} arcs"
        f"{', weighted' if run['weighted'] else ''}; "
        f"store: {run['num_tiles']} tiles, edge_cap {run['edge_cap']}, "
        f"row_cap {run['row_cap']}, {run['raw_tile_bytes']} raw tile bytes, "
        f"cache mode {run['cache_mode']}",
        f"window {run['window_s']:.3f} s (ran {run['run_s']:.3f} s to the "
        f"superstep boundary): {len(stats)} supersteps, "
        f"{run['sessions']} sessions, "
        f"{sum(s.tiles_processed for s in stats)} tiles processed, "
        f"{sum(s.tiles_skipped for s in stats)} skipped, "
        f"{run['traversed_edges']:.0f} traversed edges in the window; "
        f"reference {run['reference_s']:.3f} s",
    ]
    for s, (cpu, majflt, nivcsw) in zip(stats, run["usage"]):
        lines.append(f"  superstep {s.superstep}: {s.seconds:.3f} s, load "
                     f"{s.load_seconds:.3f}, compute {s.compute_seconds:.3f}, "
                     f"tiles {s.tiles_processed}/{s.tiles_skipped} skipped, "
                     f"updated {s.updated_vertices}; host cpu {cpu:.3f} s, "
                     f"major faults {majflt}, preempted {nivcsw}")
    return "\n".join(lines)


def main(argv=None) -> int:
    """Command-line entry point; returns the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.cell(args.workload)
    metrics = cells.metrics(args.workload, bool(args.trace))
    try:
        devs = require_chip(cell.chips)
        table = peaks(devs[0].device_kind)
    except ChipError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   peak_table=table)
    line = result_line(run, metrics, devs)
    print(_describe(run), file=sys.stderr)
    print(json.dumps(line), flush=True)
    for k, c in line["compared"].items():
        print(f"compared {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
