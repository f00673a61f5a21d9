"""Multi-process cluster driver — real N-server GraphH runs (DESIGN.md §11).

    PYTHONPATH=src python -m repro.launch.cluster --app pagerank \
        --vertices 100000 --edges 1000000 --servers 4 --transport shm

Spawns N server processes (multiprocessing ``spawn`` — safe with jax),
each running the out-of-core engine (``engine.OutOfCoreEngine`` with
``server_rank``) over its stage-2 tile share of one shared TileStore, and
exchanging per-superstep vertex updates through a real transport
(``core.transport``: shared-memory ring, or TCP sockets via ``--transport
tcp``).  Results are bit-identical to the single-process engine — the
driver verifies this across ranks on every run.

A single launch amortizes process/jit startup over many programs: pass
several vertex programs and the same N servers execute them back to back
(the exchange sequence numbers keep the BSP barriers aligned across runs).
"""
from __future__ import annotations

import argparse
import dataclasses
import multiprocessing as mp
import os
import shutil
import tempfile
import time
import traceback
from typing import Optional

import numpy as np

from repro.core.engine import EngineConfig, OutOfCoreEngine, RunResult


@dataclasses.dataclass
class ClusterConfig:
    """Knobs for a multi-process cluster run (engine knobs ride along in
    ``engine`` — its ``num_servers``/``server_rank`` are overridden per
    spawned process).  See docs/OPERATIONS.md for tuning guidance."""

    num_servers: int = 2
    #: "shm" = mmap shared-memory ring per server pair (single host);
    #: "tcp" = sockets with file rendezvous (works across hosts sharing
    #: only a filesystem)
    transport: str = "shm"
    #: per-directed-channel ring capacity in bytes (shm transport)
    ring_capacity: int = 1 << 22
    #: cross-server tile stealing between supersteps (scheduler.
    #: rebalance_assignment); the engine then runs "auto" tiled and
    #: refuses "stacked"/"merged" (engine.select_engine_mode)
    steal: bool = False
    straggler_factor: float = 1.5
    #: per-superstep exchange timeout inside each server (seconds)
    timeout_seconds: float = 180.0
    #: parent-side timeout for the whole launch (seconds)
    launch_timeout_seconds: float = 900.0
    #: what to do when a rank dies or is preempted mid-run (DESIGN.md §12):
    #: "fail" = raise ClusterFailure; "restart" = tear down, respawn the
    #: same N resuming from the latest checkpoint; "shrink" = respawn with
    #: N - dead servers (elastic resize at the superstep boundary)
    on_failure: str = "fail"
    #: supervised restart budget before giving up and re-raising
    max_restarts: int = 2
    #: engine template; num_servers/server_rank are overridden per rank
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)


class ClusterFailure(RuntimeError):
    """A cluster attempt died: one or more ranks failed, were killed, or
    were preempted.  Carries enough forensics for supervision (and tests):
    ``dead_ranks``, ``pids`` (of every spawned rank, dead or reaped), and
    ``preempted`` (True when the rank saved a checkpoint and exited
    cleanly on SIGTERM rather than crashing)."""

    def __init__(self, message: str, dead_ranks=(), pids=(),
                 preempted: bool = False):
        super().__init__(message)
        self.dead_ranks = list(dead_ranks)
        self.pids = list(pids)
        self.preempted = preempted


@dataclasses.dataclass
class ClusterResult:
    """Parent-side result of :func:`run_cluster`."""

    results: list            # rank 0's RunResult per program
    rank_reports: list       # one dict per rank: wire/raw bytes, steals, s
    # final values bit-identical across all ranks; always True on a
    # returned result (run_cluster RAISES on divergence), kept so callers
    # can assert the invariant explicitly
    verified: bool
    #: supervised restarts consumed before this result was produced
    restarts: int = 0
    #: server count of the attempt that finished (< num_servers after a
    #: shrink resize)
    final_servers: int = 0

    def wire_bytes_per_superstep(self, app_index: int = 0) -> list:
        """Cluster-total measured wire bytes per superstep for one app."""
        return [h.wire_bytes for h in self.results[app_index].history]


def _server_main(rank: int, store_root: str, cfg: ClusterConfig,
                 progs: list, run_dir: str, conn) -> None:
    """Entry point of one spawned server process: build transport +
    exchange + engine for ``rank``, run every program, ship results back
    through ``conn``.  Errors are reported (never silently dropped) so the
    parent can tear the cluster down."""
    from repro.core import transport as transport_mod
    from repro.core.distributed import ClusterExchange
    from repro.graphio.formats import TileStore
    from repro.runtime.ft import Preempted

    transport = None
    exchange = None
    try:
        store = TileStore(store_root)
        store.load_meta()
        # checkpoints go to per-program subdirectories (configured below,
        # after resume can remap the assignment but before the exchange
        # snapshot), so the engine ctor must not claim the shared root
        ecfg = dataclasses.replace(
            cfg.engine, num_servers=cfg.num_servers, server_rank=rank,
            checkpoint_dir=None)
        eng = OutOfCoreEngine(store, ecfg)
        transport = transport_mod.make_transport(
            cfg.transport, rank, cfg.num_servers, run_dir)
        if eng.fault is not None:
            # same injector instance as the engine's sites, so once-specs
            # share one claim namespace per rank
            transport = transport_mod.FaultInjectingTransport(
                transport, eng.fault)
        exchange = ClusterExchange(
            transport, comm_mode=ecfg.comm_mode,
            compressor=ecfg.comm_compressor, threshold=ecfg.comm_threshold,
            assignment=eng.assignment,
            edges_per_tile=eng.plan.edges_per_tile,
            steal=cfg.steal, straggler_factor=cfg.straggler_factor,
            timeout=cfg.timeout_seconds)
        eng.exchange = exchange
        results = []
        t0 = time.perf_counter()
        for i, prog in enumerate(progs):
            if cfg.engine.checkpoint_dir:
                eng.configure_checkpoint(
                    os.path.join(cfg.engine.checkpoint_dir, f"prog_{i:02d}"))
                # resume may have adopted a remapped assignment (elastic
                # N->M resize); refresh the exchange's snapshot
                exchange.assignment = [list(a) for a in eng.assignment]
            results.append(eng.run(prog))
        report = dict(
            rank=rank,
            seconds=time.perf_counter() - t0,
            # what THIS rank put on the wire (cluster totals live in the
            # per-superstep history of every rank's RunResult)
            wire_bytes=exchange.sent_wire_bytes,
            raw_bytes=exchange.sent_raw_bytes,
            steal_moves=exchange.steal_moves,
            final_assignment=[list(a) for a in eng.assignment],
        )
        conn.send(("ok", results, report))
    except Preempted as e:
        # state is saved (the engine checkpointed before raising): report
        # the resume boundary and exit cleanly so supervision can resume
        try:
            conn.send(("preempted", e.superstep, dict(rank=rank)))
        except (OSError, ValueError):
            pass
        raise SystemExit(0)
    except BaseException:
        try:
            conn.send(("error", traceback.format_exc(), None))
        except (OSError, ValueError):
            pass
        raise SystemExit(1)
    finally:
        if exchange is not None:
            exchange.close()
        if transport is not None:
            transport.close()
        conn.close()


def _teardown(procs) -> None:
    """Bounded-time teardown: terminate, then escalate to SIGKILL.

    A rank blocked inside a transport recv can ignore SIGTERM for the
    socket timeout; the kill escalation guarantees no child outlives the
    parent by more than ~10s and none leaks."""
    for p in procs:
        if p.is_alive():
            p.terminate()
    for p in procs:
        p.join(timeout=5.0)
    for p in procs:
        if p.is_alive():
            p.kill()
            p.join(timeout=5.0)


def _run_attempt(store_root: str, progs: list, cfg: ClusterConfig,
                 run_dir: str) -> ClusterResult:
    """One supervised attempt: spawn N ranks, collect results, raise
    ClusterFailure (after bounded teardown) when any rank dies, errors,
    or reports preemption."""
    from repro.core import transport as transport_mod

    n = cfg.num_servers
    if cfg.transport == "shm":
        transport_mod.create_ring_files(run_dir, n, cfg.ring_capacity)

    ctx = mp.get_context("spawn")
    procs, conns = [], []
    try:
        for rank in range(n):
            parent_conn, child_conn = ctx.Pipe(duplex=False)
            p = ctx.Process(
                target=_server_main,
                args=(rank, store_root, cfg, progs, run_dir, child_conn),
                name=f"graphh-server-{rank}", daemon=True)
            p.start()
            child_conn.close()
            procs.append(p)
            conns.append(parent_conn)

        pids = [p.pid for p in procs]
        deadline = time.monotonic() + cfg.launch_timeout_seconds
        payloads: list = [None] * n
        pending = set(range(n))
        while pending:
            for r in list(pending):
                if conns[r].poll(0.1):
                    try:
                        payloads[r] = conns[r].recv()
                    except EOFError:
                        raise ClusterFailure(
                            f"cluster server {r} died (exit code "
                            f"{procs[r].exitcode}) without reporting",
                            dead_ranks=[r], pids=pids)
                    pending.discard(r)
                    if payloads[r][0] == "error":
                        # fail fast: peers are now blocked on this rank's
                        # missing frames; the finally below reaps them
                        raise ClusterFailure(
                            f"cluster server {r} failed:\n{payloads[r][1]}",
                            dead_ranks=[r], pids=pids)
                    if payloads[r][0] == "preempted":
                        raise ClusterFailure(
                            f"cluster server {r} preempted; checkpoint "
                            f"saved at superstep boundary {payloads[r][1]}",
                            dead_ranks=[r], pids=pids, preempted=True)
                elif not procs[r].is_alive() and not conns[r].poll(0.1):
                    raise ClusterFailure(
                        f"cluster server {r} died (exit code "
                        f"{procs[r].exitcode}) without reporting",
                        dead_ranks=[r], pids=pids)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"cluster launch timed out; pending ranks {sorted(pending)}")
        for p in procs:
            p.join(timeout=30.0)
    finally:
        _teardown(procs)

    all_results = [payloads[r][1] for r in range(n)]
    reports = [payloads[r][2] for r in range(n)]
    diverged = [(a, r) for a in range(len(progs)) for r in range(1, n)
                if not np.array_equal(all_results[0][a].values,
                                      all_results[r][a].values)]
    if diverged:
        raise RuntimeError(
            "cluster ranks diverged — final values not bit-identical for "
            f"(app index, rank): {diverged}; this is a wrong answer, not "
            "a degraded one (transport/decode bug or broken hardware)")
    return ClusterResult(results=all_results[0], rank_reports=reports,
                         verified=True, final_servers=n)


def _probe_devices(conn) -> None:
    """Child-process body of :func:`local_devices`."""
    import jax

    conn.send((jax.default_backend(), jax.local_device_count()))
    conn.close()


def local_devices() -> tuple[str, int]:
    """``(platform, local device count)`` that the ranks will see.

    Ranks inherit this process's environment, so a ``JAX_PLATFORMS`` that
    leaves out the TPU answers without starting JAX (``("cpu", 0)`` — the
    count is not needed there).  Otherwise a short-lived spawned child
    asks JAX and exits, releasing the chips: the parent itself never
    initialises a backend, because a parent holding the chip would lock
    every rank out of it."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        return plats.split(",")[0], 0
    ctx = mp.get_context("spawn")
    parent_conn, child_conn = ctx.Pipe(duplex=False)
    p = ctx.Process(target=_probe_devices, args=(child_conn,),
                    name="graphh-device-probe", daemon=True)
    p.start()
    child_conn.close()
    try:
        if not parent_conn.poll(300.0):
            raise TimeoutError("device probe did not answer in 300 s")
        return parent_conn.recv()
    finally:
        parent_conn.close()
        _teardown([p])


def check_ranks_fit(num_servers: int) -> None:
    """Refuse, before any rank spawns, more ranks than local TPU chips:
    one chip belongs to one process, so a surplus rank would fail or hang
    waiting for a chip.  Other platforms are not limited."""
    platform, count = local_devices()
    if platform == "tpu" and num_servers > count:
        raise ValueError(
            f"{num_servers} cluster ranks but only {count} local TPU "
            f"chip(s): each rank needs a chip of its own; use --servers "
            f"<= {count}")


def run_cluster(store_root: str, progs: list,
                cfg: ClusterConfig = ClusterConfig(),
                run_dir: Optional[str] = None,
                keep_run_dir: bool = False) -> ClusterResult:
    """Run ``progs`` (VertexProgram instances) on an N-server cluster over
    the tile store at ``store_root``.

    The parent creates the rendezvous directory (+ shared-memory ring
    files for the shm transport), spawns the N server processes, collects
    each rank's results, verifies the final value arrays are bit-identical
    across ranks (divergence RAISES — a divergent cluster run is a wrong
    answer, never a degraded one), and returns rank 0's results with
    per-rank wire/steal reports.

    Failure handling follows ``cfg.on_failure`` (DESIGN.md §12): with
    ``"fail"`` any rank failure tears the cluster down and raises
    ClusterFailure with that rank's traceback; ``"restart"`` respawns the
    same N (resuming from the latest checkpoint when
    ``cfg.engine.checkpoint_dir`` is set — otherwise a clean rerun, which
    is equally bit-identical, just slower); ``"shrink"`` respawns with
    ``N - dead`` servers, remapping the checkpointed assignment at the
    superstep boundary (elastic resize).  Each attempt gets a fresh
    rendezvous subdirectory — stale ring frames from a killed attempt
    must never be replayed into the next.

    On a TPU platform more ranks than local chips are refused with a
    ``ValueError`` before anything is spawned (:func:`check_ranks_fit`)."""
    check_ranks_fit(cfg.num_servers)
    base_dir = run_dir or tempfile.mkdtemp(prefix="graphh_cluster_")
    own_dir = run_dir is None
    acfg = cfg
    restarts = 0
    try:
        while True:
            attempt_dir = os.path.join(base_dir, f"attempt_{restarts:02d}")
            os.makedirs(attempt_dir, exist_ok=True)
            try:
                res = _run_attempt(store_root, progs, acfg, attempt_dir)
                res.restarts = restarts
                return res
            except ClusterFailure as e:
                if (cfg.on_failure not in ("restart", "shrink")
                        or restarts >= cfg.max_restarts):
                    raise
                restarts += 1
                new_n = acfg.num_servers
                if cfg.on_failure == "shrink":
                    new_n = max(1, acfg.num_servers -
                                len(set(e.dead_ranks)))
                # resume only works with a checkpoint directory; without
                # one the restart is a clean rerun from superstep 0
                resume = bool(acfg.engine.checkpoint_dir)
                acfg = dataclasses.replace(
                    acfg, num_servers=new_n,
                    engine=dataclasses.replace(acfg.engine, resume=resume))
    finally:
        if own_dir and not keep_run_dir:
            shutil.rmtree(base_dir, ignore_errors=True)


def parse_admit_plan(specs) -> Optional[tuple]:
    """``--admit`` specs -> ``EngineConfig.admit_plan``: each
    ``"SS:seed1,seed2"`` entry schedules those query seeds for admission
    at the end of superstep SS (batched apps only; DESIGN.md §13)."""
    if not specs:
        return None
    plan = []
    for spec in specs:
        try:
            ss, seeds = spec.split(":", 1)
            plan.append((int(ss), tuple(int(s)
                                        for s in seeds.split(","))))
        except ValueError:
            raise SystemExit(f"--admit {spec!r}: expected 'SS:seed,seed'")
    return tuple(sorted(plan))


def _build_progs(args) -> list:
    """Vertex program list for the CLI (mirrors launch.graph seeding)."""
    from repro.core.apps import APPS

    batched = args.app in ("ppr", "msbfs", "landmarks")
    if batched:
        if args.seeds:
            seeds = tuple(int(s) for s in args.seeds.split(","))
        else:
            q = args.queries or 8
            rng = np.random.default_rng(args.seed)
            seeds = tuple(int(v) for v in
                          rng.choice(args.vertices, size=q, replace=False))
        key = {"ppr": "seeds", "msbfs": "sources", "landmarks": "landmarks"}
        return [APPS[args.app](**{key[args.app]: seeds})]
    if args.queries or args.seeds:
        raise SystemExit(f"--queries/--seeds only apply to batched apps "
                         f"(ppr/msbfs/landmarks), not {args.app}")
    return [APPS[args.app]()]


def main(argv=None) -> ClusterResult:
    """CLI: build (or reuse) a tile store, run one app on an N-server
    cluster, print per-superstep wire bytes and per-rank reports."""
    from repro.core.apps import APPS
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.graph import build_store
    from repro.graphio.formats import TileStore

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="pagerank", choices=sorted(APPS))
    ap.add_argument("--graph", default="rmat",
                    choices=["rmat", "uniform", "banded"])
    ap.add_argument("--vertices", type=int, default=100_000)
    ap.add_argument("--edges", type=int, default=1_000_000)
    ap.add_argument("--tile-size", type=int, default=65536)
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--transport", default="shm", choices=["shm", "tcp"])
    ap.add_argument("--steal", action="store_true",
                    help="cross-server tile stealing between supersteps")
    ap.add_argument("--supersteps", type=int, default=30)
    ap.add_argument("--comm-mode", default="hybrid",
                    choices=["dense", "sparse", "hybrid"])
    ap.add_argument("--cache-mb", type=float, default=1024)
    ap.add_argument("--cache-mode", default="auto")
    ap.add_argument("--cache-policy", default="lru",
                    choices=["lru", "tiered", "cost-aware"])
    ap.add_argument("--cache-promote-hits", type=int, default=2)
    ap.add_argument("--static-order", action="store_true")
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--prefetch-depth", type=int, default=4)
    ap.add_argument("--prefetch-workers", type=int, default=2)
    ap.add_argument("--stack-size", type=int, default=4)
    ap.add_argument("--num-intervals", type=int, default=0)
    ap.add_argument("--no-interval-order", action="store_true")
    ap.add_argument("--disk-mode", type=int, default=1)
    ap.add_argument("--store", default=None)
    ap.add_argument("--reuse", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=None)
    ap.add_argument("--seeds", default=None)
    ap.add_argument("--vertex-memory-budget", type=float, default=None,
                    metavar="MB")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for superstep checkpoints (shared by "
                         "all ranks; enables --resume and supervised "
                         "restart, DESIGN.md §12)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="write a checkpoint every K superstep boundaries "
                         "(0 = final checkpoint only)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in "
                         "--checkpoint-dir (bit-identical to the "
                         "uninterrupted run; N may differ from the saved "
                         "run — the assignment is remapped)")
    ap.add_argument("--preemptible", action="store_true",
                    help="SIGTERM => checkpoint at the next superstep "
                         "boundary and exit cleanly for later --resume")
    ap.add_argument("--on-failure", default="fail",
                    choices=["fail", "restart", "shrink"],
                    help="rank-death policy: fail fast, restart same N "
                         "from the latest checkpoint, or shrink to the "
                         "survivors (elastic resize)")
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--inject", action="append", default=None,
                    metavar="SPEC",
                    help="fault-injection spec, repeatable: e.g. "
                         "'rank=1,superstep=2,site=superstep,kind=kill' "
                         "(runtime.faults.parse_spec); once-markers "
                         "persist under --checkpoint-dir so a fault does "
                         "not re-fire after a supervised restart")
    ap.add_argument("--verify-clean", action="store_true",
                    help="after the (possibly faulted/restarted) cluster "
                         "run, re-run uninterrupted in-process and fail "
                         "unless the answers are byte-for-byte identical")
    ap.add_argument("--admit", action="append", default=None,
                    metavar="SS:SEEDS",
                    help="scripted mid-run admission for batched apps "
                         "(DESIGN.md §13), repeatable: '4:17,42' splices "
                         "queries seeded at vertices 17 and 42 into "
                         "retired [V,Q] slots at the end of superstep 4. "
                         "The plan replicates to every rank; rank 0 "
                         "admits (its frame header carries the record) "
                         "and peers splice deterministically from it")
    args = ap.parse_args(argv)

    if args.reuse and args.store:
        store = TileStore(args.store)
        store.load_meta()
    else:
        store = build_store(args)

    fault_plan = None
    if args.inject:
        from repro.runtime import faults

        marker_dir = None
        if args.checkpoint_dir:
            marker_dir = os.path.join(args.checkpoint_dir, "fault_markers")
            os.makedirs(marker_dir, exist_ok=True)
        fault_plan = faults.parse_plan(args.inject, marker_dir=marker_dir)

    ecfg = EngineConfig(
        comm_mode=args.comm_mode,
        cache_capacity_bytes=int(args.cache_mb * 1e6),
        cache_mode=args.cache_mode if args.cache_mode == "auto"
        else int(args.cache_mode),
        cache_policy=args.cache_policy,
        cache_promote_hits=args.cache_promote_hits,
        cache_aware_order=not args.static_order,
        max_supersteps=args.supersteps,
        pipeline=args.pipeline,
        prefetch_depth=args.prefetch_depth,
        prefetch_workers=args.prefetch_workers,
        stack_size=args.stack_size,
        vertex_memory_budget=(None if args.vertex_memory_budget is None
                              else int(args.vertex_memory_budget * 1e6)),
        num_intervals=args.num_intervals,
        interval_aware_order=not args.no_interval_order,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume,
        preemptible=args.preemptible,
        fault_plan=fault_plan,
        admit_plan=parse_admit_plan(args.admit),
    )
    cfg = ClusterConfig(num_servers=args.servers, transport=args.transport,
                        steal=args.steal, on_failure=args.on_failure,
                        max_restarts=args.max_restarts, engine=ecfg)
    progs = _build_progs(args)
    t0 = time.time()
    out = run_cluster(store.root, progs, cfg)
    dt = time.time() - t0
    res = out.results[0]
    wire = sum(h.wire_bytes for h in res.history)
    net = sum(h.network_bytes for h in res.history)
    print(f"{args.app} x{args.servers} servers [{args.transport}"
          f"{', steal' if args.steal else ''}]: {res.supersteps} supersteps "
          f"in {dt:.1f}s (converged={res.converged}, "
          f"bit-identical across ranks={out.verified}"
          + (f", {out.restarts} restarts -> {out.final_servers} servers"
             if out.restarts else "") + ")")
    if args.verify_clean:
        clean_cfg = dataclasses.replace(
            ecfg, num_servers=args.servers, server_rank=None,
            checkpoint_dir=None, checkpoint_every=0, resume=False,
            preemptible=False, fault_plan=None)
        clean_eng = OutOfCoreEngine(store, clean_cfg)
        for i, prog in enumerate(_build_progs(args)):
            clean = clean_eng.run(prog)
            if not np.array_equal(clean.values, out.results[i].values):
                raise SystemExit(
                    f"verify-clean FAILED: app index {i} differs from the "
                    "uninterrupted run")
        print("  verify-clean: byte-identical to the uninterrupted run")
    print(f"  wire {wire / 1e6:.2f} MB total ({net / 1e6:.2f} MB on the "
          f"network at N-1 peers/server); per-superstep "
          f"{[h.wire_bytes for h in res.history[:8]]}{'...' if res.supersteps > 8 else ''}")
    from repro.core.partition import server_vertex_ranges

    plan = store.load_plan()
    for rep in out.rank_reports:
        ranges = server_vertex_ranges(plan.splitter,
                                      [rep["final_assignment"][rep["rank"]]])[0]
        owned = sum(hi - lo for lo, hi in ranges)
        print(f"  rank {rep['rank']}: {rep['seconds']:.1f}s, "
              f"sent {rep['wire_bytes'] / 1e6:.2f} MB, "
              f"{len(rep['final_assignment'][rep['rank']])} tiles / "
              f"{owned} rows owned"
              + (f", {rep['steal_moves']} tiles stolen" if args.steal else ""))
    return out


if __name__ == "__main__":
    main()
