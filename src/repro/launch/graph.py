"""Graph analytics driver — run GraphH apps out-of-core or distributed.

    PYTHONPATH=src python -m repro.launch.graph --app pagerank \
        --vertices 100000 --edges 1000000 --servers 4 --supersteps 20

``--servers N`` emulates the paper's N servers inside one process (the
measurable reference).  ``--cluster`` upgrades the same run to N *real*
server processes exchanging updates over a shared-memory ring or TCP
(``--transport``, DESIGN.md §11) via ``repro.launch.cluster`` — results
are bit-identical either way.
"""
from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time

import numpy as np

from repro.core.apps import APPS
from repro.core.engine import EngineConfig, OutOfCoreEngine
from repro.graphio import spe, synth
from repro.graphio.formats import TileStore
from repro.launch.compile_cache import enable_compile_cache


def build_store(args) -> TileStore:
    """SPE-preprocess the synthetic graph selected by the CLI namespace
    into a (new or ``--store``-named) TileStore; weighted edges are
    generated iff the app consumes them (sssp/landmarks)."""
    store = TileStore(args.store or tempfile.mkdtemp(prefix="graphh_"),
                      disk_mode=args.disk_mode)
    gen = {"rmat": synth.rmat_edges, "uniform": synth.uniform_edges,
           "banded": synth.banded_edges}[args.graph]
    weighted = args.app in ("sssp", "landmarks")
    t0 = time.time()
    spe.preprocess(
        lambda: gen(args.vertices, args.edges, seed=args.seed,
                    weighted=weighted),
        args.vertices, store, tile_size=args.tile_size,
        weighted=weighted,
    )
    print(f"SPE preprocessing: {time.time()-t0:.1f}s -> {store.root}")
    return store


def _serve_main(args):
    """``--serve`` / ``--serve-http``: long-lived graph-query service
    over the tile store (DESIGN.md §13/§16).  A scripted workload of
    ``--serve-requests`` mixed queries (seeded from ``--seed``) is
    offered at ``--serve-qps`` (0 = all upfront) from a feeder thread;
    the serve loop runs in the main thread so SIGTERM drains gracefully
    (exit 0).  With ``--serve-requests 0`` — always in HTTP mode — the
    service idles until SIGTERM.  ``--serve-http`` additionally binds the
    JSON-over-HTTP frontend (serve/http.py) on ``--host``/``--port`` and
    keeps it answering ``GET /v1/query/<rid>`` for ``--drain-linger-ms``
    after the drain so clients can collect in-flight results."""
    import threading

    from repro.serve.graph_service import (SERVABLE, GraphService,
                                           parse_tenants)

    apps = [a.strip() for a in args.serve_apps.split(",") if a.strip()]
    bad = [a for a in apps if a not in SERVABLE]
    if bad:
        raise SystemExit(f"--serve-apps: {bad} not servable "
                         f"(batched apps only: {', '.join(SERVABLE)})")
    if args.reuse and args.store:
        store = TileStore(args.store)
        store.load_meta()
    else:
        store = build_store(args)
    cfg = EngineConfig(
        num_servers=args.servers,
        cache_capacity_bytes=int(args.cache_mb * 1e6),
        cache_mode=args.cache_mode if args.cache_mode == "auto"
        else int(args.cache_mode),
        comm_mode=args.comm_mode,
        cache_policy=args.cache_policy,
        pipeline=args.pipeline,
        vertex_memory_budget=(None if args.vertex_memory_budget is None
                              else int(args.vertex_memory_budget * 1e6)),
        num_intervals=args.num_intervals,
        checkpoint_dir=args.checkpoint_dir,
    )
    svc = GraphService(
        store, cfg, q_slots=args.q_slots, min_fill=args.min_fill,
        max_wait_s=args.max_wait_ms / 1e3,
        default_deadline_s=(None if args.deadline_ms is None
                            else args.deadline_ms / 1e3),
        max_supersteps=args.supersteps,
        drain_mode=args.drain_mode, resume=args.resume,
        tenants=parse_tenants(args.tenants) if args.tenants else None,
        result_cache=args.result_cache)

    frontend = None
    if args.serve_http:
        from repro.serve.http import HttpFrontend

        fault = None
        if args.inject:
            from repro.runtime import faults

            fault = faults.parse_plan(args.inject).injector()
        frontend = HttpFrontend(svc, host=args.host, port=args.port,
                                fault=fault).start()
        print(f"serving http on {frontend.host}:{frontend.port}",
              flush=True)

    def feeder():
        rng = np.random.default_rng(args.seed)
        tickets = []
        for i in range(args.serve_requests):
            if args.serve_qps > 0 and i:
                time.sleep(1.0 / args.serve_qps)
            try:
                tickets.append(svc.submit(apps[i % len(apps)],
                                          int(rng.integers(args.vertices))))
            except RuntimeError:
                break               # service started draining under us
        for t in tickets:
            t.wait()
        svc.request_drain()

    if args.serve_requests and not args.serve_http:
        threading.Thread(target=feeder, daemon=True).start()
    print(f"serving {','.join(apps)} on {store.root} "
          f"(q_slots={args.q_slots}, min_fill={args.min_fill}, "
          f"max_wait={args.max_wait_ms:g} ms, drain={args.drain_mode})",
          flush=True)
    t0 = time.time()
    svc.serve()
    dt = time.time() - t0
    if frontend is not None:
        # linger: finished tickets stay pollable while clients collect
        time.sleep(max(0.0, args.drain_linger_ms) / 1e3)
        frontend.close()
    s = svc.latency_summary()
    print(f"drained: {svc.stats['done']} done, {svc.stats['timeout']} "
          f"timeout, {svc.stats['failed']} failed, "
          f"{svc.stats['refused']} refused in {dt:.1f}s "
          f"({svc.stats['done'] / max(dt, 1e-9):.2f} queries/s, "
          f"{svc.stats['supersteps']} supersteps, "
          f"{svc.stats['sessions_opened']} sessions)")
    if s.get("count"):
        print(f"  latency p50 {s['p50_ms']:.0f} ms, p99 {s['p99_ms']:.0f} "
              f"ms (queue {s['mean_queue_ms']:.0f} ms + service "
              f"{s['mean_service_ms']:.0f} ms mean); "
              f"{s['mean_supersteps']:.1f} supersteps/query mean")
    if svc.cache is not None:
        c = svc.cache.snapshot()
        print(f"  result cache: {c['hits']} hits / {c['misses']} misses "
              f"({c['entries']}/{c['capacity']} entries)")
    if svc.tenant_stats:
        parts = ", ".join(
            f"{t}: {d['admitted']} admitted/{d['submitted']} submitted"
            for t, d in sorted(svc.tenant_stats.items()))
        print(f"  tenants: {parts}")
    return svc


def main(argv=None):
    """Parse CLI flags, build/reuse a tile store, and run the selected app
    through the out-of-core engine (or hand off to the multi-process
    cluster driver when ``--cluster`` is set)."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="pagerank", choices=sorted(APPS))
    ap.add_argument("--graph", default="rmat",
                    choices=["rmat", "uniform", "banded"])
    ap.add_argument("--vertices", type=int, default=100_000)
    ap.add_argument("--edges", type=int, default=1_000_000)
    ap.add_argument("--tile-size", type=int, default=65536)
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--supersteps", type=int, default=30)
    ap.add_argument("--cache-mb", type=float, default=1024)
    ap.add_argument("--cache-mode", default="auto")
    ap.add_argument("--cache-policy", default="lru",
                    choices=["lru", "tiered", "cost-aware"],
                    help="lru = paper's whole-cache single mode; tiered / "
                         "cost-aware = per-tile hot/warm/cold ladder with "
                         "demote-before-evict (DESIGN.md §8)")
    ap.add_argument("--cache-promote-hits", type=int, default=2,
                    help="hits between tier promotions (tiered policies)")
    ap.add_argument("--static-order", action="store_true",
                    help="disable cache-hit-first tile ordering")
    ap.add_argument("--comm-mode", default="hybrid",
                    choices=["dense", "sparse", "hybrid"])
    ap.add_argument("--disk-mode", type=int, default=1)
    ap.add_argument("--store", default=None,
                    help="reuse an existing tile store directory")
    ap.add_argument("--reuse", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pipeline", action="store_true",
                    help="overlap tile I/O, compute, and broadcast "
                         "compression (DESIGN.md §7)")
    ap.add_argument("--prefetch-depth", type=int, default=4)
    ap.add_argument("--prefetch-workers", type=int, default=2)
    ap.add_argument("--seg-impl", default="jnp",
                    choices=["jnp", "pallas_onehot", "pallas_fused"],
                    help="segment-reduce backend: XLA scatter, the unfused "
                         "one-hot Pallas kernel, or the fused "
                         "gather→combine→apply kernel (DESIGN.md §14)")
    ap.add_argument("--kernel-autotune", action="store_true",
                    help="pick Pallas (BE, BR) blocks + stack size from the "
                         "roofline cost model per (app, Q, tile shape) "
                         "instead of the static (512, 256); implies the "
                         "fused kernel path")
    ap.add_argument("--stack-size", type=int, default=4,
                    help="tiles per jitted batch dispatch (pipelined mode)")
    ap.add_argument("--queries", type=int, default=None,
                    help="batched apps (ppr/msbfs/landmarks): number of "
                         "query instances to run in one edge pass; seeds "
                         "are drawn deterministically from --seed unless "
                         "--seeds is given (DESIGN.md §9)")
    ap.add_argument("--seeds", default=None,
                    help="comma-separated seed/source/landmark vertex ids "
                         "for the batched apps, e.g. --seeds 0,17,42")
    ap.add_argument("--vertex-memory-budget", type=float, default=None,
                    metavar="MB",
                    help="byte budget (in MB) for the interval-sharded "
                         "out-of-core vertex state (DESIGN.md §10); vertex "
                         "[V,Q] arrays beyond it spill to a disk tier.  "
                         "Default: fully resident (the paper's All-in-All)")
    ap.add_argument("--num-intervals", type=int, default=0,
                    help="source intervals K for the out-of-core vertex "
                         "state (0 = auto from the budget / stored plan)")
    ap.add_argument("--no-interval-order", action="store_true",
                    help="disable interval-aware tile co-scheduling in "
                         "ooc-vstate mode (falls back to cache-hit-first)")
    ap.add_argument("--cluster", action="store_true",
                    help="run --servers as N real server processes "
                         "exchanging updates over --transport instead of "
                         "emulating them in-process (DESIGN.md §11)")
    ap.add_argument("--transport", default="shm", choices=["shm", "tcp"],
                    help="cluster transport: shared-memory ring (one "
                         "host) or TCP sockets (rendezvous via a shared "
                         "filesystem)")
    ap.add_argument("--steal", action="store_true",
                    help="cluster mode: cross-server tile stealing "
                         "between supersteps (runtime.scheduler)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="superstep-boundary checkpoints here "
                         "(DESIGN.md §12); enables --resume")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint every K superstep boundaries "
                         "(0 = final checkpoint only)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint "
                         "(bit-identical; --servers may differ from the "
                         "saved run)")
    ap.add_argument("--preemptible", action="store_true",
                    help="SIGTERM => save at the next superstep boundary "
                         "and exit for later --resume")
    ap.add_argument("--on-failure", default="fail",
                    choices=["fail", "restart", "shrink"],
                    help="cluster mode: rank-death policy (restart/shrink "
                         "resume from --checkpoint-dir)")
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--inject", action="append", default=None,
                    metavar="SPEC",
                    help="fault-injection spec (runtime.faults), "
                         "repeatable — fault drills only")
    ap.add_argument("--verify-clean", action="store_true",
                    help="cluster mode: diff the run against an "
                         "uninterrupted in-process rerun")
    ap.add_argument("--admit", action="append", default=None,
                    metavar="SS:SEEDS",
                    help="scripted mid-run admission for batched apps "
                         "(DESIGN.md §13), repeatable: '4:17,42' splices "
                         "those query seeds into retired [V,Q] slots at "
                         "the end of superstep 4")
    ap.add_argument("--serve", action="store_true",
                    help="run as a long-lived graph-query service "
                         "(DESIGN.md §13): queries admit into retired "
                         "[V,Q] slots mid-run; SIGTERM drains gracefully")
    ap.add_argument("--q-slots", type=int, default=8,
                    help="serve mode: live query columns per session")
    ap.add_argument("--min-fill", type=int, default=1,
                    help="serve mode: batch admissions until this many "
                         "queries are queued (amortizes the all-dirty "
                         "superstep an admission forces) ...")
    ap.add_argument("--max-wait-ms", type=float, default=50.0,
                    help="... but admit anyway after this long")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="serve mode: per-query deadline; overdue "
                         "queries drain with partial results")
    ap.add_argument("--serve-requests", type=int, default=32,
                    help="serve mode: scripted workload size "
                         "(0 = serve idle until SIGTERM)")
    ap.add_argument("--serve-qps", type=float, default=0.0,
                    help="serve mode: offered arrival rate for the "
                         "scripted workload (0 = submit all upfront)")
    ap.add_argument("--serve-apps", default="ppr,msbfs",
                    help="serve mode: comma list of batched apps the "
                         "scripted workload mixes")
    ap.add_argument("--drain-mode", default="finish",
                    choices=["finish", "checkpoint"],
                    help="serve mode: on SIGTERM, run in-flight queries "
                         "to convergence or checkpoint them for a "
                         "--resume'd service restart")
    ap.add_argument("--serve-http", action="store_true",
                    help="serve mode with the JSON-over-HTTP frontend "
                         "(serve/http.py, DESIGN.md §16): POST /v1/query, "
                         "GET /v1/query/<rid>, /v1/stats, /healthz; "
                         "implies --serve and idles until SIGTERM")
    ap.add_argument("--host", default="127.0.0.1",
                    help="HTTP frontend bind address")
    ap.add_argument("--port", type=int, default=8080,
                    help="HTTP frontend port (0 = ephemeral; the bound "
                         "port is printed as 'serving http on ...')")
    ap.add_argument("--tenants", default=None, metavar="NAME:W,...",
                    help="serve mode: tenant weights for deficit-round-"
                         "robin fair admission, e.g. 'alice:3,bob:1' "
                         "(unknown tenants serve at weight 1)")
    ap.add_argument("--result-cache", type=int, default=0,
                    metavar="ENTRIES",
                    help="serve mode: exact result-cache capacity keyed "
                         "by (app, seed, graph fingerprint); repeated "
                         "seeds return without consuming a [V,Q] slot "
                         "(0 = off)")
    ap.add_argument("--drain-linger-ms", type=float, default=500.0,
                    help="HTTP serve mode: keep GET /v1/query/<rid> "
                         "answering this long after the drain so "
                         "clients can collect in-flight results")
    args = ap.parse_args(argv)

    if args.serve or args.serve_http:
        return _serve_main(args)

    if args.cluster:
        from repro.launch import cluster as cluster_mod

        cl_argv = ["--app", args.app, "--graph", args.graph,
                   "--vertices", str(args.vertices),
                   "--edges", str(args.edges),
                   "--tile-size", str(args.tile_size),
                   "--servers", str(args.servers),
                   "--transport", args.transport,
                   "--supersteps", str(args.supersteps),
                   "--comm-mode", args.comm_mode,
                   "--cache-mb", str(args.cache_mb),
                   "--cache-mode", str(args.cache_mode),
                   "--cache-policy", args.cache_policy,
                   "--cache-promote-hits", str(args.cache_promote_hits),
                   "--prefetch-depth", str(args.prefetch_depth),
                   "--prefetch-workers", str(args.prefetch_workers),
                   "--stack-size", str(args.stack_size),
                   "--num-intervals", str(args.num_intervals),
                   "--disk-mode", str(args.disk_mode),
                   "--seed", str(args.seed),
                   "--checkpoint-every", str(args.checkpoint_every),
                   "--on-failure", args.on_failure,
                   "--max-restarts", str(args.max_restarts)]
        for flag, on in (("--steal", args.steal),
                         ("--pipeline", args.pipeline),
                         ("--static-order", args.static_order),
                         ("--no-interval-order", args.no_interval_order),
                         ("--reuse", args.reuse),
                         ("--resume", args.resume),
                         ("--preemptible", args.preemptible),
                         ("--verify-clean", args.verify_clean)):
            if on:
                cl_argv.append(flag)
        if args.checkpoint_dir:
            cl_argv += ["--checkpoint-dir", args.checkpoint_dir]
        for spec in args.inject or ():
            cl_argv += ["--inject", spec]
        for spec in args.admit or ():
            cl_argv += ["--admit", spec]
        if args.store:
            cl_argv += ["--store", args.store]
        if args.queries:
            cl_argv += ["--queries", str(args.queries)]
        if args.seeds:
            cl_argv += ["--seeds", args.seeds]
        if args.vertex_memory_budget is not None:
            cl_argv += ["--vertex-memory-budget",
                        str(args.vertex_memory_budget)]
        return cluster_mod.main(cl_argv)

    if args.reuse and args.store:
        store = TileStore(args.store)
        store.load_meta()
    else:
        store = build_store(args)

    cfg = EngineConfig(
        num_servers=args.servers,
        cache_capacity_bytes=int(args.cache_mb * 1e6),
        cache_mode=args.cache_mode if args.cache_mode == "auto"
        else int(args.cache_mode),
        comm_mode=args.comm_mode,
        cache_policy=args.cache_policy,
        cache_promote_hits=args.cache_promote_hits,
        cache_aware_order=not args.static_order,
        seg_impl=args.seg_impl,
        kernel_autotune=args.kernel_autotune,
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
    )
    if args.inject:
        from repro.runtime import faults

        cfg = dataclasses.replace(cfg, fault_plan=faults.parse_plan(
            args.inject))
    if args.admit:
        from repro.launch.cluster import parse_admit_plan

        cfg = dataclasses.replace(cfg,
                                  admit_plan=parse_admit_plan(args.admit))
    eng = OutOfCoreEngine(store, cfg)
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
        prog = APPS[args.app](**{key[args.app]: seeds})
    elif args.queries or args.seeds:
        raise SystemExit(f"--queries/--seeds only apply to batched apps "
                         f"(ppr/msbfs/landmarks), not {args.app}")
    else:
        prog = APPS[args.app]()
    t0 = time.time()
    res = eng.run(prog)
    dt = time.time() - t0
    print(f"{args.app}: {res.supersteps} supersteps in {dt:.1f}s "
          f"(mean {res.mean_superstep_seconds()*1000:.0f} ms/superstep, "
          f"converged={res.converged})")
    if args.kernel_autotune and eng.kernel_choice is not None:
        c = eng.kernel_choice
        print(f"  kernel autotune [{prog.combine}, Q="
              f"{getattr(prog, 'num_queries', 1)}]: BE={c.block_e} "
              f"BR={c.block_r} stack={c.stack_size} ({c.bound}-bound, "
              f"ceiling {c.edges_per_s:.2e} edges/s)")
    if batched:
        q = len(seeds)
        io = sum(x.disk_bytes_read for x in res.history)
        retired = [(g, int(s)) for g, s in enumerate(res.per_query_supersteps)]
        print(f"  {q} queries in one edge pass: "
              f"tile I/O {io/1e6:.1f} MB total = {io/q/1e6:.2f} MB/query, "
              f"{dt/q*1000:.0f} ms/query; per-query supersteps "
              f"{[s for _, s in retired]}")
    if not res.history:
        # --resume against a FINAL checkpoint short-circuits: the stored
        # result is returned without executing a superstep, so there are
        # no per-superstep stats to report.
        print("  resumed a finished run from its final checkpoint "
              "(no supersteps executed)")
        return res
    h = res.history[-1]
    print(f"  cache hit ratio {h.cache_hit_ratio:.2f}, "
          f"net {sum(x.network_bytes for x in res.history)/1e6:.1f} MB total, "
          f"mode={eng.cache_mode}, "
          f"disk-stall {res.disk_stall_fraction()*100:.0f}% of wall time"
          f"{' (pipelined)' if args.pipeline else ''}")
    n = len(res.history)
    h2d = sum(x.h2d_bytes for x in res.history) / n
    d2h = sum(x.d2h_bytes for x in res.history) / n
    real = sum(x.edges_real for x in res.history)
    padded = sum(x.edges_padded for x in res.history)
    fill = f"{100 * real / padded:.1f}%" if padded else "n/a (no tile ran)"
    print(f"  host->device {h2d / 1e9:.3g} GB/superstep, device->host "
          f"{d2h / 1e9:.3g} GB/superstep, edge fill {fill} "
          f"({real} real edges in {padded} padded slots)")
    if args.vertex_memory_budget is not None:
        vs = eng.vstate.stats
        faults = sum(x.vstate_faults for x in res.history)
        spill = sum(x.vstate_spill_bytes for x in res.history)
        load = sum(x.vstate_load_bytes for x in res.history)
        print(f"  vertex state [{eng.vstate.num_intervals} intervals, "
              f"budget {args.vertex_memory_budget:g} MB]: "
              f"{faults} interval faults, {load/1e6:.1f} MB faulted in, "
              f"{spill/1e6:.1f} MB spilled to disk, "
              f"{vs.dirty_writebacks} dirty writebacks")
    if args.cache_policy != "lru":
        promo = sum(x.cache_promotions for x in res.history)
        demo = sum(x.cache_demotions for x in res.history)
        tiers = ", ".join(
            f"{name}: {d['tiles']} tiles/{d['bytes']/1e6:.1f} MB "
            f"({d['hits']} hits)"
            for name, d in sorted(h.cache_tiers.items()))
        print(f"  cache tiers [{args.cache_policy}]: {tiers or 'empty'}; "
              f"{promo} promotions, {demo} demotions")
    return res


if __name__ == "__main__":
    main()
