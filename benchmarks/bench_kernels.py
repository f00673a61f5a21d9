"""Kernel micro-benchmarks: Pallas vs jnp oracle vs numpy.

On the CPU the Pallas kernels run in interpret mode, so their timing there
is only the cost of the correctness path; the derived column reports
achieved GB/s for the oracle (the XLA-compiled path).

``bench_kernel_fused`` sweeps fused-kernel block sizes per app-monoid and
validates the roofline autotuner's pick against a measured grid search;
rows land in ``BENCH_kernels.json`` (override via ``BENCH_KERNELS_OUT``)
for the perf-trajectory artifacts.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import emit


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_segment_sum():
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    for E, R in [(1 << 16, 4096), (1 << 20, 32768)]:
        c = jnp.asarray(rng.normal(size=E).astype(np.float32))
        d = jnp.asarray(np.sort(rng.integers(0, R, E)).astype(np.int32))
        t_ref = _time(lambda a, b: ref.segment_sum(a, b, R), c, d)
        gbps = E * 8 / t_ref / 1e9
        emit(f"kern.segsum.ref.E{E}", t_ref * 1e6, f"GBps={gbps:.2f}")
        if E <= 1 << 16:   # interpret mode is slow; validate small only
            t_pal = _time(lambda a, b: ops.segment_sum(a, b, R), c, d)
            path = "interp" if ops.interpret_mode() else "mosaic"
            emit(f"kern.segsum.pallas_{path}.E{E}", t_pal * 1e6,
                 f"interpret={ops.interpret_mode()}")


def bench_gab_superstep():
    """Engine-level throughput: edges/s for one PageRank superstep."""
    from benchmarks.common import make_store
    from repro.core.apps import PageRank
    from repro.core.engine import EngineConfig, OutOfCoreEngine

    nv, ne = 100_000, 1_000_000
    store = make_store(nv, ne, 65536)
    eng = OutOfCoreEngine(store, EngineConfig(num_servers=1,
                                              max_supersteps=5))
    res = eng.run(PageRank())
    sec = res.mean_superstep_seconds()
    emit("kern.gab.superstep.1M_edges", sec * 1e6,
         f"Medges_per_s={ne/sec/1e6:.1f}")


def _kernels_out_path() -> str:
    return os.environ.get("BENCH_KERNELS_OUT", "BENCH_kernels.json")


def _save_kernels(key: str, payload) -> None:
    path = _kernels_out_path()
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data[key] = payload
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)


def bench_kernel_fused():
    """Fused GAB kernel block sweep: roofline-model pick vs grid search.

    Per app-monoid the sweep measures the fused kernel at a grid of
    (BE, BR) candidates plus the historical static (512, 256) and the
    autotuner's pick, then reports the model's edges/sec ceiling and the
    measured gap to that roofline.  Asserts the pick's measured time does
    not lose to the static default beyond timing noise — the acceptance
    gate for EngineConfig.kernel_autotune's default candidacy.

    The measured gate only applies on a TPU backend: interpret-mode
    emulation cost scales with padded block *area*, so on CPU the grid
    search rewards tiny blocks that a real TPU would spend all its time
    dispatching.  Everywhere the bench still enforces the deterministic
    model-side relation (pick never predicts worse than static) and
    records the full measured grid so the inversion is visible in the
    artifact rather than papered over.
    """
    import jax
    from repro.kernels.gab_fused import FusedSpec, gab_fused
    from repro.kernels.ops import interpret_mode
    from repro.roofline import kernel_tune

    smoke = common.SMOKE
    edge_cap, row_cap = (2048, 256) if smoke else (16384, 1024)
    noise_tol = 1.6 if smoke else 1.25
    apps = [
        ("pagerank", 1, FusedSpec(combine="sum", scale_aux="inv",
                                  apply="affine", alpha=0.15, beta=0.85,
                                  update_tol=1e-8)),
        ("sssp", 1, FusedSpec(combine="min", add_edge=True, apply="min")),
        ("msbfs", 8, FusedSpec(combine="min", add_const=1.0, apply="min")),
    ]
    rng = np.random.default_rng(0)
    results = {}
    for app, q, spec in apps:
        shape = (edge_cap,) if q == 1 else (edge_cap, q)
        sv = jnp.asarray(np.abs(rng.normal(size=shape)).astype(np.float32))
        a = (jnp.asarray(rng.random(edge_cap).astype(np.float32))
             if spec.scale_aux else None)
        b = (jnp.asarray(rng.random(edge_cap).astype(np.float32))
             if spec.add_edge else None)
        dst = jnp.asarray(np.sort(
            rng.integers(0, row_cap, edge_cap)).astype(np.int32))
        oshape = (row_cap,) if q == 1 else (row_cap, q)
        old = jnp.asarray(
            np.abs(rng.normal(size=oshape)).astype(np.float32) + 1.0)
        nr = jnp.int32(row_cap)

        choice = kernel_tune.pick_blocks(spec.combine, q, edge_cap, row_cap)
        grid = [(128, 128), (256, 256), kernel_tune.STATIC_BLOCKS,
                choice.blocks]
        budget = kernel_tune.vmem_budget()
        grid = [g for g in dict.fromkeys(grid)
                if kernel_tune.vmem_plan_bytes(spec.combine, q, *g)
                <= budget]

        timed = {}
        for be, br in grid:
            t = _time(lambda: gab_fused(spec, sv, a, b, dst, old, None, nr,
                                        row_cap, block_e=be, block_r=br,
                                        interpret=interpret_mode()),
                      iters=2 if smoke else 3)
            timed[(be, br)] = t
            emit(f"kern.fused.{app}.BE{be}_BR{br}", t * 1e6,
                 f"Medges_per_s={edge_cap/t/1e6:.2f}")
        best = min(timed, key=timed.get)
        t_pick = timed[choice.blocks]
        t_static = timed[kernel_tune.STATIC_BLOCKS]
        gap = t_pick / choice.roofline_s
        emit(f"kern.fused.{app}.model_pick", t_pick * 1e6,
             f"BE={choice.block_e};BR={choice.block_r}"
             f";stack={choice.stack_size};bound={choice.bound}"
             f";ceiling_edges_per_s={choice.edges_per_s:.3e}"
             f";roofline_gap={gap:.1f}x"
             f";grid_best=BE{best[0]}_BR{best[1]}")
        results[app] = {
            "q": q, "edge_cap": edge_cap, "row_cap": row_cap,
            "pick": list(choice.blocks), "stack_size": choice.stack_size,
            "bound": choice.bound,
            "predicted_s": choice.predicted_s,
            "roofline_s": choice.roofline_s,
            "ceiling_edges_per_s": choice.edges_per_s,
            "measured_pick_s": t_pick,
            "measured_static_s": t_static,
            "measured_roofline_gap": gap,
            "grid": {f"{be}x{br}": t for (be, br), t in timed.items()},
            "grid_best": list(best),
        }
        # the model must never *predict* worse than the static default...
        static_cost = kernel_tune.tile_cost(
            spec.combine, q, edge_cap, row_cap, *kernel_tune.STATIC_BLOCKS)
        assert choice.predicted_s <= static_cost.predicted_s, app
        # ...and on real hardware the measured pick must match/beat it
        if jax.default_backend() == "tpu":
            assert t_pick <= t_static * noise_tol, (
                f"{app}: autotuned {choice.blocks} measured {t_pick:.4f}s "
                f"vs static {kernel_tune.STATIC_BLOCKS} {t_static:.4f}s")
    _save_kernels("kernel_fused_sweep", {
        "smoke": smoke,
        "backend": jax.default_backend(),
        "measured_gate": jax.default_backend() == "tpu",
        "bandwidth_bytes_per_s": kernel_tune.measured_bandwidth(),
        "apps": results,
    })


ALL = [bench_segment_sum, bench_gab_superstep, bench_kernel_fused]
