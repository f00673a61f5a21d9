"""Roofline-driven block-size autotuner for the fused GAB kernel.

Picks ``(BE, BR, stack_size)`` for ``kernels/gab_fused.py`` per
``(combine, Q, edge_cap, row_cap)`` from a dry-run cost model instead of
the historical hand-picked ``(512, 256)`` (DESIGN.md §14):

  * **Visited edge blocks** — each row block streams only the edge
    blocks holding its edges; on a dst-sorted tile consecutive row blocks
    share at most one boundary block, so the kernel visits at most
    ``n_eblocks + n_rblocks - 1`` (edge block, row block) pairs.
  * **HBM traffic** — every visited pair streams one edge block
    (``src [Q,BE]`` + ``dst`` + optional scale/add streams), plus one
    read/write of the row-block arrays.
  * **Compute** — per visited pair, per-monoid arithmetic intensity: the
    sum monoid is a ``2·Q·BE·BR`` MXU contraction, min/max a
    ``~3·Q·BE·BR`` masked VPU select+reduce (no MXU form), and the
    one-hot build costs ``BE·BR`` compares either way.
  * **Overhead** — a per-grid-step cost (DMA issue + semaphore sync) that
    penalizes tiny ``BE``; this is what makes big edge blocks win once
    VMEM allows them.
  * **VMEM feasibility** — double-buffered edge slots + the resident
    accumulator + row-block I/O + the one-hot (and, for min/max, the
    ``[Q, BE, BR]`` select) must fit a VMEM budget; this is the ceiling
    that forces min/max and wide-Q configs to smaller blocks.

``predicted_s = max(hbm/bw, compute) + overhead``; the roofline ceiling
(``edges_per_s``) drops the overhead term — the gap between a measured
run and that ceiling is what ``bench_kernel_fused`` reports per app.

The chip constants come from ``roofline/hw.py`` by the default device's
``device_kind`` (an unknown TPU raises); CPU interpret mode rehearses the
v5e kernel and plans with the v5e constants.  The bandwidth is the
published HBM figure on TPU and a measured host ``memcpy`` figure on the
CPU (interpret mode streams through host memory), so predicted times are
honest on both substrates.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from repro.roofline import hw

#: candidate block sizes — MXU/lane-aligned multiples of 128
_BE_CANDIDATES = (128, 256, 512, 1024, 2048, 4096)
_BR_CANDIDATES = (128, 256, 512, 1024, 2048)
#: fraction of VMEM the kernel may plan for (the rest: spills, metadata)
_VMEM_FRACTION = 0.5
STATIC_BLOCKS = (512, 256)      # the historical hand-picked default


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """One tuned kernel configuration + its model terms."""

    block_e: int
    block_r: int
    stack_size: int             # tiles per pipelined dispatch
    predicted_s: float          # model seconds per tile (incl. overhead)
    roofline_s: float           # max(bytes/bw, compute) — no overhead
    edges_per_s: float          # edge_cap / roofline_s: the ceiling
    hbm_bytes: int
    flops: int                  # MXU flops (sum monoid contraction)
    vpu_ops: int                # elementwise ops (one-hot + min/max path)
    bound: str                  # "memory" | "compute"

    @property
    def blocks(self) -> tuple[int, int]:
        return (self.block_e, self.block_r)


def _roundup(x: int, m: int) -> int:
    return max(-(-x // m) * m, m)


def target_chip() -> hw.ChipSpec:
    """Constants of the chip the fused kernel runs on: the default
    device's kind (``hw.chip`` raises for one it does not know), or the
    v5e the kernel is written for when the CPU interprets it."""
    import jax

    dev = jax.devices()[0]
    return hw.chip(hw.V5E if dev.platform == "cpu" else dev.device_kind)


def vmem_budget() -> int:
    """Bytes of VMEM a pick may plan for on the target chip."""
    return int(_VMEM_FRACTION * target_chip().vmem_bytes)


@functools.lru_cache(maxsize=1)
def measured_bandwidth() -> float:
    """Effective stream bandwidth in bytes/s.

    On TPU: the chip's published HBM figure.  On the CPU (interpret mode)
    a tiny host memcpy microbench — best of three copies of a 32 MB
    buffer — since that is the memory the interpreted kernel streams.
    """
    import jax

    if jax.default_backend() != "cpu":
        return float(target_chip().hbm_bw)
    buf = np.ones(32 * 1024 * 1024 // 8, dtype=np.float64)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(np.empty_like(buf), buf)
        best = min(best, time.perf_counter() - t0)
    return (2 * buf.nbytes) / max(best, 1e-9)


def _n_streams(q: int) -> int:
    # dst + src always stream; scale/add streams are app-dependent — plan
    # for the worst shipped case (one extra f32 stream) so one choice
    # serves every program at a given (combine, Q, shape).
    return 3


def vmem_plan_bytes(combine: str, q: int, block_e: int, block_r: int) -> int:
    """Planned VMEM footprint of the fused kernel at (BE, BR)."""
    qp = _roundup(q, hw.SUBLANES)
    slots = 2 * (qp * block_e + (_n_streams(q) - 1) * block_e) * 4
    acc = qp * block_r * 4
    row_io = 4 * qp * block_r * 4           # old + base + new + upd blocks
    onehot = block_e * block_r * 4
    sel = qp * block_e * block_r * 4 if combine in ("min", "max") else 0
    return slots + acc + row_io + onehot + sel


def tile_cost(combine: str, q: int, edge_cap: int, row_cap: int,
              block_e: int, block_r: int,
              bandwidth: float | None = None) -> KernelChoice:
    """Model one (BE, BR) config for one tile shape; stack_size unset (0)."""
    bw = measured_bandwidth() if bandwidth is None else bandwidth
    chip = target_chip()
    qp = _roundup(q, hw.SUBLANES)
    ep = _roundup(edge_cap, block_e)
    rp = _roundup(row_cap, block_r)
    n_rb = rp // block_r
    n_eb = ep // block_e
    visited = min(n_eb * n_rb, n_eb + n_rb - 1)   # (edge, row) block pairs

    block_bytes = block_e * (4 * qp + 4 * (_n_streams(q) - 1))
    row_bytes = rp * qp * 4 * 4             # old+base in, new+upd out
    hbm_bytes = visited * block_bytes + row_bytes

    pair = block_e * block_r
    onehot_ops = visited * pair
    if combine == "sum":
        flops = 2 * qp * visited * pair
        vpu_ops = onehot_ops
    else:
        flops = 0
        vpu_ops = 3 * qp * visited * pair + onehot_ops
    compute_s = flops / chip.peak_flops_f32 + vpu_ops / chip.vpu_ops

    roofline_s = max(hbm_bytes / bw, compute_s)
    overhead_s = (visited + n_rb) * hw.GRID_STEP_OVERHEAD_S
    predicted_s = roofline_s + overhead_s
    return KernelChoice(
        block_e=block_e, block_r=block_r, stack_size=0,
        predicted_s=predicted_s, roofline_s=roofline_s,
        edges_per_s=edge_cap / max(roofline_s, 1e-12),
        hbm_bytes=hbm_bytes, flops=flops, vpu_ops=vpu_ops,
        bound="memory" if hbm_bytes / bw >= compute_s else "compute",
    )


def _stack_size(predicted_s: float) -> int:
    """Tiles per pipelined dispatch: enough that the host dispatch cost
    stays under ~5% of the stack's kernel time, clamped to [1, 16]."""
    k = hw.HOST_DISPATCH_S / (0.05 * max(predicted_s, 1e-9))
    return int(min(16, max(1, np.ceil(k))))


def pick_blocks(combine: str, q: int, edge_cap: int, row_cap: int,
                bandwidth: float | None = None,
                vmem_bytes: int | None = None) -> KernelChoice:
    """The autotuned (BE, BR, stack_size) for one (app-monoid, Q, tile).

    Deterministic: candidates are the 128-aligned grid capped at the
    padded tile shape (a block bigger than the tile only pads), filtered
    by the VMEM plan, ranked by predicted time with smaller-footprint
    tie-breaking.  The static (512, 256) default is always a candidate
    when feasible, so the pick can never model-predict worse than it.
    """
    budget = (vmem_budget() if vmem_bytes is None
              else int(_VMEM_FRACTION * vmem_bytes))
    be_cap = _roundup(edge_cap, 128)
    br_cap = _roundup(row_cap, 128)
    cands = []
    for be in _BE_CANDIDATES:
        if be > max(be_cap, _BE_CANDIDATES[0]):
            continue
        for br in _BR_CANDIDATES:
            if br > max(br_cap, _BR_CANDIDATES[0]):
                continue
            if vmem_plan_bytes(combine, q, be, br) > budget:
                continue
            cands.append(tile_cost(combine, q, edge_cap, row_cap, be, br,
                                   bandwidth=bandwidth))
    if not cands:  # degenerate budget: smallest legal block
        cands = [tile_cost(combine, q, edge_cap, row_cap, 128, 128,
                           bandwidth=bandwidth)]
    best = min(cands, key=lambda c: (c.predicted_s,
                                     c.block_e * c.block_r, c.block_e))
    return dataclasses.replace(best, stack_size=_stack_size(best.predicted_s))
