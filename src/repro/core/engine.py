"""Out-of-core GAB engine — the paper's MPE (§III-C, Algorithm 5).

Emulates N servers x T workers in one process with *real* out-of-core
behaviour: tiles live in the TileStore (disk tier), each server owns a
round-robin tile subset and an EdgeCache over "idle" memory, vertex state
is fully replicated (All-in-All), and the per-superstep Broadcast payloads
are measured (and actually compressed) through core.comm.

This is the measurable CPU reference implementation; distributed.py maps
the identical superstep onto a device mesh with shard_map.
"""
from __future__ import annotations

import dataclasses
import tempfile
import threading
import time
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import comm, obs
from repro.core.bloom import SourceBlockBitmap, BloomFilter
from repro.core.cache import EdgeCache, auto_select_mode, DEFAULT_GAMMAS
from repro.core.checkpoint import GraphCheckpointer
from repro.core.gab import (VertexProgram, run_tile, run_tile_sharded,
                            run_tile_stack)
from repro.core.partition import (assign_tiles, assign_tiles_balanced,
                                  plan_intervals)
from repro.core.tiles import compute_source_footprint, tile_edge_values
from repro.core.vstate import VertexStateStore
from repro.graphio.formats import TileStore
from repro.runtime.elastic import remap_assignment
from repro.runtime.faults import FaultPlan
from repro.runtime.ft import Preempted, PreemptionGuard


@dataclasses.dataclass
class EngineConfig:
    """All engine knobs (one dataclass so cluster server processes can ship
    it through multiprocessing spawn).  Field groups are commented below;
    see docs/OPERATIONS.md for tuning guidance."""
    num_servers: int = 1
    num_workers: int = 1                    # paper's T (accounting only here)
    cache_capacity_bytes: int = 1 << 30     # per server
    cache_mode: int | str = "auto"          # 1..4 or "auto" (lru policy)
    # --- adaptive multi-tier cache (DESIGN.md §8) ---
    # "lru": paper-faithful whole-cache single mode + LRU eviction
    # "tiered": per-tile hot/warm/cold ladder, demote-before-evict
    # "cost-aware": tiered with decompress-seconds-saved/byte victims
    cache_policy: str = "lru"
    cache_promote_hits: int = 2             # hits between tier promotions
    # cache-hit-first tile ordering: resident tiles run while the prefetcher
    # pulls the misses (order is irrelevant to results — disjoint rows)
    cache_aware_order: bool = True
    comm_mode: str = "hybrid"               # dense | sparse | hybrid
    comm_compressor: str = "zstd-1"         # paper default: snappy
    comm_threshold: float = comm.DENSITY_THRESHOLD
    tile_skipping: bool = True
    skip_filter: str = "bitmap"             # "bitmap" (exact) | "bloom" (paper)
    skip_density_threshold: float = 0.05    # paper: only when few updates
    seg_impl: str = "jnp"
    # --- fused-kernel block autotuning (DESIGN.md §14) ---
    # pick (BE, BR, stack_size) for the Pallas kernel paths from the
    # roofline cost model (roofline/kernel_tune.py) per (app monoid, Q,
    # tile shape) instead of the static (512, 256) defaults.  Also
    # promotes seg_impl="jnp" to "pallas_fused" — autotuning targets the
    # fused gather→combine→apply kernel.
    kernel_autotune: bool = False
    # explicit (BE, BR) override for the Pallas kernel paths; None = the
    # kernel's static defaults (or the autotuner's pick when
    # kernel_autotune is on).  Takes precedence over the autotuner.
    kernel_blocks: Optional[tuple] = None
    max_supersteps: int = 200
    balanced_assignment: bool = False       # beyond-paper LPT stage-2
    bloom_bits: int = 1 << 16
    block_shift: int = 8
    # --- beyond-paper performance features (EXPERIMENTS.md §Perf) ---
    # "tiled": paper-faithful one-tile-at-a-time processing
    # "stacked": device-resident stacked tiles, one scan per server (the
    #            HBM tier of the cache hierarchy; falls back to tiled for
    #            tiles beyond device_budget_bytes or when skipping is on)
    # "merged": per-server fused edge lists of real edges, device-resident
    # "auto": "stacked" when every executed server's padded tile share
    #         and the superstep's vertex arrays fit device_budget_bytes,
    #         vertex state is in memory and no tiles are stolen, else
    #         "tiled" (select_engine_mode)
    engine_mode: str = "auto"
    device_budget_bytes: int = 1 << 30      # per server: resident tile bytes
    # wire accounting: "full" compresses every payload (measured bytes);
    # "sampled" compresses every 4th superstep and reuses the last ratio
    comm_accounting: str = "full"
    # --- pipelined superstep (DESIGN.md §7): overlap tile N+1 load with
    # tile N compute and server s-1 broadcast-compression.  pipeline=False
    # keeps the paper-faithful serial loop as the baseline.
    pipeline: bool = False
    prefetch_depth: int = 4                 # tiles read+decompressed ahead
    prefetch_workers: int = 2               # parallel read/decompress threads
    stack_size: int = 4                     # tiles per jitted batch dispatch
    # record every tile-skip decision (superstep, active ids, run/skipped
    # tile lists) into engine.skip_log — test/debug aid for the skip-filter
    # safety property; off by default (the active-id snapshot costs memory)
    debug_skip_log: bool = False
    # --- out-of-core vertex state (DESIGN.md §10) ---
    # byte budget for the interval-sharded VertexStateStore's in-memory
    # tiers (hot ndarrays + warm compressed blobs); beyond it, interval
    # blocks spill to a disk tier.  None keeps the paper's fully-resident
    # [V, Q] vertex arrays.  Runs every session tiled (stacked/merged
    # need the full value array on device).
    vertex_memory_budget: Optional[int] = None
    # source intervals K; 0 = auto (sized so ~4 value blocks fit the
    # budget, or the store's preprocessed interval plan when present)
    num_intervals: int = 0
    # co-order tiles to maximize *joint* residency of edge tiles (edge
    # cache) and source intervals (vertex cache); only active in ooc-vstate
    # mode — superstep 0 falls back to cache-hit-first ordering while
    # footprints are still unknown
    interval_aware_order: bool = True
    # --- multi-process cluster runtime (DESIGN.md §11) ---
    # when set, this engine instance is ONE server of an N-server cluster:
    # it executes only rank ``server_rank`` of the stage-2 assignment and
    # merges the other servers' per-superstep updates through the
    # ClusterExchange passed to the constructor.  None = the classic
    # single-process engine emulating all N servers itself.
    server_rank: Optional[int] = None
    # --- superstep checkpointing + fault tolerance (DESIGN.md §12) ---
    # directory for superstep-boundary checkpoints (core.checkpoint); None
    # disables checkpointing entirely
    checkpoint_dir: Optional[str] = None
    # save every K superstep boundaries (rank 0 / classic engine only);
    # 0 = no periodic saves (still saves on preemption + run completion)
    checkpoint_every: int = 0
    checkpoint_keep: int = 2
    # resume from the latest checkpoint in checkpoint_dir: adopt its tile
    # assignment (remapped via elastic.remap_assignment when num_servers
    # changed — the mid-run N->M resize path) and continue from the saved
    # superstep boundary; bit-identical to the uninterrupted run
    resume: bool = False
    # latch SIGTERM/SIGINT at the BSP barrier: save a checkpoint and raise
    # runtime.ft.Preempted instead of dying mid-superstep (spot reclaim);
    # requires checkpoint_dir
    preemptible: bool = False
    # deterministic fault injection (runtime.faults.FaultPlan) — test-only;
    # arms engine sites "superstep"/"barrier", the ckpt.* save sites, and
    # (in cluster launches) "transport.send"
    fault_plan: Optional[FaultPlan] = None
    # --- step-driven sessions + mid-run query admission (DESIGN.md §13) ---
    # scripted admissions for batch runs: tuple of (after_superstep, seeds)
    # entries — each seeds tuple is spliced into the [V, Q] state as fresh
    # query columns at the END of superstep ``after_superstep`` (their
    # first compute superstep is after_superstep + 1), in every execution
    # mode.  Cluster launches replicate the plan to every rank through
    # this config so peers know the run is not done while entries pend,
    # but the admission records themselves always originate at rank 0 and
    # ride its update frame.  Ignored for 1-D (single-query) programs.
    admit_plan: Optional[tuple] = None


@dataclasses.dataclass
class SuperstepStats:
    """Per-superstep measurements (bytes are real payload/compressed sizes,
    seconds wall-clock).  Cluster runs report cluster-total wire bytes,
    rank-local cache/io counters."""
    superstep: int
    seconds: float
    load_seconds: float
    compute_seconds: float
    updated_vertices: int
    density: float
    tiles_processed: int
    tiles_skipped: int
    raw_bytes: int            # sum over servers of broadcast payload
    wire_bytes: int           # after compression
    network_bytes: int        # wire * (N-1): each server ships to N-1 peers
    cache_hit_ratio: float
    disk_bytes_read: int      # bytes read from the disk tier THIS superstep
    # time the compute loop spent *blocked* waiting for tile data.  Serial
    # engine: equals the full load time.  Pipelined engine: only the residual
    # wait after prefetch overlap — the disk-stall the pipeline couldn't hide.
    stall_seconds: float = 0.0
    # disk read + (de)compress busy time this superstep, wherever it ran
    # (inline for the serial engine, prefetch threads for the pipelined one)
    io_busy_seconds: float = 0.0
    # tiered-cache activity this superstep (zeros for policy="lru")
    cache_promotions: int = 0
    cache_demotions: int = 0
    # per-tier residency at the barrier: {tier: {tiles, bytes, hits}}
    cache_tiers: dict = dataclasses.field(default_factory=dict)
    # --- multi-query accounting (DESIGN.md §9; all trivial for 1-D runs) ---
    # query columns still live when this superstep started
    active_queries: int = 1
    # updated (vertex, query) cells; == updated_vertices for 1-D runs
    updated_pairs: int = 0
    # {global query id: updated-cell count} for active queries
    updated_per_query: dict = dataclasses.field(default_factory=dict)
    # global query ids whose columns converged (and were compacted out)
    # at the end of this superstep
    retired_queries: tuple = ()
    # global query ids spliced in (admitted) at the end of this superstep —
    # their first compute superstep is the next one (DESIGN.md §13)
    admitted_queries: tuple = ()
    # global query ids force-retired mid-flight (session drain) at the end
    # of this superstep; their per-query supersteps stay -1
    drained_queries: tuple = ()
    # --- out-of-core vertex state (DESIGN.md §10; zeros when in-memory) ---
    vstate_faults: int = 0          # interval blocks decoded (warm + cold)
    vstate_load_bytes: int = 0      # compressed bytes faulted back in
    vstate_spill_bytes: int = 0     # compressed bytes written to the disk tier
    vstate_dirty_intervals: int = 0 # intervals written back (and broadcast)
    # --- the tile step's phases and traffic (core/obs.py; each seconds
    # field is its graphh.tile.* span's host time): compute_seconds is
    # dispatch + fetch + split and the few statements between them
    dispatch_seconds: float = 0.0   # inputs built, tile step enqueued
    fetch_seconds: float = 0.0      # results copied back to the host
    split_seconds: float = 0.0      # updated rows picked out
    h2d_bytes: int = 0              # host arrays handed to the device
    d2h_bytes: int = 0              # device arrays fetched to the host
    edges_real: int = 0             # real edges of the processed tiles
    edges_padded: int = 0           # their padded edge slots
    tiles_resident: int = 0         # processed tiles held on the device

    @property
    def io_hidden_seconds(self) -> float:
        """I/O busy time overlapped behind compute instead of stalling it.
        ~0 for the serial engine by construction."""
        return max(self.io_busy_seconds - self.stall_seconds, 0.0)


@dataclasses.dataclass
class RunResult:
    """Final vertex values [V(, Q)] + aux arrays + per-superstep history of
    one engine run."""
    values: np.ndarray
    aux: dict
    history: list[SuperstepStats]
    supersteps: int
    converged: bool
    # multi-query runs: supersteps each query column took to converge
    # (index = global query id; -1 if it hit max_supersteps); None for 1-D
    per_query_supersteps: Optional[np.ndarray] = None

    def total_seconds(self) -> float:
        """Wall-clock sum over all supersteps."""
        return sum(h.seconds for h in self.history)

    def _steady_state(self, skip_first: bool) -> list[SuperstepStats]:
        """History minus the warm-up superstep — unless that would leave
        nothing to average (single-superstep runs fall back to the full
        history, an empty history to the empty list, never an empty slice
        fed to a mean/division)."""
        hs = self.history[1:] if skip_first else self.history
        return hs if hs else self.history

    def mean_superstep_seconds(self, skip_first: bool = True) -> float:
        """Steady-state mean seconds per superstep (see ``_steady_state``)."""
        hs = self._steady_state(skip_first)
        return float(np.mean([h.seconds for h in hs])) if hs else 0.0

    def disk_stall_fraction(self, skip_first: bool = True) -> float:
        """Fraction of wall time the compute loop was blocked on tile I/O."""
        hs = self._steady_state(skip_first)
        tot = sum(h.seconds for h in hs)
        return sum(h.stall_seconds for h in hs) / tot if tot > 0 else 0.0


#: device bytes of one padded edge slot of a stacked tile: int32 source,
#: int32 local destination, float32 edge value
STACK_SLOT_BYTES = 12


def resident_vertex_bytes(nv: int, row_cap: int, edge_cap: int,
                          values: np.ndarray, aux: dict) -> int:
    """Device bytes a resident superstep holds besides its stacks, for a
    session's vertex arrays ``values`` ([V] or [V, Q]) and ``aux``: the
    values and aux on the device (twice over, as a superstep's put overlaps
    the last one's arrays), the scan's padded values, aux, outputs and
    update flags, its [V] results, and one tile's gathered sources and
    contributions."""
    row = values.nbytes // nv                  # one vertex's values
    flags = row // values.dtype.itemsize       # its update flags
    aux_row = sum(a.nbytes for a in aux.values()) // nv
    return (2 * nv * (row + aux_row)
            + (nv + row_cap + 1) * (2 * row + aux_row + flags)
            + nv * (row + flags)
            + edge_cap * (2 * row + aux_row))


def select_engine_mode(cfg: EngineConfig, edge_cap: int,
                       share_tiles: list[int], vertex_bytes: int,
                       steal: bool = False) -> str:
    """The execution mode of a session: ``"tiled"`` whenever the vertex
    state is out of core, else the mode ``cfg.engine_mode`` names, with
    ``"auto"`` resolved from what the engine can observe.  It is
    ``"stacked"`` (every tile resident on the device, one scan per server
    per superstep) when each executed server's ``share_tiles[i]`` padded
    tiles and the superstep's ``vertex_bytes`` (``resident_vertex_bytes``)
    take at most ``cfg.device_budget_bytes`` and no tiles are stolen, else
    ``"tiled"``.  Stores over the budget never go partly resident.
    Stealing moves tiles between servers, so it refuses the modes that
    pin tiles to a device."""
    if cfg.vertex_memory_budget is not None:
        return "tiled"
    if steal and cfg.engine_mode not in ("tiled", "auto"):
        raise ValueError("tile stealing requires engine_mode 'tiled' or "
                         "'auto' (stacked/merged pin tiles to devices)")
    if cfg.engine_mode != "auto":
        return cfg.engine_mode
    per_tile = edge_cap * STACK_SLOT_BYTES
    fits = all(n * per_tile + vertex_bytes <= cfg.device_budget_bytes
               for n in share_tiles)
    return "stacked" if fits and not steal else "tiled"


class OutOfCoreEngine:
    """The out-of-core superstep engine (see module docstring).

    One instance either emulates all ``cfg.num_servers`` servers in-process
    (the classic mode) or — with ``cfg.server_rank`` set and a
    ``distributed.ClusterExchange`` passed as ``exchange`` — acts as one
    real server of a multi-process cluster, merging peer updates at the
    BSP barrier through the exchange (DESIGN.md §11).  Results are
    bit-identical either way: tiles own disjoint dst rows, the per-tile
    math is the same jitted gather/apply, and update value bytes
    round-trip the wire exactly."""

    def __init__(self, store: TileStore, config: EngineConfig = EngineConfig(),
                 exchange=None):
        self.store = store
        self.cfg = config
        self.exchange = exchange
        self.plan = store.load_plan()
        self.in_degree, self.out_degree = store.load_degrees()
        P, N = self.plan.num_tiles, config.num_servers
        # cluster mode: this process executes exactly one server's share
        if config.server_rank is not None:
            if not 0 <= config.server_rank < N:
                raise ValueError(
                    f"server_rank {config.server_rank} outside 0..{N - 1}")
            self.exec_servers = [config.server_rank]
        else:
            self.exec_servers = list(range(N))
        if exchange is not None and len(self.exec_servers) != 1:
            raise ValueError(
                "a ClusterExchange needs exactly one executed server per "
                "process — set cfg.server_rank (or num_servers=1)")
        self._adopt_assignment(
            assign_tiles_balanced(self.plan.edges_per_tile, N)
            if config.balanced_assignment else assign_tiles(P, N))

        # --- checkpointing + fault injection (DESIGN.md §12) ---
        #: per-process arm of cfg.fault_plan (None = no injection)
        self.fault = (config.fault_plan.injector(rank=config.server_rank)
                      if config.fault_plan is not None else None)
        #: the run's GraphCheckpointer (None = checkpointing disabled)
        self.ckpt: Optional[GraphCheckpointer] = None
        self._guard: Optional[PreemptionGuard] = None
        self.configure_checkpoint(config.checkpoint_dir)

        # Per-server edge caches (paper: idle memory on each server);
        # only the servers this process executes get one.
        if config.cache_mode == "auto":
            # Working set per server ~ share of total on-disk tile bytes.
            total = sum(store.tile_disk_bytes(t) for t in range(P))
            mode = auto_select_mode(total // max(N, 1), config.cache_capacity_bytes)
        else:
            mode = int(config.cache_mode)
        self.cache_mode = mode
        self.caches = {
            s: EdgeCache(store, config.cache_capacity_bytes, mode,
                         policy=config.cache_policy,
                         promote_hits=config.cache_promote_hits)
            for s in self.exec_servers
        }
        self._filters: Optional[list] = None  # built during first superstep
        self._merged_fn = None
        # fused-kernel autotuning (DESIGN.md §14): memoized KernelChoice per
        # (combine, Q); ``kernel_choice`` holds the last resolved pick for
        # stats/CLI reporting
        self._kernel_choices: dict = {}
        self.kernel_choice = None
        self._streamed: dict[int, list[int]] = {s: [] for s in self.exec_servers}
        #: populated when cfg.debug_skip_log: one dict per (superstep, server)
        #: with the active source ids and the run/skipped tile partition
        self.skip_log: list[dict] = []
        self._wire_ratio: Optional[float] = None
        # Per-superstep deltas are computed against these cumulative-counter
        # baselines; run() re-baselines them at its start (a stale baseline
        # from a previous run / external cache activity would corrupt the
        # first superstep's deltas).
        self._io_busy_cum = 0.0   # cache io_seconds at end of last superstep
        self._promo_cum = 0       # cache promotions at end of last superstep

    # ------------------------------------------------------------------
    def kernel_plan(self, prog) -> tuple[str, Optional[tuple], int]:
        """Resolve ``(seg_impl, blocks, stack_size)`` for this program.

        With ``cfg.kernel_autotune`` the roofline cost model
        (roofline/kernel_tune.py) picks the Pallas ``(BE, BR)`` blocks and
        the pipelined stack size per ``(combine, Q, tile shape)`` —
        memoized, so the dry-run model runs once per program family — and
        ``seg_impl="jnp"`` is promoted to the fused kernel path.  An
        explicit ``cfg.kernel_blocks`` wins over the autotuner; without
        either, the kernels' static defaults apply (blocks=None).
        """
        cfg = self.cfg
        seg_impl = cfg.seg_impl
        if cfg.kernel_autotune and seg_impl == "jnp":
            seg_impl = "pallas_fused"
        stack_k = max(1, cfg.stack_size)
        if cfg.kernel_blocks is not None:
            return seg_impl, tuple(cfg.kernel_blocks), stack_k
        if not cfg.kernel_autotune:
            return seg_impl, None, stack_k
        q = int(getattr(prog, "num_queries", 1) or 1)
        key = (prog.combine, q)
        if key not in self._kernel_choices:
            from repro.roofline import kernel_tune

            self._kernel_choices[key] = kernel_tune.pick_blocks(
                prog.combine, q, self.plan.edge_cap, self.plan.row_cap)
        choice = self._kernel_choices[key]
        self.kernel_choice = choice
        return seg_impl, choice.blocks, choice.stack_size
        self._demo_cum = 0
        self._disk_cum = 0        # cache disk_bytes_read at last superstep
        # --- out-of-core vertex state (DESIGN.md §10) ---
        self._ooc = False
        #: the run's interval-sharded VertexStateStore (ooc mode only)
        self.vstate: Optional[VertexStateStore] = None
        self._iv_splitter: Optional[np.ndarray] = None
        self._iv_t2i: Optional[np.ndarray] = None
        self._use_meta_fp = False
        self._tile_iv_ids: dict[int, frozenset] = {}
        self._vs_faults_cum = 0
        self._vs_load_cum = 0
        self._vs_spill_cum = 0

    # ------------------------------------------------------------------
    # superstep checkpointing + crash-consistent resume (DESIGN.md §12)
    # ------------------------------------------------------------------
    def configure_checkpoint(self, directory: Optional[str]) -> None:
        """(Re)point the engine at a checkpoint directory — called from
        ``__init__`` and per program by the cluster server (multi-program
        launches use per-program subdirectories).

        With ``cfg.resume`` and an existing checkpoint, adopts the saved
        per-server tile assignment *now* (engine construction order needs
        the assignment before the ClusterExchange exists): verbatim when
        the saved server count matches ``cfg.num_servers``, else remapped
        through ``elastic.remap_assignment`` — the mid-run N->M elastic
        resize.  All ranks derive the identical assignment from the same
        replicated manifest."""
        if directory is None:
            self.ckpt = None
            return
        self.ckpt = GraphCheckpointer(directory, keep=self.cfg.checkpoint_keep,
                                      fault=self.fault)
        if not self.cfg.resume:
            return
        peek = self.ckpt.peek_manifest()
        if peek is None:
            return
        saved = peek[1].get("assignment")
        if not saved:
            return
        n = self.cfg.num_servers
        if len(saved) == n:
            self._adopt_assignment([list(map(int, a)) for a in saved])
        else:
            self._adopt_assignment(remap_assignment(
                [list(map(int, a)) for a in saved], n,
                self.plan.edges_per_tile))

    def _adopt_assignment(self, assignment: list[list[int]]) -> None:
        """Take ``assignment`` as the per-server tile shares and drop
        resident stacks built for other shares."""
        self.assignment = assignment
        #: per-server device-resident tiles, built at the first dense
        #: superstep of a stacked or merged session
        self._stacks: Optional[dict] = None

    def resolve_mode(self, values: np.ndarray, aux: dict) -> str:
        """The execution mode of a session over the vertex arrays
        ``values`` ([V] or [V, Q]) and ``aux`` ([V] or [V, Q] each)
        (``select_engine_mode``)."""
        plan = self.plan
        return select_engine_mode(
            self.cfg, plan.edge_cap,
            [len(self.assignment[s]) for s in self.exec_servers],
            resident_vertex_bytes(plan.num_vertices, plan.row_cap,
                                  plan.edge_cap, values, aux),
            steal=getattr(self.exchange, "steal", False))

    def _save_final(self, values, aux_np, per_query_ss, converged,
                    supersteps: int) -> None:
        """Publish the run's result as a ``final`` checkpoint (step =
        supersteps + 1, strictly after every boundary save, so LATEST
        lands on it): a supervised restart then skips this program
        entirely instead of recomputing it."""
        manifest = dict(
            superstep=int(supersteps),
            final=True,
            converged=bool(converged),
            supersteps=int(supersteps),
            multi_q=per_query_ss is not None,
            num_servers=int(self.cfg.num_servers),
            assignment=[[int(t) for t in a] for a in self.assignment],
        )
        state: dict = {"values": values, "aux": aux_np}
        if per_query_ss is not None:
            state["per_query_ss"] = per_query_ss
        self.ckpt.save_graph(int(supersteps) + 1, state, manifest)

    @staticmethod
    def _result_from_final(loaded) -> RunResult:
        """RunResult reconstructed from a ``final`` checkpoint (resumed
        after the run already completed; history is gone — only the
        answers and convergence metadata persist)."""
        m, st = loaded.manifest, loaded.state
        pq = (np.asarray(st["per_query_ss"]) if "per_query_ss" in st
              else None)
        return RunResult(
            values=np.asarray(st["values"]),
            aux={k: np.asarray(v) for k, v in st.get("aux", {}).items()},
            history=[], supersteps=int(m.get("supersteps", m["superstep"])),
            converged=bool(m.get("converged", False)),
            per_query_supersteps=pq)

    # ------------------------------------------------------------------
    @staticmethod
    def _split_updates(rows, new, upd):
        """Per-tile (or per-server) update extraction, shape-polymorphic.

        rows [R] global vertex ids; new/upd [R] or [R, Qa].  Returns
        (vertex ids with any update, their value rows, per-query mask rows
        or None for 1-D runs)."""
        if upd.ndim == 2:
            vmask = upd.any(axis=1)
            return rows[vmask], new[vmask], upd[vmask]
        return rows[upd], new[upd], None

    def open_session(self, prog: VertexProgram, *,
                     q_slots: Optional[int] = None,
                     max_supersteps: Optional[int] = None) -> "EngineSession":
        """Open a step-driven session over ``prog`` (DESIGN.md §13).

        The session owns all per-run state; one ``session.step()`` call
        executes exactly one superstep, and between barriers the caller
        may ``admit()`` fresh queries into retired ``[V, Q]`` slots or
        ``drain()`` live ones.  ``q_slots`` caps the live query columns
        (default: the program's initial batch width); admissions beyond
        it queue until retirement frees a slot.  At most one ooc-vstate
        session may be live per engine at a time (sessions share the
        engine's edge caches, skip filters and interval bookkeeping)."""
        return EngineSession(self, prog, q_slots=q_slots,
                             max_supersteps=max_supersteps)

    def run(self, prog: VertexProgram,
            max_supersteps: Optional[int] = None) -> RunResult:
        """Run ``prog`` to convergence (no updated cells cluster-wide) or
        ``max_supersteps``.  Bit-identical across engine modes, cache
        policies, pipelining, ooc vertex state, cluster execution, and
        crash/resume (DESIGN.md §12: resuming a checkpoint replays the
        remaining supersteps to byte-identical values).

        A thin wrapper over ``open_session``: steps one EngineSession to
        completion (honoring ``cfg.admit_plan`` scripted admissions along
        the way) and returns its result — so batch callers and the online
        serving path (serve/graph_service.py) share one superstep loop.

        With ``cfg.preemptible`` + checkpointing, SIGTERM/SIGINT during
        the run latch a flag; at the next BSP barrier the engine saves a
        checkpoint and raises ``runtime.ft.Preempted``.  The prior signal
        handlers are always restored, even on exceptions."""
        guard = None
        if self.cfg.preemptible and self.ckpt is not None:
            guard = PreemptionGuard().install()
        self._guard = guard
        session = None
        try:
            session = self.open_session(prog, max_supersteps=max_supersteps)
            while not session.finished:
                session.step()
            return session.result()
        finally:
            if session is not None:
                session.close()
            if guard is not None:
                guard.restore()
            self._guard = None

    # ------------------------------------------------------------------
    def _measure_broadcast(self, si, sv, sm, nv, qa, dtype, background=False):
        """Build one server's broadcast payload and measure its wire size —
        inline (returns a BroadcastRecord) or on the comm executor
        (returns a Future resolving to one).  ``sm`` is the per-query
        updated mask for multi-query runs ([len(si), qa]) or None; the 2-D
        payload then covers only the ``qa`` still-active query columns.

        Ooc-vstate mode ships per-dirty-interval sections instead of one
        whole-V payload (DESIGN.md §10) — built straight from the sparse
        update lists, so no [V, Q]-sized buffer is ever densified."""
        cfg = self.cfg
        if self._ooc:
            plan = (comm.plan_broadcast_intervals_async if background
                    else comm.plan_broadcast_intervals)
            return plan(si, sv, sm, self._iv_splitter,
                        threshold=cfg.comm_threshold,
                        compressor=cfg.comm_compressor,
                        mode=cfg.comm_mode)
        if sm is not None:
            upd_mask = np.zeros((nv, qa), dtype=bool)
            upd_mask[si] = sm
        else:
            upd_mask = np.zeros(nv, dtype=bool)
            upd_mask[si] = True
        plan = comm.plan_broadcast_async if background else comm.plan_broadcast
        return plan(
            _densify(sv, si, nv, qa if sm is not None else None, dtype),
            upd_mask,
            threshold=cfg.comm_threshold,
            compressor=cfg.comm_compressor,
            mode=cfg.comm_mode,
        )

    # ------------------------------------------------------------------
    # pipelined path (cfg.pipeline): prefetch thread + batched dispatch
    # ------------------------------------------------------------------
    def _run_tiles_pipelined(self, s, tids, prog, values_dev, aux_dev,
                             filters, nv, tally):
        """Overlapped tile processing for one server (DESIGN.md §7).

        A background thread reads + decompresses up to ``prefetch_depth``
        tiles ahead through the server's EdgeCache while the consumer
        stacks ``stack_size`` tiles and dispatches them as one jitted
        ``run_tile_stack`` call.  The consumer's queue-wait (its
        ``graphh.tile.load`` span in ``tally``) is the disk stall the
        pipeline failed to hide.

        Returns ([indices], [values], [query masks], compute_s) with
        results identical to the serial per-tile loop: tiles own disjoint
        row ranges and the per-tile math is the same jitted gather/apply.
        The query-mask list is empty for 1-D runs.
        """
        from repro.core.distributed import pad_stack_to
        from repro.core.tiles import stack_tiles

        cfg = self.cfg
        if not tids:
            return [], [], [], 0.0
        if self._ooc:
            # ooc-vstate: the prefetcher still overlaps edge-tile reads with
            # compute, but tiles dispatch one at a time through the sharded
            # step (stacking would need the full [V] array on device)
            return self._run_tiles_pipelined_ooc(s, tids, prog, filters, nv,
                                                 tally)
        row_cap = self.plan.row_cap
        seg_impl, kblocks, stack_k = self.kernel_plan(prog)
        comp_s = 0.0
        masked_acc = upd_acc = None
        batch: list = []

        def flush():
            nonlocal comp_s, masked_acc, upd_acc, batch
            t0 = time.perf_counter()
            with tally.dispatch:
                stk = stack_tiles(batch, row_cap)
                if len(batch) < stack_k:
                    stk = pad_stack_to(stk, stack_k)  # one compiled shape
                tally.sent(*(stk[k] for k in ("src", "dst_local", "val",
                                              "row_start", "num_rows")))
                new_masked, upd = run_tile_stack(
                    prog, values_dev, aux_dev, stk, row_cap, seg_impl,
                    kblocks)
                if masked_acc is None:
                    masked_acc, upd_acc = new_masked, upd
                else:  # disjoint row ranges: set-where-updated is exact
                    masked_acc = jnp.where(upd, new_masked, masked_acc)
                    upd_acc = jnp.logical_or(upd_acc, upd)
            comp_s += time.perf_counter() - t0
            batch = []

        it = self.store.prefetch_iter(tids, depth=cfg.prefetch_depth,
                                      cache=self.caches[s],
                                      workers=cfg.prefetch_workers)
        try:
            while True:
                with tally.load:
                    try:
                        tid, tile = next(it)
                    except StopIteration:
                        break
                    if filters is not None and filters[tid] is None:
                        filters[tid] = self._make_filter(tile, nv)
                tally.tiles(tile.meta.num_edges, tile.meta.edge_cap)
                batch.append(tile)
                if len(batch) == stack_k:
                    flush()
            if batch:
                flush()
        finally:
            it.close()

        t0 = time.perf_counter()
        with tally.fetch:
            masked, upd = tally.to_host(masked_acc, upd_acc)
        with tally.split:
            si, sv, sm = self._split_updates(
                np.arange(values_dev.shape[0]), masked, upd)
        comp_s += time.perf_counter() - t0
        return [si], [sv], [] if sm is None else [sm], comp_s

    # ------------------------------------------------------------------
    # stacked fast path (engine_mode="stacked"): device-resident tiles
    # ------------------------------------------------------------------
    def _build_stacks(self, nv: int, tally: obs.Tally) -> None:
        """Build the per-server device-resident tile stacks for
        ``engine_mode="stacked"`` — up to ``device_budget_bytes`` of tiles
        per server live on device; the rest stream per superstep.  The
        bytes moved count in ``tally``."""
        from repro.core.tiles import stack_tiles

        budget = self.cfg.device_budget_bytes
        per_tile = self.plan.edge_cap * STACK_SLOT_BYTES
        self._stacks = {}
        for s in self.exec_servers:
            fit = max(1, budget // per_tile)
            resident = self.assignment[s][:fit]
            self._streamed[s] = self.assignment[s][fit:]
            tiles = [self.caches[s].get(t) for t in resident]
            stk = stack_tiles(tiles, self.plan.row_cap)
            keys = ("src", "dst_local", "val", "row_start", "num_rows")
            tally.sent(*(stk[k] for k in keys))
            self._stacks[s] = {k: jnp.asarray(stk[k]) for k in keys}

    def _build_merged(self, nv: int, tally: obs.Tally) -> None:
        """engine_mode="merged" (§Perf It5): per-server fused edge lists,
        the bytes moved counted in ``tally``."""
        self._stacks = {}
        for s in self.exec_servers:
            self._streamed[s] = []
            srcs, dsts, vals = [], [], []
            owned = np.zeros(nv + 1, dtype=bool)
            for tid in self.assignment[s]:
                t = self.caches[s].get(tid)
                n = t.meta.num_edges
                srcs.append(t.src[:n])
                dsts.append(t.dst_local[:n].astype(np.int64) + t.meta.row_start)
                from repro.core.tiles import tile_edge_values
                vals.append(tile_edge_values(t)[:n])
                owned[t.meta.row_start: t.meta.row_end] = True
            host = dict(src=np.concatenate(srcs).astype(np.int32),
                        dst=np.concatenate(dsts).astype(np.int32),
                        val=np.concatenate(vals), owned=owned[:nv])
            tally.sent(*host.values())
            self._stacks[s] = {k: jnp.asarray(v) for k, v in host.items()}

    def _merged_step(self, prog, values_dev, aux_dev, m):
        from repro.core.gab import merged_server_step

        if self._merged_fn is None:
            from functools import partial

            @partial(jax.jit, static_argnums=(0, 1, 2))
            def fn(p, seg_impl, blocks, values, aux, src, dst, val, owned):
                return merged_server_step(p, values, aux, src, dst, val,
                                          owned, seg_impl, blocks)

            self._merged_fn = fn
        seg_impl, kblocks, _ = self.kernel_plan(prog)
        return self._merged_fn(prog, seg_impl, kblocks, values_dev, aux_dev,
                               m["src"], m["dst"], m["val"], m["owned"])

    def _stack_step(self, prog, values_dev, aux_dev, stack):
        """One server's superstep over its resident stack: one device
        program (``gab._jit_run_tile_stack``) scanning every tile."""
        seg_impl, kblocks, _ = self.kernel_plan(prog)
        return run_tile_stack(prog, values_dev, aux_dev, stack,
                              self.plan.row_cap, seg_impl, kblocks)

    # ------------------------------------------------------------------
    def _make_filter(self, tile, nv):
        srcs = tile.source_ids()
        if self.cfg.skip_filter == "bitmap":
            f = SourceBlockBitmap(nv, self.cfg.block_shift)
        else:
            f = BloomFilter(num_bits=self.cfg.bloom_bits)
        f.add(srcs)
        return f

    def _order_cache_first(self, s: int, tids: list[int]) -> list[int]:
        """Cache-hit-first scheduling: resident tiles run immediately while
        the prefetcher pulls the misses from disk.  Stable within each
        class, and order never changes results (tiles own disjoint rows)."""
        cache = self.caches[s]
        resident = {t for t in tids if cache.contains(t)}
        if not resident or len(resident) == len(tids):
            return list(tids)
        return ([t for t in tids if t in resident]
                + [t for t in tids if t not in resident])

    # ------------------------------------------------------------------
    # out-of-core vertex state (DESIGN.md §10)
    # ------------------------------------------------------------------
    def _build_vstate(self, values: np.ndarray,
                      aux_np: dict) -> VertexStateStore:
        """Shard the freshly initialized [V(, Q)] arrays into an
        interval-sharded store under ``cfg.vertex_memory_budget``."""
        cfg = self.cfg
        stored = self.store.load_interval_plan()
        if cfg.num_intervals:
            k = cfg.num_intervals
        else:
            # auto: size intervals so ~4 blocks of the full per-vertex
            # state fit the budget — gather always has headroom to hold
            # the dst block plus several source blocks hot
            total = values.nbytes + sum(a.nbytes for a in aux_np.values())
            k = max(2, int(np.ceil(total / max(cfg.vertex_memory_budget / 4,
                                               1))))
        if stored is not None and (cfg.num_intervals == 0
                                   or stored.num_intervals == cfg.num_intervals):
            iv = stored   # honor the preprocessed plan: footprint metadata
        else:             # in the tile store refers to *its* boundaries
            iv = plan_intervals(self.plan.splitter, k)
        self._use_meta_fp = (stored is not None
                             and np.array_equal(iv.splitter, stored.splitter))
        self._iv_splitter = iv.splitter
        self._iv_t2i = iv.tile_to_interval
        self._tile_iv_ids = {}
        spill_dir = tempfile.mkdtemp(prefix="_vstate_", dir=self.store.root)
        vstore = VertexStateStore(iv.splitter, cfg.vertex_memory_budget,
                                  spill_dir)
        self.vstate = vstore
        vstore.add_array("value", values)
        for name, arr in aux_np.items():
            vstore.add_array(name, arr)
        return vstore

    def _tile_footprint(self, tile):
        """(interval ids, cumulative edge ptr, bucket-sort permutation) for
        one tile — from the tile's recorded metadata when the store was
        preprocessed with this interval plan, else computed on the fly."""
        m = tile.meta
        if (self._use_meta_fp and m.src_intervals is not None
                and tile.iv_perm is not None):
            ids, ptr, perm = m.src_intervals, m.src_interval_ptr, tile.iv_perm
        else:
            ids, ptr, perm = compute_source_footprint(
                tile.src, m.num_edges, self._iv_splitter)
        # remember the joint footprint (src intervals + dst interval) for
        # the co-scheduler; tiny (a frozenset of ints per tile)
        self._tile_iv_ids[m.tile_id] = (
            frozenset(ids) | {int(self._iv_t2i[m.tile_id])})
        return ids, ptr, perm

    def _ooc_tile_dispatch(self, prog, tile, nv, tally):
        """One tile's Gather+Apply against the interval-sharded vertex
        state: materialize per-edge source inputs interval by interval,
        slice the dst rows from the tile's own interval block, dispatch the
        jitted sharded step (the host arrays it hands over count in
        ``tally``).  Returns (rows [row_cap] host ids, new, updated) like
        ``gab.run_tile`` — valid rows bit-identical to the in-memory path
        (see gab.tile_gather_apply_sharded)."""
        vstore = self.vstate
        m = tile.meta
        row_cap = self.plan.row_cap
        ids, ptr, perm = self._tile_footprint(tile)
        names = ("value",) + tuple(prog.src_aux)
        bufs = {}
        for name in names:
            dt, tail = vstore.spec(name)
            bufs[name] = np.zeros((m.edge_cap,) + tail, dt)
        src = tile.src
        for j, iv in enumerate(ids):
            sl = perm[ptr[j]: ptr[j + 1]]
            lo, _hi = vstore.interval_range(int(iv))
            local = src[sl] - lo
            for name in names:
                bufs[name][sl] = vstore.get_block(name, int(iv))[local]
        ivd = int(self._iv_t2i[m.tile_id])
        lo_d, _hi_d = vstore.interval_range(ivd)
        r0, r1 = m.row_start - lo_d, m.row_end - lo_d
        vdt, vtail = vstore.spec("value")
        old = np.zeros((row_cap,) + vtail, vdt)
        old[: m.num_rows] = vstore.get_block("value", ivd)[r0:r1]
        dst_aux = {}
        for name in prog.dst_aux:
            dt, tail = vstore.spec(name)
            buf = np.zeros((row_cap,) + tail, dt)
            buf[: m.num_rows] = vstore.get_block(name, ivd)[r0:r1]
            dst_aux[name] = buf
        seg_impl, kblocks, _ = self.kernel_plan(prog)
        edge_val = tile_edge_values(tile)
        tally.sent(*bufs.values(), edge_val, tile.dst_local, old,
                   *dst_aux.values(), scalars=1)
        new, upd = run_tile_sharded(
            prog, bufs["value"], {k: bufs[k] for k in prog.src_aux},
            edge_val, tile.dst_local, old, dst_aux,
            m.num_rows, row_cap, seg_impl, kblocks)
        rows = np.minimum(m.row_start + np.arange(row_cap), nv - 1)
        return rows, new, upd

    def _ooc_column(self, vstore: VertexStateStore, c: int) -> np.ndarray:
        """Assemble one query column of the sharded value array."""
        return np.concatenate(
            [vstore.get_block("value", k)[:, c]
             for k in range(vstore.num_intervals)])

    def _run_tiles_pipelined_ooc(self, s, tids, prog, filters, nv, tally):
        cfg = self.cfg
        comp_s = 0.0
        s_idx: list = []
        s_val: list = []
        s_msk: list = []
        it = self.store.prefetch_iter(tids, depth=cfg.prefetch_depth,
                                      cache=self.caches[s],
                                      workers=cfg.prefetch_workers)
        try:
            while True:
                with tally.load:
                    try:
                        tid, tile = next(it)
                    except StopIteration:
                        break
                    if filters is not None and filters[tid] is None:
                        filters[tid] = self._make_filter(tile, nv)
                tally.tiles(tile.meta.num_edges, tile.meta.edge_cap)
                t0 = time.perf_counter()
                with tally.dispatch:
                    rows, new, upd = self._ooc_tile_dispatch(prog, tile, nv,
                                                             tally)
                with tally.fetch:
                    rows, new, upd = tally.to_host(rows, new, upd)
                with tally.split:
                    ri, rv, rm = self._split_updates(rows, new, upd)
                    s_idx.append(ri)
                    s_val.append(rv)
                    if rm is not None:
                        s_msk.append(rm)
                comp_s += time.perf_counter() - t0
        finally:
            it.close()
        return s_idx, s_val, s_msk, comp_s

    def _order_joint_residency(self, s: int, tids: list[int]) -> list[int]:
        """Interval-aware co-scheduling (DESIGN.md §10): greedily pick the
        tile whose joint footprint (source intervals + dst interval)
        overlaps most with a simulated LRU set of hot vertex intervals,
        breaking ties toward edge-cache-resident tiles — maximizing joint
        residency of the edge cache and the vertex-state hot tier.  Order
        never changes results (disjoint rows, BSP barrier).  Falls back to
        cache-hit-first while footprints are unknown (superstep 0)."""
        fps = self._tile_iv_ids
        if any(t not in fps for t in tids):
            return self._order_cache_first(s, tids)
        if len(tids) > 256:
            # the greedy below is O(T^2); past a few hundred tiles its
            # Python cost rivals the tile compute, and its behaviour on
            # locality-structured inputs is a contiguous sweep starting
            # from the hot end anyway — compute that sweep directly
            return self._order_interval_sweep(tids)
        cache = self.caches[s]
        cap = max(1, self.vstate.hot_block_capacity("value"))
        sim: OrderedDict[int, None] = OrderedDict(
            (k, None) for k in sorted(self.vstate.hot_intervals("value")))
        edge_res = {t for t in tids if cache.contains(t)}
        ivd = {t: int(self._iv_t2i[t]) for t in tids}
        last: Optional[int] = None
        remaining = list(tids)
        order: list[int] = []
        while remaining:
            best, best_score = None, None
            for t in remaining:
                # hot-source-interval overlap first; then stay near the
                # previous pick's dst interval so the walk sweeps
                # contiguously instead of thrashing on overlap ties (a
                # contiguous sweep is what keeps the fault count at
                # ~K - cap per pass); edge-cache residency breaks what
                # remains — ranked below the sweep because letting
                # scattered resident edge tiles pull the walk around
                # costs more vertex faults than it saves edge decodes
                score = (len(fps[t] & sim.keys()),
                         -abs(ivd[t] - last) if last is not None else 0,
                         t in edge_res)
                if best_score is None or score > best_score:
                    best, best_score = t, score
            order.append(best)
            remaining.remove(best)
            last = ivd[best]
            for ivk in sorted(fps[best]):
                sim.pop(ivk, None)
                sim[ivk] = None
            while len(sim) > cap:
                sim.popitem(last=False)
        return order

    def _order_interval_sweep(self, tids: list[int]) -> list[int]:
        """O(T log T) large-fleet fallback for the co-scheduler: sort tiles
        by dst interval and run the sweep toward the end *away* from the
        currently-hot intervals, so the walk starts where residency is and
        alternating supersteps sweep boustrophedon instead of rewinding to
        vertex 0 against the LRU."""
        hot = self.vstate.hot_intervals("value")
        order = sorted(tids, key=lambda t: int(self._iv_t2i[t]))
        if not hot:
            return order
        mid = (self._iv_t2i[order[0]] + self._iv_t2i[order[-1]]) / 2.0
        if np.mean(sorted(hot)) > mid:   # hot mass sits at the high end
            order.reverse()              # -> start there, sweep downward
        return order

    def _agg_cache_stats(self) -> dict:
        """Aggregate hit/miss/tier/io counters over the edge caches this
        process executes (all servers classically; one in cluster mode)."""
        caches = list(self.caches.values())
        hits = sum(c.stats.hits for c in caches)
        misses = sum(c.stats.misses for c in caches)
        tiers: dict[str, dict] = {}
        for c in caches:
            for name, d in c.tier_snapshot().items():
                agg = tiers.setdefault(name, dict(tiles=0, bytes=0, hits=0))
                agg["tiles"] += d.get("tiles", 0)
                agg["bytes"] += d.get("bytes", 0)
                agg["hits"] += d.get("hits", 0)
        return dict(
            hit_ratio=hits / max(hits + misses, 1),
            disk_bytes_read=sum(c.stats.disk_bytes_read for c in caches),
            io_seconds=sum(c.stats.disk_seconds + c.stats.decompress_seconds
                           + c.stats.retier_seconds for c in caches),
            promotions=sum(c.stats.promotions for c in caches),
            demotions=sum(c.stats.demotions for c in caches),
            tiers=tiers,
        )


def _densify(vals: np.ndarray, idx: np.ndarray, nv: int,
             nq: Optional[int], dtype) -> np.ndarray:
    out = np.zeros((nv, nq) if nq is not None else nv, dtype=dtype)
    out[idx] = vals
    return out


class EngineSession:
    """Step-driven run state over one :class:`OutOfCoreEngine` (DESIGN.md
    §13).

    One ``step()`` call executes exactly one superstep — compute, BSP
    barrier, update apply, query retirement — and between barriers the
    session accepts **mid-run query admission**: ``admit(seeds)`` queues
    fresh queries that are spliced into retired ``[V, Q]`` columns at the
    next barrier (the inverse of retirement's column compaction), and
    ``drain(qids)`` force-retires live columns.  ``run()`` is a thin loop
    over a session, so batch runs and the online serving path
    (serve/graph_service.py) share this superstep implementation.

    State machine: OPEN --step()*--> FINISHED --result()--> closed.  A
    session is FINISHED when it converged with no admission backlog, or
    hit ``max_supersteps``.  ``result()`` finalizes (flushes live columns,
    closes the ooc spill tier, publishes the final checkpoint) and returns
    the same :class:`RunResult` the monolithic loop used to.

    Admission protocol (all execution modes apply it at the same point in
    the barrier, so results stay bit-identical across them):

    1. natural retirement — columns with zero updated cells freeze into
       the result buffer and compact out;
    2. drains — force-frozen columns (``per_query_supersteps`` stays -1);
    3. admissions — fresh columns splice into ``values``/per-query aux/
       ``active_q`` with state built by ``prog.with_queries(seeds).init``,
       and the next superstep runs **all** tiles (``_force_full``) so skip
       filters and interval dirty tracking see the new column as all-dirty
       for one superstep (filters have no false negatives, so forcing a
       full pass is always safe).

    Cluster mode: rank 0 collects the admission/drain record *before* the
    exchange and ships it in its update frame (``transport.encode_frame``
    ``control=``); every rank — including rank 0 — then applies the record
    it reads back from ``ExchangeResult.control``, so all ranks splice
    identically.  Peers follow deterministically and must not ``admit()``
    themselves.

    Thread-safety: ``admit()``/``drain()`` may be called from any thread
    (the service's submit path); ``step()``/``result()`` must be called
    from one driver thread.
    """

    #: lock discipline, enforced by tools/analyze.py --check locks
    #: (admission/drain queues are filled by the serving thread while the
    #: driver thread splices them at the barrier)
    _guarded_by = {"_admit_queue": "_lock", "_drain_queue": "_lock",
                   "next_qid": "_lock"}

    def __init__(self, engine: OutOfCoreEngine, prog: VertexProgram, *,
                 q_slots: Optional[int] = None,
                 max_supersteps: Optional[int] = None):
        self.eng = engine
        self.prog = prog
        cfg = engine.cfg
        nv = self.nv = engine.plan.num_vertices
        self._lock = threading.Lock()
        self._admit_queue: list[tuple[int, int]] = []
        self._drain_queue: list[int] = []
        self._force_full = False
        self._final_result: Optional[RunResult] = None
        self._closed = False
        self.history: list[SuperstepStats] = []
        self.converged = False
        self.finished = False
        self.vstore: Optional[VertexStateStore] = None
        self._ooc = False

        # Re-baseline the engine's cumulative-counter deltas: a second
        # session on the same engine — or cache activity between sessions
        # (warm()/maintain()/direct get()s) — must not leak into this
        # session's first superstep.
        cs = engine._agg_cache_stats()
        engine._io_busy_cum = cs["io_seconds"]
        engine._promo_cum = cs["promotions"]
        engine._demo_cum = cs["demotions"]
        engine._disk_cum = cs["disk_bytes_read"]

        state = prog.init(nv, engine.out_degree.astype(np.float64),
                          engine.in_degree.astype(np.float64))
        self.values = np.asarray(state.pop("value"))
        self.aux_np = {k: np.asarray(v) for k, v in state.items()}
        self.vdtype = self.values.dtype

        # --- multi-query bookkeeping (DESIGN.md §9) ---
        # values [V, Q]: Q program instances share every tile visit.  A
        # query column that produces zero updates in a superstep has
        # reached its fixpoint; it is *retired* — its column is written to
        # the result buffer and compacted out so later supersteps no
        # longer pay for it.  The freed slot is what admission refills.
        self.multi_q = self.values.ndim == 2
        self.nq_total = self.values.shape[1] if self.multi_q else 1
        self.active_q = np.arange(self.nq_total)  # global ids, live columns
        self.final_values = self.values.copy() if self.multi_q else None
        self.per_query_ss = (np.full(self.nq_total, -1, dtype=np.int64)
                             if self.multi_q else None)
        #: superstep each column's compute began at (0 for initial
        #: queries) — per_query_ss is convergence superstep RELATIVE to
        #: this, so an admitted query reports the same count as a fresh run
        self.admitted_at = (np.zeros(self.nq_total, dtype=np.int64)
                            if self.multi_q else None)
        #: {global qid: seed vertex} lineage for every column ever admitted
        self.query_seeds: dict[int, int] = {
            int(i): int(s)
            for i, s in enumerate(getattr(prog, "queries", ()))}
        self.next_qid = self.nq_total if self.multi_q else 1
        self.q_slots = (max(1, int(q_slots)) if q_slots is not None
                        else max(1, self.nq_total))
        self._plan_pending: list[tuple[int, tuple]] = (
            [(int(after), tuple(int(s) for s in seeds))
             for after, seeds in (cfg.admit_plan or ())]
            if self.multi_q else [])

        # --- crash-consistent resume (DESIGN.md §12): overwrite the fresh
        # init with the latest checkpoint's state and continue from its
        # superstep boundary.  A "final" checkpoint short-circuits: the
        # run already completed — the session opens FINISHED with its
        # stored result (supervised restarts skip finished programs).
        self.start_ss = 0
        loaded = None
        if engine.ckpt is not None and cfg.resume:
            loaded = engine.ckpt.load_graph()
        if loaded is not None and loaded.manifest.get("final"):
            self._final_result = engine._result_from_final(loaded)
            self.converged = self._final_result.converged
            self.finished = True
            return
        if loaded is not None:
            m, st = loaded.manifest, loaded.state
            self.start_ss = int(m["superstep"])
            if loaded.vstate:
                self.values = np.asarray(loaded.vstate["value"])
                self.aux_np = {k: np.asarray(v)
                               for k, v in loaded.vstate.items()
                               if k != "value"}
            else:
                self.values = np.asarray(st["values"])
                self.aux_np = {k: np.asarray(v)
                               for k, v in st.get("aux", {}).items()}
            if self.multi_q:
                self.active_q = np.asarray(m["active_q"], dtype=np.int64)
                self.final_values = np.asarray(st["final_values"])
                self.per_query_ss = np.asarray(st["per_query_ss"], np.int64)
                self.nq_total = len(self.per_query_ss)
                self.admitted_at = (
                    np.asarray(st["admitted_at"], np.int64)
                    if "admitted_at" in st
                    else np.zeros(self.nq_total, dtype=np.int64))
                self.next_qid = int(m.get("next_qid", self.nq_total))
                saved_seeds = {int(g): int(s)
                               for g, s in m.get("queries", {}).items()}
                if saved_seeds:
                    self.query_seeds = saved_seeds
                # plan entries that fired before the boundary are already
                # in the restored state — replay only the future ones
                self._plan_pending = [e for e in self._plan_pending
                                      if e[0] >= self.start_ss]

        # --- out-of-core vertex state (DESIGN.md §10): with a vertex
        # memory budget the [V(, Q)] arrays move into an interval-sharded
        # VertexStateStore and the full arrays are dropped.  stacked/
        # merged need the full value array on device, so ooc runs tiled.
        self._ooc = engine._ooc = cfg.vertex_memory_budget is not None
        #: the resolved execution mode: "tiled", "stacked" or "merged"
        self.engine_mode = engine.resolve_mode(self.values, self.aux_np)
        if self._ooc:
            self.vstore = engine._build_vstate(self.values, self.aux_np)
            engine._vs_faults_cum = self.vstore.stats.faults
            engine._vs_load_cum = self.vstore.stats.load_bytes
            engine._vs_spill_cum = self.vstore.stats.spill_bytes
            self.values = None
            self.aux_np = {}
            self.aux_dev = None
        else:
            self.aux_dev = {k: jnp.asarray(v) for k, v in self.aux_np.items()}

        self.max_ss = max_supersteps or cfg.max_supersteps
        self.updated_ids = np.arange(nv)  # everything "updated" pre step 0
        if loaded is not None:
            # the skip pre-pass keys off the last superstep's update set —
            # part of the boundary state (filters are rebuilt lazily; they
            # have no false negatives, so a missing filter only costs work)
            self.updated_ids = np.asarray(loaded.state["updated_ids"],
                                          np.int64)
        self.building_filters = cfg.tile_skipping
        self.filters: list = ([None] * engine.plan.num_tiles
                              if self.building_filters else [])

    # -- public session surface --------------------------------------------
    @property
    def superstep(self) -> int:
        """Index of the next superstep ``step()`` will execute."""
        return self._ss if hasattr(self, "_ss") else self.start_ss

    @property
    def active_queries(self) -> tuple[int, ...]:
        """Global qids of the currently live query columns."""
        return tuple(int(g) for g in self.active_q) if self.multi_q else ()

    @property
    def free_slots(self) -> int:
        """Query slots available for admission right now."""
        if not self.multi_q:
            return 0
        with self._lock:
            queued = len(self._admit_queue)
        return max(0, self.q_slots - len(self.active_q) - queued)

    def admit(self, seeds) -> list[int]:
        """Queue fresh queries (seed vertices) for admission at the next
        barrier; returns their global qids.  Thread-safe.  Queries beyond
        the free ``q_slots`` stay queued until retirement frees slots.
        Cluster mode: rank 0 only (peers follow the control record)."""
        if not self.multi_q:
            raise RuntimeError("admission needs a batched [V, Q] program")
        if (self.eng.exchange is not None
                and getattr(self.eng.exchange, "rank", 0) != 0):
            raise RuntimeError("cluster admissions originate at rank 0 — "
                               "peers splice from the control record")
        if self.finished:
            raise RuntimeError("session is finished")
        with self._lock:
            gqs = []
            for s in seeds:
                g = self.next_qid
                self.next_qid += 1
                self._admit_queue.append((g, int(s)))
                gqs.append(g)
        return gqs

    def drain(self, qids) -> None:
        """Force-retire live columns at the next barrier: their partial
        values freeze into the result buffer and ``per_query_supersteps``
        stays -1 (deadline misses in the serving path).  Thread-safe."""
        with self._lock:
            self._drain_queue.extend(int(g) for g in qids)

    def query_result(self, gq: int) -> np.ndarray:
        """The frozen [V] column of query ``gq`` — valid once it retired
        (or drained); before that it holds the admission-time state."""
        return np.asarray(self.final_values[:, int(gq)]).copy()

    def query_supersteps(self, gq: int) -> int:
        """Supersteps query ``gq`` took to converge, counted from its own
        admission (== a fresh single-query run's count); -1 while live or
        if it was drained."""
        return int(self.per_query_ss[int(gq)])

    def checkpoint(self) -> None:
        """Save a resumable boundary checkpoint of the session right now
        (manifest carries the per-slot query lineage, so a serving session
        resumes with renumbering and accounting intact)."""
        if self.eng.ckpt is None:
            raise RuntimeError("engine has no checkpoint directory")
        self._save_boundary(self.superstep - 1)

    def close(self) -> None:
        """Release per-run scratch (the ooc spill tier).  Idempotent;
        ``result()`` already closed the store on the normal path."""
        if self._closed:
            return
        self._closed = True
        if self.vstore is not None and self._final_result is None:
            self.vstore.close()

    # -- the superstep ------------------------------------------------------
    def step(self) -> SuperstepStats:
        """Execute exactly one superstep (compute → barrier → apply →
        retirement → drains → admissions) and return its stats.  Raises
        ``runtime.ft.Preempted`` after a preemption checkpoint when the
        engine's guard latched a signal.  The whole superstep is the span
        ``graphh.superstep`` (core/obs.py), keyed by its index."""
        if self.finished:
            raise RuntimeError("session is finished — open a new one")
        with obs.span(obs.SUPERSTEP, superstep=self.superstep):
            return self._step()

    def _step(self) -> SuperstepStats:
        eng = self.eng
        cfg = eng.cfg
        prog = self.prog
        nv = self.nv
        ooc = self._ooc
        multi_q = self.multi_q
        vstore = self.vstore
        vdtype = self.vdtype
        row_cap = eng.plan.row_cap
        filters = self.filters
        building_filters = self.building_filters
        ss = self._ss = getattr(self, "_ss", self.start_ss)

        if eng.fault is not None:
            eng.fault.check("superstep", ss)
        t_start = time.perf_counter()
        qa = len(self.active_q) if multi_q else 1  # live columns this step
        # a batched session with zero live columns still steps (waiting on
        # scheduled/queued admissions): no compute, but the barrier — and
        # in cluster mode the exchange carrying the control record — runs
        run_compute = not (multi_q and qa == 0)
        tally = obs.Tally()
        values_dev = None
        if run_compute and not ooc:
            with obs.span(obs.VALUES_PUT):
                tally.sent(self.values)
                values_dev = jnp.asarray(self.values)
        comp_s = 0.0
        stall_s = 0.0
        tiles_done = 0
        tiles_skipped = 0
        upd_idx_parts: list[np.ndarray] = []
        upd_val_parts: list[np.ndarray] = []
        upd_msk_parts: list[np.ndarray] = []
        per_server_updates: list[tuple] = []
        bcast_futures: dict[int, object] = {}
        # ooc-vstate always measures: the sampled estimator models a
        # whole-V payload (global density switch, no interval headers),
        # which would mix incompatible models with the per-interval
        # records the sampled supersteps learn their ratio from
        sample = ooc or not (cfg.comm_accounting == "sampled"
                             and ss % 4 != 0
                             and eng._wire_ratio is not None)

        # a column admitted at the previous barrier must be treated as
        # all-dirty for one superstep: run every tile once (filters have
        # no false negatives, so a full pass can only do extra work,
        # never change results), then fall back to skip filters
        force_full = self._force_full
        self._force_full = False
        skip_on = (
            cfg.tile_skipping
            and ss > 0
            and not force_full
            and len(self.updated_ids) < cfg.skip_density_threshold * nv
            and eng._filters is not None
        )
        active_words = None
        if skip_on and cfg.skip_filter == "bitmap":
            active_words = SourceBlockBitmap.active_words_from_ids(
                self.updated_ids, nv, cfg.block_shift
            )

        for s in (eng.exec_servers if run_compute else ()):
            s_idx: list[np.ndarray] = []
            s_val: list[np.ndarray] = []
            s_msk: list[np.ndarray] = []
            server_tiles = eng.assignment[s]
            if self.engine_mode in ("stacked", "merged") and not skip_on:
                if eng._stacks is None:
                    with tally.load:
                        if self.engine_mode == "merged":
                            eng._build_merged(nv, tally)
                        else:
                            eng._build_stacks(nv, tally)
                        if building_filters:
                            for st in eng.exec_servers:
                                n_res = (len(eng.assignment[st])
                                         - len(eng._streamed[st]))
                                for tid in eng.assignment[st][:n_res]:
                                    if filters[tid] is None:
                                        filters[tid] = eng._make_filter(
                                            eng.caches[st].get(tid), nv)
                resident = eng.assignment[s][:len(eng.assignment[s])
                                             - len(eng._streamed[s])]
                real = int(eng.plan.edges_per_tile[resident].sum())
                # merged lists hold only real edges; stacks pad each tile
                tally.tiles(real, real if self.engine_mode == "merged"
                            else len(resident) * eng.plan.edge_cap,
                            resident=len(resident))
                t0 = time.perf_counter()
                with tally.dispatch:
                    step_fn = (eng._merged_step
                               if self.engine_mode == "merged"
                               else eng._stack_step)
                    new_masked, upd = step_fn(prog, values_dev, self.aux_dev,
                                              eng._stacks[s])
                with tally.fetch:
                    new_masked, upd = tally.to_host(new_masked, upd)
                with tally.split:
                    si, sv, sm = eng._split_updates(np.arange(nv),
                                                    new_masked, upd)
                    s_idx.append(si)
                    s_val.append(sv.astype(vdtype))
                    if sm is not None:
                        s_msk.append(sm)
                comp_s += time.perf_counter() - t0
                tiles_done += len(resident)
                server_tiles = eng._streamed[s]

            # Tile-skipping pre-pass: the filter set is fixed for the
            # whole superstep, so the survivor list can be computed up
            # front (and handed to the prefetcher in pipelined mode).
            if skip_on:
                run_list = []
                with obs.span(obs.SKIP):
                    for tid in server_tiles:
                        f = eng._filters[tid]
                        # a stolen tile may not have a filter yet on this
                        # server (cluster mode) — run it, never skip blind
                        hit = f is None or (
                            f.intersects(active_words)
                            if cfg.skip_filter == "bitmap"
                            else f.might_contain_any(self.updated_ids)
                        )
                        if hit:
                            run_list.append(tid)
                        else:
                            tiles_skipped += 1
                if cfg.debug_skip_log:
                    eng.skip_log.append(dict(
                        superstep=ss, server=s,
                        active=np.asarray(self.updated_ids).copy(),
                        run=list(run_list),
                        skipped=[t for t in server_tiles
                                 if t not in run_list]))
            else:
                run_list = list(server_tiles)

            if ooc and cfg.interval_aware_order and len(run_list) > 1:
                run_list = eng._order_joint_residency(s, run_list)
            elif cfg.cache_aware_order and len(run_list) > 1:
                run_list = eng._order_cache_first(s, run_list)

            # every wait for a tile blocks compute: serially the whole
            # load, pipelined what the prefetcher failed to hide
            loaded_before = tally.load.seconds
            if cfg.pipeline:
                p_idx, p_val, p_msk, cp = eng._run_tiles_pipelined(
                    s, run_list, prog, values_dev, self.aux_dev,
                    filters if building_filters else None, nv, tally)
                s_idx += p_idx
                s_val += p_val
                s_msk += p_msk
                comp_s += cp
                tiles_done += len(run_list)
            else:
                for tid in run_list:
                    with tally.load:
                        tile = eng.caches[s].get(tid)
                        if building_filters and filters[tid] is None:
                            filters[tid] = eng._make_filter(tile, nv)
                    tally.tiles(tile.meta.num_edges, tile.meta.edge_cap)

                    t0 = time.perf_counter()
                    with tally.dispatch:
                        if ooc:
                            rows, new, upd = eng._ooc_tile_dispatch(
                                prog, tile, nv, tally)
                        else:
                            seg_impl, kblocks, _ = eng.kernel_plan(prog)
                            edge_val = tile_edge_values(tile)
                            tally.sent(tile.src, tile.dst_local, edge_val,
                                       scalars=2)
                            rows, new, upd = run_tile(
                                prog, values_dev, self.aux_dev,
                                (tile.src, tile.dst_local, edge_val),
                                tile.meta.row_start, tile.meta.num_rows,
                                row_cap, seg_impl, kblocks,
                            )
                    with tally.fetch:
                        rows, new, upd = tally.to_host(rows, new, upd)
                    with tally.split:
                        ri, rv, rm = eng._split_updates(rows, new, upd)
                        s_idx.append(ri)
                        s_val.append(rv)
                        if rm is not None:
                            s_msk.append(rm)
                    comp_s += time.perf_counter() - t0
                    tiles_done += 1
            stall_s += tally.load.seconds - loaded_before
            si = np.concatenate(s_idx) if s_idx else np.zeros(0, np.int64)
            val_shape = (0, qa) if multi_q else (0,)
            sv = (np.concatenate(s_val) if s_val
                  else np.zeros(val_shape, vdtype))
            sm = None
            if multi_q:
                sm = (np.concatenate(s_msk) if s_msk
                      else np.zeros(val_shape, dtype=bool))
            per_server_updates.append((si, sv, sm))
            upd_idx_parts.append(si)
            upd_val_parts.append(sv)
            if multi_q:
                upd_msk_parts.append(sm)
            if cfg.pipeline and sample and eng.exchange is None:
                # overlap this server's payload compression with the next
                # server's compute; records collected at the barrier below
                # (cluster mode measures from the real transport instead)
                bcast_futures[s] = eng._measure_broadcast(
                    si, sv, sm, nv, qa, vdtype, background=True)
        if not run_compute:
            for _ in eng.exec_servers:
                per_server_updates.append((np.zeros(0, np.int64),
                                           np.zeros((0, qa), vdtype),
                                           np.zeros((0, qa), dtype=bool)))

        own_tiles = [t for s in eng.exec_servers
                     for t in eng.assignment[s]]
        if building_filters and all(filters[t] is not None
                                    for t in own_tiles):
            eng._filters = filters
            self.building_filters = False

        with obs.span(obs.BARRIER):
            # --- Broadcast (BSP barrier): measure payloads, apply updates ---
            if eng.fault is not None:
                eng.fault.check("barrier", ss)
            raw_b = wire_b = 0
            control = None
            if eng.exchange is not None:
                # cluster mode (DESIGN.md §11): ship this server's updates
                # through the real transport, merge every peer's frame — the
                # exchange IS the global barrier, and the byte counts are
                # measured from the frames that actually travelled.  Rank 0
                # collects the admission/drain record pre-exchange (it must
                # ride its frame); every rank applies the record it reads
                # back below, after natural retirement.
                if eng.exchange.rank == 0:
                    control = self._collect_control(
                        ss, qa, set(self.active_queries), set())
                si, sv, sm = per_server_updates[0]
                xr = eng.exchange.exchange(
                    idx=si, vals=sv, mask=sm, nv=nv,
                    splitter=eng._iv_splitter if ooc else None,
                    compute_seconds=comp_s, control=control)
                control = xr.control
                all_idx, all_val, all_msk = xr.idx, xr.vals, xr.mask
                raw_b, wire_b = xr.raw_bytes, xr.wire_bytes
                if xr.assignment is not None:
                    # cross-server tile stealing: every server derived the
                    # same new ownership from the same replicated timings
                    eng._adopt_assignment([list(a) for a in xr.assignment])
            else:
                with obs.span(obs.BARRIER_MEASURE):
                    for k, s in enumerate(eng.exec_servers):
                        if not run_compute:
                            break
                        si, sv, sm = per_server_updates[k]
                        if sample:
                            if s in bcast_futures:
                                rec = bcast_futures[s].result()
                            else:
                                rec = eng._measure_broadcast(
                                    si, sv, sm, nv, qa, vdtype)
                            raw_b += rec.raw_bytes
                            wire_b += rec.wire_bytes
                        else:
                            pairs = (int(sm.sum()) if sm is not None
                                     else len(si))
                            n_eff = nv * qa
                            est = comm.wire_bytes_estimate(
                                n_eff, pairs / max(n_eff, 1),
                                # 2-D sparse payloads pack (vertex,
                                # query) u32 pairs
                                index_bytes=8 if sm is not None else 4)
                            raw_b += est
                            wire_b += int(est * eng._wire_ratio)
                if sample and raw_b:
                    eng._wire_ratio = wire_b / raw_b
                all_idx = (np.concatenate(upd_idx_parts) if upd_idx_parts
                           else np.zeros(0, np.int64))
                all_val = (np.concatenate(upd_val_parts) if upd_val_parts
                           else np.zeros((0, qa) if multi_q else (0,), vdtype))
                all_msk = None
                if multi_q:
                    all_msk = (np.concatenate(upd_msk_parts) if upd_msk_parts
                               else np.zeros((0, qa), dtype=bool))
            if multi_q:
                upd_per_q = all_msk.sum(axis=0)
                updated_pairs = int(all_msk.sum())
            else:
                upd_per_q = None
                updated_pairs = int(len(all_idx))
            with obs.span(obs.BARRIER_APPLY):
                dirty_ivs = 0
                if ooc:
                    # dirty-interval writeback (DESIGN.md §10): load only the
                    # interval blocks that received updates, apply in place,
                    # write back dirty — clean intervals are never touched.
                    if len(all_idx):
                        ivs = vstore.interval_of(all_idx)
                        for iv in np.unique(ivs):
                            ksel = ivs == iv
                            lo, _hi = vstore.interval_range(int(iv))
                            blk = vstore.get_block("value", int(iv)).copy()
                            loc = all_idx[ksel] - lo
                            if multi_q:
                                # per-cell application: a row touched by
                                # query A must not clobber query B's
                                # untouched column
                                cur = blk[loc]
                                msk = all_msk[ksel]
                                cur[msk] = all_val[ksel][msk]
                                blk[loc] = cur
                            else:
                                blk[loc] = all_val[ksel]
                            vstore.write_block("value", int(iv), blk)
                            dirty_ivs += 1
                elif multi_q:
                    # per-cell application: a row touched by query A must
                    # not clobber query B's column with a masked zero /
                    # sub-tol value
                    cur = self.values[all_idx]
                    cur[all_msk] = all_val[all_msk]
                    self.values[all_idx] = cur
                else:
                    self.values[all_idx] = all_val
                self.updated_ids = all_idx

            with obs.span(obs.BARRIER_CACHE):
                # Re-tier at the barrier: off the tile hot path, after this
                # superstep's access pattern has updated the per-tile counters.
                if cfg.cache_policy != "lru":
                    for c in eng.caches.values():
                        c.maintain()

                cache_stats = eng._agg_cache_stats()
                io_busy = cache_stats["io_seconds"] - eng._io_busy_cum
                eng._io_busy_cum = cache_stats["io_seconds"]
                promo = cache_stats["promotions"] - eng._promo_cum
                demo = cache_stats["demotions"] - eng._demo_cum
                eng._promo_cum = cache_stats["promotions"]
                eng._demo_cum = cache_stats["demotions"]
                # the cache counter is cumulative over the run; the stat is the
                # per-superstep delta (like io_busy/promotions above)
                disk_b = cache_stats["disk_bytes_read"] - eng._disk_cum
                eng._disk_cum = cache_stats["disk_bytes_read"]
                vs_faults = vs_load = vs_spill = 0
                if ooc:
                    vst = vstore.stats
                    vs_faults = vst.faults - eng._vs_faults_cum
                    vs_load = vst.load_bytes - eng._vs_load_cum
                    vs_spill = vst.spill_bytes - eng._vs_spill_cum
                    eng._vs_faults_cum = vst.faults
                    eng._vs_load_cum = vst.load_bytes
                    eng._vs_spill_cum = vst.spill_bytes

            # --- barrier bookkeeping: natural retirement → drains →
            # admissions (the same order in every execution mode — see
            # class docstring).
            retired: tuple = ()
            drained: tuple = ()
            admitted: tuple = ()
            upd_map: dict = {}
            ctl_pending = 0
            if multi_q:
                upd_map = {int(g): int(n)
                           for g, n in zip(self.active_q, upd_per_q)}
                done = np.nonzero(upd_per_q == 0)[0]
                retired = tuple(int(self.active_q[c]) for c in done)
                if eng.exchange is None:
                    # classic mode collects post-retirement: a slot freed at
                    # this barrier refills at this same barrier
                    control = self._collect_control(
                        ss, qa - len(done), set(self.active_queries),
                        set(retired))
                ctl_admit, ctl_drain, ctl_pending = comm.unpack_admissions(
                    control)
                drained = tuple(g for g in ctl_drain
                                if g in set(self.active_queries)
                                and g not in set(retired))
                freeze = sorted(set(int(c) for c in done)
                                | {int(np.nonzero(self.active_q == g)[0][0])
                                   for g in drained})
                if freeze:
                    keep = np.ones(qa, dtype=bool)
                    keep[freeze] = False
                    done_set = set(int(c) for c in done)
                    if ooc:
                        for c in freeze:
                            gq = int(self.active_q[c])
                            self.final_values[:, gq] = eng._ooc_column(
                                vstore, c)
                            if c in done_set:
                                self.per_query_ss[gq] = (
                                    ss + 1 - int(self.admitted_at[gq]))
                        q_names = [n for n in vstore.names()
                                   if vstore.spec(n)[1] == (qa,)]
                        vstore.compact_columns(q_names, keep)
                    else:
                        for c in freeze:
                            gq = int(self.active_q[c])
                            self.final_values[:, gq] = self.values[:, c]
                            if c in done_set:
                                self.per_query_ss[gq] = (
                                    ss + 1 - int(self.admitted_at[gq]))
                        self.values = np.ascontiguousarray(
                            self.values[:, keep])
                        for k in list(self.aux_np):
                            a = self.aux_np[k]
                            if a.ndim == 2 and a.shape[1] == qa:  # per-query
                                self.aux_np[k] = np.ascontiguousarray(
                                    a[:, keep])
                                tally.sent(self.aux_np[k])
                                self.aux_dev[k] = jnp.asarray(self.aux_np[k])
                    self.active_q = self.active_q[keep]
                if ctl_admit:
                    self._apply_admissions(ctl_admit, ss, tally)
                    admitted = tuple(int(g) for g, _ in ctl_admit)
                    self._force_full = True
            # every rank drops the plan entries that fired at this barrier
            # (peers never fire them, but must agree the backlog shrank)
            self._plan_pending = [e for e in self._plan_pending if e[0] > ss]

        stats = SuperstepStats(
            superstep=ss,
            seconds=time.perf_counter() - t_start,
            compute_seconds=comp_s,
            updated_vertices=int(len(all_idx)),
            density=float(len(all_idx)) / max(nv, 1),
            tiles_processed=tiles_done,
            tiles_skipped=tiles_skipped,
            raw_bytes=raw_b,
            wire_bytes=wire_b,
            network_bytes=wire_b * max(cfg.num_servers - 1, 0),
            cache_hit_ratio=cache_stats["hit_ratio"],
            disk_bytes_read=disk_b,
            stall_seconds=stall_s,
            io_busy_seconds=io_busy,
            cache_promotions=promo,
            cache_demotions=demo,
            cache_tiers=cache_stats["tiers"],
            active_queries=qa,
            updated_pairs=updated_pairs,
            updated_per_query=upd_map,
            retired_queries=retired,
            admitted_queries=admitted,
            drained_queries=drained,
            vstate_faults=vs_faults,
            vstate_load_bytes=vs_load,
            vstate_spill_bytes=vs_spill,
            vstate_dirty_intervals=dirty_ivs,
            **tally.stats(),
        )
        self.history.append(stats)
        self.converged = (len(self.active_q) == 0 if multi_q
                          else len(all_idx) == 0)
        self._ss = ss + 1
        with self._lock:
            backlog = (bool(self._plan_pending) or ctl_pending > 0
                       or bool(self._admit_queue))
        self.finished = ((self.converged and not backlog)
                         or self._ss >= self.max_ss)

        # --- superstep-boundary checkpoint + preemption (DESIGN.md §12)
        # Written AFTER update apply + retirement + admission — this
        # boundary's state is exactly what superstep ss+1 starts from.
        # State is fully replicated, so rank 0 is the single periodic
        # writer; a preempted rank may also save (collision-safe publish).
        if eng.ckpt is not None and not self.finished:
            due = (cfg.checkpoint_every > 0
                   and (ss + 1) % cfg.checkpoint_every == 0
                   and cfg.server_rank in (None, 0))
            preempt = eng._guard is not None and eng._guard.triggered
            if due or preempt:
                self._save_boundary(ss)
            if preempt:
                if ooc:
                    vstore.close()
                raise Preempted(ss + 1)
        return stats

    # -- result / epilogue ---------------------------------------------------
    def result(self) -> RunResult:
        """Finalize the session and return its RunResult (same contract as
        the pre-session monolithic ``run()``): flush still-live columns,
        materialize + close the ooc store, publish the final checkpoint."""
        if self._final_result is not None:
            return self._final_result
        if not self.finished:
            raise RuntimeError(
                "session still live — step() to completion or drain first")
        eng = self.eng
        ooc, vstore = self._ooc, self.vstore
        values, aux_np = self.values, self.aux_np
        if self.multi_q:
            # flush columns still live at max_supersteps into the result
            for c, gq in enumerate(self.active_q):
                self.final_values[:, int(gq)] = (
                    eng._ooc_column(vstore, c) if ooc else values[:, c])
            values = self.final_values
        elif ooc:
            values = vstore.materialize("value")
        if ooc:
            # the result materializes the final arrays; the working state
            # and its disk spill tier are per-run scratch
            aux_np = {n: vstore.materialize(n) for n in vstore.names()
                      if n != "value"}
            vstore.close()
        # supersteps counts GLOBALLY (resume continues the numbering, so a
        # resumed run reports the same count as the uninterrupted one even
        # though its history holds only the post-resume entries)
        supersteps = self.start_ss + len(self.history)
        if eng.ckpt is not None and eng.cfg.server_rank in (None, 0):
            eng._save_final(values, aux_np, self.per_query_ss,
                            self.converged, supersteps)
        self._final_result = RunResult(
            values=values, aux=aux_np, history=self.history,
            supersteps=supersteps, converged=self.converged,
            per_query_supersteps=self.per_query_ss)
        return self._final_result

    # -- admission internals -------------------------------------------------
    def _collect_control(self, ss: int, live_base: int, active_set: set,
                         retired_set: set) -> Optional[dict]:
        """Assemble this barrier's admission/drain control record (rank 0
        / classic engine only).  ``live_base`` is the column count that
        survives this barrier's natural retirement (cluster mode passes
        the conservative pre-retirement count — a slot freed at the same
        barrier refills one barrier later there); scheduled ``admit_plan``
        entries fire first and bypass the slot cap, then queued live
        admissions fill the remaining free slots."""
        if not self.multi_q:
            return None
        with self._lock:
            drains: list[int] = []
            for g in self._drain_queue:
                if g not in drains:
                    drains.append(g)
            self._drain_queue.clear()
            live_drains = [g for g in drains
                           if g in active_set and g not in retired_set]
            admit: list[tuple[int, int]] = []
            for after, seeds in self._plan_pending:
                if after == ss:
                    for s in seeds:
                        admit.append((self.next_qid, int(s)))
                        self.next_qid += 1
            free = self.q_slots - (live_base - len(live_drains))
            while self._admit_queue and free > 0:
                admit.append(self._admit_queue.pop(0))
                free -= 1
            return comm.pack_admissions(admit, drains,
                                        len(self._admit_queue))

    def _apply_admissions(self, admit: list, ss: int,
                          tally: obs.Tally) -> None:
        """Splice freshly admitted query columns into the live state — the
        inverse of retirement's compaction.  Initial column state comes
        from ``prog.with_queries(seeds).init`` (column math is independent
        of batch context, so the spliced column is bit-identical to a
        fresh single-query run); per-query aux arrays ([V, q_new]) splice
        alongside, shared aux is untouched.  Deterministic given the
        control record, so every cluster rank converges to identical
        state.  Aux arrays sent to the device count in ``tally``."""
        eng = self.eng
        nv = self.nv
        gqs = [int(g) for g, _ in admit]
        seeds = [int(s) for _, s in admit]
        sub = self.prog.with_queries(seeds)
        state = sub.init(nv, eng.out_degree.astype(np.float64),
                         eng.in_degree.astype(np.float64))
        new_vals = np.asarray(state.pop("value")).astype(self.vdtype)
        qn = len(gqs)
        per_q_aux = {k: np.asarray(v) for k, v in state.items()
                     if np.asarray(v).ndim == 2
                     and np.asarray(v).shape[1] == qn}
        hi = max(gqs) + 1
        if hi > len(self.per_query_ss):
            grow = hi - len(self.per_query_ss)
            self.per_query_ss = np.concatenate(
                [self.per_query_ss, np.full(grow, -1, np.int64)])
            self.admitted_at = np.concatenate(
                [self.admitted_at, np.zeros(grow, np.int64)])
            self.final_values = np.ascontiguousarray(np.concatenate(
                [self.final_values,
                 np.zeros((nv, grow), self.final_values.dtype)], axis=1))
        for g, s in zip(gqs, seeds):
            self.admitted_at[g] = ss + 1
            self.query_seeds[g] = s
        self.final_values[:, gqs] = new_vals
        self.nq_total = len(self.per_query_ss)
        # peers renumber from the control record (rank 0 assigned at
        # collect time); max() keeps both sides monotonic — under the lock,
        # since the serving thread's admit() bumps the counter concurrently
        with self._lock:
            self.next_qid = max(self.next_qid, hi)
        if self._ooc:
            self.vstore.append_columns({"value": new_vals, **per_q_aux})
        else:
            self.values = np.ascontiguousarray(
                np.concatenate([self.values, new_vals], axis=1))
            for k, arr in per_q_aux.items():
                self.aux_np[k] = np.ascontiguousarray(
                    np.concatenate([self.aux_np[k], arr], axis=1))
                tally.sent(self.aux_np[k])
                self.aux_dev[k] = jnp.asarray(self.aux_np[k])
        self.active_q = np.concatenate(
            [self.active_q, np.asarray(gqs, dtype=self.active_q.dtype)])

    # -- checkpoint ----------------------------------------------------------
    def _save_boundary(self, ss: int) -> None:
        """Write the superstep-``ss+1`` boundary checkpoint: manifest
        (resume point, live queries + per-slot lineage, replicated
        assignment) + state leaves; ooc runs flush vertex state as
        interval blocks instead of leaves (dirty blocks only — clean ones
        hardlink, see core.checkpoint)."""
        eng, cfg = self.eng, self.eng.cfg
        with self._lock:
            next_qid = int(self.next_qid)
        manifest = dict(
            superstep=ss + 1,
            final=False,
            converged=False,
            multi_q=bool(self.multi_q),
            nq_total=int(self.nq_total),
            num_servers=int(cfg.num_servers),
            assignment=[[int(t) for t in a] for a in eng.assignment],
            active_q=([int(g) for g in self.active_q]
                      if self.multi_q else None),
            next_qid=next_qid,
            queries={str(g): int(s) for g, s in self.query_seeds.items()},
        )
        state: dict = {"updated_ids": np.asarray(self.updated_ids,
                                                 np.int64)}
        if self.multi_q:
            state["final_values"] = self.final_values
            state["per_query_ss"] = self.per_query_ss
            state["admitted_at"] = self.admitted_at
        if self.vstore is None:
            state["values"] = self.values
            state["aux"] = self.aux_np
        eng.ckpt.save_graph(ss + 1, state, manifest, vstore=self.vstore)
