"""GAB (Gather-Apply-Broadcast) computation model (paper §III-C).

A vertex-centric program supplies:
  * ``init``     — initial vertex value array + auxiliary per-vertex arrays
  * ``gather``   — per-edge contribution f(src_value, edge_value, aux_src)
  * ``combine``  — the reduction monoid over contributions ("sum"/"min"/"max")
  * ``apply``    — new_value g(old_value, accumulator, aux_dst)

The engine runs supersteps: every server holds a replica of *all* vertex
values (All-in-All policy), processes its assigned tiles one at a time
(Gather+Apply are purely local), and Broadcasts only *updated* values.

This module contains the jit-friendly single-tile and stacked-tile step
functions; orchestration lives in engine.py (out-of-core) and
distributed.py (shard_map).

Multi-query axis (DESIGN.md §9): vertex values may be ``[V]`` (classic,
one program instance) or ``[V, Q]`` (Q program instances evaluated in the
same tile visit — personalized PageRank seeds, multi-source BFS, landmark
distances).  Every step function here is shape-polymorphic over that
trailing query axis; per-vertex aux arrays may likewise be ``[V]``
(shared across queries) or ``[V, Q]`` (per-query, e.g. PPR seed mass).
One edge pass then serves Q queries: the dominant out-of-core I/O cost is
paid once and the Pallas one-hot contraction becomes a real GEMM.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_COMBINE_IDENTITY = {
    "sum": 0.0,
    "min": jnp.inf,
    "max": -jnp.inf,
}


def segment_reduce(
    data: Array,
    segment_ids: Array,
    num_segments: int,
    combine: str,
    impl: str = "jnp",
    sorted_ids: bool = True,
    blocks: Optional[tuple[int, int]] = None,
) -> Array:
    """Reduce ``data`` into ``num_segments`` buckets with the given monoid.

    ``data`` may be ``[E]`` or ``[E, Q]`` (multi-query); segments always
    run along axis 0.

    impl="jnp" uses XLA scatter-reduce; impl="pallas_onehot" routes through
    the Pallas block kernels (see kernels/gab_gather.py): the sum monoid
    becomes an MXU one-hot contraction, min/max a masked VPU reduction —
    ``blocks`` overrides the static ``(BE, BR)`` kernel block sizes (the
    roofline autotuner's choice, see roofline/kernel_tune.py).
    Tile edges are CSR-sorted by dst (build_tile invariant), so
    ``sorted_ids=True`` by default — XLA's sorted-scatter path (§Perf It4).
    """
    if impl == "pallas_onehot":
        from repro.kernels import ops as _kops

        fn = {"sum": _kops.segment_sum, "min": _kops.segment_min,
              "max": _kops.segment_max}.get(combine)
        if fn is None:
            raise ValueError(f"unknown combine: {combine}")
        if blocks is not None:
            return fn(data, segment_ids, num_segments,
                      block_e=blocks[0], block_r=blocks[1])
        return fn(data, segment_ids, num_segments)
    kw = dict(num_segments=num_segments, indices_are_sorted=sorted_ids)
    if combine == "sum":
        return jax.ops.segment_sum(data, segment_ids, **kw)
    if combine == "min":
        return jax.ops.segment_min(data, segment_ids, **kw)
    if combine == "max":
        return jax.ops.segment_max(data, segment_ids, **kw)
    raise ValueError(f"unknown combine: {combine}")


@dataclasses.dataclass(eq=False)  # identity hash: instances are jit static args
class VertexProgram:
    """Base class for GAB vertex programs.  Subclasses override the four
    hooks below; all jnp code must be jit-compatible.

    Batched (multi-query) programs override ``num_queries`` (> 1) and
    return a ``[V, Q]`` ``value`` from :meth:`init`; their hooks then see
    ``[E, Q]`` / ``[R, Q]`` arrays and must broadcast 1-D shared aux
    explicitly (e.g. ``aux[k][:, None]``).  Per-query aux arrays are
    ``[V, Q]`` and are column-compacted alongside values when queries
    retire (engine.py)."""

    combine: str = "sum"
    #: names of auxiliary per-vertex arrays gathered at the *source* side
    src_aux: tuple[str, ...] = ()
    #: names of auxiliary per-vertex arrays consumed by apply at the dst side
    dst_aux: tuple[str, ...] = ()
    #: tolerance used to decide whether a value "changed" (paper: broadcast
    #: only updated values); exact (0.0) for discrete programs.
    update_tol: float = 0.0

    # number of query instances batched into one edge pass; values are
    # [V, num_queries] when > 1 (plain class attr, not a dataclass field —
    # batched subclasses override it with a property derived from seeds)
    num_queries = 1

    # -- hooks ------------------------------------------------------------
    def init(self, num_vertices: int, out_degree: np.ndarray,
             in_degree: np.ndarray, **kw) -> dict[str, np.ndarray]:
        """Return {"value": ..., <aux name>: ...} — value ``[V(, Q)]``,
        aux arrays ``[V]``, given out/in degrees ``[V]``."""
        raise NotImplementedError

    def gather(self, src_value: Array, edge_val: Array,
               aux: dict[str, Array]) -> Array:
        """Per-edge message: f(src values [E(, Q)], edge values [E], src aux)."""
        raise NotImplementedError

    def apply(self, old_value: Array, accum: Array,
              aux: dict[str, Array]) -> Array:
        """New dst values g(old [R(, Q)], accumulated messages, dst aux)."""
        raise NotImplementedError

    # -- derived ----------------------------------------------------------
    @property
    def identity(self) -> float:
        """Identity element of the combine monoid (0 / +inf / -inf)."""
        return _COMBINE_IDENTITY[self.combine]

    def updated_mask(self, old: Array, new: Array) -> Array:
        """Elementwise "value changed" mask over old/new ``[V(, Q)]`` —
        exact (!=) or |new - old| > update_tol for tolerance-based
        programs like PageRank."""
        if self.update_tol > 0.0:
            return jnp.abs(new - old) > self.update_tol
        return new != old

    def fused_spec(self):
        """:class:`repro.kernels.gab_fused.FusedSpec` describing this
        program's gather/apply in the affine form the fused Pallas kernel
        executes, or ``None`` when the program has no such form — the
        ``pallas_fused`` path then falls back to the unfused one-hot
        kernel for this program."""
        return None


# ---------------------------------------------------------------------------
# jit-friendly tile step
# ---------------------------------------------------------------------------

def _bcast_rows(mask: Array, ref: Array) -> Array:
    """Broadcast a per-row [R] mask against [R] or [R, Q] data."""
    return mask[:, None] if ref.ndim == 2 else mask


def _dslice(buf: Array, start, rows: int) -> Array:
    """dynamic_slice of ``rows`` leading rows starting at ``start``,
    covering the full trailing (query) axis if present."""
    return jax.lax.dynamic_slice(
        buf, (start,) + (0,) * (buf.ndim - 1), (rows,) + buf.shape[1:])


def _dupdate(buf: Array, window: Array, start) -> Array:
    return jax.lax.dynamic_update_slice(
        buf, window, (start,) + (0,) * (buf.ndim - 1))


def _row_pad(arr: Array, pad: int) -> Array:
    """Append ``pad`` zero rows (any trailing shape) to ``arr``."""
    z = jnp.zeros((pad,) + arr.shape[1:], arr.dtype)
    return jnp.concatenate([arr, z])


def _fused_tile(prog, fs, src_vals, src_aux, edge_val, dst_local, old,
                dst_aux, num_rows, row_cap, blocks):
    """Dispatch one tile through the fused gather→combine→apply kernel
    (kernels/gab_fused.py).  The per-edge affine terms are formed here —
    ``a = src_aux[scale_aux] * edge_val`` matches the programs' own gather
    expressions bit-for-bit (edge_val is exactly 1.0 on real unweighted
    edges) — and the kernel returns the applied+masked row block."""
    from repro.kernels import gab_fused as _gf
    from repro.kernels import ops as _kops

    a = src_aux[fs.scale_aux] * edge_val if fs.scale_aux else None
    b = edge_val if fs.add_edge else None
    base = dst_aux[fs.base_aux] if fs.base_aux else None
    be, br = blocks if blocks is not None else (
        _gf.DEFAULT_BLOCK_E, _gf.DEFAULT_BLOCK_R)
    return _gf.gab_fused(
        fs, src_vals, a, b, dst_local, old, base, num_rows, row_cap,
        block_e=be, block_r=br, interpret=_kops.interpret_mode(),
    )


def _unfused_impl(seg_impl: str) -> str:
    """The segment-reduce impl backing programs without a FusedSpec (and
    the merged path) when the engine asks for ``pallas_fused``."""
    return "pallas_onehot" if seg_impl == "pallas_fused" else seg_impl


def tile_gather_apply(
    prog: VertexProgram,
    values: Array,                # [V] replicated vertex values
    aux: dict[str, Array],        # per-vertex aux arrays, each [V]
    src: Array,                   # [E] global source ids (padding -> sink row)
    dst_local: Array,             # [E] dst - row_start; padding == row_cap
    edge_val: Array,              # [E]
    row_start: Array,             # scalar int32
    num_rows: Array,              # scalar int32 (<= row_cap)
    row_cap: int,
    seg_impl: str = "jnp",
    blocks: Optional[tuple[int, int]] = None,
) -> tuple[Array, Array, Array]:
    """Gather+Apply for one tile.

    Returns (rows [row_cap] global ids clipped to V-1, new_values
    [row_cap(, Q)], updated [row_cap(, Q)] bool).  Rows beyond num_rows are
    masked not-updated.  ``values`` may be [V] or [V, Q] (multi-query).
    seg_impl="pallas_fused" runs gather/combine/apply/mask as one fused
    Pallas kernel (DESIGN.md §14); ``blocks`` carries the autotuned
    ``(BE, BR)`` to either Pallas path.

    The operations carry the named scopes ``graphh.gather`` (source
    reads and per-edge messages), ``graphh.combine`` (the reduction into
    rows) and ``graphh.apply`` (row reads, apply, update mask).  The fused
    kernel reduces and applies in one, under ``graphh.combine``.
    """
    nv = values.shape[0]
    with jax.named_scope("graphh.gather"):
        src_vals = jnp.take(values, src, axis=0)
        src_aux = {k: jnp.take(aux[k], src, axis=0) for k in prog.src_aux}
    local_rows = jnp.arange(row_cap, dtype=jnp.int32)
    rows = jnp.minimum(row_start + local_rows, nv - 1)

    fs = prog.fused_spec() if seg_impl == "pallas_fused" else None
    if fs is not None:
        with jax.named_scope("graphh.apply"):
            old = jnp.take(values, rows, axis=0)
            dst_aux = {k: jnp.take(aux[k], rows, axis=0)
                       for k in prog.dst_aux}
        with jax.named_scope("graphh.combine"):
            new, updated = _fused_tile(prog, fs, src_vals, src_aux, edge_val,
                                       dst_local, old, dst_aux, num_rows,
                                       row_cap, blocks)
        return rows, new, updated

    with jax.named_scope("graphh.gather"):
        contrib = prog.gather(src_vals, edge_val, src_aux)
    with jax.named_scope("graphh.combine"):
        accum = segment_reduce(
            contrib, dst_local, row_cap + 1, prog.combine,
            impl=_unfused_impl(seg_impl), blocks=blocks,
        )[:row_cap]

    with jax.named_scope("graphh.apply"):
        old = jnp.take(values, rows, axis=0)
        dst_aux = {k: jnp.take(aux[k], rows, axis=0) for k in prog.dst_aux}
        new = prog.apply(old, accum, dst_aux)
        valid = _bcast_rows(local_rows < num_rows, new)
        new = jnp.where(valid, new, old)
        updated = jnp.logical_and(valid, prog.updated_mask(old, new))
    return rows, new, updated


def tile_gather_apply_sharded(
    prog: VertexProgram,
    src_vals: Array,              # [E(, Q)] pre-gathered source values
    src_aux: dict[str, Array],    # pre-gathered per-edge aux, each [E(, ...)]
    edge_val: Array,              # [E]
    dst_local: Array,             # [E] dst - row_start; padding routes inert
    old: Array,                   # [row_cap(, Q)] this tile's current rows
    dst_aux: dict[str, Array],    # dst-side aux rows, each [row_cap(, ...)]
    num_rows: Array,              # scalar int32 (<= row_cap)
    row_cap: int,
    seg_impl: str = "jnp",
    blocks: Optional[tuple[int, int]] = None,
) -> tuple[Array, Array]:
    """Gather+Apply for one tile with *pre-gathered* source-side inputs —
    the out-of-core vertex-state path (DESIGN.md §10).

    The engine materializes ``src_vals``/``src_aux`` interval-by-interval
    from the :class:`~repro.core.vstate.VertexStateStore` (so no full [V]
    array ever exists) and slices ``old``/``dst_aux`` from the tile's own
    dst-interval block.  Edge *order* is untouched — only the fill of the
    pre-gathered buffers walks intervals — so contributions reduce in
    exactly the same order as :func:`tile_gather_apply` and valid rows are
    bit-identical to the in-memory path.  Padding slots hold zeros instead
    of ``values[0]``; they only ever reduce into the masked-out sink row.

    Returns (new_values [row_cap(, Q)], updated [row_cap(, Q)] bool).
    """
    fs = prog.fused_spec() if seg_impl == "pallas_fused" else None
    if fs is not None:
        return _fused_tile(prog, fs, src_vals, src_aux, edge_val, dst_local,
                           old, dst_aux, num_rows, row_cap, blocks)

    contrib = prog.gather(src_vals, edge_val, src_aux)
    accum = segment_reduce(
        contrib, dst_local, row_cap + 1, prog.combine,
        impl=_unfused_impl(seg_impl), blocks=blocks,
    )[:row_cap]
    new = prog.apply(old, accum, dst_aux)
    local_rows = jnp.arange(row_cap, dtype=jnp.int32)
    valid = _bcast_rows(local_rows < num_rows, new)
    new = jnp.where(valid, new, old)
    updated = jnp.logical_and(valid, prog.updated_mask(old, new))
    return new, updated


def stacked_tiles_step(
    prog: VertexProgram,
    values: Array,
    aux: dict[str, Array],
    stk: dict[str, Array],        # stacked tiles (tiles.stack_tiles output)
    row_cap: int,
    seg_impl: str = "jnp",
    blocks: Optional[tuple[int, int]] = None,
) -> tuple[Array, Array]:
    """Process a stack of tiles via lax.scan (one server's local work for a
    superstep).  Returns (new_masked [V(, Q)], updated [V(, Q)] bool): the
    updated value where updated, else 0.

    Masked values (new where updated, else 0) + the update mask make the
    cross-server Broadcast a plain psum pair: tiles own disjoint row
    ranges, so exactly one server contributes per vertex.  (Additive
    deltas would NaN on +/-inf-valued programs like SSSP.)

    Tiles own *contiguous* dst ranges (the paper's 1-D layout), so the
    per-tile update is a dynamic-slice read-modify-write on padded buffers
    rather than a scatter (§Perf It3: ~2x on the CPU engine; on TPU this is
    the difference between a DUS and a gather/scatter pair).

    The scan body carries the named scopes of :func:`tile_gather_apply`:
    ``graphh.gather``, ``graphh.combine`` and ``graphh.apply`` (which also
    writes the tile's rows into the outputs).
    """
    nv = values.shape[0]
    pad = row_cap + 1
    tail = values.shape[1:]            # () or (Q,) — the query axis
    values_p = _row_pad(values, pad)
    aux_p = {k: _row_pad(aux[k], pad) for k in prog.dst_aux}

    fs = prog.fused_spec() if seg_impl == "pallas_fused" else None

    def body(carry, tile):
        out_p, upd_p = carry
        row_start = tile["row_start"]
        num_rows = tile["num_rows"]

        with jax.named_scope("graphh.gather"):
            src_vals = jnp.take(values, tile["src"], axis=0)
            src_aux = {k: jnp.take(aux[k], tile["src"], axis=0)
                       for k in prog.src_aux}
        with jax.named_scope("graphh.apply"):
            old = _dslice(values_p, row_start, row_cap)
            dst_aux = {k: _dslice(aux_p[k], row_start, row_cap)
                       for k in prog.dst_aux}
        if fs is not None:
            with jax.named_scope("graphh.combine"):
                new, updated = _fused_tile(
                    prog, fs, src_vals, src_aux, tile["val"],
                    tile["dst_local"], old, dst_aux, num_rows, row_cap,
                    blocks)
        else:
            with jax.named_scope("graphh.gather"):
                contrib = prog.gather(src_vals, tile["val"], src_aux)
            with jax.named_scope("graphh.combine"):
                accum = segment_reduce(
                    contrib, tile["dst_local"], row_cap + 1, prog.combine,
                    impl=_unfused_impl(seg_impl), blocks=blocks)[:row_cap]
            with jax.named_scope("graphh.apply"):
                new = prog.apply(old, accum, dst_aux)
                local = jnp.arange(row_cap, dtype=jnp.int32)
                valid = _bcast_rows(local < num_rows, new)
                new = jnp.where(valid, new, old)
                updated = jnp.logical_and(valid,
                                          prog.updated_mask(old, new))

        with jax.named_scope("graphh.apply"):
            cur = _dslice(out_p, row_start, row_cap)
            # set-where-updated (overlap-safe)
            window = jnp.where(updated, new, cur)
            out_p = _dupdate(out_p, window, row_start)
            cur_u = _dslice(upd_p, row_start, row_cap)
            upd_p = _dupdate(upd_p, cur_u | updated, row_start)
        return (out_p, upd_p), None

    delta0 = jnp.zeros((nv + pad,) + tail, values.dtype)
    upd0 = jnp.zeros((nv + pad,) + tail, dtype=bool)
    scan_tiles = {
        "src": stk["src"],
        "dst_local": stk["dst_local"],
        "val": stk["val"],
        "row_start": stk["row_start"],
        "num_rows": stk["num_rows"],
    }
    (out_p, upd_p), _ = jax.lax.scan(body, (delta0, upd0), scan_tiles)
    return out_p[:nv], upd_p[:nv]


def merged_server_step(
    prog: VertexProgram,
    values: Array,                # [V]
    aux: dict[str, Array],
    src: Array,                   # [E_s] all real edges of this server's tiles
    dst: Array,                   # [E_s] global dst ids, sorted (padding = V)
    edge_val: Array,              # [E_s]
    owned: Array,                 # [V] bool: rows covered by this server
    seg_impl: str = "jnp",
    blocks: Optional[tuple[int, int]] = None,
) -> tuple[Array, Array]:
    """§Perf It5: one fused gather/segment-sum/apply per server.

    Tiles' dst ranges are disjoint and each vertex's in-edges live in one
    tile, so merging a server's tiles into a single edge list and reducing
    straight into [V] is exact; apply runs on all rows and is masked by
    ownership.  Removes the tile scan, the per-tile slicing, and all edge
    padding (only real edges are stored).

    The merged path masks rows by *ownership* rather than a contiguous
    ``num_rows`` window, which the fused kernel's row test cannot express —
    ``pallas_fused`` therefore degrades to the unfused one-hot kernel here
    (same autotuned blocks)."""
    nv = values.shape[0]
    src_vals = jnp.take(values, src, axis=0)
    src_aux = {k: jnp.take(aux[k], src, axis=0) for k in prog.src_aux}
    contrib = prog.gather(src_vals, edge_val, src_aux)
    accum = segment_reduce(contrib, dst, nv + 1, prog.combine,
                           impl=_unfused_impl(seg_impl), blocks=blocks)[:nv]
    dst_aux = {k: aux[k] for k in prog.dst_aux}
    new = prog.apply(values, accum, dst_aux)
    own = _bcast_rows(owned, new)
    new = jnp.where(own, new, values)
    updated = jnp.logical_and(own, prog.updated_mask(values, new))
    new_masked = jnp.where(updated, new, jnp.zeros_like(values))
    return new_masked, updated


# ---------------------------------------------------------------------------
# Single-tile jit wrapper used by the out-of-core engine (static shapes keyed
# by (edge_cap, row_cap), so one compile serves every tile).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 7, 8, 9))
def _jit_tile_step(prog, values, aux, src, dst_local, edge_val,
                   row_start_num_rows, row_cap, seg_impl, blocks):
    row_start, num_rows = row_start_num_rows
    return tile_gather_apply(
        prog, values, aux, src, dst_local, edge_val,
        row_start, num_rows, row_cap, seg_impl, blocks,
    )


def run_tile(prog, values, aux, tile_arrays, row_start, num_rows,
             row_cap, seg_impl="jnp", blocks=None):
    """Out-of-core engine entry point for one tile (host arrays ok)."""
    src, dst_local, edge_val = tile_arrays
    return _jit_tile_step(
        prog, values, aux, src, dst_local, edge_val,
        (jnp.int32(row_start), jnp.int32(num_rows)), row_cap, seg_impl,
        blocks,
    )


@partial(jax.jit, static_argnums=(0, 8, 9, 10))
def _jit_tile_step_sharded(prog, src_vals, src_aux, edge_val, dst_local,
                           old, dst_aux, num_rows, row_cap, seg_impl,
                           blocks):
    return tile_gather_apply_sharded(
        prog, src_vals, src_aux, edge_val, dst_local, old, dst_aux,
        num_rows, row_cap, seg_impl, blocks,
    )


def run_tile_sharded(prog, src_vals, src_aux, edge_val, dst_local, old,
                     dst_aux, num_rows, row_cap, seg_impl="jnp",
                     blocks=None):
    """Ooc-vstate engine entry point for one tile (host arrays ok); one
    compile serves every tile (shapes keyed by (edge_cap, row_cap, Q))."""
    return _jit_tile_step_sharded(
        prog, src_vals, src_aux, edge_val, dst_local, old, dst_aux,
        jnp.int32(num_rows), row_cap, seg_impl, blocks,
    )


# ---------------------------------------------------------------------------
# Stacked-tile entry, dispatched as ONE jitted scan: K prefetched tiles padded
# to a fixed stack size (the pipelined engine), or a server's whole
# device-resident stack (the stacked engine's superstep).  Compilation is
# keyed by (K, edge_cap, row_cap), so each stack shape compiles once.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _jit_run_tile_stack(prog, values, aux, stk, row_cap, seg_impl, blocks):
    return stacked_tiles_step(prog, values, aux, stk, row_cap, seg_impl,
                              blocks)


def run_tile_stack(prog, values, aux, stk, row_cap, seg_impl="jnp",
                   blocks=None):
    """Process a K-tile stack (``tiles.stack_tiles`` output, possibly padded
    with inert tiles via ``distributed.pad_stack_to``) in one dispatch.

    Returns (new_masked [V], updated [V] bool) — identical per-row results
    to running ``run_tile`` over the same tiles one at a time, since tiles
    own disjoint row ranges.
    """
    scan = {k: jnp.asarray(stk[k])
            for k in ("src", "dst_local", "val", "row_start", "num_rows")}
    return _jit_run_tile_stack(prog, values, aux, scan, row_cap, seg_impl,
                               blocks)
