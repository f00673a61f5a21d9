"""Fused Pallas gather→combine→apply kernel (DESIGN.md §14).

The unfused path runs the GAB hot loop as separate XLA dispatches with HBM
round-trips between them: gather materializes ``contrib [E, Q]``, the
one-hot kernel reduces it, then apply/updated-mask run as follow-up
elementwise ops over the row block.  This kernel fuses the whole chain:

  * the per-edge message is computed *inside* the kernel from streamed
    source values (``contrib = src·a + b`` — every shipped vertex program
    is an affine gather, see :class:`FusedSpec`),
  * edge blocks stream HBM→VMEM through an explicit two-slot
    double-buffered DMA (the pipelined engine's overlap idea pushed down
    to kernel granularity: block i+1 copies while block i computes),
  * the output row block stays resident in a VMEM accumulator across the
    whole edge contraction (grid is 1-D over row blocks; the edge loop is
    a ``fori_loop`` inside the kernel),
  * each row block visits only the edge blocks that hold one of its edges:
    the wrapper computes per-row-block ``[lo, hi)`` edge-block bounds
    (contiguous ranges for the dst-sorted tiles the engine builds) and
    the kernel reads them from SMEM.  Without the bound every row block
    streams the whole tile: Q·E·R one-hot MACs per tile, 2.2e11 at Q=8
    on a Graph500 scale-22 tile of 160k edges × 172k rows, where the
    bounded loop does about Q·(E·BR + R·BE),
  * apply (damped affine update / min-max relaxation) and the per-
    ``(vertex, query)`` updated mask are computed in-kernel before the
    single write-back of the row block.

Per row block of ``BR`` rows the kernel reads its edge blocks ×
``BE × (Q + #streams)`` f32 lanes and writes ``BR × Q`` twice (values + mask) — the contrib array,
the accumulator round-trip, and the mask pass never touch HBM.

Bit-identity contract: with equal ``(BE, BR)`` the accumulation order is
exactly the unfused one-hot kernel's (identity-init, ascending edge
blocks, the same ``dot_general``/masked-select per block; a skipped block
holds no edge of the row block, so it would only have added the monoid
identity), and the apply formulas mirror ``core/apps.py`` term-for-term.  The one caveat is the
apply's multiply-add: XLA may contract the *unfused* path's
``alpha*base + beta*accum`` into an FMA (it does on CPU whenever the row
offset is traced, and deletes ``optimization_barrier``/bitcast pins that
would prevent it), while this kernel computes it with two roundings.
FMA and two-rounding provably coincide when both products are exactly
representable in f32 — true for min/max applies (no multiply-add) and
for power-of-two affine coefficients — so every shipped app is
bit-identical to the unfused path except PageRank/PPR at
non-power-of-two damping, where the divergence is bounded by the last
ulp of the apply.  tests/test_gab_fused.py asserts the exact cases with
``array_equal`` and the dampened ones at float tolerance; DESIGN.md §14
records the full analysis.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gab_gather import (  # noqa: F401  (re-exported defaults)
    DEFAULT_BLOCK_E,
    DEFAULT_BLOCK_R,
    SUBLANES,
    _IDENTITY,
    _pad_axis,
)


@dataclasses.dataclass(frozen=True)
class FusedSpec:
    """Static description of a vertex program's gather/apply for fusion.

    Gather (per edge ``e``, query ``q``):
        ``contrib[q, e] = src[q, e] (· a[e]) (+ edge_val[e]) (+ add_const)``
    where ``a[e] = src_aux[scale_aux][e] · edge_val[e]`` is computed by the
    caller.  Covers every shipped app: PageRank/PPR scale by the shared
    1/out-degree factor, SSSP/landmarks add the edge weight, BFS adds 1.

    Apply (per row ``r``, query ``q``), on the block-resident accumulator:
        ``affine``: ``new = alpha · base + beta · accum`` (``base`` is the
        ``base_aux`` dst rows, or the implicit 1.0 — damped PageRank/PPR)
        ``min``/``max``: ``new = min/max(old, accum)`` (relaxation merge)

    The updated mask follows ``VertexProgram.updated_mask``: exact ``!=``
    when ``update_tol == 0`` else ``|new - old| > update_tol``.
    """

    combine: str                      # "sum" | "min" | "max"
    scale_aux: str | None = None      # src-aux name; a = aux[src] * edge_val
    add_edge: bool = False            # contrib += edge_val
    add_const: float | None = None    # contrib += const (BFS hop increment)
    apply: str = "min"                # "affine" | "min" | "max"
    alpha: float = 0.0                # affine: new = alpha*base + beta*accum
    beta: float = 1.0
    base_aux: str | None = None       # dst-aux name for base; None -> 1.0
    update_tol: float = 0.0


def _kernel(spec: FusedSpec, block_e: int, block_r: int,
            nr_ref, bounds_ref, *refs):
    """Grid = (num_row_blocks,).  Streams edge blocks ``[lo, hi)`` of this
    row block (``bounds_ref[2j]``, ``bounds_ref[2j + 1]``) through a 2-slot
    VMEM scratch with overlapped DMA, accumulating into ``acc``; applies the
    vertex update + mask once at the end and writes the row block back."""
    # unpack the spec-dependent ref list: HBM streams, row-blocked ins/outs,
    # then scratch (the wrapper builds the same order)
    it = iter(refs)
    dst_hbm = next(it)
    src_hbm = next(it)
    a_hbm = next(it) if spec.scale_aux else None
    b_hbm = next(it) if spec.add_edge else None
    old_ref = next(it)
    base_ref = next(it) if spec.base_aux else None
    new_ref = next(it)
    upd_ref = next(it)
    acc = next(it)
    dst_s = next(it)
    src_s = next(it)
    a_s = next(it) if spec.scale_aux else None
    b_s = next(it) if spec.add_edge else None
    sem = next(it)

    j = pl.program_id(0)
    qp = src_s.shape[1]
    combine = spec.combine

    streams = [(dst_hbm, dst_s, 0), (src_hbm, src_s, 1)]
    if a_s is not None:
        streams.append((a_hbm, a_s, 2))
    if b_s is not None:
        streams.append((b_hbm, b_s, 3))

    def copies(i, slot):
        return [pltpu.make_async_copy(
            hbm.at[:, pl.ds(i * block_e, block_e)], scr.at[slot],
            sem.at[slot, s]) for hbm, scr, s in streams]

    def start(i, slot):
        for cp in copies(i, slot):
            cp.start()

    def wait(i, slot):
        for cp in copies(i, slot):
            cp.wait()

    lo = bounds_ref[2 * j]
    hi = bounds_ref[2 * j + 1]
    acc[...] = jnp.full_like(acc, _IDENTITY[combine])

    @pl.when(lo < hi)
    def _first():
        start(lo, 0)

    def body(i, _):
        slot = jax.lax.rem(i - lo, 2)

        @pl.when(i + 1 < hi)
        def _prefetch():
            start(i + 1, jax.lax.rem(i + 1 - lo, 2))

        wait(i, slot)
        src = src_s[slot]                       # [qp, BE]
        contrib = src
        if a_s is not None:
            contrib = contrib * a_s[slot]       # [1, BE] broadcast over qp
        if b_s is not None:
            contrib = contrib + b_s[slot]
        if spec.add_const is not None:
            contrib = contrib + jnp.float32(spec.add_const)

        dst = dst_s[slot][0]                    # [BE] local row ids
        rows = j * block_r + jax.lax.broadcasted_iota(
            jnp.int32, (block_e, block_r), 1)
        hit = dst[:, None] == rows              # [BE, BR]

        if combine == "sum":
            h = hit.astype(contrib.dtype)
            part = jax.lax.dot_general(
                contrib, h,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                   # [qp, BR] on the MXU
            acc[...] += part
        else:
            ident = jnp.asarray(_IDENTITY[combine], dtype=contrib.dtype)
            sel = jnp.where(hit[None, :, :], contrib[:, :, None], ident)
            red = (jnp.min(sel, axis=1) if combine == "min"
                   else jnp.max(sel, axis=1))
            cur = acc[...]
            acc[...] = (jnp.minimum(cur, red) if combine == "min"
                        else jnp.maximum(cur, red))
        return 0

    jax.lax.fori_loop(lo, hi, body, 0)

    # ---- fused apply + updated mask on the resident row block -----------
    accum = acc[...]                            # [qp, BR]
    old = old_ref[...]
    if spec.apply == "affine":
        alpha = jnp.float32(spec.alpha)
        beta = jnp.float32(spec.beta)
        if base_ref is not None:
            new = alpha * base_ref[...] + beta * accum
        else:
            new = alpha + beta * accum
    elif spec.apply == "min":
        new = jnp.minimum(old, accum)
    else:
        new = jnp.maximum(old, accum)

    local = j * block_r + jax.lax.broadcasted_iota(
        jnp.int32, (qp, block_r), 1)
    valid = local < nr_ref[0]
    new = jnp.where(valid, new, old)
    if spec.update_tol > 0.0:
        upd = jnp.abs(new - old) > jnp.float32(spec.update_tol)
    else:
        upd = new != old
    new_ref[...] = new
    upd_ref[...] = jnp.logical_and(valid, upd).astype(jnp.float32)


def _edge_block_bounds(dst: jax.Array, block_e: int, block_r: int,
                       n_rblocks: int) -> jax.Array:
    """Per row block ``j``, the edge blocks ``[lo_j, hi_j)`` spanning every
    edge whose dst falls in it, from padded dst ``[E_pad]``; flattened as
    ``[lo_0, hi_0, lo_1, hi_1, ...]`` (int32 ``[2 * n_rblocks]``) for SMEM.
    Edges routed past the last row block are never hit and are left out;
    a row block with no edge gets ``lo == hi == 0``."""
    e = jnp.arange(dst.shape[0], dtype=jnp.int32)
    rb = dst // block_r
    first = jax.ops.segment_min(e, rb, num_segments=n_rblocks)
    last = jax.ops.segment_max(e, rb, num_segments=n_rblocks)
    has = last >= first
    lo = jnp.where(has, first // block_e, 0)
    hi = jnp.where(has, last // block_e + 1, 0)
    return jnp.stack([lo, hi], axis=1).reshape(-1).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "row_cap", "block_e", "block_r", "interpret"),
)
def gab_fused(
    spec: FusedSpec,
    src_vals: jax.Array,          # [E] or [E, Q] pre-gathered source values
    a: jax.Array | None,          # [E] gather scale, or None
    b: jax.Array | None,          # [E] gather additive term, or None
    dst_local: jax.Array,         # [E] local dst row ids (padding == row_cap)
    old: jax.Array,               # [row_cap] or [row_cap, Q] current rows
    base: jax.Array | None,       # [row_cap(, Q)] affine base rows, or None
    num_rows: jax.Array,          # scalar int32 (<= row_cap)
    row_cap: int,
    block_e: int = DEFAULT_BLOCK_E,
    block_r: int = DEFAULT_BLOCK_R,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """One fused Gather+Apply tile step.

    Shapes: src_vals ``[E(, Q)]``, a/b/dst_local ``[E]``, old/base
    ``[R(, Q)]`` with R = row_cap.

    Returns ``(new [row_cap(, Q)], updated [row_cap(, Q)] bool)`` with the
    exact semantics of ``core/gab.tile_gather_apply``'s reduce+apply+mask
    tail: rows at or beyond ``num_rows`` keep ``old`` and are not-updated.
    Padding edges (``dst_local == row_cap``) reduce into the sink row,
    which lives past the returned slice — identical discard semantics to
    the unfused ``num_segments = row_cap + 1`` convention.  ``interpret``
    runs the kernel body on the host backend
    (``kernels.ops.interpret_mode()``: CPU only).
    """
    assert src_vals.ndim in (1, 2) and old.ndim == src_vals.ndim
    squeeze = src_vals.ndim == 1
    sv = src_vals[:, None] if squeeze else src_vals      # [E, Q]
    ov = old[:, None] if squeeze else old                # [row_cap, Q]
    bv = None if base is None else (base[:, None] if squeeze else base)
    e, q = sv.shape
    e_pad = max(-(-e // block_e) * block_e, block_e)
    r_pad = max(-(-row_cap // block_r) * block_r, block_r)
    q_pad = max(-(-q // SUBLANES) * SUBLANES, SUBLANES)

    def prep_edge(x, fill=0.0):
        return _pad_axis(x.astype(jnp.float32)[None, :], e_pad, fill, axis=1)

    def prep_rows(x):
        xt = _pad_axis(x.astype(jnp.float32).T, r_pad, 0.0, axis=1)
        return _pad_axis(xt, q_pad, 0.0, axis=0)         # [qp, r_pad]

    # [Q, E] layout (edges on lanes); kernel-side edge padding routes to the
    # out-of-range row r_pad so it never hits a one-hot lane.
    src_p = _pad_axis(_pad_axis(sv.astype(jnp.float32).T, e_pad, 0.0, axis=1),
                      q_pad, 0.0, axis=0)
    dst_p = _pad_axis(dst_local.astype(jnp.int32), e_pad,
                      jnp.int32(r_pad))[None, :]
    bounds = _edge_block_bounds(dst_p[0], block_e, block_r, r_pad // block_r)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    rowblk = pl.BlockSpec((q_pad, block_r), lambda j: (0, j))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem, smem, hbm, hbm]
    inputs = [jnp.asarray(num_rows, jnp.int32).reshape(1), bounds, dst_p,
              src_p]
    if spec.scale_aux:
        in_specs.append(hbm)
        inputs.append(prep_edge(a))
    if spec.add_edge:
        in_specs.append(hbm)
        inputs.append(prep_edge(b))
    in_specs.append(rowblk)
    inputs.append(prep_rows(ov))
    if spec.base_aux:
        in_specs.append(rowblk)
        inputs.append(prep_rows(bv))

    scratch = [
        pltpu.VMEM((q_pad, block_r), jnp.float32),       # resident accumulator
        pltpu.VMEM((2, 1, block_e), jnp.int32),          # dst double-buffer
        pltpu.VMEM((2, q_pad, block_e), jnp.float32),    # src double-buffer
    ]
    n_streams = 2
    if spec.scale_aux:
        scratch.append(pltpu.VMEM((2, 1, block_e), jnp.float32))
        n_streams += 1
    if spec.add_edge:
        scratch.append(pltpu.VMEM((2, 1, block_e), jnp.float32))
        n_streams += 1
    scratch.append(pltpu.SemaphoreType.DMA((2, n_streams)))

    new_p, upd_p = pl.pallas_call(
        functools.partial(_kernel, spec, block_e, block_r),
        grid=(r_pad // block_r,),
        in_specs=in_specs,
        out_specs=[rowblk, rowblk],
        out_shape=[jax.ShapeDtypeStruct((q_pad, r_pad), jnp.float32),
                   jax.ShapeDtypeStruct((q_pad, r_pad), jnp.float32)],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*inputs)

    new = new_p[:q, :row_cap].astype(old.dtype).T
    upd = upd_p[:q, :row_cap].astype(bool).T
    if squeeze:
        return new[:, 0], upd[:, 0]
    return new, upd
