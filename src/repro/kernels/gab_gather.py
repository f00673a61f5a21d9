"""Pallas TPU kernel for the GAB gather hot loop (paper §III-C).

The per-tile segment reduction ``out[r] = ⊕_{e: dst[e]=r} contrib[e]`` is
the SpMV-shaped inner loop of every GraphH superstep.  A CPU/GPU CSR walk
(pointer chasing) has no good TPU analogue, so we *re-shape the irregular
reduction into dense systolic work* (DESIGN.md §3/§4):

  sum monoid:  per (row-block j, edge-block i) grid step, build the one-hot
               matrix ``H[e, r] = (dst[e] == j*BR + r)`` in VMEM and
               accumulate ``contrib @ H`` on the MXU — each edge block
               costs Q x BE x BR MACs, turning gather-scatter into matmul.
               The contraction asks for HIGHEST (f32) precision: Mosaic's
               default rounds f32 operands to bf16, which on a v5e put
               PageRank contributions 0.3% off the f32 sum.
  min/max:     same tiling, but a masked VPU reduction over the edge axis
               (select + min), since min-plus has no MXU form.

Multi-query axis (DESIGN.md §9): ``contrib`` may be ``[E]`` or ``[E, Q]``
(Q batched program instances sharing one edge pass).  Internally the
contrib block is laid out ``[Q, BE]`` so the sum monoid contracts
``[Q, BE] x [BE, BR] -> [Q, BR]`` — the Q=1 rank-1 matvec becomes a real
GEMM at Q>1 and MXU utilization rises with the batch for free (H is built
once per block regardless of Q).

Block sizes default to (BE, BR) = (512, 256): H is 512x256 f32 = 512 KB of
VMEM, the contrib block Q x 2 KB, the out block Q x 1 KB.  The min/max
select materializes [Q, BE, BR] (4 MiB at Q=8), so very large Q or blocks
need smaller BE/BR; the v5e compiler accepts the fused kernel's plans of
tens of MiB and refuses a (4096, 2048) min block for VMEM
(tests/test_tpu_compile.py pins both).  All dims are multiples of 128 for
MXU/lane alignment.  The edge-block axis
is the innermost grid dimension so the output row block stays resident
across the whole contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_E = 512
DEFAULT_BLOCK_R = 256
SUBLANES = 8  # f32 tiles are (8, 128): the second-minor dim must be a multiple

_IDENTITY = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}


def _kernel(dst_ref, contrib_ref, out_ref, *, block_r: int, combine: str):
    """Grid = (num_row_blocks, num_edge_blocks); edge axis innermost."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _IDENTITY[combine])

    dst = dst_ref[0, :]                    # [BE] int32 (global row ids)
    c = contrib_ref[...]                   # [Q, BE]
    j = pl.program_id(0)
    be = dst.shape[0]
    # rows covered by this output block: j*BR + [0, BR)
    rows = j * block_r + jax.lax.broadcasted_iota(jnp.int32, (be, block_r), 1)
    hit = dst[:, None] == rows             # [BE, BR] one-hot (padding misses all)

    if combine == "sum":
        h = hit.astype(c.dtype)
        acc = jax.lax.dot_general(
            c, h,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                   # [Q, BR] on the MXU
        out_ref[...] += acc.astype(out_ref.dtype)
    else:
        ident = jnp.asarray(_IDENTITY[combine], dtype=c.dtype)
        sel = jnp.where(hit[None, :, :], c[:, :, None], ident)   # [Q, BE, BR]
        red = jnp.min(sel, axis=1) if combine == "min" else jnp.max(sel, axis=1)
        cur = out_ref[...]
        out_ref[...] = (jnp.minimum(cur, red) if combine == "min"
                        else jnp.maximum(cur, red))


def _pad_axis(x: jax.Array, size: int, fill, axis: int = 0) -> jax.Array:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    shape = list(x.shape)
    shape[axis] = pad
    return jnp.concatenate([x, jnp.full(shape, fill, dtype=x.dtype)], axis=axis)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "combine", "block_e", "block_r", "interpret"),
)
def segment_reduce_pallas(
    contrib: jax.Array,
    dst: jax.Array,
    num_segments: int,
    combine: str = "sum",
    block_e: int = DEFAULT_BLOCK_E,
    block_r: int = DEFAULT_BLOCK_R,
    *,
    interpret: bool,
) -> jax.Array:
    """Segment-reduce ``contrib`` by ``dst`` into ``num_segments`` buckets.

    ``contrib`` is ``[E]`` (returns ``[num_segments]``) or ``[E, Q]``
    (returns ``[num_segments, Q]``).  Shapes are padded to block multiples;
    padded edges use an out-of-range dst so they never hit a one-hot lane —
    an edge block made entirely of padding contributes only identities.
    dtype follows ``contrib``.  ``interpret`` runs the kernel body on the
    host backend (``kernels.ops.interpret_mode()``: CPU only).
    """
    assert contrib.ndim in (1, 2) and dst.ndim == 1
    assert contrib.shape[0] == dst.shape[0]
    squeeze = contrib.ndim == 1
    cq = contrib[:, None] if squeeze else contrib     # [E, Q]
    e, q = cq.shape
    e_pad = max(((e + block_e - 1) // block_e) * block_e, block_e)
    r_pad = max(((num_segments + block_r - 1) // block_r) * block_r, block_r)
    # Q rides the sublane dim of every block: Mosaic rejects block shapes
    # whose second-minor dim is not a multiple of the 8-sublane tile, so pad
    # Q up and slice on return.  Padded query rows carry the identity and
    # never reach the caller.
    q_pad = max(((q + SUBLANES - 1) // SUBLANES) * SUBLANES, SUBLANES)

    # [Q, E] layout: the edge axis lands on TPU lanes, Q on sublanes.
    contrib_p = _pad_axis(cq.astype(jnp.float32).T, e_pad, 0.0, axis=1)
    contrib_p = _pad_axis(contrib_p, q_pad, _IDENTITY[combine], axis=0)
    dst_p = _pad_axis(dst.astype(jnp.int32), e_pad, jnp.int32(r_pad))[None, :]

    grid = (r_pad // block_r, e_pad // block_e)
    out = pl.pallas_call(
        functools.partial(_kernel, block_r=block_r, combine=combine),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_e), lambda j, i: (0, i)),   # dst
            pl.BlockSpec((q_pad, block_e), lambda j, i: (0, i)),   # contrib
        ],
        out_specs=pl.BlockSpec((q_pad, block_r), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((q_pad, r_pad), jnp.float32),
        interpret=interpret,
    )(dst_p, contrib_p)
    out = out[:q, :num_segments].astype(contrib.dtype)
    return out[0] if squeeze else out.T
