"""jit'd public wrappers for the Pallas kernels.

The kernels are written for the TPU and compile with Mosaic there.  On the
CPU backend, and only there, they run in interpret mode (Pallas executes
the kernel body with the XLA CPU backend) — the tests' rehearsal of the
same kernel code.  Any other backend gets the Mosaic lowering and fails
loudly rather than silently interpreting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import gab_gather as _gg
from repro.kernels import ref as _ref


def interpret_mode() -> bool:
    """True iff the default backend is the CPU: the kernels' ``interpret``
    argument for every call site."""
    return jax.default_backend() == "cpu"


def _needs_exact_fallback(contrib: jax.Array) -> bool:
    """True when the f32 round-trip inside the kernel could lose bits.

    The one-hot kernel computes in f32, which represents integers exactly
    only up to 2^24, so decide statically from dtype (int8/int16 always
    fit; wider ints may not).
    """
    return (jnp.issubdtype(contrib.dtype, jnp.integer)
            and contrib.dtype.itemsize >= 4)


def segment_sum(contrib: jax.Array, dst: jax.Array, num_segments: int,
                block_e: int = _gg.DEFAULT_BLOCK_E,
                block_r: int = _gg.DEFAULT_BLOCK_R) -> jax.Array:
    """Sum-reduce contrib ``[E]`` or ``[E, Q]`` by dst ``[E]`` into
    ``[R]`` / ``[R, Q]`` rows (R = num_segments)."""
    if _needs_exact_fallback(contrib):
        return _ref.segment_sum(contrib, dst, num_segments)
    return _gg.segment_reduce_pallas(
        contrib, dst, num_segments, combine="sum",
        block_e=block_e, block_r=block_r, interpret=interpret_mode(),
    )


def segment_min(contrib: jax.Array, dst: jax.Array, num_segments: int,
                block_e: int = _gg.DEFAULT_BLOCK_E,
                block_r: int = _gg.DEFAULT_BLOCK_R) -> jax.Array:
    """Min-reduce contrib ``[E]`` or ``[E, Q]`` by dst ``[E]`` into
    ``[R]`` / ``[R, Q]`` rows (+inf for empty segments)."""
    if _needs_exact_fallback(contrib):
        return _ref.segment_min(contrib, dst, num_segments)
    return _gg.segment_reduce_pallas(
        contrib, dst, num_segments, combine="min",
        block_e=block_e, block_r=block_r, interpret=interpret_mode(),
    )


def segment_max(contrib: jax.Array, dst: jax.Array, num_segments: int,
                block_e: int = _gg.DEFAULT_BLOCK_E,
                block_r: int = _gg.DEFAULT_BLOCK_R) -> jax.Array:
    """Max-reduce contrib ``[E]`` or ``[E, Q]`` by dst ``[E]`` into
    ``[R]`` / ``[R, Q]`` rows (-inf for empty segments)."""
    if _needs_exact_fallback(contrib):
        return _ref.segment_max(contrib, dst, num_segments)
    return _gg.segment_reduce_pallas(
        contrib, dst, num_segments, combine="max",
        block_e=block_e, block_r=block_r, interpret=interpret_mode(),
    )

