"""Pure-jnp oracles for the Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_sum(contrib: jax.Array, dst: jax.Array, num_segments: int) -> jax.Array:
    """Sum contrib ``[E(, Q)]`` by dst ``[E]`` into ``[R(, Q)]``."""
    return jax.ops.segment_sum(contrib, dst, num_segments=num_segments)


def segment_min(contrib: jax.Array, dst: jax.Array, num_segments: int) -> jax.Array:
    """Min of contrib ``[E(, Q)]`` by dst ``[E]`` into ``[R(, Q)]``
    (+inf when empty)."""
    return jax.ops.segment_min(contrib, dst, num_segments=num_segments)


def segment_max(contrib: jax.Array, dst: jax.Array, num_segments: int) -> jax.Array:
    """Max of contrib ``[E(, Q)]`` by dst ``[E]`` into ``[R(, Q)]``
    (-inf when empty)."""
    return jax.ops.segment_max(contrib, dst, num_segments=num_segments)

