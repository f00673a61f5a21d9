"""Production meshes (functions, never module-level constants — importing
this module must not touch jax device state)."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(axes: tuple[str, ...] = ("data",)) -> Mesh:
    """Whatever devices exist locally, flattened onto the first axis."""
    n = jax.device_count()
    shape = (n,) + (1,) * (len(axes) - 1)
    return _mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return _mesh(shape, axes)


def graph_engine_axes(mesh: Mesh) -> tuple[str, ...]:
    """GraphH tile-shard axes: servers = pod x data, workers = model —
    tiles shard over all of them (DESIGN.md §5)."""
    return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)


def make_cluster_mesh(num_servers: int) -> Mesh:
    """1-D ("server",) mesh modelling the multi-process cluster runtime
    (DESIGN.md §11) for the shard_map dry-run path: one mesh slot per
    server process, so ``distributed.build_superstep`` over this mesh
    lowers the same per-server tile shard + hybrid broadcast the real
    cluster executes.  Requires >= ``num_servers`` local (or
    ``--xla_force_host_platform_device_count``-emulated) devices."""
    if jax.device_count() < num_servers:
        raise ValueError(
            f"need {num_servers} devices for a {num_servers}-server mesh; "
            f"have {jax.device_count()} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_servers} "
            "before importing jax to emulate them)")
    return _mesh((num_servers,), ("server",))
