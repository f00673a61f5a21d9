"""Spans and counters of one superstep, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named ``graphh.*``: it
shows in a ``jax.profiler`` trace on the engine thread's line, beside the
host events of JAX and the device's operations, and records nothing while
no trace runs.  The spans nest as the work does::

    graphh.superstep (superstep=<n>)      all of EngineSession.step
      graphh.values.put                   [V(, Q)] values to the device
      graphh.skip                         skip pre-pass, per server
      graphh.tile.load                    edge-cache get + skip-filter build
      graphh.tile.dispatch                inputs built, tile step enqueued
      graphh.tile.fetch                   the step's results to the host
      graphh.tile.split                   updated rows picked out
      graphh.barrier
        graphh.barrier.measure            broadcast payload, compressed
        graphh.barrier.apply              updates written into the values
        graphh.barrier.cache              cache maintain, counter deltas

The four tile spans are timed: a :class:`Tally` pairs each with the
``SuperstepStats`` field its host seconds add up in, and counts the bytes
that cross between host and device and the real and padded edge slots of
the tiles processed.
"""
from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation as span

SUPERSTEP = "graphh.superstep"
VALUES_PUT = "graphh.values.put"
SKIP = "graphh.skip"
TILE_LOAD = "graphh.tile.load"
TILE_DISPATCH = "graphh.tile.dispatch"
TILE_FETCH = "graphh.tile.fetch"
TILE_SPLIT = "graphh.tile.split"
BARRIER = "graphh.barrier"
BARRIER_MEASURE = "graphh.barrier.measure"
BARRIER_APPLY = "graphh.barrier.apply"
BARRIER_CACHE = "graphh.barrier.cache"

#: bytes of each int32 scalar a tile step hands to the device
SCALAR_BYTES = 4


class Phase:
    """A timed span: entering opens the ``TraceAnnotation`` ``name`` and
    starts the host clock; leaving adds the elapsed seconds to
    :attr:`seconds`.  Not re-entrant: one phase object is entered by one
    thread at a time."""

    __slots__ = ("name", "seconds", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0
        self._ann = None
        self._t0 = 0.0

    def __enter__(self) -> "Phase":
        self._ann = span(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds += time.perf_counter() - self._t0
        self._ann.__exit__(*exc)


class Tally:
    """What one superstep accumulates, under its ``SuperstepStats`` names.

    - ``load``, ``dispatch``, ``fetch``, ``split``: the timed tile spans
      (:class:`Phase`), feeding ``load_seconds``, ``dispatch_seconds``,
      ``fetch_seconds`` and ``split_seconds``;
    - ``h2d_bytes``: ``nbytes`` of every host array handed to the device;
    - ``d2h_bytes``: ``nbytes`` of every device array fetched to the host;
    - ``edges_real`` / ``edges_padded``: real edges and padded edge slots of
      the tiles processed;
    - ``tiles_resident``: processed tiles that ran from edges held on the
      device across supersteps (resident stacks or merged edge lists).
    """

    def __init__(self):
        self.load = Phase(TILE_LOAD)
        self.dispatch = Phase(TILE_DISPATCH)
        self.fetch = Phase(TILE_FETCH)
        self.split = Phase(TILE_SPLIT)
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.edges_real = 0
        self.edges_padded = 0
        self.tiles_resident = 0

    def sent(self, *arrays, scalars: int = 0) -> None:
        """Count host ``arrays`` (and ``scalars`` int32 scalars) handed to
        the device."""
        self.h2d_bytes += (sum(int(a.nbytes) for a in arrays)
                           + scalars * SCALAR_BYTES)

    def to_host(self, *arrays) -> list[np.ndarray]:
        """``np.asarray`` of each array (any shape, e.g. rows ``[R]`` and
        values ``[R, Q]``), counting the device arrays among them as
        fetched bytes."""
        out = []
        for a in arrays:
            if isinstance(a, jax.Array):
                self.d2h_bytes += int(a.nbytes)
            out.append(np.asarray(a))
        return out

    def tiles(self, real: int, padded: int, resident: int = 0) -> None:
        """Count processed tiles' ``real`` edges and ``padded`` slots, and
        the ``resident`` tiles among them that the device already held."""
        self.edges_real += int(real)
        self.edges_padded += int(padded)
        self.tiles_resident += int(resident)

    def stats(self) -> dict:
        """The accumulated numbers as ``SuperstepStats`` keyword args."""
        return dict(
            load_seconds=self.load.seconds,
            dispatch_seconds=self.dispatch.seconds,
            fetch_seconds=self.fetch.seconds,
            split_seconds=self.split.seconds,
            h2d_bytes=self.h2d_bytes, d2h_bytes=self.d2h_bytes,
            edges_real=self.edges_real, edges_padded=self.edges_padded,
            tiles_resident=self.tiles_resident)
