"""Device trace: recording a few supersteps, and reducing the trace to numbers.

The profiler writes an XSpace (``*.xplane.pb``). Device planes are named
``/device:TPU:<n>``; their ``XLA Modules`` line holds one event per executed
program and their ``XLA Ops`` line one per operation. Host threads are lines of
the ``/host:CPU`` plane; the harness's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``) sit on the line of the engine's thread. All events share one clock, in nanoseconds from the start of the
trace.

:func:`reduce` reads, over the window between the first and the last
``bench.superstep`` span of the trace:

- ``busy_s``: the union of the device's operation intervals, averaged over
  the device planes;
- ``kernel_s``: the device time of each program, by name (``XLA Modules``);
- ``top_ops``: the operations that took most device time, named
  ``<program>/<operation>``;
- ``idle_gaps``: idle device time by what the host was doing: the innermost
  ``bench.*`` span and the innermost other event of that thread that cover the
  middle of the gap (``python`` when no event does, as for numpy code).
"""
from __future__ import annotations

import bisect
import collections
import os
import re
from pathlib import Path
from typing import Optional

SPAN_PREFIX = "bench."
STEP_SPAN = "bench.superstep"
_DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")


def _program(name: str) -> str:
    """``jit__jit_tile_step(1125...)`` -> ``jit__jit_tile_step``."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0].strip()


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class _Cover:
    """Innermost-event lookup over one thread's events (start, end, name),
    which nest: the innermost event covering ``t`` is the latest-started
    one that does, or one of its enclosing events."""

    def __init__(self, events):
        self.ev = sorted(events, key=lambda ev: (ev[0], -ev[1]))
        self.starts = [ev[0] for ev in self.ev]
        self.parent = []
        stack: list[int] = []
        for i, (s, _, _) in enumerate(self.ev):
            while stack and self.ev[stack[-1]][1] < s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def innermost(self, t: float) -> Optional[str]:
        """Name of the innermost event covering ``t``, or None."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.ev[i][1] < t:
            i = self.parent[i]
        return self.ev[i][2] if i >= 0 else None


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under ``trace_dir``, or None."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return str(found[-1]) if found else None


def reduce(xplane_path: str, top: int = 10) -> Optional[dict]:
    """Reduce the trace file ``xplane_path`` (see :func:`reduce_space`)."""
    import jax

    return reduce_space(jax.profiler.ProfileData.from_file(xplane_path), top)


def reduce_space(space, top: int = 10) -> Optional[dict]:
    """Reduce one trace (a ``jax.profiler.ProfileData``) to the numbers
    above; None when it holds no ``bench.superstep`` span or no device
    plane."""
    host_lines = []
    devices = []
    for plane in space.planes:
        if plane.name == "/host:CPU":
            host_lines = [[(e.start_ns, e.end_ns, e.name) for e in ln.events]
                          for ln in plane.lines]
        elif _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: [(e.start_ns, e.end_ns, e.name)
                               for e in ln.events] for ln in plane.lines}
            devices.append(lines)
    steps = [ev for line in host_lines for ev in line if ev[2] == STEP_SPAN]
    if not steps or not devices:
        return None
    lo = min(s for s, _, _ in steps)
    hi = max(e for _, e, _ in steps)
    engine_thread = next(line for line in host_lines
                         if any(ev[2] == STEP_SPAN for ev in line))
    spans = _Cover([ev for ev in engine_thread
                    if ev[2].startswith(SPAN_PREFIX)])
    others = _Cover([ev for ev in engine_thread
                     if not ev[2].startswith(SPAN_PREFIX)])

    busy_total = 0.0
    kernel = collections.Counter()
    ops = collections.Counter()
    gaps = collections.Counter()
    for lines in devices:
        modules = [ev for ev in lines.get("XLA Modules", [])
                   if ev[1] > lo and ev[0] < hi]
        op_evs = [ev for ev in lines.get("XLA Ops", [])
                  if ev[1] > lo and ev[0] < hi]
        busy = _union(_clip([(s, e) for s, e, _ in modules + op_evs], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for s, e, name in modules:
            kernel[_program(name)] += e - s
        starts = [s for s, _, _ in modules]
        for s, e, name in op_evs:
            i = bisect.bisect_right(starts, s) - 1
            owner = (_program(modules[i][2])
                     if i >= 0 and modules[i][1] >= e else "?")
            ops[f"{owner}/{_op(name)}"] += e - s
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            span = spans.innermost(mid) or "outside"
            what = others.innermost(mid) or "python"
            gaps[f"{span}/{what}"] += e - s
    n = len(devices)
    ns = 1e-9 / n
    return dict(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * ns,
        supersteps=len(steps),
        kernel_s={k: v * ns for k, v in kernel.items()},
        top_ops=[[k, v * ns] for k, v in ops.most_common(top)],
        idle_gaps=[[k, v * ns] for k, v in gaps.most_common(top)],
    )


class Tracer:
    """Starts and stops the profiler around whole supersteps of the window
    (host Python tracing off: it would slow the host loop several-fold)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.active = False
        self.started_at: Optional[float] = None

    def start(self, now: float) -> None:
        """Start tracing (``now`` is the host clock, for :meth:`elapsed`)."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.active = True
        self.started_at = now

    def stop(self) -> None:
        """Stop tracing and write the trace under ``log_dir``."""
        import jax

        if self.active:
            jax.profiler.stop_trace()
            self.active = False

    def elapsed(self, now: float) -> float:
        """Seconds since :meth:`start`."""
        return now - self.started_at if self.started_at is not None else 0.0
