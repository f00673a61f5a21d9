"""h2d_gb (GB/superstep): the engine's ``h2d_bytes`` (the ``nbytes`` of every
host array a superstep hands to the device: tile arrays, vertex values,
scalars) per superstep of the window, in 1e9 bytes. Reported in traced runs;
a program without the counter reports nothing."""


def reduce(run):
    """Mean host-to-device gigabytes per window superstep, or None."""
    stats = run["stats"]
    if run.get("trace") is None or not stats:
        return None
    if not all(hasattr(s, "h2d_bytes") for s in stats):
        return None
    return sum(s.h2d_bytes for s in stats) / 1e9 / len(stats)
