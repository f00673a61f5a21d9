"""barrier_s (s/superstep): superstep time outside tile loads and tile steps
(the skip pre-pass, the barrier's payload accounting, the update apply and
retirement), per superstep of the window."""


def reduce(run):
    """Mean seconds per window superstep outside load and compute."""
    stats = run["stats"]
    if not stats:
        return None
    return sum(s.seconds - s.load_seconds - s.compute_seconds
               for s in stats) / len(stats)
