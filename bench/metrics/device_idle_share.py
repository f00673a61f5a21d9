"""device_idle_share (%): the share of the traced window in which no
operation ran on the device (1 - the union of device-operation intervals over
the window, averaged over the chips)."""


def reduce(run):
    """Idle percentage of the traced window, or None without a trace."""
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
