"""What several per-layer readers share."""


def idle_percent(run):
    """Share of the traced slice in which no operation ran on the device;
    None where the trace holds no device."""
    t = run.trace
    if t is None or not t.chips or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
