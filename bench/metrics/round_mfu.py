"""The whole round's share of the chip's peak: the least time for the
round's required work (``counts.round_work``) times the rounds completed in
the traced slice, over the slice's length."""
from bench import counts


def read(run):
    t, c = run.trace, run.counters
    if t is None or not t.chips or not c.get("traced_rounds"):
        return None
    tmin, _ = counts.min_time_s(*c["round_work"], run.peaks)
    return 100.0 * c["traced_rounds"] * tmin / t.window_s
