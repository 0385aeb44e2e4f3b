"""Share of its roofline that the round's combine reaches: the least time
to read the updates D once and read and write the float32 parameters
(``P·n·itemsize + 2·n·4`` bytes; ``2·P·n`` FLOPs), times the rounds in the
traced slice, over the device time of the compiled combine (``apply_mix``)
in the slice."""
from bench import counts

PROGRAM = r"apply_mix"


def combine_work(P: int, n: int, itemsize: int, param_itemsize: int = 4):
    """``α @ D`` into the parameters, leaf by leaf: D read once, the
    parameters read and written once."""
    return 2.0 * P * n, P * n * itemsize + 2.0 * n * param_itemsize


def read(run):
    t, c = run.trace, run.counters
    if t is None or not c.get("traced_rounds"):
        return None
    secs = t.modules_matching(PROGRAM)
    if secs <= 0:
        return None
    tmin, _ = counts.min_time_s(*combine_work(c["P"], c["n"], c["itemsize"]),
                                run.peaks)
    return 100.0 * c["traced_rounds"] * tmin / secs
