"""Share of its roofline that the round statistics pass (``G = D Dᵀ``,
``C = D GMᵀ``) reaches: the least time for reading D and GM once, over the
device time of the compiled accumulate pass in the traced slice.  The pass
is one program whichever backend the kernel registry picked for each slab
(the Pallas kernel or ``stream_stats_xla``), so its program's time counts
the same work either way."""
from bench import counts

PROGRAM = r"accumulate"


def read(run):
    t, c = run.trace, run.counters
    if t is None or not c.get("traced_rounds"):
        return None
    secs = t.modules_matching(PROGRAM)
    if secs <= 0:
        return None
    flops, nbytes = counts.stream_stats_work(c["P"], c["n"], c["itemsize"])
    tmin, _ = counts.min_time_s(flops, nbytes, run.peaks)
    return 100.0 * c["traced_rounds"] * tmin / secs
