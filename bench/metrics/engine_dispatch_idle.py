"""Share of the traced slice in which the first chip was idle while the
host was inside one of the round engine's ``repro/stream_*`` spans: all
idle gaps, each part put down to the innermost span covering it
(``bench/program_spans.py``, on the host's clock).  The whole split, by
span, and the longest gaps go to stderr.  None where the program wrote no ``repro/`` span."""
from bench import program_spans

PREFIX = "repro/stream_"


def read(run):
    spans = program_spans.for_run(run)
    if spans is None:
        return None
    split = program_spans.idle_split(spans, run.trace.window_s)
    program_spans.log_split(split, spans.shift_ns)
    return split.percent(PREFIX)
