"""Programs compiled (or loaded from the compile cache) inside the traced
slice: the program's ``repro/compile`` markers there.  Set-up warms every
shape, so a steady window reads 0.  None where the program wrote no
``repro/`` span into the trace."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    if spans is None:
        return None
    return program_spans.compiles_in_slice(spans, run.trace.window_s)
