"""Device time per round of the flat views' relayout copies: the programs
``flat_view_slab`` (``repro.core.flatten``) compiles, one per leaf shape,
over the rounds in the traced slice.  None where the program runs no such
program (the views are then copies under another name, or none)."""

PROGRAM = r"flat_view"


def read(run):
    t, c = run.trace, run.counters
    if t is None or not c.get("traced_rounds"):
        return None
    secs = t.modules_matching(PROGRAM)
    if secs <= 0:
        return None
    return 1e3 * secs / c["traced_rounds"]
