"""Device time of one decode step: the engine's decode-chunk program (the
program whose operations include the ``flash_decode`` kernel) over the
decode steps it ran in the traced slice."""
import re

KERNEL = re.compile(r"flash_decode")


def read(run):
    t, c = run.trace, run.counters
    steps = c.get("traced", {}).get("stats", {}).get("decode_steps")
    if t is None or not steps:
        return None
    secs = sum(s for m, s in t.module_s.items()
               if any(KERNEL.search(op) for op in t.module_ops.get(m, ())))
    return 1e3 * secs / steps if secs > 0 else None
