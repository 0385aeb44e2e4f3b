"""Readings behind a serving cell's rates and limits; not part of a
benchmark run.  One process, one JSON line per reading on stdout.

    python3 bench/serve_control.py --workload <name> --sweep 2,4,6 \
        --seconds 51 --seed 7
    python3 bench/serve_control.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 20

``--sweep``: the cell's engine and weights, built once, under the cell's mix
at each rate in turn for ``--seconds``, then drained: requests completed in
the window, the backlog left at its end, tokens per second, request times,
and the queue wait (admission − due) of the requests due in each quarter of
the window.  The knee is the highest rate whose queue wait does not grow
from quarter to quarter.

``--seeds``: for each seed, the run's own set-up, a window of ``--seconds``
at the cell's rate, the drain, and the run's comparison (``readings``); for
the control seeds also the control's reading on the same sample: the
reference in float8, in the program's place.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import harness  # noqa: E402
from bench.drivers import serve as S  # noqa: E402


def _ctx(cell, seed, seconds):
    return harness.Context(cell, seed, seconds,
                           harness.Tracer(False, harness.OUT), T_PROCESS,
                           harness.log)


def _quartile_waits(win, sched, seconds):
    out = []
    for q in range(4):
        lo, hi = q * seconds / 4, (q + 1) * seconds / 4
        waits = [c.t_admit_wall - win.t0 - sched.due[r]
                 for r, c in win.done.items() if lo <= sched.due[r] < hi]
        out.append(S._percentile(waits, 50))
    return out


def sweep(cell, rates, seconds, seed):
    import numpy as np
    setup = S.build(_ctx(cell, seed, seconds))
    eng = setup.engine
    for rate in rates:
        tr = {**cell.traffic, "rate_per_s": rate}
        sched = S.schedule(tr, seconds, seed, setup.arch.vocab)
        ctx = _ctx(cell, seed, seconds)
        win = S.drive(ctx, eng, sched)
        completed = len(win.done)
        tokens = sum(len(c.tokens) for c in win.done.values())
        backlog = win.submitted - completed
        S.drain(eng, win, 120.0)
        lat = [c.t_finish_wall - win.t0 - sched.due[r]
               for r, c in win.done.items()]
        print(json.dumps({
            "rate_per_s": rate, "due": win.submitted, "completed": completed,
            "backlog_at_close": backlog,
            "unfinished_after_drain": win.submitted - len(win.done),
            "tokens_per_s": tokens / win.window_s,
            "request_p50_s": S._percentile(lat, 50),
            "request_p90_s": S._percentile(lat, 90),
            "queue_wait_p50_by_quarter_s": _quartile_waits(win, sched,
                                                           seconds),
            "occupancy": win.stats["occupancy_steps"]
            / max(win.stats["decode_steps"], 1),
            "generator_late_max_s": float(np.max(win.lateness))}),
            flush=True)


def seed_readings(cell, seeds, control, seconds):
    tr, sv = cell.traffic, cell.config["serving"]
    for seed in seeds:
        t = time.perf_counter()
        ctx = _ctx(cell, seed, seconds)
        setup = S.build(ctx)
        win = S.drive(ctx, setup.engine, setup.sched)
        S.drain(setup.engine, win, 120.0)
        seqs = S.sample(setup, win.done, tr)
        prog = S.prefill_logits(setup.engine, seqs)
        setup.engine = None
        gc.collect()
        res = {"seed": seed, "due": win.submitted, "finished": len(win.done),
               **S.readings(setup, seqs, prog, sv, control=seed in control)}
        res["seconds"] = time.perf_counter() - t
        print(json.dumps(res), flush=True)
        del setup, win, prog
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    harness.use_compile_cache()
    harness.require_tpu(cell.chips)
    if args.sweep:
        sweep(cell, [float(r) for r in args.sweep.split(",")], args.seconds,
              args.seed)
    if args.seeds:
        seed_readings(cell, [int(s) for s in args.seeds.split(",")],
                      {int(s) for s in args.control_seeds.split(",") if s},
                      args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
