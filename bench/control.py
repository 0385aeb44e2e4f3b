"""Readings that the correctness limits are set from, for one cell: the
program's reading of each compared number on many seeds, and the control's
(the reference one precision step below the configuration's, in the
program's place) on a few.  Not part of a benchmark run.

    python3 bench/control.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 1,2,3

One process, one JSON line per seed on stdout.  The program's readings come
from its timed path at the cell's own sizes (the engine, inputs and round
that the window drives); the control runs on the same inputs.
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


def fedround_readings(ctx, control: bool):
    import jax
    import numpy as np
    from bench.drivers import fedround as F
    tr = ctx.cell.traffic
    eng, D, GM, params, groups, prec = F.build(ctx)
    rctx, delta, new_params, _ = F.round_once(eng, params, D, GM, groups,
                                              prec)
    jax.block_until_ready(new_params)
    out = {"G": np.asarray(rctx.G), "C": np.asarray(rctx.C),
           "eff": np.asarray(delta.w),
           "params": {k: np.asarray(v) for k, v in new_params.items()}}
    del rctx, delta, new_params, eng
    beta, ridge = float(tr["beta"]), float(tr["ridge"])
    res = {"program": F.compare(out, D, GM, params, groups, beta, ridge)}
    if control:
        ctl = F.control_round(D, GM, params, groups, beta, ridge)
        res["control"] = F.compare(ctl, D, GM, params, groups, beta, ridge)
    return res


READERS = {"fedround": fedround_readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    harness.use_compile_cache()
    harness.require_tpu(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds:
        ctx = harness.Context(cell, seed, 0.0,
                              harness.Tracer(False, harness.OUT), T_PROCESS,
                              harness.log)
        t = time.perf_counter()
        res = READERS[cell.kind](ctx, seed in ctl)
        res.update(seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(res), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
