"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window with a profiler slice and reports the per-layer metrics, with
``busy_s`` / ``window_s`` and a breakdown.  The last line of stdout is the
result JSON; the numbers the correctness check compared, each beside its
limit, are the last lines of stderr and the result's last key.  There is
no CPU path: without enough TPU chips the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import harness  # noqa: E402


def measure(cell: "harness.Cell", seed: int, seconds: float, trace: bool,
            t_process: float, device_guard: bool = True,
            peaks=None) -> str:
    """Drive one run of ``cell`` and return its result line.  Tests turn
    ``device_guard`` off to drive a run on the CPU, and pass ``peaks``."""
    import jax

    if device_guard:
        devs = harness.require_tpu(cell.chips)
        peaks = harness.peaks_for(devs[0].device_kind)
    else:
        devs = jax.devices()[:cell.chips]
    out_dir = harness.OUT / f"{cell.name}.seed{seed}"
    tracer = harness.Tracer(trace, out_dir)
    ctx = harness.Context(cell, seed, seconds, tracer, t_process,
                          harness.log)
    outcome = harness.load_driver(cell.kind).run(ctx)
    device = harness.device_info(devs, outcome.memory_peak_bytes)

    breakdown = None
    if trace:
        from bench import trace_reduce
        summary = trace_reduce.reduce_run(tracer.xplane(), tracer.window_s)
        run = harness.Run(cell, outcome.counters, summary, peaks, device)
        metrics = {}
        for m in cell.per_layer:
            value = harness.load_metric(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = summary.breakdown()
    else:
        metrics = {m["name"]: {"value": outcome.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    for c in outcome.checks:
        harness.log(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
                    f"{'' if c.ok else '  FAILED'}")
    return harness.result_line(outcome, metrics, device, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.find_cell(harness.load_benchmark(), args.workload)
        harness.use_compile_cache()
        line = measure(cell, args.seed, args.seconds, bool(args.trace),
                       T_PROCESS)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
