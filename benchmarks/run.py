"""Benchmark harness — one module per paper table/figure (deliverable d).

  fig2_3  — contextual K₂/μ variants (paper Figs. 2-3)
  fig4_5  — algorithm comparison: FedAvg/FedProx/FOLB vs contextual (Figs. 4-5)
  fig6    — rounds-to-accuracy across the four datasets (Fig. 6)
  fig7    — aggregation-variable (α) statistics per stage (Fig. 7)
  async   — async edge runtime vs sync under straggler severity sweep
  hier    — hierarchical vs flat contextual: fan-in / tier-depth sweep
  fleet   — fleet-scale rounds: 10³→10⁶ devices via cohort scheduling
  bigmodel— streamed big-model round engine: memory model + equivalence
  robust  — adversarial & churn sweep: robust contextual vs plain vs FedAvg
  kernels — Pallas hot-spot micro-benchmarks
  roofline— per-(arch × shape × mesh) roofline terms from the dry-run

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks round counts.

Every bench runs under a ``JsonlTracker`` streaming live per-round events to
``BENCH_<name>.jsonl`` (tail it to watch a run).  ``--json`` additionally
writes each JSON-capable bench (one whose ``run`` returns a records dict) to
``BENCH_<name>.json`` — *derived from the trace* via
``bench_trace.derive_bench_json``, so the jsonl stream is the single source
of truth for the committed snapshots.
"""
import argparse
import json
import sys

from .bench_trace import derive_bench_json


def _registry():
    """name -> (module, kwargs_fn(quick) -> run kwargs, emits_json)."""
    from . import (async_vs_sync, bigmodel_round, compress_sweep,
                   fig2_3_k2_variants, fig4_5_algorithms,
                   fig6_rounds_to_accuracy, fig7_alpha_stages, fleet_scale,
                   hier_vs_flat, kernel_bench, robust_suite, roofline_report,
                   serve_bench)
    return {
        "fig2_3": (fig2_3_k2_variants,
                   lambda q: dict(rounds=10 if q else 25), False),
        "fig4_5": (fig4_5_algorithms,
                   lambda q: dict(rounds=12 if q else 40), False),
        "fig6": (fig6_rounds_to_accuracy,
                 lambda q: dict(rounds=15 if q else 50), False),
        "fig7": (fig7_alpha_stages,
                 lambda q: dict(rounds=10 if q else 30), False),
        "async": (async_vs_sync,
                  lambda q: dict(rounds=12 if q else 30,
                                 aggs=12 if q else 30), True),
        "hier": (hier_vs_flat, lambda q: dict(rounds=8 if q else 20), True),
        "fleet": (fleet_scale, lambda q: dict(rounds=3, quick=q), True),
        "bigmodel": (bigmodel_round,
                     lambda q: dict(rounds=8 if q else 16, quick=q), True),
        "compress": (compress_sweep,
                     lambda q: dict(rounds=8 if q else 16), True),
        "robust": (robust_suite,
                   lambda q: dict(rounds=10 if q else 20), True),
        "serve": (serve_bench, lambda q: dict(quick=q), True),
        "kernels": (kernel_bench, lambda q: dict(quick=q), True),
        "roofline": (roofline_report, lambda q: {}, False),
    }


def main() -> None:
    registry = _registry()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: " + ",".join(registry))
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<name>.json for each JSON-capable "
                         "bench in the selection")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(registry)
        if unknown:
            ap.error(f"unknown bench(es) {sorted(unknown)}; "
                     f"have {sorted(registry)}")

    from repro.obs import JsonlTracker, use_tracker

    from .common import publish_bench, use_compile_cache

    use_compile_cache()

    print("name,us_per_call,derived")
    wrote_json = False
    for name, (module, kwargs_fn, emits_json) in registry.items():
        if only is not None and name not in only:
            continue
        trace_path = f"BENCH_{name}.jsonl"
        with use_tracker(JsonlTracker(trace_path)):
            results = module.run(**kwargs_fn(args.quick))
            if emits_json:
                publish_bench(results)
        print(f"streamed {trace_path}", file=sys.stderr)
        if args.json and emits_json:
            path = f"BENCH_{name}.json"
            with open(path, "w") as f:
                json.dump(derive_bench_json(trace_path), f, indent=2)
            print(f"wrote {path} (derived from {trace_path})",
                  file=sys.stderr)
            wrote_json = True
    if args.json and not wrote_json:
        print("--json: no JSON-capable bench in the selection; "
              "no file written", file=sys.stderr)


if __name__ == "__main__":
    main()
