"""Fleet-scale rounds: 10³ → 10⁶ simulated devices per round.

Sweeps the fleet size of a two-tier stacked topology under the cohort
scheduler (one vectorized batch dispatch per round, v2 counter-based RNG
stream) and a :class:`repro.data.VirtualFleetDataset` whose shards are
generated inside the jit boundary — no per-device Python objects, no
(N, m, dim) host array — and reports per size: devices per round, final
training loss and cloud-uplink bytes (deterministic accounting — gated),
plus warm round wall-clock, devices/second throughput and peak host RSS
(machine-dependent — gate-ignored).  A 64-device record cross-checks the
fleet path against the per-device event scheduler on a shared scenario:
identical virtual times and byte accounting, losses equal to float
tolerance (the equivalence the fleet tests assert).

Quick mode (CI + the committed ``BENCH_fleet.json``) sweeps 10³→10⁵; full
mode adds the 10⁶ record with every metric suffixed ``_ungated`` so a
full-mode refresh never perturbs the quick-mode baseline the gate diffs.

Emits ``name,us_per_call,derived`` rows like every other benchmark module;
``collect()`` returns a JSON-ready dict for ``run.py --json``
(→ ``BENCH_fleet.json``).
"""
from __future__ import annotations

import resource
from typing import Dict, List

import jax

from repro.data import VirtualFleetDataset
from repro.edge import array_bimodal_fleet, bimodal_fleet
from repro.fl import run_hier_simulation
from repro.hier import (HierConfig, stacked_two_tier, two_tier_topology)
from repro.models import get_model
from repro.models.config import ArchConfig
from repro.models.logistic import logistic_apply, logistic_loss

from .common import emit

SEED = 42
QUICK_SIZES = (1_000, 10_000, 100_000)
FULL_SIZES = QUICK_SIZES + (1_000_000,)
DIM, CLASSES, SAMPLES = 16, 4, 16
# in-jit shard buffer cap: above this cohort size the virtual batch update
# runs in chunks (at most two compiled shapes)
COHORT_CHUNK = 131_072


def fleet_params():
    return get_model(ArchConfig(name="lr", family="logreg", input_dim=DIM,
                                num_classes=CLASSES)
                     ).init(jax.random.PRNGKey(0))


def _cfg() -> HierConfig:
    return HierConfig(aggregator="hier_contextual", lr=0.1, mu=0.0,
                      batch_size=8, min_epochs=1, max_epochs=1)


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def fleet_gateways(n_dev: int) -> int:
    return max(4, n_dev // 500)


def fleet_run(n_dev: int, rounds: int, params, *, mesh=None):
    """One cohort-scheduled two-tier run over an ``n_dev`` virtual fleet;
    ``mesh`` (e.g. ``repro.sharding.specs.fleet_mesh()``) shards the cohort
    over its ``'fleet'`` axis."""
    ds = VirtualFleetDataset(num_devices=n_dev, samples_per_device=SAMPLES,
                             dim=DIM, num_classes=CLASSES, seed=3)
    topo = stacked_two_tier(array_bimodal_fleet(n_dev), fleet_gateways(n_dev))
    return run_hier_simulation(
        f"fleet_{n_dev}", logistic_loss, logistic_apply, params, ds,
        _cfg(), topo, num_rounds=rounds, selection_seed=SEED,
        eval_every=rounds, scheduler_mode="cohort", rng_stream="v2",
        mesh=mesh,
        cohort_chunk=COHORT_CHUNK if n_dev > COHORT_CHUNK else None)


def _fleet_record(n_dev: int, rounds: int, params) -> dict:
    gws = fleet_gateways(n_dev)
    r = fleet_run(n_dev, rounds, params)
    steady = r.engine.get("steady_wall_time_per_round_s") or 0.0
    return {
        "scenario": "fleet", "fleet_size": n_dev, "num_gateways": gws,
        "devices_per_round": r.dispatched // rounds,
        "final_train_loss": r.train_loss[-1],
        "cloud_uplink_bytes": r.cloud_uplink_bytes,
        "total_bytes": r.total_bytes,
        "t_virtual_end": r.times[-1],
        # machine-dependent throughput columns (gate-ignored)
        "warm_round_wall_time_ms": steady * 1e3,
        "devices_per_s": (r.dispatched / rounds) / steady if steady else 0.0,
        "peak_rss_mb": _peak_rss_mb(),
        **r.engine,
    }


def _equivalence_record(rounds: int, params) -> dict:
    """Same 64-device/4-gateway scenario down both paths: per-device event
    scheduler over materialized shards vs cohort scheduler over the virtual
    fleet.  Virtual clocks and byte ledgers must agree exactly; losses to
    float tolerance."""
    n_dev, gws = 64, 4
    ds = VirtualFleetDataset(num_devices=n_dev, samples_per_device=SAMPLES,
                             dim=DIM, num_classes=CLASSES, seed=3)
    kw = dict(num_rounds=rounds, selection_seed=SEED, eval_every=rounds,
              rng_stream="v2")
    ev = run_hier_simulation(
        "fleet_eq_event", logistic_loss, logistic_apply, params,
        ds.materialize(), _cfg(), two_tier_topology(bimodal_fleet(n_dev), gws),
        scheduler_mode="event", **kw)
    co = run_hier_simulation(
        "fleet_eq_cohort", logistic_loss, logistic_apply, params, ds, _cfg(),
        stacked_two_tier(array_bimodal_fleet(n_dev), gws),
        scheduler_mode="cohort", **kw)
    gap = max(abs(a - b) for a, b in zip(ev.train_loss, co.train_loss))
    return {
        "scenario": "equivalence_64", "fleet_size": n_dev,
        "num_gateways": gws, "final_train_loss": co.train_loss[-1],
        "loss_gap_vs_event": gap,
        "cloud_uplink_bytes": co.cloud_uplink_bytes,
        "bytes_equal_event_path": co.cloud_uplink_bytes
        == ev.cloud_uplink_bytes and co.total_bytes == ev.total_bytes,
        "times_equal_event_path": co.times == ev.times,
    }


def collect(rounds: int = 3, quick: bool = True) -> Dict[str, List[dict]]:
    """Run the sweep and return JSON-ready records (also used by --json)."""
    params = fleet_params()
    records = [_equivalence_record(rounds, params)]
    for n_dev in QUICK_SIZES:
        records.append(_fleet_record(n_dev, rounds, params))
    if not quick:
        # the 10⁶ demonstration rides gate-ignored metric names so a
        # full-mode refresh never perturbs the quick-mode baseline
        rec = _fleet_record(FULL_SIZES[-1], rounds, params)
        records.append({
            "scenario": "fleet_1m_ungated",
            **{f"{k}_ungated": v for k, v in rec.items()
               if k != "scenario"},
        })
    return {"benchmark": "fleet_scale", "rounds": rounds,
            "records": records}


def run(rounds: int = 3, quick: bool = True) -> Dict[str, List[dict]]:
    results = collect(rounds, quick)
    for rec in results["records"]:
        size = rec.get("fleet_size", rec.get("fleet_size_ungated", 0))
        loss = rec.get("final_train_loss",
                       rec.get("final_train_loss_ungated", float("nan")))
        dps = rec.get("devices_per_s", rec.get("devices_per_s_ungated", 0.0))
        wall = rec.get("warm_round_wall_time_ms",
                       rec.get("warm_round_wall_time_ms_ungated", 0.0))
        derived = f"size={size};loss={loss:.4f}"
        if "loss_gap_vs_event" in rec:
            derived += (f";gap_vs_event={rec['loss_gap_vs_event']:.2e};"
                        f"bytes_equal={rec['bytes_equal_event_path']}")
        else:
            derived += f";devices_per_s={dps:.0f};warm_round={wall:.1f}ms"
        emit(f"fleet_scale/{rec['scenario']}/n{size}", wall * 1e3, derived)
    return results
