"""Transformer-width rounds: streamed engine vs dense (P, n) round matrices.

Three scenarios ride one JSON (``BENCH_bigmodel.json``):

  * ``accum_oracle``       — streamed (G, C) accumulation vs the dense f32
    oracle: max-|err| is the regression signal (gated; a chunking bug shows
    up here first).
  * ``logreg_64dev_4gw``   — the headline 64-device/4-gateway hier scenario
    run end-to-end on BOTH engines: the streamed loss must match the fused
    loss within the BENCH_hier tolerance band, byte accounting must match
    exactly, and the warm ms/round ratio (gate-ignored, machine-dependent)
    documents the small-model overhead of streaming.
  * ``transformer_stream`` — a P=16 round over transformer-shaped bf16
    update pytrees (quick ≈ 3.7M params for CI; full ≥ 50M — the regime the
    dense engine cannot hold).  Records the deterministic memory model:
    ``peak_round_matrix_bytes`` (streamed, O(P·chunk + P²)) vs
    ``dense_round_matrix_bytes`` (2·P·n·4), the savings factor, and the
    ``meets_mem_target`` ≤ 25% acceptance bit — all compared near-exactly
    by the regression gate.  In quick mode the streamed round delta is also
    diffed against the fused engine on the same data (max-|err| gated).

Emits ``name,us_per_call,derived`` rows; ``collect()`` returns the JSON
records for ``run.py --json``.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solve import SolveConfig
from repro.hier.fused import HierRoundEngine
from repro.hier.streamed import StreamedRoundEngine, dense_round_bytes
from repro.kernels import ops

from .common import emit

SEED = 42
P_ROUND = 16
GATEWAYS = 4
CHUNK = 1 << 18          # 2·16·262144·4 B ≈ 33.5 MB streamed working set
# transformer-shaped rounds: (d_model, vocab, layers)
QUICK_SHAPE = (256, 2048, 4)      # ≈ 3.7M params (CI-sized)
FULL_SHAPE = (1024, 8192, 4)      # ≈ 58.7M params


def transformer_stacked(d_model: int, vocab: int, layers: int, P: int,
                         dtype=jnp.bfloat16, seed: int = 0):
    """Stacked transformer-shaped update/gradient pytrees (leading P axis),
    bf16 like real training deltas; f32 accumulation happens per chunk."""
    shapes = {"embed": (vocab, d_model)}
    for l in range(layers):
        for w in ("wq", "wk", "wv", "wo"):
            shapes[f"layer{l}/{w}"] = (d_model, d_model)
        shapes[f"layer{l}/w_up"] = (d_model, 4 * d_model)
        shapes[f"layer{l}/w_down"] = (4 * d_model, d_model)
        shapes[f"layer{l}/ln"] = (d_model,)
    key = jax.random.PRNGKey(seed)

    def draw(i, shape):
        return (0.01 * jax.random.normal(jax.random.fold_in(key, i),
                                         (P,) + shape)).astype(dtype)

    deltas = {k: draw(i, s) for i, (k, s) in enumerate(shapes.items())}
    grads = {k: draw(i + len(shapes), s)
             for i, (k, s) in enumerate(shapes.items())}
    template = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
    n = sum(int(np.prod(s)) for s in shapes.values())
    return deltas, grads, template, n


def cohorts(P: int, gws: int) -> List[List[int]]:
    per = P // gws
    return [list(range(g * per, (g + 1) * per)) for g in range(gws)]


def round_once(eng, template, deltas, grads, cohorts):
    """One full tier-tree round through the engine-agnostic context API:
    gateway solves → cloud γ stage → combine into the parameters."""
    ctx = eng.begin_round(deltas, grads)
    sums = [ctx.gateway(c) for c in cohorts]
    counts = [float(len(c)) for c in cohorts]
    ghat = ctx.compose_grads([s["ghat"] for s in sums], counts)
    delta, info = ctx.cloud_combo([s["u_bar"] for s in sums], counts, ghat)
    new_params = ctx.apply(template, delta)
    return ctx, delta, new_params, info


def _time_rounds(eng, template, deltas, grads, cohorts, reps: int) -> float:
    _, _, p, _ = round_once(eng, template, deltas, grads, cohorts)
    jax.block_until_ready(p)                      # warm-up pays the compiles
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _, _, p, _ = round_once(eng, template, deltas, grads, cohorts)
        jax.block_until_ready(p)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _accum_oracle_record(quick: bool) -> dict:
    P, n = 12, (1 << 16) + 77 if quick else (1 << 20) + 77
    key = jax.random.PRNGKey(3)
    D = jax.random.normal(key, (P, n), jnp.float32)
    GM = jax.random.normal(jax.random.fold_in(key, 1), (P, n), jnp.float32)
    G0, C0 = ops.stream_stats(D, GM, backend="ref")
    G1, C1 = ops.stream_stats(D, GM, backend="xla", block_n=1 << 13)
    return {
        "scenario": "accum_oracle", "num_rows": P, "num_cols": n,
        "accum_max_abs_err_G": float(jnp.max(jnp.abs(G1 - G0))),
        "accum_max_abs_err_C": float(jnp.max(jnp.abs(C1 - C0))),
    }


def logreg_fleet_problem():
    """The headline 64-device / 4-gateway bimodal logreg fleet:
    ``(dataset, params, HierConfig, topology)`` for ``run_hier_simulation``
    with ``logistic_loss`` / ``logistic_apply``."""
    from repro.data import make_synthetic
    from repro.data.federated import FederatedDataset
    from repro.edge import bimodal_fleet
    from repro.hier import HierConfig, two_tier_topology
    from repro.models import get_model
    from repro.models.config import ArchConfig

    n_dev = 64
    xs, ys = make_synthetic(1.0, 1.0, num_devices=n_dev,
                            samples_per_device=60, dim=60, seed=0)
    ds = FederatedDataset(xs, ys, np.ones(ys.shape, np.float32),
                          xs.reshape(-1, xs.shape[-1])[:400],
                          ys.reshape(-1)[:400], 10)
    params = get_model(ArchConfig(name="lr", family="logreg", input_dim=60,
                                  num_classes=10)).init(jax.random.PRNGKey(0))
    fleet = bimodal_fleet(n_dev, slowdown=10.0, dropout_slow=0.05, seed=0)
    cfg = HierConfig(aggregator="hier_contextual", lr=0.2, batch_size=10,
                     min_epochs=1, max_epochs=10)
    return ds, params, cfg, two_tier_topology(fleet, GATEWAYS)


def _logreg_record(rounds: int) -> dict:
    from repro.fl import run_hier_simulation
    from repro.models.logistic import logistic_apply, logistic_loss

    ds, params, cfg, topo = logreg_fleet_problem()
    runs = {}
    for engine in ("fused", "streamed"):
        runs[engine] = run_hier_simulation(
            engine, logistic_loss, logistic_apply, params, ds, cfg, topo,
            num_rounds=rounds, selection_seed=SEED, eval_every=rounds,
            engine=engine)
    rf, rs = runs["fused"], runs["streamed"]
    warm_f = rf.engine["steady_wall_time_per_round_s"]
    warm_s = rs.engine["steady_wall_time_per_round_s"]
    return {
        "scenario": "logreg_64dev_4gw", "gateways": GATEWAYS,
        "bench_rounds": rounds,
        "final_loss_fused": rf.train_loss[-1],
        "final_loss_streamed": rs.train_loss[-1],
        "loss_gap_streamed_vs_fused": abs(rs.train_loss[-1]
                                          - rf.train_loss[-1]),
        "cloud_uplink_bytes_fused": rf.cloud_uplink_bytes,
        "cloud_uplink_bytes_streamed": rs.cloud_uplink_bytes,
        # machine-dependent (gate-ignored): the ≤1.25× small-model criterion
        "fused_steady_wall_time_per_round_s": warm_f,
        "streamed_steady_wall_time_per_round_s": warm_s,
        "streamed_vs_fused_warm_wall_time_ratio": warm_s / max(warm_f, 1e-9),
    }


def _transformer_record(quick: bool) -> dict:
    d_model, vocab, layers = QUICK_SHAPE if quick else FULL_SHAPE
    deltas, grads, template, n = transformer_stacked(d_model, vocab, layers,
                                                     P_ROUND, seed=1)
    cfg = SolveConfig(beta=5.0, ridge=1e-6)
    groups = cohorts(P_ROUND, GATEWAYS)
    seng = StreamedRoundEngine(template, cfg, "contextual", chunk=CHUNK)
    secs = _time_rounds(seng, template, deltas, grads, groups,
                        reps=2 if quick else 3)
    peak = seng.peak_round_bytes(P_ROUND)
    dense = dense_round_bytes(P_ROUND, n)
    rec = {
        "scenario": "transformer_stream", "gateways": GATEWAYS,
        "num_params": n, "num_devices_round": P_ROUND, "chunk_cols": CHUNK,
        "peak_round_matrix_bytes": peak,
        "dense_round_matrix_bytes": dense,
        "peak_savings_vs_dense": dense / peak,
        "meets_mem_target": bool(peak <= 0.25 * dense),
        "streamed_round_time_s": secs,
    }
    if quick:
        # CI-sized: the dense engine still fits — diff the round deltas
        feng = HierRoundEngine(template, cfg, "contextual")
        ctx, sdelta, _, _ = round_once(seng, template, deltas, grads,
                                       groups)
        _, fdelta, _, _ = round_once(feng, template, deltas, grads,
                                     groups)
        rec["delta_max_abs_err"] = float(jnp.max(jnp.abs(
            ctx.materialize(sdelta) - fdelta)))
    return rec


def collect(rounds: int = 16, quick: bool = False) -> Dict[str, List[dict]]:
    records = [_accum_oracle_record(quick), _logreg_record(rounds),
               _transformer_record(quick)]
    return {"benchmark": "bigmodel_round", "quick": quick,
            "rounds": rounds, "records": records}


def run(rounds: int = 16, quick: bool = False) -> Dict[str, List[dict]]:
    results = collect(rounds, quick)
    for rec in results["records"]:
        if rec["scenario"] == "accum_oracle":
            derived = (f"errG={rec['accum_max_abs_err_G']:.2e};"
                       f"errC={rec['accum_max_abs_err_C']:.2e}")
            us = 0.0
        elif rec["scenario"] == "logreg_64dev_4gw":
            derived = (f"gap={rec['loss_gap_streamed_vs_fused']:.4f};"
                       f"warm_ratio="
                       f"{rec['streamed_vs_fused_warm_wall_time_ratio']:.2f}")
            us = rec["streamed_steady_wall_time_per_round_s"] * 1e6
        else:
            derived = (f"n={rec['num_params']};"
                       f"peak={rec['peak_round_matrix_bytes'] / 2 ** 20:.1f}MB;"
                       f"dense={rec['dense_round_matrix_bytes'] / 2 ** 20:.1f}MB;"
                       f"savings={rec['peak_savings_vs_dense']:.1f}x;"
                       f"meets25%={rec['meets_mem_target']}")
            us = rec["streamed_round_time_s"] * 1e6
        emit(f"bigmodel_round/{rec['scenario']}", us, derived)
    return results
