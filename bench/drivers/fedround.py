"""Driver for ``kind: fedround`` traffic: back-to-back contextual rounds of
an aggregation server over P clients' updates, through the program's
``StreamedRoundEngine``.

Inputs (made here from the seed, on the device, in one jitted call): the
parameters of the configuration's leaves in float32, and P clients'
updates D and gradient estimates GM in bfloat16.  Each round is
``begin_round`` → one solve per gateway → ``compose_grads`` →
``cloud_combo`` → ``apply``, and ends in ``block_until_ready`` of the new
parameters.  The tier solves run at the matmul precision the configuration
states (``solve_precision``), set through JAX's
``default_matmul_precision`` around the solve stages: the program's own
default on the TPU is one bfloat16 pass.  Every round aggregates the same updates into the same starting
parameters, so each round's answer is the same and the last one is
compared with ``bench/reference/fedround.py``.

The round driver (``round_once``, ``cohorts``) is copied from
``benchmarks/bigmodel_round.py`` so that later changes there cannot move
this benchmark.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import counts
from bench.harness import Check, Context, Outcome, peak_bytes, seed_ints
from bench.reference import fedround as ref


# ------------------------------------------------------------------ inputs

def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """One decoder layer's leaves as the program's model init lays them out
    (per-head q/k norms, float32 router at its published width), with the
    experts this chip holds."""
    d = cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    ff, E = cfg["intermediate_size"], cfg["num_experts"]
    return {
        "attn/wq": (d, H * hd), "attn/wk": (d, KV * hd),
        "attn/wv": (d, KV * hd), "attn/wo": (H * hd, d),
        "attn/q_norm": (hd,), "attn/k_norm": (hd,),
        "ln1": (d,), "ln2": (d,),
        "moe/router": (d, cfg["router_outputs"]),
        "moe/w_gate": (E, d, ff), "moe/w_up": (E, d, ff),
        "moe/w_down": (E, ff, d),
    }


def make_inputs(shapes: Dict[str, Tuple[int, ...]], P: int, seed: int,
                scale: float):
    """(D, GM, params) from ``seed`` in one jitted call.  Client i's update
    has scale ``scale·(1 + i/P)`` and its gradient estimate leans against
    it (``GM = −D/2 + noise``), so the solves see distinct clients."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)

    @jax.jit
    def gen(key):
        row = (scale * (1.0 + jnp.arange(P, dtype=jnp.float32) / P))
        D, GM, params = {}, {}, {}
        for i, name in enumerate(names):
            s = shapes[name]
            kd, kg, kp = jax.random.split(jax.random.fold_in(key, i), 3)
            r = row.reshape((P,) + (1,) * len(s))
            d = jax.random.normal(kd, (P,) + s, jnp.float32) * r
            g = -0.5 * d + jax.random.normal(kg, (P,) + s, jnp.float32) * r
            D[name] = d.astype(jnp.bfloat16)
            GM[name] = g.astype(jnp.bfloat16)
            params[name] = jax.random.normal(kp, s, jnp.float32) * 0.02
        return D, GM, params

    return gen(jax.random.PRNGKey(seed))


def cohorts(P: int, gws: int) -> List[List[int]]:
    per = P // gws
    return [list(range(g * per, (g + 1) * per)) for g in range(gws)]


def round_once(eng, params, deltas, grads, groups, precision):
    """One full tier-tree round through the engine's context API: gateway
    solves → cloud γ stage → combine into the parameters.  The solves run
    at matmul ``precision``."""
    import jax
    ctx = eng.begin_round(deltas, grads)
    with jax.default_matmul_precision(precision):
        sums = [ctx.gateway(c) for c in groups]
        counts_ = [float(len(c)) for c in groups]
        ghat = ctx.compose_grads([s["ghat"] for s in sums], counts_)
        delta, info = ctx.cloud_combo([s["u_bar"] for s in sums], counts_,
                                      ghat)
    new_params = ctx.apply(params, delta)
    return ctx, delta, new_params, info


# ------------------------------------------------------------------ checks

def compare(out: Dict[str, Any], D, GM, params, groups, beta, ridge
            ) -> Dict[str, float]:
    """The round ``out`` (G, C, eff, new params) against the plain
    reference on the same inputs.  G and C entries are scaled by their
    Cauchy-Schwarz bounds; the weights by the largest reference weight; the
    parameters' change per leaf by the reference change's norm (worst
    leaf)."""
    names = sorted(D)
    d_l = [D[k] for k in names]
    g_l = [GM[k] for k in names]
    G, C, gg = ref.statistics(d_l, g_l)
    sol = ref.solve_round(G, C, groups, beta, ridge)
    delta = ref.round_delta(d_l, sol["eff"])
    dn = np.sqrt(np.diag(G))
    gn = np.sqrt(gg)
    Gp = np.asarray(out["G"], np.float64)
    Cp = np.asarray(out["C"], np.float64)
    eff = np.asarray(out["eff"], np.float64)
    worst = 0.0
    for k, dref in zip(names, delta):
        got = np.asarray(out["params"][k], np.float64) - np.asarray(
            params[k], np.float64)
        want = np.asarray(dref, np.float64)
        worst = max(worst, float(np.linalg.norm(got - want)
                                 / max(np.linalg.norm(want), 1e-30)))
    return {
        "G_rel_err": float(np.max(np.abs(Gp - G) / np.outer(dn, dn))),
        "C_rel_err": float(np.max(np.abs(Cp - C) / np.outer(dn, gn))),
        "weights_rel_err": float(np.max(np.abs(eff - sol["eff"]))
                                 / np.max(np.abs(sol["eff"]))),
        "params_rel_err": worst,
    }


def control_round(D, GM, params, groups, beta, ridge) -> Dict[str, Any]:
    """The reference one precision step down (see
    ``bench/reference/fedround.py``), in the program's place."""
    names = sorted(D)
    d_l = [D[k] for k in names]
    G, C, _ = ref.statistics(d_l, [GM[k] for k in names], low=True)
    sol = ref.solve_round(G, C, groups, beta, ridge, low=True)
    delta = ref.round_delta(d_l, sol["eff"], low=True)
    return {"G": G, "C": C, "eff": sol["eff"],
            "params": {k: np.asarray(params[k], np.float64)
                       + np.asarray(dl, np.float64)
                       for k, dl in zip(names, delta)}}


# ------------------------------------------------------------------ window

def build(ctx: Context):
    """Set-up: inputs, the engine, two warm-up rounds (the first compiles
    and autotunes, the second must find everything compiled)."""
    import jax
    from repro.core.solve import SolveConfig
    from repro.hier.streamed import StreamedRoundEngine

    tr = ctx.cell.traffic
    shapes = leaf_shapes(ctx.cell.config)
    P = int(tr["clients"])
    (s_data,) = seed_ints(ctx.seed, 1)
    D, GM, params = make_inputs(shapes, P, s_data, float(tr["update_scale"]))
    jax.block_until_ready((D, GM, params))
    eng = StreamedRoundEngine(params,
                              SolveConfig(beta=float(tr["beta"]),
                                          ridge=float(tr["ridge"])),
                              "contextual", chunk=int(tr["chunk_cols"]))
    groups = cohorts(P, int(tr["gateways"]))
    prec = ctx.cell.config["solve_precision"]
    for _ in range(2):
        _, _, p, _ = round_once(eng, params, D, GM, groups, prec)
        jax.block_until_ready(p)
    return eng, D, GM, params, groups, prec


def run(ctx: Context) -> Outcome:
    import jax
    from repro.kernels import registry

    tr = ctx.cell.traffic
    eng, D, GM, params, groups, prec = build(ctx)
    for r in registry.autotune_records() + registry.static_picks():
        ctx.log(f"kernel pick: {r['op']} {r['bucket']} -> "
                f"{r['backend_selected']}")
    setup_s = time.perf_counter() - ctx.t_process

    trace_at = max(0.0, ctx.seconds / 2 - float(tr["trace_s"]) / 2)
    rounds = traced = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        if not ctx.tracer.running and now >= trace_at:
            ctx.tracer.start()
        elif ctx.tracer.running and now >= trace_at + float(tr["trace_s"]):
            ctx.tracer.stop()
        with ctx.tracer.annotate("bench/round"):
            rctx, delta, new_params, info = round_once(eng, params, D, GM,
                                                       groups, prec)
            jax.block_until_ready(new_params)
        rounds += 1
        traced += ctx.tracer.running
    window_s = time.perf_counter() - t0
    ctx.tracer.stop()
    out = {"G": np.asarray(rctx.G), "C": np.asarray(rctx.C),
           "eff": np.asarray(delta.w),
           "params": {k: np.asarray(v) for k, v in new_params.items()}}
    peak = peak_bytes()
    del rctx, delta, new_params, info, eng

    readings = compare(out, D, GM, params, groups, float(tr["beta"]),
                       float(tr["ridge"]))
    limits = ctx.cell.limits
    checks = [Check(k, readings[k], float(limits[k])) for k in limits]
    n = sum(int(np.prod(s)) for s in leaf_shapes(ctx.cell.config).values())
    return Outcome(
        end_to_end={"rounds_per_s": rounds / window_s, "setup_s": setup_s},
        attempted=rounds, failed=0, checks=checks,
        counters={"rounds": rounds, "window_s": window_s,
                  "traced_rounds": traced, "P": len(sum(groups, [])),
                  "n": n, "itemsize": 2,
                  "round_work": counts.round_work(len(sum(groups, [])), n,
                                                  2)},
        memory_peak_bytes=peak)

