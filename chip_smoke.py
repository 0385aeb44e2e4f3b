"""Bring-up check: the system's two halves on one TPU, through their normal
entry points, at full model width.

    python chip_smoke.py              # one chip: serve, train, aggregate
    python chip_smoke.py --chips 4    # one host of four chips: the
                                      # fleet-sharded cohort run vs one chip

Phases (one process, in order):

  * ``serve``     — qwen3-14b at its published widths in bf16, depth cut to
    8 of 40 layers, random weights from a seed, through ``ModelBus`` +
    ``DecodeEngine`` exactly as ``python -m repro.launch.serve`` runs it.
    Checks: every request returns its tokens, the teacher-forced logits of
    the generated sequences are finite and rank every greedy pick at (or
    within bf16 noise of) the top, and the Pallas ``flash_decode`` kernel
    agrees with the reference at the engine's shapes.
  * ``train``     — ``run_hier_simulation`` (the paper's path) on the
    64-device / 4-gateway bimodal logreg fleet, fused and streamed round
    engines: losses agree within the bench-regression band, loss falls.
  * ``aggregate`` — one ``StreamedRoundEngine`` round over ≈58.7M-parameter
    transformer-shaped bf16 updates (P=16): (G, C) against the ``xla``
    ``stream_stats`` on the same arrays.

Each phase prints its compile and steady time, the device's
``peak_bytes_in_use`` (process maximum so far) and the backend every kernel
op resolved to, with a reason for any that is not ``pallas``.  The last
line of stdout is one JSON object naming the device.  There is no CPU path:
without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import bigmodel_round, fleet_scale  # noqa: E402
from benchmarks.check_regression import LOOSE_ABS, LOOSE_REL  # noqa: E402
from benchmarks.common import use_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.solve import SolveConfig  # noqa: E402
from repro.fl import run_hier_simulation  # noqa: E402
from repro.hier.streamed import StreamedRoundEngine  # noqa: E402
from repro.kernels import ops, registry  # noqa: E402
from repro.launch.serve import build_engine, serve_random_prompts  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.models.logistic import logistic_apply, logistic_loss  # noqa: E402
from repro.sharding.specs import fleet_mesh  # noqa: E402

# full-width serving: qwen3-14b, depth cut to fit one 16 GB chip
SERVE_LAYERS = 8
SERVE_SLOTS, SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW = 8, 8, 128, 32
# flash_decode vs an f32 reference at "highest" matmul precision, on bf16
# N(0, 1) inputs: one bf16 ulp at unit scale
DECODE_KERNEL_TOL = 2.0 ** -7
# a greedy pick may trail the reference top logit by this share of the
# row's (max - mean) spread: bf16 noise between the decode path and the
# full-sequence forward, far below what a wrong kernel gives (a random token
# trails by about the whole spread)
GREEDY_SLACK = 0.1
TRAIN_ROUNDS = 3
FLEET4_DEVICES, FLEET4_ROUNDS = 100_000, 3
FLEET4_LOSS_TOL = 1e-5          # the fleet-sharding parity band
# what the sharded run's kernel ops resolve to, and why (run_hier_simulation
# pins them; no autotune record is made)
SHARDED_KERNELS = ("xla: Pallas TPU kernels cannot be partitioned over a "
                   "multi-device mesh")


class SmokeFailure(RuntimeError):
    """A phase's output failed its check."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_devices(count: int):
    """The device guard: TPU only, exactly ``count`` chips.  Exits non-zero
    (printing why) on anything else — there is no CPU path."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != count:
        print(f"chip_smoke: needs {count} TPU device(s), JAX reports "
              f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})",
              file=sys.stderr)
        raise SystemExit(1)
    return devs


def peak_bytes(device=None):
    """``peak_bytes_in_use`` of ``device`` (default: device 0) — the process
    maximum so far — or None where the backend reports no memory stats."""
    stats = (device or jax.devices()[0]).memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------- serve

@functools.partial(jax.jit, static_argnums=2)
def _greedy_agreement(logits, tokens, prompt_len: int):
    """Per generated position t: how far the engine's pick tokens[t+1]
    trails the reference top logit, as a share of the row's max - mean."""
    logits = logits.astype(jnp.float32)                   # (R, S, V)
    rows = logits[:, prompt_len - 1:-1]                   # predict t+1
    picks = tokens[:, prompt_len:]
    top = jnp.max(rows, axis=-1)
    spread = jnp.maximum(top - jnp.mean(rows, axis=-1), 1e-30)
    picked = jnp.take_along_axis(rows, picks[..., None], axis=-1)[..., 0]
    return {"all_finite": jnp.all(jnp.isfinite(logits)),
            "max_trail_share": jnp.max((top - picked) / spread),
            "argmax_match": jnp.mean(
                (jnp.argmax(rows, axis=-1) == picks).astype(jnp.float32))}


def phase_serve(cfg, *, slots: int, requests: int, prompt_len: int,
                new_tokens: int) -> dict:
    """Serve ``requests`` random prompts twice through the engine (cold,
    then warm) and check the second batch against the model's
    full-sequence forward."""
    bundle = get_model(cfg)
    max_seq = prompt_len + new_tokens
    t0 = time.perf_counter()
    eng = build_engine(cfg, bundle, slots=slots, max_seq=max_seq)
    params = eng.bus.snapshot().params
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    _, done, cold_s = serve_random_prompts(
        eng, requests=requests, prompt_len=prompt_len,
        new_tokens=new_tokens, seed=1)
    _check(len(done) == requests, f"cold serve returned {len(done)} of "
                                  f"{requests} requests")
    prompts, done, steady_s = serve_random_prompts(
        eng, requests=requests, prompt_len=prompt_len,
        new_tokens=new_tokens, seed=2)
    _check(len(done) == requests, f"serve returned {len(done)} of "
                                  f"{requests} requests")
    for c in done:
        _check(len(c.tokens) == new_tokens,
               f"request {c.rid}: {len(c.tokens)} tokens, want {new_tokens}")
        _check(all(0 <= t < cfg.vocab_size for t in c.tokens),
               f"request {c.rid}: token id out of range")
    seqs = jnp.asarray([list(prompts[c.rid]) + c.tokens
                        for c in sorted(done, key=lambda c: c.rid)],
                       jnp.int32)
    logits = jax.jit(bundle.forward)(params, {"tokens": seqs})
    agree = {k: float(v) for k, v in
             _greedy_agreement(logits, seqs, prompt_len).items()}
    del logits
    _check(agree["all_finite"] == 1.0, "non-finite logits")
    _check(agree["max_trail_share"] <= GREEDY_SLACK,
           f"a greedy pick trails the reference top logit by "
           f"{agree['max_trail_share']:.3f} of the spread "
           f"(slack {GREEDY_SLACK})")

    # the decode kernel alone, at the engine's shapes, against the oracle
    hd, KV = cfg.resolved_head_dim, cfg.num_kv_heads
    key = jax.random.PRNGKey(3)
    dt = jnp.dtype(cfg.dtype)
    q = jax.random.normal(key, (slots, KV, cfg.num_heads // KV, hd), dt)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (slots, max_seq, KV, hd), dt)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (slots, max_seq, KV, hd), dt)
    lengths = jnp.linspace(1, max_seq, slots).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        o_ref, lse_ref = ops.flash_decode(q, k, v, lengths, backend="ref")
    o, lse = ops.flash_decode(q, k, v, lengths, backend="pallas")
    kernel_err = max(float(jnp.max(jnp.abs(o - o_ref))),
                     float(jnp.max(jnp.abs(lse - lse_ref))))
    _check(kernel_err <= DECODE_KERNEL_TOL,
           f"flash_decode max|err| {kernel_err} vs ref")

    tokens = requests * new_tokens
    return {"init_s": init_s, "cold_s": cold_s, "steady_s": steady_s,
            "compile_s": cold_s - steady_s,
            "steady_tokens_per_s": tokens / steady_s,
            "decode_steps": eng.stats["decode_steps"],
            "prefill_chunks": eng.stats["prefill_chunks"],
            "flash_decode_max_abs_err": kernel_err, **agree}


# --------------------------------------------------------------------- train

def phase_train(*, rounds: int) -> dict:
    """The paper's hierarchical path on the 64-device / 4-gateway fleet,
    each engine run twice (cold, then warm): identical reruns, losses of the
    two engines within the bench band, and the loss falls."""
    ds, params, cfg, topo = bigmodel_round.logreg_fleet_problem()
    out = {}
    losses = {}
    for engine in ("fused", "streamed"):
        walls, runs = [], []
        for rep in ("cold", "warm"):
            t0 = time.perf_counter()
            # eval_every=1: every round's loss reaches the host, so the
            # wall time covers the device work
            r = run_hier_simulation(
                f"{engine}_{rep}", logistic_loss, logistic_apply, params, ds,
                cfg, topo, num_rounds=rounds,
                selection_seed=bigmodel_round.SEED, eval_every=1,
                engine=engine)
            walls.append(time.perf_counter() - t0)
            runs.append(r)
        _check(runs[0].train_loss == runs[1].train_loss,
               f"{engine}: rerun changed the losses")
        loss = runs[1].train_loss
        _check(all(np.isfinite(loss)), f"{engine}: non-finite loss {loss}")
        _check(loss[-1] < loss[0], f"{engine}: loss did not fall: {loss}")
        losses[engine] = loss
        out[f"{engine}_cold_s"] = walls[0]
        out[f"{engine}_steady_s_per_round"] = walls[1] / rounds
        out[f"{engine}_compile_s"] = walls[0] - walls[1]
        out[f"{engine}_losses"] = loss
        out[f"{engine}_cloud_uplink_bytes"] = runs[1].cloud_uplink_bytes
    f, s = losses["fused"][-1], losses["streamed"][-1]
    band = max(abs(f) * LOOSE_REL, LOOSE_ABS)
    _check(abs(f - s) <= band,
           f"streamed loss {s} vs fused {f}: outside the band {band}")
    _check(out["fused_cloud_uplink_bytes"]
           == out["streamed_cloud_uplink_bytes"],
           "fused/streamed byte ledgers differ")
    out["loss_gap_streamed_vs_fused"] = abs(f - s)
    return out


# ----------------------------------------------------------------- aggregate

def phase_aggregate(*, shape, P: int, chunk: int) -> dict:
    """One streamed round at transformer width, twice (cold, then warm);
    (G, C) checked against the ``xla`` stream_stats on the same arrays."""
    deltas, grads, template, n = bigmodel_round.transformer_stacked(
        *shape, P, seed=1)
    jax.block_until_ready((deltas, grads))
    eng = StreamedRoundEngine(template, SolveConfig(beta=5.0, ridge=1e-6),
                              "contextual", chunk=chunk)
    groups = bigmodel_round.cohorts(P, bigmodel_round.GATEWAYS)
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        ctx, _, new_params, _ = bigmodel_round.round_once(
            eng, template, deltas, grads, groups)
        jax.block_until_ready(new_params)
        walls.append(time.perf_counter() - t0)
    G, C = ctx.G, ctx.C
    G0 = jnp.zeros((P, P), jnp.float32)
    C0 = jnp.zeros((P, P), jnp.float32)
    for d, g in zip(jax.tree_util.tree_leaves(deltas),
                    jax.tree_util.tree_leaves(grads)):
        Gp, Cp = ops.stream_stats(d.reshape(P, -1), g.reshape(P, -1),
                                  backend="xla", block_n=chunk)
        G0, C0 = G0 + Gp, C0 + Cp
    # G entries against their Cauchy-Schwarz scale sqrt(G_ii G_jj)
    diag = jnp.sqrt(jnp.diag(G0))
    g_err = float(jnp.max(jnp.abs(G - G0) / jnp.outer(diag, diag)))
    c_err = float(jnp.max(jnp.abs(C - C0)) / jnp.max(jnp.abs(C0)))
    _check(bool(jnp.all(jnp.isfinite(new_params["embed"]))),
           "non-finite combined params")
    _check(g_err < 1e-4 and c_err < 1e-4,
           f"streamed (G, C) vs xla: rel err G {g_err}, C {c_err}")
    return {"num_params": n, "P": P, "chunk_cols": chunk,
            "update_bytes": sum(x.nbytes for x in
                                jax.tree_util.tree_leaves((deltas, grads))),
            "cold_s": walls[0], "steady_s": walls[1],
            "compile_s": walls[0] - walls[1],
            "G_rel_err_vs_xla": g_err, "C_rel_err_vs_xla": c_err}


# ---------------------------------------------------------------- four chips

def phase_fleet4(*, n_dev: int, rounds: int) -> dict:
    """The cohort-scheduled fleet run sharded over every local device
    (``fleet_mesh()``) against the same run on one device: byte ledgers
    equal, losses within the parity band, per-device peak bytes."""
    params = fleet_scale.fleet_params()
    t0 = time.perf_counter()
    sharded = fleet_scale.fleet_run(n_dev, rounds, params, mesh=fleet_mesh())
    sharded_s = time.perf_counter() - t0
    peaks_sharded = [peak_bytes(d) for d in jax.devices()]
    t0 = time.perf_counter()
    single = fleet_scale.fleet_run(n_dev, rounds, params)
    single_s = time.perf_counter() - t0
    gap = max(abs(a - b) for a, b in zip(sharded.train_loss,
                                         single.train_loss))
    _check(sharded.cloud_uplink_bytes == single.cloud_uplink_bytes
           and sharded.total_bytes == single.total_bytes,
           "sharded/single byte ledgers differ")
    _check(gap <= FLEET4_LOSS_TOL, f"sharded vs single loss gap {gap}")
    return {"fleet_size": n_dev, "rounds": rounds,
            "devices": len(jax.devices()),
            "sharded_kernel_backend": SHARDED_KERNELS,
            "sharded_wall_s": sharded_s, "single_wall_s": single_s,
            "loss_gap": gap, "final_loss": sharded.train_loss[-1],
            "cloud_uplink_bytes": sharded.cloud_uplink_bytes,
            "total_bytes": sharded.total_bytes,
            "peak_bytes_after_sharded": peaks_sharded,
            "peak_bytes_after_single": [peak_bytes(d)
                                        for d in jax.devices()]}


# -------------------------------------------------------------------- report

def backend_reason(rec: dict) -> str:
    """Why a resolved kernel op is not on ``pallas`` ('' when it is)."""
    chosen = rec["backend_selected"]
    if chosen == "pallas":
        return ""
    if "pallas" not in ops.backends(rec["op"]):
        return "no pallas kernel registered for this op"
    if not ops.on_tpu():
        return "off the TPU pallas runs only in interpret mode, never picked"
    if "us_per_call_pallas" in rec:
        return (f"autotune: {chosen} {rec[f'us_per_call_{chosen}']:.1f} us "
                f"beat pallas {rec['us_per_call_pallas']:.1f} us")
    return "pallas supports() rejects this shape"


def _kernel_picks() -> dict:
    """(op, bucket) -> record for every autotuned and static pick so far."""
    return {(r["op"], r["bucket"]): r for r in
            registry.autotune_records() + registry.static_picks()}


_CACHE_EVENTS = {"hits": 0}


def _count_cache_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_EVENTS["hits"] += 1


def run_phase(name: str, fn, **kw) -> dict:
    """Run one phase and print its result, device peak bytes and the
    kernel picks it made."""
    before = _kernel_picks()
    hits0 = _CACHE_EVENTS["hits"]
    t0 = time.perf_counter()
    result = fn(**kw)
    result["phase_wall_s"] = time.perf_counter() - t0
    result["peak_bytes_in_use"] = peak_bytes()
    result["compile_cache_hits"] = _CACHE_EVENTS["hits"] - hits0
    print(f"[{name}] " + json.dumps(result, default=float), flush=True)
    picks = [r for key, r in _kernel_picks().items() if key not in before]
    for r in picks:
        why = backend_reason(r)
        print(f"[{name}] kernel {r['op']} {r['bucket']} -> "
              f"{r['backend_selected']}" + (f"  ({why})" if why else ""),
              flush=True)
    result["kernel_picks"] = picks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fleet-sharded phase on a "
                         "four-chip host")
    args = ap.parse_args(argv)
    devs = require_devices(args.chips)
    use_compile_cache()
    jax.monitoring.register_event_listener(_count_cache_event)
    print(f"device: {devs[0].device_kind} x {len(devs)}  jax {jax.__version__}"
          f"  compile cache: {jax.config.jax_compilation_cache_dir}",
          flush=True)

    if args.chips == 4:
        run_phase("fleet4", phase_fleet4, n_dev=FLEET4_DEVICES,
                  rounds=FLEET4_ROUNDS)
    else:
        cfg = get_config("qwen3-14b").with_overrides(num_layers=SERVE_LAYERS)
        serve = run_phase("serve", phase_serve, cfg=cfg, slots=SERVE_SLOTS,
                          requests=SERVE_REQUESTS, prompt_len=SERVE_PROMPT,
                          new_tokens=SERVE_NEW)
        decode = {r["backend_selected"] for r in serve["kernel_picks"]
                  if r["op"] == "flash_decode"}
        _check(decode == {"pallas"},
               f"serving decode resolved flash_decode to {decode}")
        run_phase("train", phase_train, rounds=TRAIN_ROUNDS)
        run_phase("aggregate", phase_aggregate,
                  shape=bigmodel_round.FULL_SHAPE, P=bigmodel_round.P_ROUND,
                  chunk=bigmodel_round.CHUNK)

    print(json.dumps({"ok": True,
                      "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}}))


if __name__ == "__main__":
    main()
