"""Driver for ``kind: serve`` traffic: open-loop requests to the program's
continuous-batching ``DecodeEngine``, which serves a mixture-of-experts
language model published on a ``ModelBus``.

Set-up first maps the choices the configuration states beyond its sizes
(the top-k gate's renormalisation, the scope of the q/k norm) to the
program's ``ArchConfig`` by one table (``CHOICES``), and refuses, before
any weights are made, a configuration the program cannot express.
Weights are made here from the seed, on the device, in one jitted call, in
the program's parameter layout (``jax.eval_shape`` of the program's own
init) and in the types it serves them in.  Traffic comes from the mix's
parameters (:func:`schedule`): every seed sends the same arrival gaps,
prompt lengths and output lengths in the same order, which the mix's
``order_seed`` fixes, and the run's seed draws the prompt ids, so the work
of a run does not depend on its seed.  Set-up warms every program the
window drives (both engine programs, every slot, prompts of one chunk and
of several); the window submits each request when it falls due and steps
the engine between arrivals.  Each request is timed from when it was due.

The mix is judged on its tail: after the window the engine runs on,
without new arrivals, until every request due in the window has finished
(at most ``drain_s`` seconds; one that has not is ``failed``).

``correct``: once the window has closed and the device's peak has been
read, a sample of the finished requests drawn from the seed, the longest
among them, is run through the plain reference
(``bench/reference/moe_lm.py``), each prompt with its served tokens, and
through the engine's own prefill program.  The numbers compared are those
the cell's limits name (``bench/limits/<cell>.json``): the share of served
tokens whose reference logit lies more than 0.3 below the reference's best
at their position, and the same share for the prefill program's first
choice at every position.  The others are logged.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import re
import statistics
import time
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from bench.harness import BenchError, Check, Context, Outcome, peak_bytes, \
    seed_ints
from bench.reference import moe_lm as ref

NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")


# ------------------------------------------------------------------ model

# Each choice the reference makes beyond the sizes: what the configuration
# states it by, its value in ``ref.Arch``, the ``ArchConfig`` field that
# carries it to the program, and what the program does where ``ArchConfig``
# has no such field (``models/moe.py`` renormalises the top-k gate;
# ``models/attention.py::_project_qkv`` normalises q and k per head).
CHOICES = (
    ("norm_topk_prob (top-k gate renormalised)", "norm_topk",
     "norm_topk_prob", True),
    ("model_type (scope of the q/k RMSNorm)", "qk_scope", "qk_norm_scope",
     "head"),
)


def program_config(cfg: Dict[str, Any]):
    """The program's ``ArchConfig`` for the configuration file's published
    sizes and choices (``CHOICES``).  A choice that ``ArchConfig`` has no
    field for, and that differs from what the program does without one, is
    refused: the program cannot serve it as the configuration states."""
    from repro.models.config import ArchConfig
    a = ref.arch(cfg)
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    choices, refused = {}, []
    for stated_by, attr, field, without in CHOICES:
        value = getattr(a, attr)
        if field in fields:
            choices[field] = value
        elif value != without:
            refused.append(f"{stated_by} is {value!r}, but ArchConfig has "
                           f"no field {field!r} (without it the program "
                           f"serves {without!r})")
    if refused:
        raise BenchError(f"configuration {cfg['name']}: the program cannot "
                         "serve it as stated: " + "; ".join(refused))
    return ArchConfig(
        name=cfg["name"], family="moe", num_layers=a.layers, d_model=a.d,
        num_heads=a.heads, num_kv_heads=a.kv_heads, head_dim=a.head_dim,
        d_ff=a.ff, vocab_size=a.vocab, rope_theta=a.theta, qk_norm=True,
        activation=cfg["hidden_act"], num_experts=a.experts,
        experts_per_token=a.top_k, num_shared_experts=0, norm_eps=a.eps,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], **choices)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make_params(pcfg, seed: int):
    """Random weights in the program's layout, from ``seed``, in one jitted
    call: each RMSNorm's stored offset from a scale of one ~ 0.1·N(0, 1),
    the embedding ~ 0.02·N(0, 1), every other matrix ~ N(0, 1/fan_in)."""
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import init_lm

    shapes = jax.eval_shape(lambda k: init_lm(pcfg, k),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def gen(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            name = _leaf_name(path)
            if name in NORMS:
                scale = 0.1
            elif name == "embed":
                scale = 0.02
            else:
                scale = s.shape[-2] ** -0.5
            z = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32)
            out.append((z * scale).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return gen(jax.random.PRNGKey(seed))


def reference_weights(a: "ref.Arch", params) -> Dict[str, Any]:
    """The program's parameters as the reference reads them.  The program
    stores each RMSNorm's scale as its offset from one
    (``repro.models.layers.rms_norm``); a q/k norm stored per head is
    repeated over the heads where the architecture normalises the whole
    projection."""
    import jax.numpy as jnp
    b, attn, moe = params["blocks"], params["blocks"]["attn"], \
        params["blocks"]["moe"]

    def qk(w, heads):
        w = 1.0 + w.astype(jnp.float32)
        if a.qk_scope == "projection" and w.shape[-1] == a.head_dim:
            w = jnp.tile(w, (1, heads))
        return w

    head = params.get("lm_head")
    return {
        "embed": params["embed"],
        "final_norm": 1.0 + params["final_norm"].astype(jnp.float32),
        "head": params["embed"].T if head is None else head,
        "layers": {
            "ln1": 1.0 + b["ln1"].astype(jnp.float32),
            "ln2": 1.0 + b["ln2"].astype(jnp.float32),
            "q_norm": qk(attn["q_norm"], a.heads),
            "k_norm": qk(attn["k_norm"], a.kv_heads),
            "wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"],
            "wo": attn["wo"], "router": moe["router"],
            "w_gate": moe["w_gate"], "w_up": moe["w_up"],
            "w_down": moe["w_down"]},
    }


# ------------------------------------------------------------------ traffic

@dataclass
class Schedule:
    due: np.ndarray          # seconds after the window opens, ascending
    prompts: List[np.ndarray]
    max_new: np.ndarray

    @property
    def n(self) -> int:
        return len(self.due)


def _lognormal_set(n: int, spec: Dict[str, Any]) -> np.ndarray:
    """``n`` lengths at the midpoints of ``n`` equal slices of a lognormal
    (median, sigma), rounded and clipped to [min, max]."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def schedule(tr: Dict[str, Any], seconds: float, seed: int, vocab: int
             ) -> Schedule:
    """The requests due in a window of ``seconds``: ``rate_per_s·seconds``
    of them, Poisson arrivals at that rate.  The gaps are the midpoints of
    equal slices of the exponential distribution, scaled so that the last
    request falls due half a mean gap before the window closes; prompt and
    output lengths likewise from their lognormals.  The mix's
    ``order_seed`` permutes each set, so every run sends them in one order;
    ``seed`` draws the prompt ids (uniform over the vocabulary)."""
    rate = float(tr["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= (seconds - 0.5 / rate) / gaps.sum()
    plen = _lognormal_set(n, tr["prompt_len"])
    nout = _lognormal_set(n, tr["output_len"])
    order = np.random.default_rng(int(tr["order_seed"]))
    gaps, plen, nout = (order.permutation(gaps), order.permutation(plen),
                        order.permutation(nout))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, int(p), dtype=np.int64)
               .astype(np.int32) for p in plen]
    return Schedule(np.cumsum(gaps), prompts, nout)


# ------------------------------------------------------------------ engine

@dataclass
class Setup:
    arch: "ref.Arch"
    params: Any
    engine: Any
    sched: Schedule
    sample_seed: int


def build_engine(ctx: Context, params):
    from repro.serve import DecodeEngine, ModelBus
    sv = ctx.cell.config["serving"]
    return DecodeEngine(
        program_config(ctx.cell.config), ModelBus(params),
        num_slots=int(sv["num_slots"]), max_seq=int(sv["max_seq"]),
        scan_chunk=int(sv["scan_chunk"]),
        prefill_chunk_tokens=int(sv["prefill_chunk_tokens"]),
        greedy=bool(sv["greedy"]))


def warm_up(eng, vocab: int) -> None:
    """Two requests per slot, one of several prefill chunks and several
    decode chunks and one of a single short chunk, run until the engine is
    idle: every program and slot the window uses is compiled and touched."""
    C, T, B = eng.prefill_chunk_tokens, eng.scan_chunk, eng.num_slots
    rng = np.random.default_rng(0)
    for i in range(2 * B):
        plen, new = (C + 1, T + 2) if i % 2 == 0 else (3, 2)
        eng.submit(rng.integers(0, vocab, plen).tolist(), max_new=new)
    eng.run()


def build(ctx: Context) -> Setup:
    """Set-up: weights from the seed, the engine over a ``ModelBus``, the
    warm-up and the window's schedule."""
    import jax
    cfg = ctx.cell.config
    a = ref.arch(cfg)
    s_weights, s_traffic, s_sample = seed_ints(ctx.seed, 3)
    params = make_params(program_config(cfg), s_weights)
    jax.block_until_ready(params)
    eng = build_engine(ctx, params)
    warm_up(eng, a.vocab)
    sched = schedule(ctx.cell.traffic, ctx.seconds, s_traffic, a.vocab)
    return Setup(a, params, eng, sched, s_sample)


# ------------------------------------------------------------------ window

@dataclass
class Window:
    t0: float                     # the window opens, host clock
    done: Dict[int, Any]          # rid -> Completion
    window_s: float
    submitted: int
    lateness: List[float]         # submit time − due time, seconds
    stats: Dict[str, float]       # the engine's counters over the window
    traced: Dict[str, Any]
    long_steps: List[List[float]]  # [seconds, prefill chunks] of steps > 0.5 s


def drive(ctx: Context, eng, sched: Schedule) -> Window:
    tr = ctx.cell.traffic
    trace_s = float(tr["trace_s"])
    trace_at = max(0.0, ctx.seconds / 2 - trace_s / 2)
    done: Dict[int, Any] = {}
    lateness: List[float] = []
    s0 = dict(eng.stats)
    traced: Dict[str, Any] = {}
    long_steps: List[List[float]] = []
    t_stats = None
    nxt = 0
    t0 = time.perf_counter()

    def submit_due(now):
        nonlocal nxt
        with ctx.tracer.annotate("bench/submit"):
            while nxt < sched.n and sched.due[nxt] <= now:
                eng.submit(sched.prompts[nxt].tolist(),
                           max_new=int(sched.max_new[nxt]), rid=nxt)
                lateness.append(time.perf_counter() - t0 - sched.due[nxt])
                nxt += 1

    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        if not ctx.tracer.running and t_stats is None and now >= trace_at:
            ctx.tracer.start()
            t_stats = dict(eng.stats)
        elif ctx.tracer.running and now >= trace_at + trace_s:
            ctx.tracer.stop()
            traced["stats"] = {k: eng.stats[k] - t_stats[k]
                               for k in t_stats}
        submit_due(now)
        if eng.idle:
            until = sched.due[nxt] if nxt < sched.n else ctx.seconds
            with ctx.tracer.annotate("bench/idle"):
                time.sleep(max(0.0, min(until, ctx.seconds) - now))
            continue
        p0, t1 = eng.stats["prefill_chunks"], time.perf_counter()
        with ctx.tracer.annotate("bench/step"):
            for c in eng.step():
                done[c.rid] = c
        if time.perf_counter() - t1 > 0.5:
            long_steps.append([time.perf_counter() - t1,
                               eng.stats["prefill_chunks"] - p0])
    window_s = time.perf_counter() - t0
    if ctx.tracer.running:
        ctx.tracer.stop()
        traced["stats"] = {k: eng.stats[k] - t_stats[k] for k in t_stats}
    stats = {k: eng.stats[k] - s0[k] for k in s0}
    # requests that fell due while the last step ran
    submit_due(ctx.seconds)
    return Window(t0, done, window_s, nxt, lateness, stats, traced,
                  long_steps)


def drain(eng, win: Window, limit_s: float) -> None:
    """Run on without arrivals until every submitted request is done."""
    t_end = time.perf_counter() + limit_s
    while not eng.idle and time.perf_counter() < t_end:
        for c in eng.step():
            win.done[c.rid] = c


# ------------------------------------------------------------------ checks

def check_sample(done: Dict[int, Any], seed: int, min_tokens: int
                 ) -> List[int]:
    """Finished requests to compare: the longest (prompt and output), then
    others in an order drawn from ``seed``, until ``min_tokens`` served
    tokens are in the sample."""
    rids = sorted(done)
    if not rids:
        return []
    size = {r: done[r].prompt_len + len(done[r].tokens) for r in rids}
    longest = max(rids, key=lambda r: (size[r], r))
    order = [longest] + [int(r) for r in np.random.default_rng(seed)
                         .permutation(rids) if r != longest]
    out, n = [], 0
    for r in order:
        if n >= min_tokens:
            break
        out.append(r)
        n += len(done[r].tokens)
    return out


def _bucket(n: int, max_seq: int) -> int:
    b = 256
    while b < n:
        b *= 2
    return min(b, max(max_seq, n))


def _sequence(prompt: np.ndarray, toks: List[int]) -> np.ndarray:
    """A prompt and its served tokens but the last: the positions whose
    logits gave each served token."""
    return np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])


def prefill_logits(eng, seqs) -> List[np.ndarray]:
    """Logits at every position of each prompt with its served tokens, from
    the engine's own compiled prefill program (``_prefill_fn``, the one the
    window drives, at its chunk width, into slot 0 of its cache), on the
    host in the type the program gives them."""
    import jax.numpy as jnp
    C = eng.prefill_chunk_tokens
    cache, out = eng._cache, []
    for prompt, toks in seqs:
        full = _sequence(prompt, toks)
        parts = []
        for start in range(0, len(full), C):
            chunk = np.zeros(C, np.int32)
            part = full[start:start + C]
            chunk[:len(part)] = part
            logits, cache = eng._prefill_fn(
                eng._params, cache, jnp.asarray(chunk),
                jnp.asarray(0, jnp.int32), jnp.asarray(start, jnp.int32))
            parts.append(np.asarray(logits[:len(part)]))
        out.append(np.concatenate(parts))
    eng._cache = cache
    return out


def _summary(gaps: np.ndarray, errs: np.ndarray, pgaps: np.ndarray
             ) -> Dict[str, float]:
    return {
        "served_gap_max": float(np.max(gaps)),
        "served_not_first_pct": float(100.0 * np.mean(gaps > 0)),
        # a bf16 near-tie among the router's top 8 shifts a sound run's
        # token by up to ~0.3-0.6; only a few tokens per thousand lie past
        # 0.3 in sound runs, a few per hundred under fp8 (PERF.md §2)
        "served_gap_over_0.3_pct": float(100.0 * np.mean(gaps > 0.3)),
        "prefill_gap_over_0.3_pct": float(100.0 * np.mean(pgaps > 0.3)),
        "prefill_logit_err": float(np.median(errs)),
        "prefill_not_first_pct": float(100.0 * np.mean(pgaps > 0)),
        "tokens": int(gaps.size), "positions": int(errs.size)}


def compare(a: "ref.Arch", w, seqs, prog: List[np.ndarray], max_seq: int,
            block: int, control: bool = False) -> Dict[str, Dict]:
    """The reference's forward over each prompt with its served tokens,
    against what the program gave: for each served token, how far its
    reference logit lies below the reference's best at its position; for
    every position, the RMS of the prefill program's logit error over the
    RMS of the reference's logits, and how far the reference logit of the
    prefill program's first choice lies below the reference's best.  ``control=True`` reads the same for the control -- the
    reference in float8 -- in the program's place: its first choices as
    served tokens, its logits as the prefill's."""
    import jax.numpy as jnp

    def rel_err(got, want):
        return jnp.sqrt(jnp.mean((got - want) ** 2, -1)
                        / jnp.mean(want ** 2, -1))

    sides = ("program", "control") if control else ("program",)
    acc = {k: ([], [], []) for k in sides}
    for (prompt, toks), p_logits in zip(seqs, prog):
        full = _sequence(prompt, toks)
        n, first_served = len(full), len(prompt) - 1
        x = np.zeros(_bucket(n, max_seq), np.int32)
        x[:n] = full
        picks = np.zeros(n, np.int32)
        picks[first_served:] = toks
        h = ref.hidden(a, w, jnp.asarray(x))
        h_low = ref.hidden(a, w, jnp.asarray(x), low=True) if control \
            else None
        for b0 in range(0, n, block):
            rows = np.minimum(np.arange(b0, b0 + block), n - 1)
            m = min(block, n - b0)
            want = ref.head(a, w["final_norm"], w["head"], h,
                            jnp.asarray(rows))
            best = jnp.max(want, -1)
            served = rows[:m] >= first_served
            for side in sides:
                if side == "program":
                    got = jnp.asarray(p_logits[rows], jnp.float32)
                    pick = jnp.asarray(picks[rows])
                else:
                    got = ref.head(a, w["final_norm"], w["head"], h_low,
                                   jnp.asarray(rows), low=True)
                    pick = jnp.argmax(got, -1)
                gap = best - jnp.take_along_axis(want, pick[:, None],
                                                 -1)[:, 0]
                pgap = best - jnp.take_along_axis(
                    want, jnp.argmax(got, -1)[:, None], -1)[:, 0]
                gaps, errs, pgaps = acc[side]
                gaps.append(np.asarray(gap, np.float64)[:m][served])
                errs.append(np.asarray(rel_err(got, want), np.float64)[:m])
                pgaps.append(np.asarray(pgap, np.float64)[:m])
    return {k: _summary(*(np.concatenate(v) for v in acc[k]))
            for k in sides}


def sample(setup: Setup, done: Dict[int, Any], traffic: Dict[str, Any]):
    """(prompt, served tokens) of the requests :func:`check_sample` picks."""
    rids = check_sample(done, setup.sample_seed, int(traffic["check_tokens"]))
    return [(setup.sched.prompts[r], done[r].tokens) for r in rids]


def readings(setup: Setup, seqs, prog: List[np.ndarray],
             serving: Dict[str, Any], control: bool = False
             ) -> Dict[str, Dict]:
    w = reference_weights(setup.arch, setup.params)
    out = compare(setup.arch, w, seqs, prog, int(serving["max_seq"]),
                  int(serving["prefill_chunk_tokens"]), control=control)
    for v in out.values():
        v["requests"] = len(seqs)
    return out


# ------------------------------------------------------------------ run

def _percentile(x: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q)) if x else \
        float("nan")


def run(ctx: Context) -> Outcome:
    from repro import obs
    tr = ctx.cell.traffic
    sv = ctx.cell.config["serving"]
    setup = build(ctx)
    setup_s = time.perf_counter() - ctx.t_process
    c0 = obs.compiles()
    win = drive(ctx, setup.engine, setup.sched)
    compiles = obs.compiles() - c0
    completed = len(win.done)
    tokens = sum(len(c.tokens) for c in win.done.values())
    drain(setup.engine, win, float(tr["drain_s"]))
    # a request still unfinished counts with the time it has waited
    t_end = time.perf_counter()
    lat = [(win.done[r].t_finish_wall if r in win.done else t_end)
           - win.t0 - setup.sched.due[r] for r in range(win.submitted)]
    failed = win.submitted - len(win.done)
    e2e = {"setup_s": setup_s}
    for m in ctx.cell.end_to_end:
        q = re.fullmatch(r"request_p(\d+)_s", m["name"])
        if q:
            e2e[m["name"]] = _percentile(lat, float(q.group(1)))
    peak = peak_bytes()
    ctx.log(f"serve: {win.submitted} due, {completed} done in the window of "
            f"{win.window_s:.3f} s, {tokens} tokens, failed {failed}, "
            f"compiles in the window {compiles}; generator late by median "
            f"{_percentile(win.lateness, 50) * 1e3:.2f} ms, max "
            f"{max(win.lateness, default=0.0) * 1e3:.2f} ms; steps over "
            f"0.5 s [seconds, prefill chunks]: {win.long_steps}")
    ctx.log("serve: request time p50 {:.3f} s, p85 {:.3f} s, p90 {:.3f} s, "
            "p99 {:.3f} s over {} requests".format(
                _percentile(lat, 50), _percentile(lat, 85),
                _percentile(lat, 90), _percentile(lat, 99), len(lat)))
    counters = {"window_s": win.window_s, "traced": win.traced,
                "stats": win.stats, "completed": completed,
                "window_compiles": compiles}
    if not win.done:
        raise BenchError("no request finished: nothing to compare")
    seqs = sample(setup, win.done, tr)
    prog = prefill_logits(setup.engine, seqs)
    submitted = win.submitted
    # free the engine (its cache) before the reference runs
    setup.engine = win = None
    gc.collect()
    got = readings(setup, seqs, prog, sv)["program"]
    ctx.log("serve: readings " + json.dumps(got))
    checks = [Check(k, got[k], float(v)) for k, v in ctx.cell.limits.items()]
    return Outcome(end_to_end=e2e, attempted=submitted, failed=failed,
                   checks=checks, counters=counters, memory_peak_bytes=peak)
