"""Streamed hierarchical round engine — big-model rounds without (P, n)
round matrices.

The fused engine (``repro.hier.fused``) flattens a round's P client updates
into dense (P, n) f32 matrices.  At logreg width that is the fastest thing
to do; at transformer width it means holding P extra full-width f32 model
copies (plus another P for the gradient estimates) just to run K×K solves.
This engine exploits the identity the whole tier tree already lives on:

    every Gram block, c-term and combined update of EVERY tier is a pure
    function of the device-level pair  G = D Dᵀ,  C = D GMᵀ  ∈ R^{P×P}
    and small per-tier weight vectors.

Concretely, a gateway cohort's Gram is a sub-block ``G[idx][:, idx]``; its
c-term is a row-mix ``C[idx] @ w`` (ĝ estimates are weighted means of GM
rows); a parent tier over child combinations ``ū_g = α_g @ U_g`` has Gram
``W G Wᵀ`` where row g of W scatters α_g — and the cloud's final step is a
single effective row-mix ``Σ_g γ_g α_g`` applied to D.  So one streamed
pass over the parameter axis (leaf-aligned column chunks through the
``stream_stats`` kernel op — XLA ``lax.scan`` off-TPU, the Pallas tile
kernel on TPU) accumulates everything the round needs, the tier solves run
in P-dimensional space, and a second streamed pass writes ``α @ U``
leaf-by-leaf into the (donated, off-CPU) parameter buffers.  Peak
round-matrix memory is O(P·chunk + P²) instead of O(P·n).

Payload vectors (ū_g, ĝ_g) are **symbolic** :class:`RowMix` refs — weight
vectors over the round's P rows — until something genuinely needs n floats.
That something is the compression pipeline (``repro.compress``): sketch/
top-k encodes and error-feedback residuals consume real vectors, so
``materialize`` produces them with one chunked combine (the sketch itself
stays streaming — the counter-based RNG sketch never materializes R).
Above the first compression hop, decoded summaries are dense (n,) vectors
again; those merges delegate to the fused ``stack=True`` stages over the
small (#children, n) stacks the dense pipeline also holds.  Per-sender EF
residuals likewise remain O(#senders · n) exactly as in the dense path —
#senders is the gateway count, not P.

``run_hier_simulation`` selects this engine automatically when the dense
footprint ``2·P·n·4`` bytes exceeds ``REPRO_DENSE_ROUND_BYTES`` (default
1 GiB); ``engine=`` overrides.  Numerical parity with the fused/reference
stages (same solves, same info keys, f32 accumulation in a different
summation order) is pinned by ``tests/test_streamed_engine.py``.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.flatten import ChunkedFlatView, mix_rows
from ..core.solve import SolveConfig, bound_value, solve_alpha
from ..kernels.registry import force_backend, select_impl_for
from ..obs import spans
from . import fused as _fused

Pytree = Any

DEFAULT_CHUNK = 1 << 16
# autotune candidates are timed on specs capped to this many columns: the
# backend that wins at 4M columns wins at 400M (same memory-bound regime),
# and timing must never allocate a transformer-width dense zero array
AUTOTUNE_CAP_COLS = 1 << 22


def dense_round_bytes(P: int, n: int) -> float:
    """What the dense engine's round matrices would occupy: D + GM f32."""
    return float(2 * P * n * 4)


@dataclass
class RowMix:
    """A symbolic n-vector: ``w`` weights over the round's P stacked rows of
    the update (``src='delta'``) or gradient (``src='grad'``) pytree.  All
    uncompressed tier payloads are RowMixes; composition up the tree is
    P-dimensional algebra and never touches the parameter axis."""
    w: Any                      # (P,) numpy or jax array
    src: str                    # 'delta' | 'grad'


def _is_mix(ref) -> bool:
    return isinstance(ref, RowMix)


# ---------------------------------------------------------------------------
# process-wide compiled-stage caches (mirrors fused._STAGES)
# ---------------------------------------------------------------------------

_STAGES: Dict[Tuple, Callable] = {}
_ACCUM: Dict[Tuple, Callable] = {}


def clear_stage_cache() -> None:
    _STAGES.clear()
    _ACCUM.clear()


def _adjust(cfg: SolveConfig, *, scale: float = 1.0,
            sum_to: Optional[float] = None) -> SolveConfig:
    if scale != 1.0:
        cfg = replace(cfg, expectation_scale=cfg.expectation_scale * scale)
    if sum_to is not None:
        cfg = replace(cfg, sum_to=sum_to)
    return cfg


def _solve_info(Gs, c, cfg, mode, wts):
    """The per-tier solve + diagnostics shared by every streamed stage —
    the same math (and the same ``fused.solve_diagnostics`` info keys) as
    ``fused.summary_stage``'s body."""
    if mode == "contextual":
        alpha = solve_alpha(Gs, c, cfg)
        info = _fused.solve_diagnostics(Gs, c, alpha, cfg.beta)
    else:                                       # "mean" (hier-FedAvg tier)
        alpha = wts
        info = {"bound": bound_value(Gs, c, alpha, cfg.beta)}
    return alpha, info


def _cloud_solve_info(Gs, c, cfg):
    """Final-tier contextual solve + the cloud info keys (γ alias,
    gram_diag) — shared by the raw and combo cloud stages, mirroring
    ``fused.cloud_stage``'s body."""
    gamma = solve_alpha(Gs, c, cfg)
    info = {"alpha": gamma, "gamma": gamma,
            **_fused.solve_diagnostics(Gs, c, gamma, cfg.beta),
            "gram_diag": jnp.diag(Gs)}
    return gamma, info


def gateway_stage_for(P: int, K: int, solve_cfg: SolveConfig, mode: str, *,
                      pool_scale: float = 1.0, robust=None) -> Callable:
    """Device-tier stage over row indices: ``fn(G, C, idx (K,), counts,
    g_w?) -> {G, c, alpha, u_w, ghat_w, info}``.

    With ``robust`` (a RobustConfig) the cohort's cross sub-block
    ``C[idx][:, idx]`` — exactly the fused engine's ``Us @ GRsᵀ`` — feeds
    clip + pooling before the solve; the shipped ĝ mix stays the plain
    weighted mean (the streamed statistics hold no per-member grad norms,
    and fused/streamed parity pins that choice)."""
    if robust is not None and (mode != "contextual"
                               or not getattr(robust, "enabled", False)):
        robust = None
    key = ("stier", P, K, solve_cfg, mode, pool_scale, robust)
    fn = _STAGES.get(key)
    if fn is not None:
        return fn
    cfg = _adjust(solve_cfg, scale=pool_scale)
    if robust is not None:
        from ..robust.gramstats import robustify

    @jax.jit
    def gateway_stage(G, C, idx, counts, g_w=None):
        wts = counts / jnp.maximum(jnp.sum(counts), 1e-12)
        ghat_w = jnp.zeros((P,), jnp.float32).at[idx].set(wts)
        Gs = G[idx][:, idx]
        if robust is not None:
            Gr, cr, s = robustify(Gs, C[idx][:, idx], wts, robust)
            alpha = solve_alpha(Gr, cr, cfg)
            eff = s * alpha
            info = _fused.solve_diagnostics(Gr, cr, alpha, cfg.beta)
            info["clip_scale"] = s
            u_w = jnp.zeros((P,), jnp.float32).at[idx].set(eff)
            return {"G": Gr, "c": cr, "alpha": eff, "u_w": u_w,
                    "ghat_w": ghat_w, "info": info}
        g_solve = ghat_w if g_w is None else g_w
        c = C[idx] @ g_solve
        alpha, info = _solve_info(Gs, c, cfg, mode, wts)
        u_w = jnp.zeros((P,), jnp.float32).at[idx].set(alpha)
        return {"G": Gs, "c": c, "alpha": alpha, "u_w": u_w,
                "ghat_w": ghat_w, "info": info}

    _STAGES[key] = gateway_stage
    return gateway_stage


def merge_stage_for(P: int, K: int, solve_cfg: SolveConfig, mode: str, *,
                    sum_to: Optional[float] = 1.0) -> Callable:
    """Parent-tier stage over child row-mixes: ``fn(G, C, W (K,P),
    GW (K,P), counts, g_w?)`` — Gram ``W G Wᵀ``, c-term ``(W C) ĝ_w``."""
    key = ("smerge", P, K, solve_cfg, mode, sum_to)
    fn = _STAGES.get(key)
    if fn is not None:
        return fn
    cfg = _adjust(solve_cfg, sum_to=sum_to)

    @jax.jit
    def merge_stage(G, C, W, GW, counts, g_w=None):
        wts = counts / jnp.maximum(jnp.sum(counts), 1e-12)
        ghat_w = wts @ GW
        g_solve = ghat_w if g_w is None else g_w
        Gs = W @ G @ W.T
        c = (W @ C) @ g_solve
        alpha, info = _solve_info(Gs, c, cfg, mode, wts)
        return {"G": Gs, "c": c, "alpha": alpha, "u_w": alpha @ W,
                "ghat_w": ghat_w, "info": info}

    _STAGES[key] = merge_stage
    return merge_stage


def cloud_raw_stage_for(P: int, K: int, solve_cfg: SolveConfig, kind: str, *,
                        solve_scale: float = 1.0, robust=None) -> Callable:
    """Final tier over raw device rows (star / relay): ``fn(G, C, idx,
    counts) -> {u_w, info}`` — fused ``cloud_stage``'s math on sub-blocks,
    with the same robust clip+pool hook on the cross sub-block."""
    if robust is not None and (kind != "raw"
                               or not getattr(robust, "enabled", False)):
        robust = None
    key = ("scloud_raw", P, K, solve_cfg, kind, solve_scale, robust)
    fn = _STAGES.get(key)
    if fn is not None:
        return fn
    cfg = _adjust(solve_cfg, scale=solve_scale)
    if robust is not None:
        from ..robust.gramstats import robustify

    @jax.jit
    def cloud_raw_stage(G, C, idx, counts):
        wts = counts / jnp.maximum(jnp.sum(counts), 1e-12)
        if kind == "fedavg":
            alpha = wts
            info = {"alpha": alpha, "gamma": alpha}
        elif robust is not None:
            Gr, cr, s = robustify(G[idx][:, idx], C[idx][:, idx], wts,
                                  robust)
            gamma = solve_alpha(Gr, cr, cfg)
            alpha = s * gamma
            info = {"alpha": alpha, "gamma": alpha,
                    **_fused.solve_diagnostics(Gr, cr, gamma, cfg.beta),
                    "gram_diag": jnp.diag(Gr), "clip_scale": s}
        else:
            ghat_w = jnp.zeros((P,), jnp.float32).at[idx].set(wts)
            Gs = G[idx][:, idx]
            c = C[idx] @ ghat_w
            alpha, info = _cloud_solve_info(Gs, c, cfg)
        u_w = jnp.zeros((P,), jnp.float32).at[idx].set(alpha)
        return {"u_w": u_w, "info": info}

    _STAGES[key] = cloud_raw_stage
    return cloud_raw_stage


def cloud_combo_stage_for(P: int, K: int, solve_cfg: SolveConfig,
                          kind: str) -> Callable:
    """Final tier over child combinations: ``fn(G, C, W (K,P), g_w, counts)
    -> {eff_w, info}`` with the mass-conserving Σγ=1 solve; ``eff_w`` is
    the round's one effective row-mix ``γ @ W``."""
    key = ("scloud_combo", P, K, solve_cfg, kind)
    fn = _STAGES.get(key)
    if fn is not None:
        return fn
    cfg = _adjust(solve_cfg, sum_to=1.0 if kind == "combo" else None)

    @jax.jit
    def cloud_combo_stage(G, C, W, g_w, counts):
        wts = counts / jnp.maximum(jnp.sum(counts), 1e-12)
        if kind == "fedavg":
            gamma = wts
            info = {"alpha": gamma, "gamma": gamma}
        else:
            Gs = W @ G @ W.T
            c = (W @ C) @ g_w
            gamma, info = _cloud_solve_info(Gs, c, cfg)
        return {"eff_w": gamma @ W, "info": info}

    _STAGES[key] = cloud_combo_stage
    return cloud_combo_stage


# ---------------------------------------------------------------------------
# streamed passes (accumulate / materialize / apply)
# ---------------------------------------------------------------------------

def _accum_for(P: int, slabs_key: Tuple, chunk: int,
               impls: Tuple) -> Callable:
    """One jitted accumulate pass per (shapes, chunk, backend picks): sums
    the kernel op's per-leaf (G, C) partials under a single jit boundary —
    one dispatch per round regardless of leaf count."""
    key = (P, slabs_key, chunk, tuple(i.backend for i in impls))
    fn = _ACCUM.get(key)
    if fn is not None:
        return fn
    impl_fns = tuple(i.fn for i in impls)

    @jax.jit
    def accumulate(d_mats, g_mats):
        G = jnp.zeros((P, P), jnp.float32)
        C = jnp.zeros((P, P), jnp.float32)
        for dm, gm, f in zip(d_mats, g_mats, impl_fns):
            Gp, Cp = f(dm, gm, block_n=chunk)
            G = G + Gp
            C = C + Cp
        return G, C

    _ACCUM[key] = accumulate
    return accumulate


@jax.jit
def _materialize_mix(mats, w):
    """``w @ [slab matrices]`` concatenated to one (n,) f32 vector — the
    only place the streamed pipeline builds a full-width vector, and only
    when compression genuinely needs one."""
    return jnp.concatenate([mix_rows(w, m) for m in mats])


def _apply_fn(donate: bool) -> Callable:
    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def apply_mix(params, stacked, w):
        return jax.tree_util.tree_map(
            lambda p, s: (p + jnp.reshape(mix_rows(w, s), p.shape)
                          ).astype(p.dtype),
            params, stacked)
    return apply_mix


# CPU XLA cannot donate buffers (it would warn per compile); elsewhere the
# combine writes straight into the donated parameter allocation — but ONLY
# when the caller opted in (donation invalidates the argument buffers, so a
# caller that reuses its params across apply calls must not enable it)
_APPLY: Dict[bool, Callable] = {}


def _apply_mix(params, stacked, w, donate: bool):
    donate = donate and jax.default_backend() != "cpu"
    fn = _APPLY.get(donate)
    if fn is None:
        fn = _APPLY[donate] = _apply_fn(donate)
    return fn(params, stacked, w)


# ---------------------------------------------------------------------------
# engine / round context
# ---------------------------------------------------------------------------

class StreamedRoundEngine:
    """Drop-in peer of :class:`repro.hier.fused.HierRoundEngine`: same
    constructor signature plus ``chunk`` (column-chunk size, also the
    ``stream_stats`` autotune knob) and ``mesh`` (shard the chunk axis over
    a ``jax.sharding.Mesh`` when one is available; a ``'fleet'`` mesh axis
    additionally shards the leading P device axis of the round matrices —
    see :func:`repro.sharding.specs.stream_round_shardings`)."""

    name = "streamed"

    def __init__(self, params_template: Pytree, solve_cfg: SolveConfig,
                 tier_mode: str, gram_scope: Optional[str] = None, *,
                 chunk: Optional[int] = None,
                 mesh: Optional["jax.sharding.Mesh"] = None,
                 donate_params: bool = False, robust=None):
        self.n = int(sum(l.size for l in
                         jax.tree_util.tree_leaves(params_template)))
        self.solve_cfg = solve_cfg
        self.tier_mode = tier_mode
        self.gram_scope = gram_scope
        # RobustConfig (or None), applied at the member-level stages only —
        # same placement as the fused engine
        self.robust = robust
        self.chunk = int(chunk if chunk is not None else
                         os.environ.get("REPRO_STREAM_CHUNK", DEFAULT_CHUNK))
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        self.mesh = mesh
        # opt-in: the combine donates the params argument off-CPU.  Off by
        # default — donation deletes the caller's buffers, so only enable
        # it when every apply() consumes params the caller will replace
        # (run_hier_simulation does, and copies the caller's init_params
        # before the first round for exactly this reason).
        self.donate_params = bool(donate_params)
        # same scoped-column bookkeeping as the fused engine (int32 — reused
        # here for the dense-fallback stages of the compressed pipeline)
        self._scope_idx = _fused.scope_indices(params_template, gram_scope)
        self._scope_key = (None if self._scope_idx is None else
                           (gram_scope, len(self._scope_idx),
                            hash(self._scope_idx.tobytes())))
        # rounds begun so far; the next round's id, which its spans carry
        self.rounds = 0

    # -- memory model --------------------------------------------------------

    def peak_round_bytes(self, P: int, dense_fallback_members: int = 0
                         ) -> float:
        """Estimated peak round-matrix working set: two (P, chunk) f32
        column tiles in flight plus the two (P, P) f32 accumulators.

        ``dense_fallback_members`` accounts for the compressed pipeline:
        above a compression hop the members are decoded (n,) vectors and
        merges run on fused stack stages, so the largest summary-tier
        fan-in contributes two dense (members, n) f32 stacks (ū and ĝ) —
        the caller passes the max fan-in when compression is active (EF
        residual state is the compression pipeline's own and identical to
        the dense engine's, so it is not a round-matrix cost)."""
        bn = min(self.chunk, self.n)
        return float(2 * P * bn * 4 + 2 * P * P * 4
                     + 2 * dense_fallback_members * self.n * 4)

    # -- round entry ---------------------------------------------------------

    def begin_round(self, stacked_deltas: Pytree,
                    stacked_grads: Pytree) -> "StreamedRoundContext":
        """Open round ``self.rounds`` (counted from 0): the flat views, the
        per-slab kernel plan and the statistics pass.  Every span of the
        round (``stream_*``, here and on the returned context) carries its
        id as the ``round`` tag."""
        rnd = self.rounds
        self.rounds += 1
        if self.mesh is not None:
            from ..sharding.specs import stream_round_shardings
            stacked_deltas = jax.device_put(
                stacked_deltas,
                stream_round_shardings(self.mesh, stacked_deltas))
            stacked_grads = jax.device_put(
                stacked_grads,
                stream_round_shardings(self.mesh, stacked_grads))
        with spans.span("stream_views", round=rnd):
            dview = ChunkedFlatView(stacked_deltas, self.gram_scope)
            gview = ChunkedFlatView(stacked_grads, self.gram_scope)
        P = dview.K
        scoped = dview.scoped_slabs
        if scoped:
            with spans.span("stream_plan", round=rnd, slabs=len(scoped)):
                accumulate = self._plan(P, scoped)
            # the chunked column pass: the streamed engine's per-round hot
            # spot (walks every chunk of every slab under one jit call)
            n_chunks = sum(-(-s.width // self.chunk) for s in scoped)
            with spans.span("stream_accumulate", round=rnd, P=P,
                            chunks=n_chunks, chunk_cols=self.chunk,
                            slabs=len(scoped)):
                G, C = accumulate(tuple(s.matrix for s in scoped),
                                  tuple(gview.slabs[s.index].matrix
                                        for s in scoped))
        else:                       # scope matched nothing: degenerate zeros
            G = C = jnp.zeros((P, P), jnp.float32)
        return StreamedRoundContext(self, stacked_deltas, stacked_grads,
                                    dview, gview, G, C, rnd)

    def _plan(self, P: int, scoped) -> Callable:
        """The accumulate pass for these slabs: one ``stream_stats``
        backend per slab (autotuned once per shape bucket)."""
        impls, slabs_key = [], []
        for s in scoped:
            # timing cap preserves the width residue mod chunk so
            # alignment-based supports() checks see the true shape's
            # divisibility, and the winner at ~4M cols is the winner at
            # full width (same memory-bound regime).  When the chunk
            # itself exceeds the cap no capped width can stay
            # chunk-aligned — cap hard instead of synthesizing a spec
            # wider than the slab (which would defeat the cap's whole
            # point: select_impl_for times dense zeros of spec size).
            w = s.width
            if w > AUTOTUNE_CAP_COLS:
                if self.chunk <= AUTOTUNE_CAP_COLS:
                    w = min(w, (AUTOTUNE_CAP_COLS // self.chunk)
                            * self.chunk + w % self.chunk)
                else:
                    w = AUTOTUNE_CAP_COLS
            spec = jax.ShapeDtypeStruct((P, w), s.matrix.dtype)
            impl = select_impl_for("stream_stats", spec, spec,
                                   block_n=self.chunk)
            true_spec = jax.ShapeDtypeStruct((P, s.width), s.matrix.dtype)
            if not impl.ok_for(true_spec, true_spec, block_n=self.chunk):
                # the capped pick cannot run the real slab (e.g. the
                # pallas tile kernel on an unaligned width — its pad
                # would be the O(P·n) copy this engine exists to
                # avoid): take the streaming XLA path instead
                with force_backend("xla", op="stream_stats"):
                    impl = select_impl_for("stream_stats", spec, spec,
                                           block_n=self.chunk)
            impls.append(impl)
            slabs_key.append((P, s.width, str(s.matrix.dtype)))
        return _accum_for(P, tuple(slabs_key), self.chunk, tuple(impls))


class StreamedRoundContext:
    """One round's state: the (P, P) statistics plus views of the stacked
    update/gradient pytrees.  Mirrors :class:`FusedRoundContext`'s surface;
    refs are :class:`RowMix` until compression dense-ifies them."""

    name = "streamed"

    def __init__(self, engine: StreamedRoundEngine, stacked_deltas: Pytree,
                 stacked_grads: Pytree, dview: ChunkedFlatView,
                 gview: ChunkedFlatView, G: jax.Array, C: jax.Array,
                 round_id: int):
        self.engine = engine
        self._deltas, self._grads = stacked_deltas, stacked_grads
        self._dview, self._gview = dview, gview
        self.G, self.C = G, C
        self.P = dview.K
        self.round = round_id

    # -- device-uplink decodes (dense-engine feature) ------------------------

    def add_decoded_row(self, i: int, d_vec, g_vec) -> None:
        raise NotImplementedError(
            "device-uplink decode rows need the dense round matrices; "
            "run_hier_simulation rejects engine='streamed' for that config "
            "and auto-selects the fused engine")

    # -- gradient refs -------------------------------------------------------

    def mean_grad(self, idxs) -> RowMix:
        w = np.zeros((self.P,), np.float32)
        w[np.asarray(idxs, np.int64)] = 1.0 / len(idxs)
        return RowMix(w, "grad")

    def compose_grads(self, refs, counts):
        refs = list(refs)
        with spans.span("stream_compose", round=self.round, K=len(refs)):
            if all(_is_mix(r) for r in refs):
                w = np.asarray(counts, np.float64)
                w = w / max(float(w.sum()), 1e-12)
                acc = sum(float(wi) * jnp.asarray(r.w, jnp.float32)
                          for wi, r in zip(w, refs))
                return RowMix(acc, refs[0].src)
            vecs = tuple(self.materialize(r) for r in refs)
            return _fused.weighted_mean_rows(
                vecs, jnp.asarray(np.asarray(counts, np.float32)))

    # -- tier stages ---------------------------------------------------------

    def _mix_matrix(self, refs) -> jax.Array:
        return jnp.stack([jnp.asarray(r.w, jnp.float32) for r in refs])

    def _wrap(self, out) -> Dict[str, Any]:
        return {"G": out["G"], "c": out["c"], "alpha": out["alpha"],
                "u_bar": RowMix(out["u_w"], "delta"),
                "ghat": RowMix(out["ghat_w"], "grad"), "info": out["info"]}

    def gateway(self, idxs, *, solve_grad=None,
                pool_scale: float = 1.0) -> Dict[str, Any]:
        with spans.span("stream_gateway", round=self.round, K=len(idxs)):
            stage = gateway_stage_for(
                self.P, len(idxs), self.engine.solve_cfg,
                self.engine.tier_mode, pool_scale=pool_scale,
                robust=self.engine.robust)
            g_w = (None if solve_grad is None
                   else jnp.asarray(solve_grad.w, jnp.float32))
            out = stage(self.G, self.C,
                        jnp.asarray(np.asarray(idxs, np.int32)),
                        jnp.ones((len(idxs),), jnp.float32), g_w)
        return self._wrap(out)

    def merge(self, u_refs, g_refs, counts, *,
              solve_grad=None) -> Dict[str, Any]:
        u_refs, g_refs = list(u_refs), list(g_refs)
        with spans.span("stream_merge", round=self.round, K=len(u_refs)):
            dense = (any(not _is_mix(r) for r in u_refs + g_refs)
                     or (solve_grad is not None
                         and not _is_mix(solve_grad)))
            if dense:
                # above a compression hop the children are decoded (n,)
                # vectors: delegate to the fused stack-inside-jit stage over
                # the small (#children, n) member set the dense pipeline
                # also holds
                stage = _fused.summary_stage(
                    len(u_refs), self.engine.n, self.engine.solve_cfg,
                    self.engine.tier_mode, sum_to=1.0, stack=True,
                    scope_key=self.engine._scope_key,
                    scope_idx=self.engine._scope_idx)
                return stage(tuple(self.materialize(r) for r in u_refs),
                             tuple(self.materialize(r) for r in g_refs),
                             jnp.asarray(np.asarray(counts, np.float32)),
                             None if solve_grad is None
                             else self.materialize(solve_grad))
            stage = merge_stage_for(self.P, len(u_refs),
                                    self.engine.solve_cfg,
                                    self.engine.tier_mode, sum_to=1.0)
            g_w = (None if solve_grad is None
                   else jnp.asarray(solve_grad.w, jnp.float32))
            out = stage(self.G, self.C, self._mix_matrix(u_refs),
                        self._mix_matrix(g_refs),
                        jnp.asarray(np.asarray(counts, np.float32)), g_w)
        return self._wrap(out)

    def cloud_raw(self, idxs, kind: str, *,
                  solve_scale: float = 1.0) -> Tuple[RowMix, Dict]:
        with spans.span("stream_cloud_raw", round=self.round, K=len(idxs)):
            stage = cloud_raw_stage_for(
                self.P, len(idxs), self.engine.solve_cfg, kind,
                solve_scale=solve_scale, robust=self.engine.robust)
            out = stage(self.G, self.C,
                        jnp.asarray(np.asarray(idxs, np.int32)),
                        jnp.ones((len(idxs),), jnp.float32))
        return RowMix(out["u_w"], "delta"), out["info"]

    def cloud_combo(self, u_refs, counts, ghat, *, kind: str = "combo",
                    override=None) -> Tuple[Any, Dict]:
        u_refs = list(u_refs)
        with spans.span("stream_cloud", round=self.round, K=len(u_refs)):
            dense = (override is not None
                     or any(not _is_mix(r) for r in u_refs)
                     or (ghat is not None and not _is_mix(ghat)))
            if dense:
                stage = _fused.cloud_stage(
                    len(u_refs), self.engine.n, self.engine.solve_cfg, kind,
                    stack=True, scope_key=self.engine._scope_key,
                    scope_idx=self.engine._scope_idx)
                return stage(tuple(self.materialize(r) for r in u_refs),
                             self.materialize(ghat),
                             jnp.asarray(np.asarray(counts, np.float32)),
                             override=override)
            stage = cloud_combo_stage_for(self.P, len(u_refs),
                                          self.engine.solve_cfg, kind)
            out = stage(self.G, self.C, self._mix_matrix(u_refs),
                        jnp.asarray(ghat.w, jnp.float32),
                        jnp.asarray(np.asarray(counts, np.float32)))
        return RowMix(out["eff_w"], "delta"), out["info"]

    # -- vector materialization / final apply --------------------------------

    def materialize(self, ref) -> jax.Array:
        if not _is_mix(ref):
            return ref
        view = self._dview if ref.src == "delta" else self._gview
        with spans.span("stream_materialize", round=self.round, src=ref.src,
                        P=self.P):
            return _materialize_mix(tuple(s.matrix for s in view.slabs),
                                    jnp.asarray(ref.w, jnp.float32))

    def apply(self, params: Pytree, delta_ref) -> Pytree:
        if not _is_mix(delta_ref):
            return _fused.apply_delta(params, delta_ref)
        with spans.span("stream_apply", round=self.round, P=self.P):
            return _apply_mix(params, self._deltas,
                              jnp.asarray(delta_ref.w, jnp.float32),
                              self.engine.donate_params)
