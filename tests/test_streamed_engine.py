"""Streamed big-model round engine (PR-5 tentpole).

The streamed engine must reproduce the fused engine and the pytree
reference functions exactly (up to f32 accumulation order) on every stage,
every topology shape, and every chunk boundary — while never materializing
the (P, n) round matrices.  Covers: the ``stream_stats`` kernel op across
backends and chunk sizes (n % chunk != 0, single-chunk degenerate case),
the leaf-aligned ``ChunkedFlatView``, per-stage equivalence including the
sketch/EF compressed composition, scope × chunk interaction, the
peak-bytes estimator, and engine auto-selection.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flatten import ChunkedFlatView, tree_to_vector
from repro.core.solve import SolveConfig
from repro.hier import fused, streamed
from repro.hier.streamed import RowMix, StreamedRoundEngine, dense_round_bytes
from repro.kernels import ops, ref

TOL = dict(rtol=1e-5, atol=1e-4)


def _allclose(x, y):
    np.testing.assert_allclose(np.asarray(x, np.float32),
                               np.asarray(y, np.float32), **TOL)


def _stacked(P=8, seed=0, leaves=((3, 5), (7,), (4, 6), (1,))):
    """A stacked multi-leaf pytree (leading P axis) + its gradient twin."""
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i, shape in enumerate(leaves):
        key, k = jax.random.split(key)
        tree[f"leaf{i}"] = jax.random.normal(k, (P,) + shape, jnp.float32)
    key, k = jax.random.split(key)
    grads = jax.tree_util.tree_map(
        lambda l: jax.random.normal(jax.random.fold_in(k, l.size), l.shape),
        tree)
    return tree, grads


def _template(stacked):
    return jax.tree_util.tree_map(lambda l: l[0], stacked)


# ------------------------------------------------------------- kernel op

@pytest.mark.parametrize("P,n,bn", [(4, 333, 64), (1, 7, 64), (6, 64, 64),
                                    (5, 100, 1 << 16), (3, 129, 128)])
def test_stream_stats_backends_match_ref(P, n, bn):
    """Every backend, including the re-anchored remainder window (n % bn
    != 0) and the single-chunk degenerate case (bn >= n)."""
    key = jax.random.PRNGKey(1)
    D = jax.random.normal(key, (P, n), jnp.float32)
    GM = jax.random.normal(jax.random.fold_in(key, 1), (P, n), jnp.float32)
    want = ref.stream_stats_ref(D, GM)
    for be in ops.backends("stream_stats"):
        G, C = ops.stream_stats(D, GM, backend=be, block_n=bn)
        _allclose(G, want[0])
        _allclose(C, want[1])


def test_stream_stats_chunk_invariance():
    key = jax.random.PRNGKey(2)
    D = jax.random.normal(key, (5, 1000), jnp.float32)
    GM = jax.random.normal(jax.random.fold_in(key, 3), (5, 1000))
    base = ops.stream_stats(D, GM, backend="xla", block_n=1000)
    for bn in (64, 128, 333, 1 << 20):
        got = ops.stream_stats(D, GM, backend="xla", block_n=bn)
        _allclose(got[0], base[0])
        _allclose(got[1], base[1])


def test_stream_stats_chunk_property_sweep():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(P=st.integers(1, 9), n=st.integers(1, 500),
           bn=st.integers(1, 600), seed=st.integers(0, 999))
    def check(P, n, bn, seed):
        key = jax.random.PRNGKey(seed)
        D = jax.random.normal(key, (P, n), jnp.float32)
        GM = jax.random.normal(jax.random.fold_in(key, 1), (P, n))
        want = ref.stream_stats_ref(D, GM)
        got = ops.stream_stats(D, GM, backend="xla", block_n=bn)
        _allclose(got[0], want[0])
        _allclose(got[1], want[1])

    check()


def test_stream_stats_bf16_inputs_accumulate_f32():
    D = jnp.ones((3, 300), jnp.bfloat16)
    G, C = ops.stream_stats(D, D, backend="xla", block_n=64)
    assert G.dtype == jnp.float32
    _allclose(G, np.full((3, 3), 300.0))


# --------------------------------------------------------- chunked view

def test_chunked_flat_view_matches_dense_flatten():
    stacked, _ = _stacked(P=6)
    view = ChunkedFlatView(stacked)
    dense = fused.flatten_stacked(stacked)
    assert view.n == dense.shape[1] and view.K == 6
    _allclose(view.materialize(), dense)
    # chunk reassembly is exact and leaf-aligned for every chunk size
    for chunk in (1, 4, 7, 1000):
        got = np.zeros(dense.shape, np.float32)
        widths = []
        for off, _, mat in view.chunks(chunk):
            got[:, off:off + mat.shape[1]] = np.asarray(mat)
            widths.append(mat.shape[1])
        _allclose(got, dense)
        assert max(widths) <= chunk
    boundaries = {s.offset for s in view.slabs}
    offs = {off for off, _, _ in view.chunks(4)}
    assert boundaries <= offs           # leaf starts are chunk starts


def test_chunked_flat_view_scope_matches_scope_indices():
    stacked, _ = _stacked(P=4)
    tmpl = _template(stacked)
    view = ChunkedFlatView(stacked, scope="last_layer")
    idx = fused.scope_indices(tmpl, "last_layer")
    assert idx.dtype == np.int32        # satellite: no silent x64 downcast
    scoped_cols = sorted(
        c for s in view.scoped_slabs for c in range(s.offset,
                                                    s.offset + s.width))
    assert scoped_cols == sorted(int(i) for i in idx)


# ------------------------------------------------- per-stage equivalence

def _round_ctxs(P=8, seed=0, scope=None, chunk=None, beta=4.0):
    stacked, grads = _stacked(P=P, seed=seed)
    cfg = SolveConfig(beta=beta, ridge=1e-8)
    tmpl = _template(stacked)
    feng = fused.HierRoundEngine(tmpl, cfg, "contextual", scope)
    seng = StreamedRoundEngine(tmpl, cfg, "contextual", scope, chunk=chunk)
    return (feng.begin_round(stacked, grads),
            seng.begin_round(stacked, grads), stacked, grads, cfg)


@pytest.mark.parametrize("scope,chunk", [(None, None), (None, 7),
                                         ("leaf2", 5)])
def test_gateway_stage_matches_fused_and_reference(scope, chunk):
    from repro.hier.gateway import summarize_updates
    fctx, sctx, stacked, grads, cfg = _round_ctxs(scope=scope, chunk=chunk)
    idxs = [1, 3, 4, 6]
    fo = fctx.gateway(idxs)
    so = sctx.gateway(idxs)
    for k in ("G", "c", "alpha"):
        _allclose(so[k], fo[k])
    _allclose(sctx.materialize(so["u_bar"]), fo["u_bar"])
    _allclose(sctx.materialize(so["ghat"]), fo["ghat"])
    # and against the pytree reference
    rows = lambda tree, i: jax.tree_util.tree_map(lambda l: l[i], tree)
    s = summarize_updates(0, idxs, [rows(stacked, i) for i in idxs],
                          [rows(grads, i) for i in idxs], [1] * len(idxs),
                          cfg, gram_scope=scope)
    _allclose(so["alpha"], s.alpha)
    _allclose(sctx.materialize(so["u_bar"]), tree_to_vector(s.u_bar))


def test_merge_and_cloud_stages_match_fused():
    fctx, sctx, *_ = _round_ctxs(P=9, seed=3)
    cohorts = [[0, 1, 2], [3, 4, 5], [6, 7, 8]]
    fsums = [fctx.gateway(c) for c in cohorts]
    ssums = [sctx.gateway(c) for c in cohorts]
    counts = [3.0, 3.0, 3.0]
    fm = fctx.merge([s["u_bar"] for s in fsums[:2]],
                    [s["ghat"] for s in fsums[:2]], counts[:2])
    sm = sctx.merge([s["u_bar"] for s in ssums[:2]],
                    [s["ghat"] for s in ssums[:2]], counts[:2])
    for k in ("G", "c", "alpha"):
        _allclose(sm[k], fm[k])
    _allclose(sctx.materialize(sm["u_bar"]), fm["u_bar"])
    # cloud combo over [merged, gateway-3]
    ghat_f = fctx.compose_grads([fm["ghat"], fsums[2]["ghat"]], [6.0, 3.0])
    ghat_s = sctx.compose_grads([sm["ghat"], ssums[2]["ghat"]], [6.0, 3.0])
    fd, fi = fctx.cloud_combo([fm["u_bar"], fsums[2]["u_bar"]], [6.0, 3.0],
                              ghat_f)
    sd, si = sctx.cloud_combo([sm["u_bar"], ssums[2]["u_bar"]], [6.0, 3.0],
                              ghat_s)
    _allclose(si["gamma"], fi["gamma"])
    _allclose(si["gram_diag"], fi["gram_diag"])
    _allclose(sctx.materialize(sd), fd)


def test_cloud_raw_and_fedavg_match_fused():
    for mode, kind in (("contextual", "raw"), ("mean", "fedavg")):
        stacked, grads = _stacked(P=7, seed=4)
        cfg = SolveConfig(beta=3.0, ridge=1e-8)
        tmpl = _template(stacked)
        fctx = fused.HierRoundEngine(tmpl, cfg, mode).begin_round(stacked,
                                                                  grads)
        sctx = StreamedRoundEngine(tmpl, cfg, mode).begin_round(stacked,
                                                                grads)
        idxs = [0, 2, 3, 5, 6]
        fd, fi = fctx.cloud_raw(idxs, kind)
        sd, si = sctx.cloud_raw(idxs, kind)
        _allclose(si["gamma"], fi["gamma"])
        _allclose(sctx.materialize(sd), fd)


def test_streamed_apply_matches_dense_apply():
    fctx, sctx, stacked, _, _ = _round_ctxs(P=8, seed=5)
    w = jax.random.normal(jax.random.PRNGKey(9), (8,), jnp.float32)
    tmpl = _template(stacked)
    fres = fctx.apply(tmpl, w @ fctx.D)
    sres = sctx.apply(tmpl, RowMix(w, "delta"))
    jax.tree_util.tree_map(lambda a, b: _allclose(a, b), fres, sres)


def test_sketch_ef_composition_matches_fused():
    """Materialized refs feed the SAME EF/encode pipeline the dense engine
    runs: identical payloads, decodes and residuals at fixed seed."""
    from repro.compress import CompressConfig, ErrorFeedback
    fctx, sctx, *_ = _round_ctxs(P=8, seed=6)
    comp = CompressConfig(scheme="sign_sketch", ratio=4.0).build(fctx.D.shape[1])
    ef_f, ef_s = ErrorFeedback(), ErrorFeedback()
    for rnd in range(3):                 # residuals telescope across rounds
        fo = fctx.gateway([1, 2, 5])
        so = sctx.gateway([1, 2, 5])
        cf, df = ef_f.step(("u", 0), fo["u_bar"], comp, seed=rnd)
        cs, ds = ef_s.step(("u", 0), sctx.materialize(so["u_bar"]), comp,
                           seed=rnd)
        _allclose(cs.data[0], cf.data[0])
        _allclose(ds, df)
        _allclose(ef_s.residual[("u", 0)], ef_f.residual[("u", 0)])
    # decoded (dense) refs re-enter the streamed tiers via the fused
    # stack-stages — mixed-ref merge must still match
    fo2 = fctx.gateway([0, 4])
    so2 = sctx.gateway([0, 4])
    fm = fctx.merge([df, fo2["u_bar"]], [fo2["ghat"], fo2["ghat"]],
                    [3.0, 2.0])
    sm = sctx.merge([ds, so2["u_bar"]], [so2["ghat"], so2["ghat"]],
                    [3.0, 2.0])
    _allclose(sm["alpha"], fm["alpha"])
    _allclose(sctx.materialize(sm["u_bar"]), fm["u_bar"])


# ------------------------------------------------------ e2e + selection

def _run(ds, params, cfg, topo, engine, rounds=4, **kw):
    from repro.fl import run_hier_simulation
    from repro.models.logistic import logistic_apply, logistic_loss
    return run_hier_simulation("t", logistic_loss, logistic_apply, params,
                               ds, cfg, topo, num_rounds=rounds,
                               selection_seed=11, eval_every=rounds,
                               engine=engine, **kw)


def test_e2e_streamed_matches_fused(tiny_edge_problem):
    from repro.compress import CompressConfig
    from repro.edge import bimodal_fleet
    from repro.hier import HierConfig, two_tier_topology
    ds, params, _ = tiny_edge_problem
    fleet = bimodal_fleet(ds.num_devices, slowdown=5.0, dropout_slow=0.1,
                          seed=0)
    topo = two_tier_topology(fleet, 3)
    base = dict(lr=0.2, batch_size=10, min_epochs=1, max_epochs=3)
    for cfg in (HierConfig(aggregator="hier_contextual", **base),
                HierConfig(aggregator="hier_contextual",
                           gateway_grad="global", **base),
                HierConfig(aggregator="hier_contextual_sketch",
                           compress=CompressConfig(scheme="sign_sketch",
                                                   ratio=4.0), **base)):
        rf = _run(ds, params, cfg, topo, "fused")
        rs = _run(ds, params, cfg, topo, "streamed", stream_chunk=37)
        _allclose(rs.train_loss[-1], rf.train_loss[-1])
        assert rs.cloud_uplink_bytes == rf.cloud_uplink_bytes
        assert rs.total_bytes == rf.total_bytes
        assert rf.engine["engine_name"] == "fused"
        assert rs.engine["engine_name"] == "streamed"


def test_engine_auto_selection_budget(tiny_edge_problem, monkeypatch):
    from repro.edge import bimodal_fleet
    from repro.hier import HierConfig, two_tier_topology
    ds, params, _ = tiny_edge_problem
    fleet = bimodal_fleet(ds.num_devices, slowdown=5.0, dropout_slow=0.0,
                          seed=0)
    topo = two_tier_topology(fleet, 3)
    cfg = HierConfig(aggregator="hier_contextual", lr=0.2, batch_size=10,
                     min_epochs=1, max_epochs=2)
    r = _run(ds, params, cfg, topo, "auto", rounds=1)
    assert r.engine["engine_name"] == "fused"     # tiny model under budget
    monkeypatch.setenv("REPRO_DENSE_ROUND_BYTES", "10")
    r2 = _run(ds, params, cfg, topo, "auto", rounds=1)
    assert r2.engine["engine_name"] == "streamed"
    _allclose(r2.train_loss[-1], r.train_loss[-1])
    with pytest.raises(ValueError, match="unknown engine"):
        _run(ds, params, cfg, topo, "bogus", rounds=1)
    # explicit streamed + device-uplink decode rows must fail loudly (auto
    # quietly picks the fused engine instead)
    from repro.compress import CompressConfig
    dcfg = HierConfig(aggregator="hier_contextual_sketch",
                      compress=CompressConfig(scheme="topk", ratio=4.0,
                                              u_frac=0.75,
                                              device_uplink=True),
                      lr=0.2, batch_size=10, min_epochs=1, max_epochs=2)
    with pytest.raises(ValueError, match="device_uplink"):
        _run(ds, params, dcfg, topo, "streamed", rounds=1)
    r3 = _run(ds, params, dcfg, topo, "auto", rounds=1)
    assert r3.engine["engine_name"] == "fused"


def test_mesh_sharded_chunk_axis_single_device(tiny_edge_problem):
    from jax.sharding import Mesh
    from repro.edge import bimodal_fleet
    from repro.hier import HierConfig, two_tier_topology
    ds, params, _ = tiny_edge_problem
    fleet = bimodal_fleet(ds.num_devices, slowdown=5.0, dropout_slow=0.0,
                          seed=0)
    topo = two_tier_topology(fleet, 3)
    cfg = HierConfig(aggregator="hier_contextual", lr=0.2, batch_size=10,
                     min_epochs=1, max_epochs=2)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    r0 = _run(ds, params, cfg, topo, "streamed", rounds=2)
    r1 = _run(ds, params, cfg, topo, "streamed", rounds=2, mesh=mesh)
    _allclose(r1.train_loss[-1], r0.train_loss[-1])


# ------------------------------------------------------------ estimator

def test_peak_bytes_estimator_sanity():
    cfg = SolveConfig(beta=4.0)
    tmpl = {"w": jnp.zeros((1000, 100)), "b": jnp.zeros((100,))}
    n = 1000 * 100 + 100
    P, chunk = 16, 1 << 10
    seng = StreamedRoundEngine(tmpl, cfg, "contextual", chunk=chunk)
    feng = fused.HierRoundEngine(tmpl, cfg, "contextual")
    assert feng.peak_round_bytes(P) == dense_round_bytes(P, n)
    want = 2 * P * chunk * 4 + 2 * P * P * 4
    assert seng.peak_round_bytes(P) == want
    # the acceptance regime: big model, small chunk → way under 25% dense
    assert seng.peak_round_bytes(P) <= 0.25 * feng.peak_round_bytes(P)
    # degenerate: chunk wider than the model clamps to n (never overstates)
    tiny = StreamedRoundEngine(tmpl, cfg, "contextual", chunk=1 << 30)
    assert tiny.peak_round_bytes(P) == 2 * P * n * 4 + 2 * P * P * 4
    # compressed pipelines dense-ify above the encode hop: the estimator
    # must charge the fused-fallback (members, n) stacks
    assert (seng.peak_round_bytes(P, dense_fallback_members=4)
            == want + 2 * 4 * n * 4)
    with pytest.raises(ValueError, match="chunk"):
        StreamedRoundEngine(tmpl, cfg, "contextual", chunk=0)


def test_compressed_run_reports_dense_fallback_peak(tiny_edge_problem):
    from repro.compress import CompressConfig
    from repro.edge import bimodal_fleet
    from repro.hier import HierConfig, two_tier_topology
    ds, params, n_model = tiny_edge_problem
    fleet = bimodal_fleet(ds.num_devices, slowdown=5.0, dropout_slow=0.0,
                          seed=0)
    topo = two_tier_topology(fleet, 3)
    base = dict(lr=0.2, batch_size=10, min_epochs=1, max_epochs=2)
    plain = _run(ds, params, HierConfig(aggregator="hier_contextual",
                                        **base), topo, "streamed", rounds=1)
    comp = _run(ds, params,
                HierConfig(aggregator="hier_contextual_sketch",
                           compress=CompressConfig(scheme="sign_sketch",
                                                   ratio=4.0), **base),
                topo, "streamed", rounds=1)
    # 3 gateways report dense decodes to the cloud: 2 stacks of (3, n) f32
    assert (comp.engine["round_matrix_peak_bytes"]
            == plain.engine["round_matrix_peak_bytes"] + 2 * 3 * n_model * 4)


def test_apply_does_not_donate_by_default():
    """A caller that reuses its params across apply() calls must be safe:
    donation is an explicit engine opt-in (run_hier_simulation sets it and
    copies the caller's params first)."""
    _, sctx, stacked, _, _ = _round_ctxs(P=8, seed=7)
    assert sctx.engine.donate_params is False
    tmpl = _template(stacked)
    w = RowMix(jnp.ones((8,), jnp.float32) / 8, "delta")
    a = sctx.apply(tmpl, w)
    b = sctx.apply(tmpl, w)          # second use of tmpl must not crash
    jax.tree_util.tree_map(lambda x, y: _allclose(x, y), a, b)


def test_autotune_cap_preserves_alignment_residue(monkeypatch):
    """The timing cap must not lie to alignment-based supports() checks:
    the capped spec keeps the true width's residue mod chunk, so e.g. the
    Pallas tile kernel is only eligible when the REAL slab is aligned."""
    monkeypatch.setattr(streamed, "AUTOTUNE_CAP_COLS", 16)
    chunk = 8
    stacked, grads = _stacked(P=4, seed=9, leaves=((37,), (5, 8)))
    seen = []
    orig = streamed.select_impl_for

    def spy(op, *specs, **kw):
        seen.append(specs[0].shape)
        return orig(op, *specs, **kw)

    monkeypatch.setattr(streamed, "select_impl_for", spy)
    eng = StreamedRoundEngine(_template(stacked), SolveConfig(beta=2.0),
                              "contextual", chunk=chunk)
    eng.begin_round(stacked, grads)
    widths = {37: None, 40: None}
    for shape in seen:
        for true_w in widths:
            if shape[1] <= true_w and shape[1] % chunk == true_w % chunk:
                widths[true_w] = shape[1]
    assert all(v is not None for v in widths.values()), (seen, widths)
    from repro.kernels.ops import _stream_pallas_ok

    class Spec:
        def __init__(self, shape):
            self.shape, self.ndim = shape, len(shape)
            self.dtype = jnp.float32
    # unaligned true width stays ineligible for the padded pallas path
    assert not _stream_pallas_ok(Spec((8, 37)), Spec((8, 37)), block_n=8)
    assert _stream_pallas_ok(Spec((8, 40)), Spec((8, 40)), block_n=8)


def test_streamed_never_builds_dense_round_matrix():
    """The engine's accumulate path must call the stream_stats op on
    leaf-slab shapes, never on a concatenated (P, n) matrix."""
    from repro.kernels import registry
    stacked, grads = _stacked(P=5, seed=8)
    tmpl = _template(stacked)
    seen = []
    orig = streamed.select_impl_for

    def spy(op, *specs, **kw):
        seen.extend(s.shape for s in specs)
        return orig(op, *specs, **kw)

    streamed.select_impl_for = spy
    try:
        StreamedRoundEngine(tmpl, SolveConfig(beta=2.0), "contextual",
                            chunk=8).begin_round(stacked, grads)
    finally:
        streamed.select_impl_for = orig
    n = sum(l.size for l in jax.tree_util.tree_leaves(tmpl))
    assert seen and all(shape[1] < n for shape in seen)


def _module_name(lowered) -> str:
    return lowered.compiler_ir().operation.attributes["sym_name"].value


def test_round_programs_keep_their_names():
    """The device trace finds each program of a round by its module name
    (``bench/metrics/`` reads ``accumulate``, ``apply_mix`` and
    ``flat_view``): lower every program one streamed round runs, and pin
    the names, so a rename fails here instead of zeroing a metric."""
    from repro.core.flatten import flat_view_slab
    stacked, grads = _stacked(P=4, leaves=((3, 5), (6,)))
    params = _template(stacked)
    cfg = SolveConfig(beta=5.0, ridge=1e-6)
    eng = StreamedRoundEngine(params, cfg, "contextual", chunk=8)
    ctx = eng.begin_round(stacked, grads)
    sums = [ctx.gateway(c) for c in ([0, 1], [2, 3])]
    ghat = ctx.compose_grads([s["ghat"] for s in sums], [2.0, 2.0])
    delta, _ = ctx.cloud_combo([s["u_bar"] for s in sums], [2.0, 2.0], ghat)
    ctx.apply(params, delta)

    leaf = stacked["leaf0"]
    slabs = ctx._dview.scoped_slabs
    W = jnp.stack([jnp.asarray(s["u_bar"].w) for s in sums])
    counts = jnp.asarray([2.0, 2.0])
    programs = {
        "jit_flat_view_slab": flat_view_slab.lower(leaf, 15),
        "jit_accumulate": eng._plan(4, slabs).lower(
            tuple(s.matrix for s in slabs),
            tuple(ctx._gview.slabs[s.index].matrix for s in slabs)),
        "jit_gateway_stage": streamed.gateway_stage_for(
            4, 2, cfg, "contextual").lower(
                ctx.G, ctx.C, jnp.asarray([0, 1], jnp.int32),
                jnp.ones((2,)), None),
        "jit_merge_stage": streamed.merge_stage_for(
            4, 2, cfg, "contextual").lower(ctx.G, ctx.C, W, W, counts),
        "jit_cloud_raw_stage": streamed.cloud_raw_stage_for(
            4, 4, cfg, "raw").lower(ctx.G, ctx.C,
                                    jnp.arange(4, dtype=jnp.int32),
                                    jnp.ones((4,))),
        "jit_cloud_combo_stage": streamed.cloud_combo_stage_for(
            4, 2, cfg, "combo").lower(ctx.G, ctx.C, W,
                                      jnp.asarray(ghat.w), counts),
        "jit_apply_mix": streamed._apply_fn(False).lower(
            params, stacked, jnp.asarray(delta.w)),
    }
    assert {k: _module_name(v) for k, v in programs.items()} == {
        k: k for k in programs}
    # the view's program is the eager reshape it replaced, renamed
    eager = jax.jit(lambda v: jnp.reshape(v, (4, 15))).lower(leaf)
    body = programs["jit_flat_view_slab"].as_text().replace(
        "jit_flat_view_slab", "@")
    assert body == eager.as_text().replace("jit__lambda", "@")


# ------------------------------------------------------------- combine

# leaf shapes S of rank 1-4 (stacked as (P, *S)): minor dimensions that are
# and are not multiples of 128, the TPU's lane width
_MIX_SHAPES = [(256,), (130,), (4, 128), (3, 130), (2, 8, 256), (3, 5, 7),
               (2, 3, 8, 128), (2, 3, 7, 130)]


def _mix_case(P, shape, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    leaf = jax.random.normal(key, (P,) + shape, jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (P,), jnp.float32)
    leaf64 = np.asarray(leaf.astype(jnp.float32), np.float64)
    w64 = np.asarray(w, np.float64)
    want = np.tensordot(w64, leaf64, axes=1)
    # f32 tolerance: a few roundings of each term's magnitude
    bound = 4 * P * np.finfo(np.float32).eps * np.tensordot(
        np.abs(w64), np.abs(leaf64), axes=1)
    return leaf, w, want, bound


def _assert_near(got, want, bound):
    err = np.abs(np.asarray(got, np.float64) - want)
    assert np.all(err <= bound + 1e-30), float(np.max(err - bound))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("P", [3, 16])
@pytest.mark.parametrize("shape", _MIX_SHAPES, ids=str)
def test_combine_matches_float64_sum(shape, P, dtype):
    """``mix_rows``, the engine's ``apply_mix`` and ``stacked_weighted_sum``
    give ``Σ_k w_k·leaf[k]`` in the leaf's own shape, to f32 accuracy (the
    weights are not rounded to a bf16 leaf's dtype)."""
    from repro.core.flatten import mix_rows, stacked_weighted_sum
    leaf, w, want, bound = _mix_case(P, shape, dtype)
    got = mix_rows(w, leaf)
    assert got.shape == shape and got.dtype == jnp.float32
    _assert_near(got, want, bound)

    p = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
    new = streamed._apply_fn(False)({"x": p}, {"x": leaf}, w)["x"]
    assert new.shape == shape and new.dtype == jnp.float32
    p64 = np.asarray(p, np.float64)
    _assert_near(new, p64 + want,
                 bound + 2 * np.finfo(np.float32).eps * np.abs(p64 + want))

    summed = stacked_weighted_sum({"x": leaf}, w)["x"]
    assert summed.shape == shape and summed.dtype == dtype
    # the sum is f32; only its cast back to the leaf's dtype rounds it
    _assert_near(summed.astype(jnp.float32), want,
                 bound + float(jnp.finfo(dtype).eps) * np.abs(want))


@pytest.mark.parametrize("P", [3, 16])
def test_materialize_mix_matches_flat_view(P):
    """The compressed pipeline's full-width vector is ``w @`` the dense
    (P, n) matrix that ``ChunkedFlatView.materialize()`` implies."""
    tree = {f"l{i}": jax.random.normal(jax.random.PRNGKey(i), (P,) + s,
                                       jnp.float32).astype(jnp.bfloat16)
            for i, s in enumerate(_MIX_SHAPES)}
    view = ChunkedFlatView(tree)
    w = jax.random.normal(jax.random.PRNGKey(99), (P,), jnp.float32)
    got = streamed._materialize_mix(tuple(s.matrix for s in view.slabs), w)
    dense = np.asarray(view.materialize(), np.float64)
    assert got.shape == (view.n,)
    w64 = np.asarray(w, np.float64)
    _assert_near(got, w64 @ dense,
                 4 * P * np.finfo(np.float32).eps * (np.abs(w64) @ np.abs(dense)))


def _reshapes(text):
    """(operand dims, result dims) of every ``stablehlo.reshape``."""
    pat = re.compile(r"stablehlo\.reshape .*: \(tensor<([0-9x]*)x?\w+>\) "
                     r"-> tensor<([0-9x]*)x?\w+>")

    def dims(s):
        return [int(d) for d in s.split("x") if d]
    return [(dims(a), dims(b)) for a, b in pat.findall(text)]


def test_apply_mix_keeps_stacked_leaf_layout():
    """The combine of a stacked (P, E, d, f) bf16 leaf is a leading-axis
    reduction in the leaf's own shape: no reshape merges its two minor
    dimensions (a relayout copy of the whole leaf on a TPU) and no
    ``dot_general`` (an f32 copy of the leaf there)."""
    P, E, d, f = 16, 2, 24, 136
    params = {"e": jnp.zeros((E, d, f), jnp.float32),
              "n": jnp.zeros((f,), jnp.float32)}
    stacked = {"e": jnp.zeros((P, E, d, f), jnp.bfloat16),
               "n": jnp.zeros((P, f), jnp.bfloat16)}
    lowered = streamed._apply_fn(False).lower(params, stacked,
                                              jnp.ones((P,), jnp.float32))
    assert _module_name(lowered) == "jit_apply_mix"
    text = lowered.as_text()
    assert "dot_general" not in text and "stablehlo.dot" not in text
    merged = [(a, b) for a, b in _reshapes(text)
              if a[-2:] == [d, f] and b[-2:] != [d, f]]
    assert not merged, merged
    assert "stablehlo.reduce" in text
