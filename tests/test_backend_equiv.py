"""Backend equivalence for the kernel registry (PR-4 tentpole).

Every registry op must produce the same numbers on every backend —
compiled-XLA, interpret-mode Pallas, and the eager jnp reference — within
f32 accumulation-order tolerance, including the counter-based RNG sign
sketch against its materialized-R oracle at fixed seed.  Also covers the
registry mechanics (autotune cache, forcing, back-compat ``use_pallas``)
and the fused hier round stages against the pytree reference functions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, registry
from repro.kernels.rng_sketch import (rng_sign_matrix, rng_sketch_pallas,
                                      rng_sketch_xla, rng_sketch_adjoint_xla)

TOL = dict(rtol=1e-5, atol=1e-3)


def _data(K=7, n=333, m=11, seed=0):
    key = jax.random.PRNGKey(seed)
    U = jax.random.normal(key, (K, n), jnp.float32)
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    R = jax.random.normal(jax.random.fold_in(key, 2), (m, n), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 3), (n,), jnp.float32)
    a = jax.random.normal(jax.random.fold_in(key, 4), (K,), jnp.float32)
    return U, g, R, w, a


def _allclose(x, y):
    jax.tree_util.tree_map(
        lambda p, q: np.testing.assert_allclose(
            np.asarray(p, np.float32), np.asarray(q, np.float32), **TOL),
        list(x) if isinstance(x, tuple) else x,
        list(y) if isinstance(y, tuple) else y)


# ---------------------------------------------------------------- per-op

CALLS = {
    "gram": lambda d, be: ops.gram_and_cross(d[0], d[1], backend=be,
                                             block_n=128),
    "gram_block": lambda d, be: ops.gram_block_and_cross(
        d[0], d[0][:3], d[1], backend=be, block_n=128),
    "sketch": lambda d, be: ops.sketch_apply(d[0], d[2], backend=be,
                                             block_n=128),
    "topk": lambda d, be: ops.topk_select(d[1], 17, backend=be),
    "combine": lambda d, be: ops.weighted_combine(d[3], d[0], d[4],
                                                  backend=be, block_n=128),
    "sign_sketch": lambda d, be: ops.sign_sketch(d[0], 1234, 11, backend=be,
                                                 block_n=128),
}


@pytest.mark.parametrize("op", sorted(CALLS))
def test_every_backend_matches_ref(op):
    d = _data()
    want = CALLS[op](d, "ref")
    for be in ops.backends(op):
        got = CALLS[op](d, be)
        if op == "topk":
            # compare as dense sparse-reconstructions (tie ordering differs)
            n = d[1].shape[0]
            dv, dr = np.zeros(n), np.zeros(n)
            dv[np.asarray(got[1])] = np.asarray(got[0])
            dr[np.asarray(want[1])] = np.asarray(want[0])
            np.testing.assert_allclose(dv, dr, atol=1e-5)
        else:
            _allclose(got, want)


def test_every_op_has_all_three_backends():
    for op in ("gram", "gram_block", "sketch", "combine", "stream_stats",
               "sign_sketch", "flash_decode"):
        assert {"pallas", "xla", "ref"} <= set(ops.backends(op)), op
    # no TPU kernel (topk: Mosaic lowers neither lax.top_k nor the gather)
    for op in ("topk", "sign_sketch_adjoint"):
        assert set(ops.backends(op)) == {"xla", "ref"}, op


# ------------------------------------------------------- decode attention

def _attn_data(B=3, S=64, KV=2, G=2, hd=16, seed=2):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (B, KV, G, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd),
                         jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd),
                          jnp.float32)
    lengths = jnp.asarray([1, S // 2, S], jnp.int32)
    return q, k, v, lengths


def test_flash_decode_every_backend_matches_ref():
    """The serving hot path rides the registry like the aggregation ops
    (PR-10 satellite): all three backends agree, including masked tails,
    sliding window, and logit softcap."""
    q, k, v, lengths = _attn_data()
    want = ref.flash_decode_ref(q, k, v, lengths)
    want_w = ref.flash_decode_ref(q, k, v, lengths, window=16, softcap=5.0)
    for be in ops.backends("flash_decode"):
        _allclose(ops.flash_decode(q, k, v, lengths, backend=be), want)
        _allclose(ops.flash_decode(q, k, v, lengths, window=16,
                                   softcap=5.0, backend=be), want_w)


def test_flash_decode_autotune_streams_registry_event():
    from repro.obs import InMemoryTracker, use_tracker

    registry.clear_autotune_cache()
    q, k, v, lengths = _attn_data()
    mem = InMemoryTracker()
    with use_tracker(mem):
        ops.flash_decode(q, k, v, lengths)
        ops.flash_decode(q, k, v, lengths)        # same bucket: cached
    picks = [e.metrics for e in mem.metrics_events()
             if "kernels/autotune/op" in e.metrics]
    assert len(picks) == 1
    assert picks[0]["kernels/autotune/op"] == "flash_decode"
    assert picks[0]["kernels/autotune/backend"] in \
        ops.backends("flash_decode")
    rec = next(r for r in registry.autotune_records()
               if r["op"] == "flash_decode")
    assert rec["num_backends"] == 3
    if not ops.on_tpu():
        # interpret-mode pallas must never be timed as a candidate
        assert "us_per_call_pallas" not in rec


def test_backend_equiv_property_sweep():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(K=st.integers(1, 12), n=st.integers(8, 2000),
           seed=st.integers(0, 2 ** 16))
    def check(K, n, seed):
        key = jax.random.PRNGKey(seed)
        U = jax.random.normal(key, (K, n), jnp.float32)
        g = jax.random.normal(jax.random.fold_in(key, 1), (n,))
        want = ref.gram_ref(U, g)
        for be in ("pallas", "xla"):
            _allclose(ops.gram_and_cross(U, g, backend=be, block_n=128),
                      want)

    check()


# ------------------------------------------------- counter-based RNG sketch

def test_rng_sketch_streaming_matches_materialized_oracle():
    """The tentpole invariant: every streaming path (XLA scan, Pallas
    in-kernel generation, any chunk size) reproduces the materialized-R
    oracle exactly up to f32 accumulation order, at fixed seed."""
    U, _, _, _, _ = _data(K=5, n=700)
    seed = jnp.uint32(99)
    m = 13
    R = rng_sign_matrix(seed, m, 700)
    want = (U @ R.T) / jnp.sqrt(jnp.float32(m))
    for block in (128, 256, 1024):
        _allclose(rng_sketch_xla(U, seed, m=m, block_n=block), want)
        _allclose(rng_sketch_pallas(U, seed, m=m, block_n=block,
                                    interpret=True), want)
    # adjoint against the same R
    s = want[0]
    _allclose(rng_sketch_adjoint_xla(s, seed, n=700, block_n=256),
              (R.T @ s) / jnp.sqrt(jnp.float32(m)))


def test_rng_sketch_chunking_invariance_and_determinism():
    U, _, _, _, _ = _data(K=3, n=513)     # n prime-ish: pad path
    a = ops.sign_sketch(U, 7, 9, block_n=128)
    b = ops.sign_sketch(U, 7, 9, block_n=512)
    _allclose(a, b)
    _allclose(a, ops.sign_sketch(U, 7, 9, block_n=128))   # deterministic
    c = ops.sign_sketch(U, 8, 9, block_n=128)             # seed changes R
    assert float(jnp.max(jnp.abs(a - c))) > 1e-3


def test_rng_sign_matrix_statistics():
    """R behaves like iid ±1: zero mean, near-orthogonal rows."""
    R = rng_sign_matrix(jnp.uint32(3), 32, 8192)
    assert set(np.unique(np.asarray(R))) == {-1.0, 1.0}
    assert abs(float(R.mean())) < 0.02
    cross = np.asarray(R @ R.T / 8192) - np.eye(32)
    assert np.abs(cross).max() < 0.06                     # ~4/√n


def test_sign_sketch_compressor_never_materializes_but_matches_matrix():
    """compress.SignSketch == explicit S v with the materialized oracle."""
    from repro.compress import SignSketch
    v = jax.random.normal(jax.random.PRNGKey(5), (610,))
    c = SignSketch(m=64, seed_base=9)
    comp = c.encode(v, seed=4)
    S = c.sign_matrix(610, seed=4)
    _allclose(comp.data[0], S @ v)
    shrink = 64 / (64 + 610 + 1.0)
    _allclose(c.decode(comp), shrink * (S.T @ comp.data[0]))


# ------------------------------------------------------- registry mechanics

def test_autotune_caches_and_reports():
    registry.clear_autotune_cache()
    d = _data(K=4, n=256)
    ops.gram_and_cross(d[0], d[1])
    recs = registry.autotune_records()
    assert any(r["op"] == "gram" for r in recs)
    rec = next(r for r in recs if r["op"] == "gram")
    assert rec["backend_selected"] in ops.backends("gram")
    assert rec["num_backends"] == 3
    # off-TPU, interpret-mode pallas must never be an autotune candidate
    if not ops.on_tpu():
        assert "us_per_call_pallas" not in rec
    before = len(registry.autotune_records())
    ops.gram_and_cross(d[0], d[1])            # same bucket: no re-tune
    assert len(registry.autotune_records()) == before


def test_autotune_decisions_stream_through_tracker():
    """Every resolved dispatch — autotuned or forced — announces itself once
    per (op, bucket, backend) on the active tracker (satellite: registry
    telemetry)."""
    from repro.obs import InMemoryTracker, use_tracker

    registry.clear_autotune_cache()
    d = _data(K=4, n=256)
    mem = InMemoryTracker()
    with use_tracker(mem):
        ops.gram_and_cross(d[0], d[1])        # autotuned pick
        ops.gram_and_cross(d[0], d[1])        # cached: no second event
        with registry.force_backend("xla"):
            ops.gram_and_cross(d[0], d[1])    # forced pick, same bucket
    picks = [e.metrics for e in mem.metrics_events()
             if "kernels/autotune/op" in e.metrics]
    tuned = [m for m in picks if not m["kernels/autotune/forced"]]
    assert len(tuned) == 1
    assert tuned[0]["kernels/autotune/op"] == "gram"
    assert tuned[0]["kernels/autotune/backend"] in ops.backends("gram")
    assert any(k.startswith("kernels/autotune/us_per_call_")
               for k in tuned[0])
    forced = [m for m in picks if m["kernels/autotune/forced"]]
    assert len(forced) == 1
    assert forced[0]["kernels/autotune/op"] == "gram"
    assert forced[0]["kernels/autotune/backend"] == "xla"


def test_force_backend_scoped_and_use_pallas_compat():
    d = _data(K=4, n=256)
    want = ref.gram_ref(d[0], d[1])
    with registry.force_backend("ref"):
        _allclose(ops.gram_and_cross(d[0], d[1]), want)
    with registry.force_backend("ref", op="gram"):
        _allclose(ops.gram_and_cross(d[0], d[1]), want)
    # use_pallas=False now means the reference oracle on EVERY op (the PR-3
    # wrappers disagreed: gram ran interpret-mode Pallas off-TPU)
    _allclose(ops.gram_and_cross(d[0], d[1], use_pallas=False), want)
    _allclose(ops.gram_and_cross(d[0], d[1], use_pallas=True, block_n=128),
              want)


def test_forced_backend_is_preference_explicit_backend_is_requirement():
    """force_backend/env forcing falls back when supports() rejects the
    shapes; an explicit backend= arg is a hard requirement: it runs that
    implementation unconditionally and lets it raise."""
    d = jax.random.normal(jax.random.PRNGKey(0), (8, 300))   # not 128-aligned
    want = ref.stream_stats_ref(d, d)
    with registry.force_backend("pallas"):
        impl = registry.select_impl("stream_stats", d, d, block_n=128)
        assert impl.backend != "pallas"                   # fell back
        _allclose(ops.stream_stats(d, d, block_n=128), want)
    # explicit: the padded pallas path runs although supports() rejects it
    _allclose(ops.stream_stats(d, d, backend="pallas", block_n=128), want)
    with pytest.raises(ValueError, match="disagree"):
        ops.stream_stats(d, d[:, :200], backend="pallas", block_n=128)


def test_fused_stage_cache_rebinds_under_forced_backend():
    """The stage cache keys on the selected gram backend, so forcing a
    backend compiles a fresh stage instead of silently reusing the old."""
    from repro.core.solve import SolveConfig
    from repro.hier import fused
    cfg = SolveConfig(beta=4.0)
    U = jax.random.normal(jax.random.PRNGKey(1), (4, 200), jnp.float32)
    GR = jax.random.normal(jax.random.PRNGKey(2), (4, 200), jnp.float32)
    ones = jnp.ones((4,), jnp.float32)
    s1 = fused.summary_stage(4, 200, cfg, "contextual")
    with registry.force_backend("ref"):
        s2 = fused.summary_stage(4, 200, cfg, "contextual")
    assert s2 is not s1
    _allclose(s1(U, GR, ones)["alpha"], s2(U, GR, ones)["alpha"])
    assert fused.summary_stage(4, 200, cfg, "contextual") is s1


def test_registry_rejects_unknown():
    with pytest.raises(KeyError, match="unknown kernel op"):
        registry.dispatch("bogus_op", jnp.zeros((2, 2)))
    with pytest.raises(KeyError, match="not registered"):
        ops.gram_and_cross(jnp.zeros((2, 8)), jnp.zeros((8,)),
                           backend="bogus")


def test_dispatch_under_jit_uses_static_preference():
    """dispatch() inside a jit trace cannot time; it must still resolve."""
    d = _data(K=3, n=128)

    @jax.jit
    def f(U, g):
        return ops.gram_and_cross(U, g)

    _allclose(f(d[0], d[1]), ref.gram_ref(d[0], d[1]))


# ----------------------------------------------------- fused hier stages

def test_fused_summary_stage_matches_reference_summarize():
    """The fused gateway stage == gateway.summarize_updates on the same
    members (flat vectors as single-leaf pytrees)."""
    from repro.core.solve import SolveConfig
    from repro.hier.fused import summary_stage
    from repro.hier.gateway import summarize_updates
    key = jax.random.PRNGKey(2)
    K, n = 6, 210
    U = jax.random.normal(key, (K, n), jnp.float32)
    GR = jax.random.normal(jax.random.fold_in(key, 1), (K, n), jnp.float32)
    cfg = SolveConfig(beta=4.0, ridge=1e-8)
    for mode in ("contextual", "mean"):
        stage = summary_stage(K, n, cfg, mode)
        out = stage(U, GR, jnp.ones((K,), jnp.float32))
        s = summarize_updates(0, range(K), list(U), list(GR), [1] * K, cfg,
                              mode=mode)
        _allclose(out["alpha"], s.alpha)
        _allclose(out["u_bar"], s.u_bar)
        _allclose(out["ghat"], s.grad_est)
        _allclose(out["G"], s.G)
        _allclose(out["c"], s.c)


def test_fused_cloud_stage_matches_reference_merge():
    """The fused Σγ=1 cloud stage == merge_summaries' solve over the same
    child combinations."""
    from repro.core.solve import SolveConfig
    from repro.hier.fused import cloud_stage, summary_stage
    from repro.hier.gateway import merge_summaries, summarize_updates
    key = jax.random.PRNGKey(3)
    n = 150
    cfg = SolveConfig(beta=5.0, ridge=1e-8)
    kids = []
    for i in range(3):
        k1 = jax.random.fold_in(key, i)
        U = jax.random.normal(k1, (4, n), jnp.float32) * 0.3
        GR = jax.random.normal(jax.random.fold_in(k1, 9), (4, n),
                               jnp.float32)
        kids.append(summarize_updates(i, range(4), list(U), list(GR),
                                      [1] * 4, cfg))
    top = merge_summaries(100, kids, cfg)
    Ubar = jnp.stack([s.u_bar for s in kids])
    Ghat = jnp.stack([s.grad_est for s in kids])
    counts = jnp.asarray([s.num_updates for s in kids], jnp.float32)
    merged = summary_stage(3, n, cfg, "contextual", sum_to=1.0)(
        Ubar, Ghat, counts)
    _allclose(merged["alpha"], top.alpha)
    _allclose(merged["u_bar"], top.u_bar)
    delta, info = cloud_stage(3, n, cfg, "combo")(
        Ubar, merged["ghat"], counts)
    _allclose(info["gamma"], top.alpha)
    _allclose(delta, top.u_bar)
