"""Tests for the summary-compression subsystem (repro.compress): round-trip
and exactness anchors per scheme, EF telescoping, contraction of the shrunk
sketch decodes, sketch-space Gram correctness, the Pallas sketch kernel
against its oracle, ledger byte accounting == serialized payload
sizes, the §III-C gateway-tier pool correction, and the compressed
hierarchical simulation end to end (including exact recovery at k = n)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import (CompressConfig, ErrorFeedback,
                            IdentityCompressor, SignSketch, SRHTSketch,
                            TopKCompressor, available_schemes, fwht,
                            payload_gram)
from repro.core import SolveConfig, available_aggregators, solve_alpha
from repro.core.gram import gram_and_cross
from repro.edge import uniform_fleet
from repro.fl import run_hier_simulation
from repro.hier import (HierConfig, compressed_summary_bytes, star_topology,
                        summarize_updates, two_tier_topology)
from repro.kernels import ops
from repro.kernels.sketch import sketch_apply_pallas

import repro.hier.hier_server  # noqa: F401  (registers hier aggregators)

N = 610


def _vec(seed, n=N):
    return jax.random.normal(jax.random.PRNGKey(seed), (n,))


# ---------------------------------------------------------------------------
# scheme round trips, wire sizes, exactness anchors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["identity", "sign_sketch", "srht",
                                    "topk", "lowrank"])
def test_roundtrip_shapes_and_wire_size(scheme):
    c = CompressConfig(scheme=scheme, ratio=8.0).build(N)
    v = _vec(0)
    comp = c.encode(v, seed=3)
    dec = c.decode(comp)
    assert dec.shape == (N,)
    # serialized size is exactly what wire_floats promises — the ledger
    # property tests below lean on this
    assert comp.nbytes == pytest.approx(4.0 * c.wire_floats(N))
    if scheme != "identity":
        assert comp.nbytes < 0.3 * 4 * N            # actually compressed


def test_exactness_anchors():
    v = _vec(1)
    # top-k at k = n is the identity
    c = CompressConfig(scheme="topk", k=N).build(N)
    np.testing.assert_allclose(np.asarray(c.decode(c.encode(v))),
                               np.asarray(v), atol=1e-6)
    # SRHT at m = N (the padded power of 2) is an orthonormal transform
    c = CompressConfig(scheme="srht", sketch_dim=1024).build(N)
    np.testing.assert_allclose(np.asarray(c.decode(c.encode(v, seed=5))),
                               np.asarray(v), atol=1e-4)
    # identity is... the identity
    c = IdentityCompressor()
    np.testing.assert_allclose(np.asarray(c.decode(c.encode(v))),
                               np.asarray(v))


def test_fwht_involution_and_orthogonality():
    x = _vec(2, 128)
    y = fwht(x)
    np.testing.assert_allclose(np.asarray(fwht(y) / 128), np.asarray(x),
                               atol=1e-4)
    # H/sqrt(N) preserves norms
    assert float(jnp.linalg.norm(y) / jnp.sqrt(128.0)) == pytest.approx(
        float(jnp.linalg.norm(x)), rel=1e-5)
    with pytest.raises(ValueError, match="power-of-2"):
        fwht(jnp.zeros((100,)))


def test_sketch_decode_is_contraction():
    """The EF convergence condition: ‖x − decode(encode(x))‖ < ‖x‖ on
    average.  The *unshrunk* adjoint violates this for m ≪ n (norms inflate
    by ~n/m), which is exactly why the decodes shrink."""
    for c in (SignSketch(m=64), SRHTSketch(m=64)):
        ratios = []
        for s in range(20):
            v = _vec(100 + s)
            err = v - c.decode(c.encode(v, seed=s))
            ratios.append(float(jnp.linalg.norm(err) / jnp.linalg.norm(v)))
        assert np.mean(ratios) < 1.0, (c.name, np.mean(ratios))


def test_sign_sketch_dot_unbiased():
    """Sketch-space inner products estimate true inner products without the
    n/m distortion of decoded dots (correlated pair so signal ≫ noise)."""
    v = _vec(3)
    w = v + 0.1 * _vec(4)
    c = SignSketch(m=128)
    dots = [float(c.dot(c.encode(v, seed=s), c.encode(w, seed=s)))
            for s in range(60)]
    true = float(jnp.vdot(v, w))
    assert np.mean(dots) == pytest.approx(true, rel=0.15)
    with pytest.raises(ValueError, match="shared sketch"):
        c.dot(c.encode(v, seed=0), c.encode(w, seed=1))


def test_payload_gram_identity_matches_exact_and_srht_estimates():
    v, w, g = _vec(5), _vec(6), _vec(7)
    U = jnp.stack([v, w])
    ident = IdentityCompressor()
    G, c2 = payload_gram(ident, [ident.encode(v), ident.encode(w)],
                         [ident.encode(g), ident.encode(g)],
                         np.array([1.0, 1.0]))
    Gf, cf = gram_and_cross(U, g)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gf), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(cf), rtol=1e-4,
                               atol=1e-3)
    # srht at m = padded N is exact too (orthonormal rows)
    sk = SRHTSketch(m=1024)
    G, c2 = payload_gram(sk, [sk.encode(v, 9), sk.encode(w, 9)],
                         [sk.encode(g, 9), sk.encode(g, 9)],
                         np.array([1.0, 1.0]))
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gf), rtol=1e-3)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(cf), rtol=1e-3,
                               atol=1e-2)
    with pytest.raises(ValueError, match="shared sketch"):
        payload_gram(sk, [sk.encode(v, 0), sk.encode(w, 1)],
                     [sk.encode(g, 0), sk.encode(g, 0)], np.ones(2))


def test_mass_conserving_gamma_invariant_to_uniform_gram_rescale():
    """Why sketch-space cross-terms may price unshrunk targets while the
    combine applies shrunk decodes: scaling (G₂, c₂) jointly by s² leaves
    the Σγ=1 KKT solution exactly unchanged."""
    key = jax.random.PRNGKey(8)
    U = jax.random.normal(key, (4, 60))
    g = jax.random.normal(jax.random.fold_in(key, 1), (60,))
    G, c = gram_and_cross(U, g)
    cfg = SolveConfig(beta=3.0, ridge=1e-8, sum_to=1.0)
    gamma = solve_alpha(G, c, cfg)
    for s2 in (0.01, 0.3, 9.0):
        gamma_s = solve_alpha(s2 * G, s2 * c, cfg)
        np.testing.assert_allclose(np.asarray(gamma_s), np.asarray(gamma),
                                   rtol=1e-4, atol=1e-6)


def test_compress_config_validation_and_budget():
    with pytest.raises(KeyError, match="unknown compression scheme"):
        CompressConfig(scheme="bogus").build(100)
    with pytest.raises(ValueError, match="ratio"):
        CompressConfig(ratio=0.5)
    with pytest.raises(ValueError, match="k must be"):
        CompressConfig(k=0)
    with pytest.raises(ValueError, match="u_frac"):
        CompressConfig(u_frac=1.5)
    with pytest.raises(ValueError, match="selection scheme"):
        CompressConfig(scheme="srht", u_frac=0.75)
    assert set(available_schemes()) >= {"identity", "sign_sketch", "srht",
                                        "topk", "lowrank"}
    # every scheme meets its byte budget: <= n/ratio wire words per vector
    for scheme in ("sign_sketch", "srht", "topk", "lowrank"):
        c = CompressConfig(scheme=scheme, ratio=8.0).build(N)
        assert c.wire_floats(N) <= N / 8.0 + 1
    # the (u, g) pair splits a 2n/ratio budget by u_frac
    cu, cg = CompressConfig(scheme="topk", ratio=4.0,
                            u_frac=0.75).build_pair(N)
    assert cu.wire_floats(N) + cg.wire_floats(N) <= 2 * N / 4.0 + 2
    assert cu.wire_floats(N) > 2.5 * cg.wire_floats(N)
    # u_frac = 0.5 degenerates to two copies of build()
    cu, cg = CompressConfig(scheme="srht", ratio=4.0).build_pair(N)
    assert cu.wire_floats(N) == cg.wire_floats(N) \
        == CompressConfig(scheme="srht", ratio=4.0).build(N).wire_floats(N)
    # a skewed split of a mild joint ratio clamps at full width instead of
    # crashing on a sub-ratio < 1 the user never set
    cu, cg = CompressConfig(scheme="topk", ratio=1.2,
                            u_frac=0.75).build_pair(N)
    assert cu.wire_floats(N) <= 2 * N and cg.wire_floats(N) <= 2 * N


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

def test_error_feedback_telescopes_exactly():
    """Σ_t decode_t = Σ_t v_t − e_T — nothing is lost, only delayed."""
    ef = ErrorFeedback()
    c = TopKCompressor(k=40)
    total_in = jnp.zeros(N)
    total_out = jnp.zeros(N)
    for t in range(6):
        v = _vec(20 + t)
        _, dec = ef.step("gw", v, c, seed=t)
        total_in += v
        total_out += dec
    np.testing.assert_allclose(np.asarray(total_out + ef.residual["gw"]),
                               np.asarray(total_in), atol=1e-4)
    assert ef.residual_norm("gw") > 0
    assert ef.residual_norm("never-sent") == 0.0


def test_error_feedback_disabled_keeps_no_state():
    ef = ErrorFeedback(enabled=False)
    c = TopKCompressor(k=40)
    v = _vec(30)
    comp, dec = ef.step("gw", v, c)
    assert ef.residual == {}
    np.testing.assert_allclose(np.asarray(dec), np.asarray(c.decode(comp)))


def test_error_feedback_repeated_constant_input_converges():
    """Under a constant signal the EF-compressed stream's running mean
    approaches the signal (the classic EF sanity check)."""
    ef = ErrorFeedback()
    c = TopKCompressor(k=60)
    v = _vec(31)
    acc = jnp.zeros(N)
    T = 40
    for t in range(T):
        _, dec = ef.step("gw", v, c, seed=t)
        acc += dec
    # steady-state residual is O(1) while the mean integrates T sends, so
    # the relative error decays ~‖e_ss‖/(T·‖v‖)
    rel = float(jnp.linalg.norm(acc / T - v) / jnp.linalg.norm(v))
    assert rel < 0.15


# ---------------------------------------------------------------------------
# kernels vs oracles + ops dispatch
# ---------------------------------------------------------------------------

def test_sketch_kernel_matches_ref():
    key = jax.random.PRNGKey(0)
    U = jax.random.normal(key, (5, 333))         # K=5, m=11: both sublane-pad
    R = jax.random.normal(jax.random.fold_in(key, 1), (11, 333))
    out = sketch_apply_pallas(U, R, block_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(U @ R.T),
                               rtol=1e-4, atol=1e-4)
    d = ops.sketch_apply(U, R, use_pallas=True, block_n=128)
    np.testing.assert_allclose(np.asarray(d), np.asarray(out), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ops.sketch_apply(U, R)),
                               np.asarray(U @ R.T), rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="disagree on n"):
        sketch_apply_pallas(U, R[:, :100], interpret=True)


# ---------------------------------------------------------------------------
# §III-C pool pricing at the gateway tier
# ---------------------------------------------------------------------------

def test_gateway_pool_size_scales_solve():
    key = jax.random.PRNGKey(0)
    K, pool = 4, 12
    updates = [{"w": jax.random.normal(jax.random.fold_in(key, i), (30,))}
               for i in range(K)]
    grads = [{"w": jax.random.normal(jax.random.fold_in(key, 10 + i), (30,))}
             for i in range(K)]
    cfg = SolveConfig(beta=4.0, ridge=1e-8)
    s_plain = summarize_updates(1, range(K), updates, grads, [1] * K, cfg)
    s_pool = summarize_updates(1, range(K), updates, grads, [1] * K, cfg,
                               pool_size=pool)
    scale = (pool - 1) / (K - 1)
    np.testing.assert_allclose(np.asarray(s_pool.alpha),
                               scale * np.asarray(s_plain.alpha), rtol=1e-5)
    # "mean" tier rule is untouched (selection-unbiased already)
    m_plain = summarize_updates(1, range(K), updates, grads, [1] * K, cfg,
                                mode="mean")
    m_pool = summarize_updates(1, range(K), updates, grads, [1] * K, cfg,
                               mode="mean", pool_size=pool)
    np.testing.assert_allclose(np.asarray(m_pool.alpha),
                               np.asarray(m_plain.alpha))
    with pytest.raises(ValueError, match="pool_size"):
        summarize_updates(1, range(K), updates, grads, [1] * K, cfg,
                          pool_size=2)


# ---------------------------------------------------------------------------
# config + registry plumbing
# ---------------------------------------------------------------------------

def test_hier_sketch_config_and_registry():
    assert "hier_contextual_sketch" in available_aggregators()
    cfg = HierConfig(aggregator="hier_contextual_sketch")
    assert cfg.compress is not None              # defaulted
    assert cfg.compressing and cfg.tier_mode == "contextual"
    with pytest.raises(ValueError, match="hier_contextual_sketch"):
        HierConfig(aggregator="hier_contextual",
                   compress=CompressConfig())
    with pytest.raises(ValueError, match="gateway_grad"):
        HierConfig(aggregator="hier_contextual_sketch",
                   compress=CompressConfig(), gateway_grad="global")


# ---------------------------------------------------------------------------
# compressed hierarchical simulation end to end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_problem(tiny_edge_problem):
    # shared session-scoped dataset/model (conftest) → one set of compiled
    # functions serves both this module and test_hier
    return tiny_edge_problem


def _hier(ds, params, topo, rounds=5, **kw):
    from repro.models.logistic import logistic_apply, logistic_loss
    base = dict(aggregator="hier_contextual", lr=0.2, batch_size=10,
                min_epochs=1, max_epochs=4)
    base.update(kw)
    return run_hier_simulation("t", logistic_loss, logistic_apply, params,
                               ds, HierConfig(**base), topo,
                               num_rounds=rounds, selection_seed=11,
                               eval_every=2)


def test_compressed_sim_exact_at_full_budget(tiny_problem):
    """topk at k = n decodes exactly, so the whole compressed pipeline must
    reproduce the uncompressed hierarchical run bit-for-bit-ish."""
    ds, params, n_model = tiny_problem
    fleet = uniform_fleet(12, dropout=0.0)
    topo = two_tier_topology(fleet, 3)
    plain = _hier(ds, params, topo)
    exact = _hier(ds, params, topo, aggregator="hier_contextual_sketch",
                  compress=CompressConfig(scheme="topk", k=n_model))
    np.testing.assert_allclose(exact.train_loss, plain.train_loss, rtol=1e-4)
    # identity scheme: same losses AND strictly fewer bytes (2n+2 words vs
    # the raw summary's 2n+K²+2K+2 — the G block stays home)
    ident = _hier(ds, params, topo, aggregator="hier_contextual_sketch",
                  compress=CompressConfig(scheme="identity"))
    np.testing.assert_allclose(ident.train_loss, plain.train_loss, rtol=1e-4)
    assert ident.cloud_uplink_bytes < plain.cloud_uplink_bytes


def test_compressed_sim_learns_and_slashes_uplink(tiny_problem):
    ds, params, _ = tiny_problem
    fleet = uniform_fleet(12, dropout=0.0)
    topo = two_tier_topology(fleet, 3)
    plain = _hier(ds, params, topo, rounds=6)
    for scheme in ("topk", "srht"):
        r = _hier(ds, params, topo, rounds=6,
                  aggregator="hier_contextual_sketch",
                  compress=CompressConfig(scheme=scheme, ratio=4.0))
        assert np.isfinite(r.train_loss).all()
        assert r.train_loss[-1] < r.train_loss[0]
        assert r.cloud_uplink_bytes < 0.5 * plain.cloud_uplink_bytes


def test_ledger_matches_serialized_payload_sizes(tiny_problem):
    """CommLedger cloud-tier bytes == rounds × Σ_g serialized compressed
    summary size, computed independently from the compressor's wire format."""
    ds, params, n_model = tiny_problem
    fleet = uniform_fleet(12, dropout=0.0)      # no dropouts: cohorts fixed
    topo = two_tier_topology(fleet, 3)
    rounds = 4
    ccfg = CompressConfig(scheme="topk", ratio=4.0, u_frac=0.75)
    r = _hier(ds, params, topo, rounds=rounds,
              aggregator="hier_contextual_sketch", compress=ccfg)
    cu, cg = ccfg.build_pair(n_model)
    per_summary = compressed_summary_bytes(
        4.0 * (cu.wire_floats(n_model) + cg.wire_floats(n_model)))
    assert r.cloud_uplink_bytes == pytest.approx(rounds * 3 * per_summary)
    # uncompressed comparator: the raw summary formula still governs
    plain = _hier(ds, params, topo, rounds=rounds)
    from repro.hier import summary_bytes
    assert plain.cloud_uplink_bytes == pytest.approx(
        rounds * 3 * summary_bytes(4, n_model, include_grad=True))


def test_device_uplink_compression_star(tiny_problem):
    """Star topology with device-level EF compression: per-device residual
    state, compressed device→cloud ledger pricing BOTH streams the solve
    consumes (update and gradient), finite learning."""
    ds, params, n_model = tiny_problem
    fleet = uniform_fleet(12, dropout=0.0)
    topo = star_topology(fleet)
    ccfg = CompressConfig(scheme="topk", ratio=4.0, device_uplink=True)
    r = _hier(ds, params, topo, rounds=4,
              aggregator="hier_contextual_sketch", compress=ccfg)
    assert np.isfinite(r.train_loss).all()
    plain = _hier(ds, params, topo, rounds=4)
    assert r.cloud_uplink_bytes < 0.6 * plain.cloud_uplink_bytes
    cu, cg = ccfg.build_pair(n_model)
    per_dev = 4.0 * (cu.wire_floats(n_model) + cg.wire_floats(n_model))
    assert r.cloud_uplink_bytes == pytest.approx(4 * 12 * per_dev)


def test_compressed_sim_three_tier_geo(tiny_problem):
    from repro.hier import geo_partitioned_topology
    ds, params, _ = tiny_problem
    topo = geo_partitioned_topology(uniform_fleet(12, dropout=0.1), 2, 2)
    r = _hier(ds, params, topo, rounds=4,
              aggregator="hier_contextual_sketch",
              compress=CompressConfig(scheme="topk", ratio=4.0))
    assert np.isfinite(r.train_loss).all()
    assert r.comm["tier_3"]["bytes_up"] > 0
    assert r.comm["tier_2"]["bytes_up"] > 0


def test_compressed_sim_deterministic(tiny_problem):
    ds, params, _ = tiny_problem
    fleet = uniform_fleet(12, dropout=0.1)
    topo = two_tier_topology(fleet, 3)
    kw = dict(aggregator="hier_contextual_sketch",
              compress=CompressConfig(scheme="sign_sketch", ratio=4.0))
    r1 = _hier(ds, params, topo, **kw)
    r2 = _hier(ds, params, topo, **kw)
    assert r1.train_loss == r2.train_loss
    assert r1.cloud_uplink_bytes == r2.cloud_uplink_bytes


def test_fan_in_pool_correction_runs_in_sim(tiny_problem):
    ds, params, _ = tiny_problem
    fleet = uniform_fleet(12, dropout=0.0)
    topo = two_tier_topology(fleet, 3)
    r = _hier(ds, params, topo, fan_in=2)
    assert np.isfinite(r.train_loss).all()
    star = _hier(ds, params, star_topology(fleet), fan_in=4)
    assert np.isfinite(star.train_loss).all()


# ---------------------------------------------------------------------------
# hypothesis property tests (skipped when hypothesis is unavailable)
# ---------------------------------------------------------------------------

def test_wire_floats_matches_serialization_property():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(8, 400), seed=st.integers(0, 2 ** 16),
           scheme=st.sampled_from(["sign_sketch", "srht", "topk", "lowrank",
                                   "identity"]),
           ratio=st.sampled_from([2.0, 4.0, 8.0]))
    def check(n, seed, scheme, ratio):
        c = CompressConfig(scheme=scheme, ratio=ratio).build(n)
        v = jax.random.normal(jax.random.PRNGKey(seed), (n,))
        comp = c.encode(v, seed=seed)
        assert comp.nbytes == pytest.approx(4.0 * c.wire_floats(n))
        assert c.decode(comp).shape == (n,)

    check()
