"""Plain reference of one contextual round over a tier tree of gateways
(the paper's hierarchical aggregation, as ``repro.hier`` states it):

  * statistics ``G = D Dᵀ`` and ``C = D GMᵀ`` over the P stacked client
    updates D and gradient estimates GM, in float32 at "highest" precision;
  * each gateway g over its cohort I_g solves
    ``α_g = −(G_gg + ρ·tr(G_gg)/K·I)⁻¹ c_g / β`` with ``c_g = C[I_g] ĝ_g``,
    ĝ_g being the cohort's mean gradient;
  * the cloud solves the mass-conserving ``Σγ = 1`` problem over the
    gateways' combinations ``ū_g = α_g · D[I_g]`` against the global mean
    gradient, and the round's update is ``Σ_g γ_g ū_g``, added to the
    parameters.

Solves run on the host in float64.  Imports nothing of the program.

``low=True`` gives the control: the same round one precision step below
what the configuration states -- the statistics accumulated and summed in
bfloat16 instead of float32; the solves' products and linear systems on
bfloat16 operands (one MXU pass) instead of float32, with float32 sums
and solves; and the combine weights in float8 (e4m3) instead of the
bfloat16 the program rounds them to.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _leaf_stats(d, g):
    d = d.reshape(d.shape[0], -1).astype(jnp.float32)
    g = g.reshape(g.shape[0], -1).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    return (jnp.dot(d, d.T, precision=hi), jnp.dot(d, g.T, precision=hi),
            jnp.sum(g * g, axis=1))


@jax.jit
def _leaf_stats_low(d, g):
    d = d.reshape(d.shape[0], -1)
    g = g.reshape(g.shape[0], -1)
    dims = (((1,), (1,)), ((), ()))
    Gp = jax.lax.dot_general(d, d, dims, preferred_element_type=jnp.bfloat16)
    Cp = jax.lax.dot_general(d, g, dims, preferred_element_type=jnp.bfloat16)
    return Gp, Cp


@jax.jit
def _combine(w, d):
    m = d.reshape(d.shape[0], -1).astype(jnp.float32)
    out = jnp.dot(w.astype(jnp.float32), m,
                  precision=jax.lax.Precision.HIGHEST)
    return out.reshape(d.shape[1:])


def statistics(deltas: Sequence, grads: Sequence, *, low: bool = False):
    """(G, C, ‖GM_j‖²) summed over leaves, as float64 host arrays."""
    P = deltas[0].shape[0]
    if low:
        G = jnp.zeros((P, P), jnp.bfloat16)
        C = jnp.zeros((P, P), jnp.bfloat16)
        for d, g in zip(deltas, grads):
            Gp, Cp = _leaf_stats_low(d, g)
            G, C = G + Gp, C + Cp
        return (np.asarray(G, np.float64), np.asarray(C, np.float64), None)
    G = np.zeros((P, P))
    C = np.zeros((P, P))
    gg = np.zeros((P,))
    for d, g in zip(deltas, grads):
        Gp, Cp, ggp = _leaf_stats(d, g)
        G += np.asarray(Gp, np.float64)
        C += np.asarray(Cp, np.float64)
        gg += np.asarray(ggp, np.float64)
    return G, C, gg


def _ridge(Gs: np.ndarray, ridge: float) -> np.ndarray:
    K = Gs.shape[0]
    scale = max(np.trace(Gs) / K, 1e-30)
    return Gs + ridge * scale * np.eye(K)


def _bf16(x) -> np.ndarray:
    """``x`` rounded to bfloat16 and held in float32."""
    return np.asarray(np.asarray(x, np.float32).astype(jnp.bfloat16),
                      np.float32)


def solve_round(G: np.ndarray, C: np.ndarray, cohorts: List[List[int]],
                beta: float, ridge: float, *, low: bool = False
                ) -> Dict[str, np.ndarray]:
    """Gateway and cloud solves on the statistics; returns the round's
    effective weights over the P clients (``eff``) and the cloud's γ.
    With ``low`` every product and linear system takes bfloat16 operands
    and is summed and solved in float32."""
    r = _bf16 if low else (lambda x: x)

    def mm(a, b):
        return r(a) @ r(b)

    def solve(a, b):
        return np.linalg.solve(r(a), r(b))

    P = G.shape[0]
    W, g_w, counts = [], np.zeros(P), []
    for idx in cohorts:
        idx = np.asarray(idx)
        K = len(idx)
        ghat = np.zeros(P)
        ghat[idx] = 1.0 / K
        Gs = G[np.ix_(idx, idx)]
        c = mm(C[idx], ghat)
        alpha = -solve(_ridge(Gs, ridge), c) / beta
        u = np.zeros(P)
        u[idx] = alpha
        W.append(u)
        counts.append(K)
        g_w += K * ghat
    W = np.stack(W)
    g_w /= float(sum(counts))
    Gs = mm(mm(W, G), W.T)
    c = mm(mm(W, C), g_w)
    K = len(cohorts)
    kkt = np.zeros((K + 1, K + 1))
    kkt[:K, :K] = beta * _ridge(Gs, ridge)
    kkt[:K, K] = 1.0
    kkt[K, :K] = 1.0
    rhs = np.concatenate([-c, [1.0]])
    gamma = solve(kkt, rhs)[:K]
    return {"eff": mm(gamma, W), "gamma": gamma}


def round_delta(deltas: Sequence, eff: np.ndarray, *, low: bool = False
                ) -> List[jax.Array]:
    """The round's update per leaf, ``Σ_k eff_k D_k``, in float32.  With
    ``low`` the weights go through float8 first, as an array of their own:
    a rounding inside one compiled program may be elided by the compiler."""
    w = jnp.asarray(eff, jnp.float32)
    if low:
        w = w.astype(jnp.float8_e4m3fn)
    return [_combine(w, d) for d in deltas]
