"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gram_ref(updates: jax.Array, grad: jax.Array):
    """(G, c) in f32 — oracle for kernels.gram."""
    u = updates.astype(jnp.float32)
    g = grad.astype(jnp.float32)
    return u @ u.T, u @ g


def gram_block_ref(ua: jax.Array, ub: jax.Array, grad: jax.Array):
    """(G_ab, c_a) in f32 — oracle for kernels.gram.gram_block_pallas."""
    a = ua.astype(jnp.float32)
    b = ub.astype(jnp.float32)
    return a @ b.T, a @ grad.astype(jnp.float32)


def stream_stats_ref(deltas: jax.Array, grads: jax.Array):
    """(G = D Dᵀ, C = D GMᵀ) in f32 — oracle for kernels.stream."""
    d = deltas.astype(jnp.float32)
    g = grads.astype(jnp.float32)
    return d @ d.T, d @ g.T


def sketch_ref(updates: jax.Array, sketch: jax.Array) -> jax.Array:
    """U Rᵀ in f32 — oracle for kernels.sketch (stacked sketch-apply)."""
    return updates.astype(jnp.float32) @ sketch.astype(jnp.float32).T


def topk_ref(vec: jax.Array, k: int):
    """(values, indices i32) of the k largest-|v| entries — oracle for
    the ``topk`` op."""
    v = vec.astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(v), k)
    return jnp.take(v, idx), idx.astype(jnp.int32)


def combine_ref(params_vec: jax.Array, updates: jax.Array,
                alpha: jax.Array) -> jax.Array:
    """w + Σ α_k U_k — oracle for kernels.combine."""
    comb = jnp.einsum("k,kn->n", alpha.astype(jnp.float32),
                      updates.astype(jnp.float32))
    return (params_vec.astype(jnp.float32) + comb).astype(params_vec.dtype)


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, window: int | None = None,
                     softcap: float | None = None):
    """(o, lse) — oracle for kernels.decode_attn.

    q (B, KV, G, hd); k, v (B, S, KV, hd); lengths (B,).  ``softcap`` applies
    the tanh logit cap (gemma-style) before masking, matching
    ``models.layers.softcap``."""
    B, S, KV, hd = k.shape
    scale = hd ** -0.5
    q32 = q.astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bskd->bkgs", q32, k.astype(jnp.float32))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    kpos = jnp.arange(S)[None, None, None, :]
    ok = kpos < lengths[:, None, None, None]
    if window is not None:
        ok = ok & (kpos > lengths[:, None, None, None] - 1 - window)
    s = jnp.where(ok, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                   v.astype(jnp.float32))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return o, lse


def rng_sketch_ref(updates: jax.Array, seed, *, m: int,
                   block_n: int = 4096) -> jax.Array:
    """Materialized-R oracle for kernels.rng_sketch: builds the full sign
    matrix from the same counter-based hash, then one matmul."""
    from .rng_sketch import rng_sign_matrix
    del block_n                       # the oracle needs no tiling
    R = rng_sign_matrix(seed, m, updates.shape[1])
    return (updates.astype(jnp.float32) @ R.T) / jnp.sqrt(jnp.float32(m))


def rng_sketch_adjoint_ref(coords: jax.Array, seed, *, n: int,
                           block_n: int = 4096) -> jax.Array:
    """Materialized-R oracle for the decode-side adjoint ``Rᵀ s/√m``."""
    from .rng_sketch import rng_sign_matrix
    del block_n
    R = rng_sign_matrix(seed, coords.shape[0], n)
    return (R.T @ coords.astype(jnp.float32)) / jnp.sqrt(
        jnp.float32(coords.shape[0]))


def lse_merge_ref(o_parts: jax.Array, lse_parts: jax.Array):
    """Merge per-shard flash-decode partials.

    o_parts (P, B, KV, G, hd), lse_parts (P, B, KV, G, 1) → (o, lse)."""
    m = jnp.max(lse_parts, axis=0, keepdims=True)
    w = jnp.exp(lse_parts - m)                       # (P, …, 1)
    denom = jnp.sum(w, axis=0)
    o = jnp.sum(o_parts * w, axis=0) / jnp.maximum(denom, 1e-30)
    lse = m[0] + jnp.log(jnp.maximum(denom, 1e-30))
    return o, lse
