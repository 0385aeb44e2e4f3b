"""Public kernel entry points, dispatched through the backend registry.

Each op registers three implementations (see :mod:`repro.kernels.registry`):
``pallas`` (compiled on TPU, interpret-mode validation elsewhere), ``xla``
(jit-compiled pure-jnp — the off-TPU production path) and ``ref`` (the eager
jnp oracle).  The first call per (op, shape-bucket, platform) micro-autotunes
among the eligible backends and caches the winner in-process; interpret-mode
Pallas is never an autotune candidate off-TPU, so off-TPU runs never pay
interpret overhead — the PR-3 wrappers' inconsistent ``use_pallas or not
on_tpu()`` defaults are gone.

Back-compat forcing: ``use_pallas=True`` pins the Pallas path (interpret
off-TPU — the end-to-end kernel validation tests), ``use_pallas=False`` pins
the reference oracle; ``backend=`` names any registered backend directly.
``topk`` and ``sign_sketch_adjoint`` have no Pallas backend.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .combine import combine_pallas
from .decode_attn import flash_decode_pallas
from .gram import gram_block_pallas, gram_pallas
from .registry import (backends, dispatch, force_backend, on_tpu,
                       register_impl, select_impl)
from .rng_sketch import rng_sketch_pallas, rng_sketch_xla, \
    rng_sketch_adjoint_xla
from .sketch import sketch_apply_pallas
from .stream import pallas_tile, stream_stats_pallas, stream_stats_xla

__all__ = ["on_tpu", "gram_and_cross", "gram_block_and_cross",
           "stream_stats", "sketch_apply", "topk_select",
           "weighted_combine", "sign_sketch", "sign_sketch_adjoint",
           "flash_decode", "lse_merge",
           "backends", "dispatch", "force_backend", "select_impl"]


def _not_interpret() -> bool:
    # Pallas autotune eligibility: compiled on TPU only; interpret mode is a
    # correctness path, never a contender
    return on_tpu()


# autotune-ineligible marker: backends that could win a micro-timing at
# small/capped shapes but materialize memory the op exists to avoid
_never = (lambda: False)


def _backend_for(use_pallas: Optional[bool],
                 backend: Optional[str]) -> Optional[str]:
    if backend is not None:
        return backend
    if use_pallas is None:
        return None                   # registry decides (autotune)
    return "pallas" if use_pallas else "ref"


# --------------------------------------------------------------- gram ops

# VMEM the gram kernel's double-buffered (K, block_n) input block plus its
# resident (K, K) output may take: the v5e compiler refuses K=2000 at
# block_n=2048 (≈49 MB) and accepts K=1800 (≈42 MB)
GRAM_VMEM_BYTES = 32 << 20


def _gram_pallas_ok(u, g, block_n=2048) -> bool:
    Kp = u.shape[0] + (-u.shape[0]) % 8
    return (Kp * Kp * 4 + 2 * Kp * block_n * u.dtype.itemsize
            <= GRAM_VMEM_BYTES)


register_impl("gram", "pallas",
              lambda u, g, block_n=2048: gram_pallas(
                  u, g, block_n=block_n, interpret=not on_tpu()),
              supports=_gram_pallas_ok, eligible=_not_interpret)
_gram_xla_jit = jax.jit(ref.gram_ref)
register_impl("gram", "xla",
              lambda u, g, block_n=2048: _gram_xla_jit(u, g))
register_impl("gram", "ref", lambda u, g, block_n=2048: ref.gram_ref(u, g))


def gram_and_cross(updates: jax.Array, grad: jax.Array, *,
                   use_pallas: Optional[bool] = None,
                   block_n: int = 2048,
                   backend: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Fused G = U Uᵀ, c = U g.  updates (K, n), grad (n,)."""
    return dispatch("gram", updates, grad, block_n=block_n,
                    backend=_backend_for(use_pallas, backend))


register_impl("gram_block", "pallas",
              lambda ua, ub, g, block_n=2048: gram_block_pallas(
                  ua, ub, g, block_n=block_n, interpret=not on_tpu()),
              eligible=_not_interpret)
_gram_block_xla_jit = jax.jit(ref.gram_block_ref)
register_impl("gram_block", "xla",
              lambda ua, ub, g, block_n=2048: _gram_block_xla_jit(ua, ub, g))
register_impl("gram_block", "ref",
              lambda ua, ub, g, block_n=2048: ref.gram_block_ref(ua, ub, g))


def gram_block_and_cross(ua: jax.Array, ub: jax.Array, grad: jax.Array, *,
                         use_pallas: Optional[bool] = None,
                         block_n: int = 2048,
                         backend: Optional[str] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """One fused hierarchical-merge block: G_ab = U_a U_bᵀ AND c_a = U_a g
    (named apart from ``core.gram.gram_block``, which returns G alone)."""
    return dispatch("gram_block", ua, ub, grad, block_n=block_n,
                    backend=_backend_for(use_pallas, backend))


def _same_2d(d, g, block_n=0) -> bool:
    return (getattr(d, "ndim", 0) == 2 and tuple(d.shape) == tuple(g.shape))


def _stream_pallas_ok(d, g, block_n=2048) -> bool:
    # the pallas wrapper pads to (8-row, tile-column) tiles with jnp.pad
    # — an O(P·n) input copy that would break the streamed engine's
    # O(P·chunk) memory model, so dispatch/autotune only offer it on
    # already-aligned shapes (explicit backend="pallas" still runs the
    # padded path for validation)
    if not _same_2d(d, g):
        return False
    P, n = d.shape
    tile = pallas_tile(P, jnp.promote_types(d.dtype, g.dtype), block_n)
    return P % 8 == 0 and n % tile == 0


register_impl("stream_stats", "pallas",
              lambda d, g, block_n=2048: stream_stats_pallas(
                  d, g, block_n=block_n, interpret=not on_tpu()),
              supports=_stream_pallas_ok, eligible=_not_interpret)
register_impl("stream_stats", "xla",
              lambda d, g, block_n=1 << 16: stream_stats_xla(
                  d, g, block_n=block_n),
              supports=_same_2d)
# like sign_sketch's ref: the oracle materializes full-width f32 upcasts —
# the very copies the op exists to avoid — so it must never win an
# autotune timing at capped shapes and then OOM at production ones; reach
# it only via backend="ref" / force_backend, as tests do
register_impl("stream_stats", "ref",
              lambda d, g, block_n=1 << 16: ref.stream_stats_ref(d, g),
              supports=_same_2d, eligible=_never)


def stream_stats(deltas: jax.Array, grads: jax.Array, *,
                 use_pallas: Optional[bool] = None,
                 block_n: int = 1 << 16,
                 backend: Optional[str] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Fused round statistics G = D Dᵀ, C = D GMᵀ in one streaming pass.
    deltas/grads (P, n), any float dtype; f32 accumulation, O(P·block_n)
    working set on the streaming backends.  ``block_n`` is the column chunk
    and participates in the autotune shape bucket, so the tuner picks the
    winning (backend, chunk) pair per (P, n) bucket."""
    return dispatch("stream_stats", deltas, grads, block_n=block_n,
                    backend=_backend_for(use_pallas, backend))


# ------------------------------------------------------------ compression

register_impl("sketch", "pallas",
              lambda u, r, block_n=2048: sketch_apply_pallas(
                  u, r, block_n=block_n, interpret=not on_tpu()),
              eligible=_not_interpret)
_sketch_xla_jit = jax.jit(ref.sketch_ref)
register_impl("sketch", "xla",
              lambda u, r, block_n=2048: _sketch_xla_jit(u, r))
register_impl("sketch", "ref",
              lambda u, r, block_n=2048: ref.sketch_ref(u, r))


def sketch_apply(updates: jax.Array, sketch: jax.Array, *,
                 use_pallas: Optional[bool] = None,
                 block_n: int = 2048,
                 backend: Optional[str] = None) -> jax.Array:
    """Stacked sketch-apply ``U Rᵀ`` against an explicit sketch matrix.
    updates (K, n), sketch (m, n).  For the counter-based sign sketch that
    never materializes R, use :func:`sign_sketch`."""
    return dispatch("sketch", updates, sketch, block_n=block_n,
                    backend=_backend_for(use_pallas, backend))


# no pallas backend: the TPU kernel compiler lowers neither lax.top_k nor
# the gather a chunked selection needs
_topk_xla_jit = jax.jit(ref.topk_ref, static_argnums=1)
register_impl("topk", "xla", lambda v, k: _topk_xla_jit(v, k))
register_impl("topk", "ref", lambda v, k: ref.topk_ref(v, k))


def topk_select(vec: jax.Array, k: int, *, backend: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """k largest-|v| entries as (values, indices i32)."""
    return dispatch("topk", vec, k, backend=backend)


# ----------------------------------------------------------- combine / rng

register_impl("combine", "pallas",
              lambda w, u, a, block_n=2048: combine_pallas(
                  w, u, a, block_n=block_n, interpret=not on_tpu()),
              eligible=_not_interpret)
_combine_xla_jit = jax.jit(ref.combine_ref)
register_impl("combine", "xla",
              lambda w, u, a, block_n=2048: _combine_xla_jit(w, u, a))
register_impl("combine", "ref",
              lambda w, u, a, block_n=2048: ref.combine_ref(w, u, a))


def weighted_combine(params_vec: jax.Array, updates: jax.Array,
                     alpha: jax.Array, *, use_pallas: Optional[bool] = None,
                     block_n: int = 2048,
                     backend: Optional[str] = None) -> jax.Array:
    """w + Σ α_k U_k.  params_vec (n,), updates (K, n), alpha (K,)."""
    return dispatch("combine", params_vec, updates, alpha, block_n=block_n,
                    backend=_backend_for(use_pallas, backend))


# The ref oracle materializes the full m×n R — the very thing this op
# exists to avoid — so it is NEVER an autotune candidate (it could win a
# micro-timing at toy shapes and OOM at production ones); reach it only via
# backend="ref" / force_backend, as tests do.
register_impl("sign_sketch", "pallas",
              lambda u, seed, m, block_n=2048: rng_sketch_pallas(
                  u, seed, m=m, block_n=block_n, interpret=not on_tpu()),
              eligible=_not_interpret)
register_impl("sign_sketch", "xla",
              lambda u, seed, m, block_n=4096: rng_sketch_xla(
                  u, seed, m=m, block_n=block_n))
register_impl("sign_sketch", "ref",
              lambda u, seed, m, block_n=4096: ref.rng_sketch_ref(
                  u, seed, m=m),
              eligible=_never)


def sign_sketch(updates: jax.Array, seed, m: int, *,
                use_pallas: Optional[bool] = None, block_n: int = 4096,
                backend: Optional[str] = None) -> jax.Array:
    """Counter-based sign sketch ``U Rᵀ/√m`` (K, n) → (K, m): the Rademacher
    matrix is generated on the fly from (row, col, seed) counters and never
    materialized (see :mod:`repro.kernels.rng_sketch`).  ``seed`` is a
    uint32 scalar (array or int)."""
    seed = jnp.asarray(seed, jnp.uint32)
    return dispatch("sign_sketch", updates, seed, m, block_n=block_n,
                    backend=_backend_for(use_pallas, backend))


register_impl("sign_sketch_adjoint", "xla",
              lambda s, seed, n, block_n=4096: rng_sketch_adjoint_xla(
                  s, seed, n=n, block_n=block_n))
register_impl("sign_sketch_adjoint", "ref",
              lambda s, seed, n, block_n=4096: ref.rng_sketch_adjoint_ref(
                  s, seed, n=n),
              eligible=_never)


def sign_sketch_adjoint(coords: jax.Array, seed, n: int, *,
                        block_n: int = 4096,
                        backend: Optional[str] = None) -> jax.Array:
    """Decode-side adjoint ``Rᵀ s/√m`` (m,) → (n,), same implicit R."""
    seed = jnp.asarray(seed, jnp.uint32)
    return dispatch("sign_sketch_adjoint", coords, seed, n,
                    block_n=block_n, backend=backend)


# ------------------------------------------------------------ decode attn

register_impl("flash_decode", "pallas",
              lambda q, k, v, lengths, window=None, softcap=None,
              block_s=512: flash_decode_pallas(
                  q, k, v, lengths, block_s=block_s, window=window,
                  softcap=softcap, interpret=not on_tpu()),
              eligible=_not_interpret)
_flash_decode_xla_jit = jax.jit(ref.flash_decode_ref,
                                static_argnames=("window", "softcap"))
register_impl("flash_decode", "xla",
              lambda q, k, v, lengths, window=None, softcap=None,
              block_s=512: _flash_decode_xla_jit(
                  q, k, v, lengths, window=window, softcap=softcap))
register_impl("flash_decode", "ref",
              lambda q, k, v, lengths, window=None, softcap=None,
              block_s=512: ref.flash_decode_ref(
                  q, k, v, lengths, window=window, softcap=softcap))


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, *, window: Optional[int] = None,
                 softcap: Optional[float] = None, block_s: int = 512,
                 use_pallas: Optional[bool] = None,
                 backend: Optional[str] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Single-token attention vs a long cache; returns (o, lse) partials.

    The serving hot path (``repro.serve.DecodeEngine`` calls this per layer
    per step): q (B, KV, G, hd) against k/v (B, S, KV, hd) with per-slot
    ``lengths`` (B,) masking — exactly the continuous-batching contract.
    Dispatched through the autotune registry like the aggregation ops; the
    former manual interpret-mode branch (pallas on TPU, eager ref elsewhere
    — the eager oracle on every off-TPU decode step) is gone."""
    return dispatch("flash_decode", q, k, v, lengths, window=window,
                    softcap=softcap, block_s=block_s,
                    backend=_backend_for(use_pallas, backend))


def lse_merge(o_parts: jax.Array, lse_parts: jax.Array):
    """Combine per-shard (o, lse) partials — used after a sharded
    flash_decode where each mesh slice scanned its local cache shard."""
    return ref.lse_merge_ref(o_parts, lse_parts)
