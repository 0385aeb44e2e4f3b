"""Counter-based RNG sign sketch: ``S_U = U Rᵀ/√m`` without materializing R.

The PR-3 sign sketch regenerated the dense m×n Rademacher matrix R from its
seed on every encode — O(m·n) memory traffic for a matrix whose entries are
a pure function of (row, column, seed).  Here the signs are generated
*inside* the contraction from a counter-based hash (a murmur3-style integer
mixer over the global (row, column, seed) counters — plain uint32 ops that
lower on every backend, unlike ``jax.random`` inside a TPU Pallas kernel):

    R[i, j] = 1 − 2·msb(mix32(j ⊕ mix32(i ⊕ seed)))

so every backend produces the *identical* R without ever holding more than
one (m, block_n) tile of it:

  * ``rng_sketch_pallas``   — the tile is generated in-kernel (VMEM) per
    grid step and contracted on the MXU; only U streams from HBM.
  * ``rng_sketch_xla``      — a jit-compiled ``lax.scan`` over n-chunks with
    the same tile function; the off-TPU production path.
  * ``rng_sign_matrix``     — materializes R (the oracle the property tests
    pin the streaming paths against at fixed seed).

``rng_sketch_adjoint_xla`` applies ``Rᵀ s/√m`` the same chunked way for the
decode side.  All paths fold the 1/√m scaling in, so the sketch operator is
``S = R/√m`` with ``E[SᵀS] = I`` exactly as ``repro.compress`` assumes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_MIX1 = 0x85EBCA6B
_MIX2 = 0xC2B2AE35


def _mix32(x: jax.Array) -> jax.Array:
    """murmur3 finalizer: a 4-round avalanche mixer on uint32 counters."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_MIX1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_MIX2)
    x = x ^ (x >> 16)
    return x


def sign_tile(seed: jax.Array, row0, col0, rows: int, cols: int) -> jax.Array:
    """±1 f32 tile ``R[row0:row0+rows, col0:col0+cols]`` of the implicit
    sign matrix R(seed).  Entries depend only on the *global* (row, column)
    counters, so any tiling of the same matrix agrees exactly."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    r = r + jnp.asarray(row0, jnp.uint32)
    c = c + jnp.asarray(col0, jnp.uint32)
    h = _mix32(c ^ _mix32(r ^ jnp.asarray(seed, jnp.uint32)))
    # via int32: the TPU kernel compiler has no uint32 -> float32 cast
    return 1.0 - 2.0 * (h >> 31).astype(jnp.int32).astype(jnp.float32)


def rng_sign_matrix(seed, m: int, n: int) -> jax.Array:
    """Materialized ``R (m, n)`` — the oracle for the streaming paths (and
    the only place the full matrix ever exists; tests only)."""
    return sign_tile(seed, 0, 0, m, n)


# --------------------------------------------------------------- XLA paths

@functools.partial(jax.jit, static_argnames=("m", "block_n"))
def rng_sketch_xla(updates: jax.Array, seed, *, m: int,
                   block_n: int = 4096) -> jax.Array:
    """``updates (K, n)`` → ``U Rᵀ/√m (K, m)``, one compiled scan over
    n-chunks; the sign tile is regenerated per chunk and never stored."""
    K, n = updates.shape
    pad = (-n) % block_n
    u = jnp.pad(updates.astype(jnp.float32), ((0, 0), (0, pad)))
    steps = (n + pad) // block_n
    u = u.reshape(K, steps, block_n).transpose(1, 0, 2)

    def body(acc, xs):
        j, uc = xs
        r = sign_tile(seed, 0, j * block_n, m, block_n)
        acc = acc + jax.lax.dot_general(
            uc, r, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, None

    acc0 = jnp.zeros((K, m), jnp.float32)
    S, _ = jax.lax.scan(body, acc0,
                        (jnp.arange(steps, dtype=jnp.uint32), u))
    return S / jnp.sqrt(jnp.float32(m))


@functools.partial(jax.jit, static_argnames=("n", "block_n"))
def rng_sketch_adjoint_xla(coords: jax.Array, seed, *, n: int,
                           block_n: int = 4096) -> jax.Array:
    """``coords (m,)`` → ``Rᵀ coords/√m (n,)`` — the decode-side adjoint,
    chunked the same way (zero-padded tail sliced off exactly)."""
    m = coords.shape[0]
    pad = (-n) % block_n
    steps = (n + pad) // block_n
    s32 = coords.astype(jnp.float32)

    def body(carry, j):
        r = sign_tile(seed, 0, j * block_n, m, block_n)   # (m, bn)
        return carry, s32 @ r

    _, out = jax.lax.scan(body, 0, jnp.arange(steps, dtype=jnp.uint32))
    return out.reshape(-1)[:n] / jnp.sqrt(jnp.float32(m))


# ------------------------------------------------------------- Pallas path

def _rng_sketch_kernel(seed_ref, u_ref, su_ref, *, mp: int, block_n: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        su_ref[...] = jnp.zeros_like(su_ref)

    u = u_ref[...].astype(jnp.float32)                 # (Kp, bn)
    col0 = pl.program_id(0) * block_n
    # in-kernel counter-based RNG: the (mp, bn) sign tile is born in VMEM
    r = sign_tile(seed_ref[0, 0], 0, col0, mp, block_n)
    su_ref[...] += jax.lax.dot_general(
        u, r, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "block_n", "interpret"))
def rng_sketch_pallas(updates: jax.Array, seed, *, m: int,
                      block_n: int = 2048, interpret: bool = True
                      ) -> jax.Array:
    """Pallas twin of :func:`rng_sketch_xla`: U streams HBM→VMEM once, the
    sign tile is generated in-kernel per grid step, the (K, m) accumulator
    stays VMEM-resident.  Row-pad rows of the tile (m → mp) produce extra
    output rows that are sliced off; zero-padded U columns contribute
    nothing — both exact."""
    K, n = updates.shape
    padK, padM, padN = (-K) % 8, (-m) % 8, (-n) % block_n
    u = jnp.pad(updates, ((0, padK), (0, padN)))
    seed2d = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    Kp, Mp = K + padK, m + padM

    grid = ((n + padN) // block_n,)
    su = pl.pallas_call(
        functools.partial(_rng_sketch_kernel, mp=Mp, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((Kp, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((Kp, Mp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Kp, Mp), jnp.float32),
        interpret=interpret,
    )(seed2d, u)
    return su[:K, :m] / jnp.sqrt(jnp.float32(m))
