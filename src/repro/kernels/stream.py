"""Streaming round-statistics kernel: fused ``G = D Dᵀ`` + ``C = D GMᵀ``.

The streamed hierarchical round engine (``repro.hier.streamed``) reduces an
entire round's tier tree to the device-level pair

    G = D Dᵀ ∈ R^{P×P}      (update-update inner products)
    C = D GMᵀ ∈ R^{P×P}     (update-gradient inner products)

where D stacks the P flattened client updates and GM the matching gradient
estimates.  Every tier's Gram block is a sub-block of G, every c-term is a
row-mix of C, so one pass over the parameter axis feeds the whole tree.
Like the PR-2 Gram kernels this is a memory-bound tall-skinny contraction
(arithmetic intensity ≈ P FLOP/byte); fusing the two products reads the D
stream once instead of twice, and the GM stream rides the same pass.

Both streaming implementations keep the working set at O(P·block_n):

  * :func:`stream_stats_xla` — ``lax.scan`` over the full ``block_n``-column
    windows read via ``lax.dynamic_slice`` (no padded/transposed copy of
    the inputs, unlike ``core.gram.gram_and_cross_chunked``'s reshape —
    that copy is exactly what transformer-width rounds cannot afford), plus
    one statically-sliced remainder tile: no masking, no window ever pays
    more than its own bandwidth.
  * :func:`stream_stats_pallas` — grid over column tiles, both (P, tile)
    operand tiles ride one HBM→VMEM stream, outputs accumulate in VMEM f32
    across the grid (constant index_map).  The tile is the caller's
    ``block_n`` capped by :func:`pallas_tile` to what fits VMEM, so the
    streamed engine's column chunk never becomes the kernel's block.
    Inputs are padded to the tile boundary like the other Pallas kernels.

Inputs may be any float dtype (bf16 transformer updates upcast per tile);
accumulation is always f32.  The eager oracle lives in ``kernels.ref``
(``stream_stats_ref``); dispatch + autotune (``block_n`` participates in
the shape bucket) in ``kernels.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# bytes one (P, tile) input block may hold in VMEM.  Both inputs are
# double-buffered and upcast to f32 in the kernel; at 2 MiB per block the
# v5e compiler accepts the kernel, while a 2**18-column bf16 block at P=16
# (8 MiB) runs out of VMEM.
PALLAS_TILE_BYTES = 1 << 21


def pallas_tile(P: int, dtype, block_n: int) -> int:
    """Column tile of :func:`stream_stats_pallas` for a ``block_n`` chunk
    over (P, n) inputs of ``dtype``: ``block_n`` capped to the largest
    power of two whose sublane-padded (P, tile) block fits
    ``PALLAS_TILE_BYTES`` (never below one 128-lane tile)."""
    row_bytes = (P + (-P) % 8) * jnp.dtype(dtype).itemsize
    fit = max(PALLAS_TILE_BYTES // row_bytes, 1)
    cap = max(128, 1 << (fit.bit_length() - 1))
    return min(int(block_n), cap)


def _accum_tile(G, C, d, g):
    d = d.astype(jnp.float32)
    g = g.astype(jnp.float32)
    G = G + jax.lax.dot_general(d, d, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    C = C + jax.lax.dot_general(d, g, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    return G, C


@functools.partial(jax.jit, static_argnames=("block_n",))
def stream_stats_xla(deltas: jax.Array, grads: jax.Array, *,
                     block_n: int = 1 << 16):
    """(G, C) in one ``lax.scan`` pass of ``block_n`` columns, O(P·block_n)
    working set, no input copies.  Full windows scan unmasked; the
    ``n % block_n`` remainder is a single statically-sliced tile, so the
    memory-bound hot loop never pays a mask pass."""
    P, n = deltas.shape
    if grads.shape != deltas.shape:
        raise ValueError(f"deltas/grads disagree: {deltas.shape} vs "
                         f"{grads.shape}")
    G = jnp.zeros((P, P), jnp.float32)
    C = jnp.zeros((P, P), jnp.float32)
    if n == 0:
        return G, C
    bn = min(int(block_n), n)
    full, rem = divmod(n, bn)

    if full == 1:
        G, C = _accum_tile(G, C, deltas[:, :bn], grads[:, :bn])
    elif full > 1:
        def body(carry, i):
            start = i * bn
            d = jax.lax.dynamic_slice(deltas, (0, start), (P, bn))
            g = jax.lax.dynamic_slice(grads, (0, start), (P, bn))
            return _accum_tile(*carry, d, g), None

        (G, C), _ = jax.lax.scan(body, (G, C), jnp.arange(full))
    if rem:
        G, C = _accum_tile(G, C, deltas[:, full * bn:], grads[:, full * bn:])
    return G, C


def _stream_stats_kernel(d_ref, g_ref, G_ref, C_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        G_ref[...] = jnp.zeros_like(G_ref)
        C_ref[...] = jnp.zeros_like(C_ref)

    d = d_ref[...].astype(jnp.float32)            # (Pp, bn)
    g = g_ref[...].astype(jnp.float32)            # (Pp, bn)
    G_ref[...] += jax.lax.dot_general(
        d, d, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    C_ref[...] += jax.lax.dot_general(
        d, g, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def stream_stats_pallas(deltas: jax.Array, grads: jax.Array, *,
                        block_n: int = 2048, interpret: bool = True):
    """Pallas twin: grid over column tiles of :func:`pallas_tile` width,
    (G, C) resident in VMEM f32.  P is padded to the 8-sublane boundary,
    n to a tile multiple (zero columns contribute nothing to either
    product)."""
    P, n = deltas.shape
    if grads.shape != deltas.shape:
        raise ValueError(f"deltas/grads disagree: {deltas.shape} vs "
                         f"{grads.shape}")
    block_n = pallas_tile(P, jnp.promote_types(deltas.dtype, grads.dtype),
                          block_n)
    padP, padN = (-P) % 8, (-n) % block_n
    d = jnp.pad(deltas, ((0, padP), (0, padN)))
    g = jnp.pad(grads, ((0, padP), (0, padN)))
    Pp = P + padP

    grid = ((n + padN) // block_n,)
    G, C = pl.pallas_call(
        _stream_stats_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Pp, block_n), lambda i: (0, i)),
            pl.BlockSpec((Pp, block_n), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((Pp, Pp), lambda i: (0, 0)),
            pl.BlockSpec((Pp, Pp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Pp, Pp), jnp.float32),
            jax.ShapeDtypeStruct((Pp, Pp), jnp.float32),
        ],
        interpret=interpret,
    )(d, g)
    return G[:P, :P], C[:P, :P]
