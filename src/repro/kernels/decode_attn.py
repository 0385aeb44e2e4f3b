"""Pallas TPU kernel: flash-decode attention for long KV caches.

One new token attends to a cache of S entries (decode_32k / long_500k serve
steps).  The contraction is memory-bound (reads the whole cache once), so
the kernel streams KV blocks HBM→VMEM with online-softmax accumulators in
VMEM and emits BOTH the attention output and the log-sum-exp, enabling the
cross-shard combine when the cache's seq axis is sharded over the mesh
(`ops.flash_decode_sharded` merges per-shard partials with an LSE-weighted
sum — the collective-efficient alternative to all-gathering the cache).

Grid: (B, S/block_s) — the seq axis is innermost so accumulators stay
resident in VMEM scratch across that loop.  The per-slot lengths ride
scalar prefetch (SMEM).  K/V keep the serving cache's (B, S, KV, hd)
layout and are viewed as (B, S, KV·hd), so one block holds every KV head
of ``block_s`` rows — a (block_s, KV·hd) tile the TPU's (8, 128) tiling
accepts — and the kernel walks the heads as static lane slices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, block_s: int, num_kv: int,
                   head_dim: int, window, softcap):
    b, s_idx = pl.program_id(0), pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]                            # valid entries = pos+1
    scale = head_dim ** -0.5
    for h in range(num_kv):                        # static: KV heads
        cols = slice(h * head_dim, (h + 1) * head_dim)
        q = q_ref[0, h].astype(jnp.float32)        # (G, hd)
        k = k_ref[0, :, cols].astype(jnp.float32)  # (bs, hd)
        v = v_ref[0, :, cols].astype(jnp.float32)  # (bs, hd)
        # scale after the dot: q and k stay exact in the MXU's bf16 passes
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        kpos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                          1)
        ok = kpos < length
        if window is not None:
            ok = ok & (kpos > length - 1 - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[h]                          # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(s_idx == pl.num_programs(1) - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[...] + jnp.log(l)).astype(lse_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "window", "softcap",
                                    "interpret"))
def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        lengths: jax.Array, *, block_s: int = 512,
                        window: int | None = None,
                        softcap: float | None = None,
                        interpret: bool = True):
    """q (B, KV, G, hd); k, v (B, S, KV, hd); lengths (B,) int32 (= pos+1).

    Returns ``(o (B, KV, G, hd) f32, lse (B, KV, G, 1) f32)`` — partials
    suitable for LSE-merge across seq shards.  ``softcap`` applies the tanh
    logit cap before masking (gemma-family serving).  A cache no longer
    than ``block_s`` is one block (no pad copy of the cache).
    """
    B, S, KV, hd = k.shape
    G = q.shape[2]
    block_s = min(block_s, S)
    pad = (-S) % block_s
    if pad:
        zk = ((0, 0), (0, pad), (0, 0), (0, 0))
        k, v = jnp.pad(k, zk), jnp.pad(v, zk)
    Sp = S + pad
    # free row-major view: every KV head of a cache row is one lane run
    k = k.reshape(B, Sp, KV * hd)
    v = v.reshape(B, Sp, KV * hd)

    kernel = functools.partial(_decode_kernel, block_s=block_s, num_kv=KV,
                               head_dim=hd, window=window, softcap=softcap)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Sp // block_s),
            in_specs=[
                pl.BlockSpec((1, KV, G, hd), lambda b, s, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, block_s, KV * hd),
                             lambda b, s, lens: (b, s, 0)),
                pl.BlockSpec((1, block_s, KV * hd),
                             lambda b, s, lens: (b, s, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, KV, G, hd), lambda b, s, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, KV, G, 1), lambda b, s, lens: (b, 0, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((KV, G, 1), jnp.float32),    # running max m
                pltpu.VMEM((KV, G, 1), jnp.float32),    # running denom l
                pltpu.VMEM((KV, G, hd), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
    return o, lse
