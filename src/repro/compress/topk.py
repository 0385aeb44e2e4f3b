"""Top-k magnitude masking — the selection compressor.

Keeps the k largest-|v| coordinates and ships (value, index) pairs: 2k wire
words for an n-vector, so ``CompressConfig.ratio`` resolves ``k = n/(2·ratio)``.
The decode is the *exact* sparse vector the receiver applies — the bias
lives entirely in the dropped residual, which per-sender error feedback
(:mod:`repro.compress.error_feedback`) re-injects into the next round's
input, the classic EF construction that restores convergence for any
contraction compressor.  At k = n the scheme is the identity, the exactness
anchor the tests pin against the uncompressed pipeline.

The selection itself runs through ``kernels.ops.topk_select``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Compressed, CompressConfig, Compressor, register_scheme


class TopKCompressor(Compressor):
    """Magnitude top-k with exact sparse decode."""

    name = "topk"
    linear = False

    def __init__(self, k: int):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.k = int(k)

    def encode(self, vec: jax.Array, seed: int = 0) -> Compressed:
        from ..kernels import ops
        n = int(vec.shape[0])
        k = min(self.k, n)
        vals, idx = ops.topk_select(jnp.asarray(vec, jnp.float32), k)
        return Compressed(self.name, n, (vals, idx), seed)

    def decode(self, comp: Compressed) -> jax.Array:
        vals, idx = comp.data
        return jnp.zeros((comp.n,), jnp.float32).at[idx].set(vals)

    def wire_floats(self, n: int) -> int:
        return 2 * min(self.k, n)


def _build(cfg: CompressConfig, n: int) -> TopKCompressor:
    k = cfg.k if cfg.k is not None else max(1, int(n / (2.0 * cfg.ratio)))
    return TopKCompressor(k)


register_scheme("topk", _build)
