"""Backend-aware kernel engine for the paper's compute hot spots.

Ops (each with ``pallas`` / ``xla`` / ``ref`` backends unless noted,
autotune-dispatched through :mod:`repro.kernels.registry` — see ``ops.py``):

  * ``gram`` / ``gram_block`` — fused U Uᵀ / U g streaming contractions
    (server + hierarchical-merge aggregation)
  * ``combine``     — α-weighted update combine (paper eq. 4)
  * ``sketch``      — fused stacked sketch-apply U Rᵀ (explicit matrix)
  * ``sign_sketch`` — counter-based RNG sign sketch; R generated in-kernel,
    never materialized (``rng_sketch.py``)
  * ``topk``        — top-k magnitude selection (``xla`` / ``ref`` only)
  * ``flash_decode`` — flash-decode attention with LSE partials for
    seq-sharded KV caches (the serving path)

Pallas kernels are validated on CPU with ``interpret=True`` against the
``ref.py`` oracles and compile for real on TPU; off-TPU the autotuner picks
the jit-compiled pure-XLA formulation, never interpret mode.
"""
from .ops import (backends, dispatch, flash_decode, force_backend,
                  gram_and_cross, gram_block_and_cross, lse_merge,
                  sign_sketch, sign_sketch_adjoint, sketch_apply,
                  topk_select, weighted_combine)
from .registry import (autotune_records, available_ops,
                       clear_autotune_cache, register_impl, select_impl)

__all__ = ["autotune_records", "available_ops", "backends",
           "clear_autotune_cache", "dispatch", "flash_decode",
           "force_backend", "gram_and_cross", "gram_block_and_cross",
           "lse_merge", "register_impl", "select_impl", "sign_sketch",
           "sign_sketch_adjoint", "sketch_apply", "topk_select",
           "weighted_combine"]
