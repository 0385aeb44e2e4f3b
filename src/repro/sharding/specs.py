"""Partition-spec rules (DESIGN.md §7).

Strategy per tensor class (mesh axes: optional 'pod', 'data', 'model'):

  * large 2-D projection weights — tensor-parallel on the contraction-free
    dim over 'model'; for ≥`fsdp_threshold` params additionally FSDP the
    other dim over 'data' (all-gathered per layer by GSPMD on use);
  * expert tensors (E, d, ff) — expert-parallel: E over 'model';
  * embeddings (V, d) — vocab over 'model' (+ d over 'data' when FSDP);
  * norms / biases / small vectors — replicated;
  * activations: batch over 'data' ('pod','data' when multi-pod);
  * KV caches: batch over 'data', seq over 'model' (flash-decode LSE
    sharding — valid for every arch since seq always divides, unlike
    kv_heads);  long_500k (batch 1): seq over ('data','model').

Every axis assignment is guarded by divisibility; a non-dividing axis is
dropped (replicated) rather than producing an invalid sharding.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ArchConfig

Pytree = Any

FSDP_THRESHOLD = 7_000_000_000   # params; ≥7B also shards over 'data'


def _axis_size(mesh: Mesh, name: Optional[str]) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return mesh.shape[name]


def _fits(dim: int, mesh: Mesh, name) -> bool:
    return dim % _axis_size(mesh, name) == 0


def _guard(spec: Tuple, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop axis assignments that don't divide the corresponding dim."""
    out = []
    for dim, name in zip(shape, spec):
        out.append(name if name is not None and _fits(dim, mesh, name) else None)
    return P(*out)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def spec_for_leaf(path_str: str, shape: Tuple[int, ...], mesh: Mesh,
                  fsdp: bool) -> P:
    """Rule table: first match wins.  `shape` includes any leading stacked
    layer axis (we detect and skip it)."""
    d_axis = "data" if fsdp else None
    rules = [
        # --- MoE expert tensors (L, E, d, ff) / (E, d, ff)
        (r"moe/w_(up|gate|down)$", lambda s: ("model", d_axis, None)),
        (r"moe/router$", lambda s: (None, None)),
        # --- embeddings / unembeddings
        (r"(^|/)embed$", lambda s: ("model", d_axis)),
        (r"(^|/)lm_head$", lambda s: (d_axis, "model")),
        (r"img_proj$", lambda s: (None, "model")),
        # --- attention projections (column-parallel qkv, row-parallel o)
        (r"attn/w[qkv]$|cross/w[qkv]$", lambda s: (d_axis, "model")),
        (r"attn/wo$|cross/wo$", lambda s: ("model", d_axis)),
        (r"attn/b[qkv]$|cross/b[qkv]$", lambda s: ("model",)),
        # --- dense MLP (column-parallel up/gate, row-parallel down)
        (r"mlp/w_(up|gate)$|shared/w_(up|gate)$", lambda s: (d_axis, "model")),
        (r"mlp/w_down$|shared/w_down$", lambda s: ("model", d_axis)),
        # --- mamba2
        (r"mamba/in_proj$", lambda s: (d_axis, "model")),
        (r"mamba/out_proj$", lambda s: ("model", d_axis)),
        (r"mamba/conv_[wb]$", lambda s: (None,) * len(s)),
        # --- rwkv6
        (r"rwkv/(wr|wk|wv|wg|ffn_k|ffn_r|w_A)$", lambda s: (d_axis, "model")),
        (r"rwkv/(wo|ffn_v|w_B)$", lambda s: ("model", d_axis)),
    ]
    for pat, builder in rules:
        if re.search(pat, path_str):
            spec = builder(shape)
            # leading stacked-layer axes (scan stacks) stay unsharded
            lead = len(shape) - len(spec)
            return _guard((None,) * lead + tuple(spec), shape, mesh)
    return P()   # replicate (norms, scalars, small vectors)


def param_pspecs(cfg: ArchConfig, params_shape: Pytree, mesh: Mesh,
                 mode: str = "tp") -> Pytree:
    """Map a pytree of ShapeDtypeStructs (or arrays) to PartitionSpecs.

    ``mode='tp'`` — tensor/expert-parallel over 'model' (+FSDP ≥7B);
    ``mode='dp'`` — fully replicated params (§Perf iteration 1: small models
    use every mesh axis as data parallelism; the per-layer TP all-reduces
    disappear and the only collective left is the cohort combine)."""
    if mode == "dp":
        flat, treedef = jax.tree_util.tree_flatten(params_shape)
        return jax.tree_util.tree_unflatten(treedef, [P()] * len(flat))
    fsdp = cfg.param_count_estimate() >= FSDP_THRESHOLD
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [spec_for_leaf(_path_str(p), tuple(l.shape), mesh, fsdp)
             for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_pspec(mesh: Mesh, batch_size: int) -> P:
    """Sharding for the leading batch axis of inputs."""
    axes = [a for a in ("pod", "data") if a in mesh.shape]
    name = tuple(axes) if len(axes) > 1 else (axes[0] if axes else None)
    if name is None or batch_size % _axis_size(mesh, name) != 0:
        # try data only, else replicate (long_500k batch=1)
        if "data" in mesh.shape and batch_size % mesh.shape["data"] == 0:
            return P("data")
        return P(None)
    return P(name)


def cache_pspecs(cfg: ArchConfig, cache_shape: Pytree, mesh: Mesh,
                 batch_size: int) -> Pytree:
    """KV caches: (L, B, S, KV, hd) → batch@data, seq@model; batch-1 decode
    shards seq over ('data','model').  SSM states: (L, B, H, P[, N]) →
    batch@data, heads@model."""
    bspec = batch_pspec(mesh, batch_size)
    batch_axis = bspec[0] if len(bspec) else None
    seq_axes = ("model",) if batch_axis is not None else \
        tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    seq_axis = seq_axes if len(seq_axes) > 1 else seq_axes[0]

    def leaf_spec(path, leaf):
        shape = tuple(leaf.shape)
        name = _path_str(path)
        if leaf.ndim == 0 or "position" in name:
            return P()
        if leaf.ndim == 5:      # (L, B, S, KV, hd) stacked KV cache
            return _guard((None, batch_axis, seq_axis, None, None), shape, mesh)
        if leaf.ndim == 4 and "wkv" in name:    # rwkv (L?, B, H, P, P)…
            return _guard((None, batch_axis, "model", None), shape, mesh)
        if leaf.ndim == 5 and "ssm" in name:
            return _guard((None, batch_axis, "model", None, None), shape, mesh)
        if leaf.ndim == 4:      # (L, B, W, C) conv state or (B,S,KV,hd)
            return _guard((None, batch_axis, None, None), shape, mesh)
        if leaf.ndim == 3:
            return _guard((None, batch_axis, None), shape, mesh)
        if leaf.ndim == 2:
            return _guard((None, batch_axis), shape, mesh)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    specs = [leaf_spec(p, l) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def stream_column_shardings(mesh: Mesh, stacked: Pytree) -> Pytree:
    """Shardings for a *stacked* round pytree (leading P device axis per
    leaf) that partition the streamed engine's chunk axis: the trailing
    (column) dim of every ≥2-D leaf is sharded over every available mesh
    axis, so the ``stream_stats`` scan partitions its column windows across
    devices and GSPMD all-reduces the (P, P) accumulators.  Guarded by
    divisibility like every other rule here — a non-dividing leaf stays
    replicated rather than producing an invalid sharding."""
    axes = [a for a in ("pod", "data", "model") if a in mesh.shape]
    name = tuple(axes) if len(axes) > 1 else (axes[0] if axes else None)

    def leaf_sharding(leaf):
        shape = tuple(leaf.shape)
        if name is None or len(shape) < 2:
            return NamedSharding(mesh, P())
        spec = (None,) * (len(shape) - 1) + (name,)
        return NamedSharding(mesh, _guard(spec, shape, mesh))

    return jax.tree_util.tree_map(leaf_sharding, stacked)


def fleet_mesh(devices=None) -> Mesh:
    """One-axis 'fleet' mesh over the host's accelerators — the device-axis
    sharding entry point for fleet-scale cohorts (compose it with
    'data'/'model' axes by building the Mesh yourself)."""
    devices = jax.devices() if devices is None else devices
    return Mesh(np.asarray(devices), ("fleet",))


def stream_round_shardings(mesh: Mesh, stacked: Pytree) -> Pytree:
    """:func:`stream_column_shardings` plus a leading device-axis partition:
    with a ``'fleet'`` mesh axis the leading P (device) dim of every leaf
    shards over it — each mesh device holds its own row block of the round
    matrices, so the streamed engine's (P, n) statistics pass runs
    row-parallel — composing with the chunk-axis column sharding over the
    remaining axes.  Without a ``'fleet'`` axis this is exactly
    :func:`stream_column_shardings` (back-compat for existing meshes)."""
    if "fleet" not in mesh.shape:
        return stream_column_shardings(mesh, stacked)
    col_axes = [a for a in ("pod", "data", "model") if a in mesh.shape]
    col = tuple(col_axes) if len(col_axes) > 1 else \
        (col_axes[0] if col_axes else None)

    def leaf_sharding(leaf):
        shape = tuple(leaf.shape)
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        if len(shape) == 1:
            return NamedSharding(mesh, _guard(("fleet",), shape, mesh))
        spec = ("fleet",) + (None,) * (len(shape) - 2) + (col,)
        return NamedSharding(mesh, _guard(spec, shape, mesh))

    return jax.tree_util.tree_map(leaf_sharding, stacked)


def shard_cohort_fn(mesh: Mesh, cohort_fn, num_stacked_args: int):
    """``shard_map`` a cohort function ``(params, *stacked_args) -> pytree``
    over the ``'fleet'`` axis: params replicated, every stacked argument and
    every output leaf partitioned on its leading cohort axis — each mesh
    device trains its own block of the cohort.  Cohorts that don't divide
    the axis are padded (first row repeated) and sliced back, so any P
    works.  Returns a jitted callable."""
    import jax.numpy as jnp

    axis = mesh.shape["fleet"]
    inner = jax.shard_map(
        cohort_fn, mesh=mesh,
        in_specs=(P(),) + (P("fleet"),) * num_stacked_args,
        out_specs=P("fleet"), check_vma=False)

    @jax.jit
    def wrapped(params, *args):
        B = args[0].shape[0]
        pad = (-B) % axis
        if pad:
            args = tuple(jnp.concatenate(
                [a, jnp.repeat(a[:1], pad, axis=0)]) for a in args)
        out = inner(params, *args)
        if pad:
            out = jax.tree_util.tree_map(lambda a: a[:B], out)
        return out

    return wrapped


def named(mesh: Mesh, tree_of_specs: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_of_specs,
        is_leaf=lambda x: isinstance(x, P))
