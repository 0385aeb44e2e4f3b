"""Pytree <-> flat-vector utilities used by the aggregation math.

The contextual aggregation (paper eq. 4-8) operates on flattened update
vectors ``Δ_k = w_k^{t+1} - w^t``.  These helpers convert between model
parameter pytrees and flat vectors, and implement the paper's "last layer"
efficiency scoping (§III-B, Note on efficiency): only a named subset of the
pytree participates in the Gram/solve, while the *combine* still applies the
resulting α to the full update.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Iterable, List, Sequence

import jax
import jax.numpy as jnp

Pytree = Any


def tree_to_vector(tree: Pytree, dtype: jnp.dtype | None = jnp.float32) -> jax.Array:
    """Flatten a pytree of arrays into a single 1-D vector."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), dtype=dtype or jnp.float32)
    parts = [jnp.ravel(x).astype(dtype) if dtype is not None else jnp.ravel(x) for x in leaves]
    return jnp.concatenate(parts)


def vector_to_tree(vec: jax.Array, like: Pytree) -> Pytree:
    """Inverse of :func:`tree_to_vector` given a structural template."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out = []
    offset = 0
    for leaf in leaves:
        size = leaf.size
        out.append(jnp.reshape(vec[offset:offset + size], leaf.shape).astype(leaf.dtype))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_size(tree: Pytree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return "/".join(out)


def select_scope(tree: Pytree, scope: str | Sequence[str] | None) -> Pytree:
    """Return a sub-pytree whose leaf paths match ``scope``.

    ``scope`` semantics:
      * ``None`` or ``"full"``   -> the whole tree (identity).
      * ``"last_layer"``        -> leaves whose path matches common head names
        (``lm_head``, ``head``, ``out``, ``final``, ``unembed``, ``logits``,
        ``w``/``b`` at top level for the logistic model); falls back to the
        lexicographically last top-level key if nothing matches.
      * a regex string or list of regex strings -> leaves whose '/'-joined
        path matches any pattern.

    Non-matching leaves are replaced by zero-size arrays so the result is a
    valid pytree with stable structure (flattening simply skips them).
    """
    if scope is None or scope == "full":
        return tree

    if scope == "last_layer":
        patterns = [r"(^|/)(lm_head|head|out_proj|final|unembed|logits)(/|$)"]
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        if not any(re.search(patterns[0], _path_str(path)) for path, _ in flat):
            # Fallback: last top-level key in sorted order.
            keys = sorted({_path_str(path).split("/")[0] for path, _ in flat})
            patterns = [r"^" + re.escape(keys[-1]) + r"(/|$)"]
    elif isinstance(scope, str):
        patterns = [scope]
    else:
        patterns = list(scope)

    def keep(path_str: str) -> bool:
        return any(re.search(p, path_str) for p in patterns)

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    new_leaves = [
        leaf if keep(_path_str(path)) else jnp.zeros((0,), leaf.dtype)
        for path, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def scope_vector(tree: Pytree, scope: str | Sequence[str] | None,
                 dtype: jnp.dtype | None = jnp.float32) -> jax.Array:
    """Flatten only the scoped subset of ``tree``."""
    return tree_to_vector(select_scope(tree, scope), dtype=dtype)


@dataclass(frozen=True)
class LeafSlab:
    """One pytree leaf as a column slab of the flat ``(K, n)`` row-major
    view: ``matrix`` is ``leaf.reshape(K, -1)`` (a cheap view for contiguous
    leaves, never a cross-leaf concatenation), occupying flat columns
    ``[offset, offset + width)`` in ``tree_to_vector`` order."""
    index: int            # leaf position in tree_leaves order
    offset: int           # first flat column
    width: int            # columns (= leaf.size / K)
    in_scope: bool        # participates in the Gram scope
    matrix: jax.Array     # (K, width) view of the stacked leaf


@partial(jax.jit, static_argnums=1)
def flat_view_slab(leaf: jax.Array, width: int) -> jax.Array:
    """``leaf.reshape(K, width)``: one program per leaf shape, compiled
    under this name so that a device trace can tell the view's relayout
    copies from any other reshape."""
    return jnp.reshape(leaf, (leaf.shape[0], width))


class ChunkedFlatView:
    """Leaf-aligned column-chunk view of a *stacked* pytree (leading K axis
    per leaf) — the streaming alternative to the full ``jnp.concatenate``
    copy in ``core.aggregation._stacked_to_matrix``.

    The flat column order matches :func:`tree_to_vector` exactly (leaf
    order, row-major ravel per leaf), so a consumer that sweeps the slabs
    (or :meth:`chunks`) left to right sees the same (K, n) matrix the dense
    path materializes — without ever holding more than one chunk.  Scope is
    *leaf-granular* by construction (``select_scope`` keeps or drops whole
    leaves), so scoped reductions simply skip ``in_scope=False`` slabs
    instead of gathering columns.
    """

    def __init__(self, stacked: Pytree, scope: str | Sequence[str] | None = None):
        leaves = jax.tree_util.tree_leaves(stacked)
        if not leaves:
            raise ValueError("cannot build a flat view of an empty pytree")
        self.K = int(leaves[0].shape[0])
        bad = [tuple(l.shape) for l in leaves
               if l.ndim < 1 or l.shape[0] != self.K]
        if bad:
            raise ValueError(f"stacked pytree leaves must share the leading "
                             f"K={self.K} axis; offending shapes: {bad}")
        kept = [l.size > 0 for l in
                jax.tree_util.tree_leaves(select_scope(stacked, scope))]
        self.slabs: List[LeafSlab] = []
        offset = 0
        for i, (leaf, keep) in enumerate(zip(leaves, kept)):
            width = leaf.size // self.K
            # a leaf already (K, width) is its own view: no program runs
            # (as ``jnp.reshape`` returns such an array itself)
            same = (isinstance(leaf, jax.Array)
                    and leaf.shape == (self.K, width))
            matrix = leaf if same else flat_view_slab(leaf, width)
            self.slabs.append(LeafSlab(
                index=i, offset=offset, width=width, in_scope=bool(keep),
                matrix=matrix))
            offset += width
        self.n = offset

    @property
    def scoped_slabs(self) -> List[LeafSlab]:
        return [s for s in self.slabs if s.in_scope]

    @property
    def n_scoped(self) -> int:
        return sum(s.width for s in self.scoped_slabs)

    def chunks(self, chunk_cols: int, scoped_only: bool = False):
        """Yield ``(offset, in_scope, (K, w) matrix)`` column chunks with
        ``w <= chunk_cols``, never crossing a leaf boundary (leaf-aligned:
        a leaf wider than ``chunk_cols`` is split, narrower leaves come out
        whole).  Offsets are flat columns of the full view."""
        if chunk_cols < 1:
            raise ValueError(f"chunk_cols must be >= 1, got {chunk_cols}")
        for slab in self.slabs:
            if scoped_only and not slab.in_scope:
                continue
            for start in range(0, slab.width, chunk_cols):
                w = min(chunk_cols, slab.width - start)
                yield (slab.offset + start, slab.in_scope,
                       jax.lax.dynamic_slice(slab.matrix, (0, start),
                                             (self.K, w)))

    def materialize(self, dtype: jnp.dtype | None = jnp.float32) -> jax.Array:
        """Dense (K, n) matrix — tests / small models only; the streaming
        consumers exist so production never calls this at transformer width."""
        parts = [s.matrix.astype(dtype) if dtype is not None else s.matrix
                 for s in self.slabs]
        return jnp.concatenate(parts, axis=1)


def mix_rows(weights: jax.Array, leaf: jax.Array) -> jax.Array:
    """``Σ_k w_k · leaf[k]`` for ``weights (K,)`` and ``leaf (K, *S)`` →
    ``(*S,)`` float32, for any ``S`` — the per-leaf primitive of the
    streamed combine pass (``α @ U`` one leaf at a time).

    Weights and accumulation are float32 on every backend (a bf16 leaf is
    not multiplied by rounded weights).  The sum is a reduction over the
    leading axis in the leaf's own shape: no reshape merges its minor
    dimensions (on a TPU's tiled layout that is a relayout copy of the
    whole leaf) and no ``dot_general`` with one output row (which the TPU
    compiler rewrites as a multiply-reduce over an f32 copy of the leaf).
    XLA fuses the upcast, the multiply and the reduce into one pass that
    reads the leaf once."""
    w = weights.astype(jnp.float32).reshape((-1,) + (1,) * (leaf.ndim - 1))
    return jnp.sum(w * leaf.astype(jnp.float32), axis=0)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(a: Pytree, s) -> Pytree:
    return jax.tree_util.tree_map(lambda x: x * s, a)


def tree_weighted_sum(trees: Iterable[Pytree], weights: jax.Array) -> Pytree:
    """``Σ_k weights[k] * trees[k]`` over a list of pytrees (stacks lazily)."""
    trees = list(trees)
    assert len(trees) > 0
    def comb(*leaves):
        stacked = jnp.stack(leaves)  # (K, ...)
        w = weights.reshape((-1,) + (1,) * (stacked.ndim - 1)).astype(stacked.dtype)
        return jnp.sum(stacked * w, axis=0)
    return jax.tree_util.tree_map(comb, *trees)


def stacked_weighted_sum(stacked: Pytree, weights: jax.Array) -> Pytree:
    """Same as :func:`tree_weighted_sum` but for pre-stacked pytrees whose
    leaves have a leading K axis.  Each leaf is summed by :func:`mix_rows`
    (float32 weights and accumulation, a leading-axis reduction in the
    leaf's own shape) and cast back to the leaf's dtype."""
    def comb(leaf):
        return mix_rows(weights, leaf).astype(leaf.dtype)
    return jax.tree_util.tree_map(comb, stacked)
