"""Backend-aware kernel dispatch with micro-autotuned selection.

Every compute hot spot (``gram``, ``gram_block``, ``stream_stats``,
``sketch``, ``topk``, ``combine``, ``sign_sketch``/``sign_sketch_adjoint``)
registers one implementation per *backend*:

  * ``pallas`` — the Pallas TPU kernel, compiled on TPU.  Off-TPU the same
    kernel only runs in interpret mode (Python-per-element), so it is
    *ineligible for autotuning* there and runs only when forced — the
    correctness path for tests, never a production path.
  * ``xla``    — a jit-compiled pure-jnp formulation.  Off-TPU this is the
    production path: XLA fuses the whole op into one compiled loop nest, so
    CPU/GPU runs never pay interpret-mode or per-op dispatch overhead.
  * ``ref``    — the un-jitted jnp oracle (``kernels.ref``): eager, simple,
    the numerical ground truth everything else is tested against.

Selection is a micro-autotune pass: the first call for a given
(op, shape-bucket, platform) times every *eligible* candidate on the real
arguments (one warm-up to compile, then a few timed reps) and caches the
winner in-process.  Shape buckets round each dimension up to the next power
of two so e.g. n = 60 000 and n = 65 536 share one entry; integer keyword
parameters bucket the same way, so a streaming op's column-chunk size
(``block_n``) is part of the bucket and the tuner effectively picks the
winning (backend, chunk) pair.  The cache is
dumpable (:func:`autotune_records`) — ``benchmarks/kernel_bench.py`` writes
it to ``BENCH_kernels.json`` so the per-backend picture rides CI.

Forcing a backend (tests, debugging, benchmarks):

  * per call:   ``ops.gram_and_cross(U, g, backend="xla")``
  * scoped:     ``with registry.force_backend("ref"): ...``
  * process:    ``REPRO_KERNEL_BACKEND=xla`` in the environment

Calls made under a jit trace cannot time anything, so tracer arguments fall
back to the cached winner for the bucket, or a static preference order
(pallas on TPU, else xla) when the bucket was never tuned; those picks are
listed by :func:`static_picks`.  Fused round engines instead pick eagerly at
build time via :func:`select_impl` and close over the winning
implementation.  A candidate that fails while it is timed fails the call:
on TPU that is a Pallas kernel the compiler refused, which must surface
rather than lose the timing to ``xla`` unseen.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..obs import current_tracker, spans

# preference order used when timing is impossible (tracer args, no cache)
_STATIC_ORDER = ("pallas", "xla", "ref")

AUTOTUNE_WARMUP = 1
AUTOTUNE_ITERS = 3


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@dataclass(frozen=True)
class KernelImpl:
    """One (op, backend) implementation."""
    op: str
    backend: str
    fn: Callable
    # supports(*args, **kw) -> bool: shape/parameter constraints (e.g. the
    # stream_stats kernel only takes tile-aligned widths)
    supports: Optional[Callable[..., bool]] = None
    # eligible() -> bool: platform gate for *autotuning* (interpret-mode
    # Pallas off-TPU is never a candidate; forcing bypasses this)
    eligible: Optional[Callable[[], bool]] = None

    def ok_for(self, *args: Any, **kw: Any) -> bool:
        return self.supports is None or bool(self.supports(*args, **kw))

    def is_eligible(self) -> bool:
        return self.eligible is None or bool(self.eligible())


@dataclass
class AutotuneEntry:
    op: str
    bucket: Tuple
    backend: str                      # the winner
    timings_us: Dict[str, float] = field(default_factory=dict)


_IMPLS: Dict[str, Dict[str, KernelImpl]] = {}
_CACHE: Dict[Tuple, AutotuneEntry] = {}
_STATIC: Dict[Tuple, str] = {}     # (op, bucket) -> backend picked untimed
_FORCED: List[Tuple[Optional[str], str]] = []   # (op or None, backend) stack
_EMITTED: set = set()      # (op, bucket, backend, forced) already streamed


def _emit_decision(op: str, bucket: Tuple, backend: str,
                   timings_us: Dict[str, float], forced: bool) -> None:
    """Stream a dispatch decision the moment a bucket is resolved: the
    autotune winner with its candidate timings, or the backend a
    ``force_backend``/env override pinned.  Emitted at most once per
    (op, bucket, backend, forced) so the hot dispatch path never re-logs;
    with the default noop tracker this is one attribute check."""
    tr = current_tracker()
    if not tr.active:
        return
    key = (op, bucket, backend, forced)
    if key in _EMITTED:
        return
    _EMITTED.add(key)
    event: Dict[str, Any] = {"op": op, "bucket": repr(bucket),
                             "backend": backend, "forced": forced}
    for name, us in sorted(timings_us.items()):
        event[f"us_per_call_{name}"] = us
    tr.scope("kernels/autotune").log(event)


def register_impl(op: str, backend: str, fn: Callable, *,
                  supports: Optional[Callable[..., bool]] = None,
                  eligible: Optional[Callable[[], bool]] = None,
                  overwrite: bool = False) -> None:
    impls = _IMPLS.setdefault(op, {})
    if backend in impls and not overwrite:
        raise KeyError(f"kernel impl '{op}/{backend}' already registered")
    impls[backend] = KernelImpl(op, backend, fn, supports, eligible)


def available_ops() -> Tuple[str, ...]:
    return tuple(sorted(_IMPLS))


def backends(op: str) -> Tuple[str, ...]:
    if op not in _IMPLS:
        raise KeyError(f"unknown kernel op '{op}'; have {available_ops()}")
    return tuple(sorted(_IMPLS[op]))


class force_backend:
    """Context manager pinning dispatch to one backend (optionally one op).

    Forcing is a *preference*: a forced backend whose ``supports`` check
    rejects the call's shapes (e.g. the stream_stats kernel on a width that
    is not tile-aligned) falls back to normal selection instead of
    crashing.
    To hard-require a backend, pass ``backend=`` at the call site — that
    path runs the implementation unconditionally and lets it raise."""

    def __init__(self, backend: str, op: Optional[str] = None):
        self.entry = (op, backend)

    def __enter__(self):
        _FORCED.append(self.entry)
        return self

    def __exit__(self, *exc):
        _FORCED.remove(self.entry)
        return False


def _forced_backend(op: str) -> Optional[str]:
    for forced_op, backend in reversed(_FORCED):
        if forced_op is None or forced_op == op:
            return backend
    return os.environ.get("REPRO_KERNEL_BACKEND") or None


def _pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def _bucket(args: Tuple, kw: Dict) -> Tuple:
    """Shape bucket: pow2-rounded dims per array arg + static scalars."""
    parts: List = []
    for a in args:
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            parts.append(tuple(_pow2(d) for d in a.shape) + (str(a.dtype),))
        elif isinstance(a, (int, np.integer)):
            parts.append(("i", _pow2(int(a))))
        else:
            parts.append(("x",))
    for k in sorted(kw):
        v = kw[k]
        parts.append((k, _pow2(int(v)) if isinstance(v, (int, np.integer))
                      else str(v)))
    return tuple(parts)


def _has_tracer(args: Tuple) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in args)


def _time_impl(impl: KernelImpl, args: Tuple, kw: Dict) -> float:
    """Median wall time per call in µs (one warm-up to compile first)."""
    for _ in range(AUTOTUNE_WARMUP):
        jax.block_until_ready(impl.fn(*args, **kw))
    ts = []
    for _ in range(AUTOTUNE_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(impl.fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _candidates(op: str, args: Tuple, kw: Dict) -> List[KernelImpl]:
    return [impl for impl in _IMPLS[op].values()
            if impl.is_eligible() and impl.ok_for(*args, **kw)]


def _autotune(op: str, bucket: Tuple, args: Tuple, kw: Dict) -> AutotuneEntry:
    cands = _candidates(op, args, kw)
    if not cands:
        raise RuntimeError(f"no eligible backend for kernel op '{op}' "
                           f"(registered: {backends(op)})")
    entry = AutotuneEntry(op=op, bucket=bucket, backend=cands[0].backend)
    if len(cands) > 1:
        # one parent span per bucket resolution; each candidate timing
        # (compile warm-up + timed reps) is a child span so the autotune
        # cost inside a round's first stage is attributable per backend
        with spans.span("autotune", op=op, bucket=repr(bucket)):
            for impl in cands:
                with spans.span("candidate", op=op,
                                backend=impl.backend) as h:
                    us = _time_impl(impl, args, kw)
                    if h is not None:
                        h.tags["us_per_call"] = us
                entry.timings_us[impl.backend] = us
        entry.backend = min(entry.timings_us, key=entry.timings_us.get)
    _CACHE[(op, bucket)] = entry
    _emit_decision(op, bucket, entry.backend, entry.timings_us, forced=False)
    return entry


def select_impl(op: str, *args: Any, **kw: Any) -> KernelImpl:
    """Resolve (eagerly, with timing if needed) the implementation dispatch
    would use for these arguments — for callers that build jit-compiled
    stages and close over the winning fn."""
    if op not in _IMPLS:
        raise KeyError(f"unknown kernel op '{op}'; have {available_ops()}")
    forced = _forced_backend(op)
    if forced is not None:
        if forced not in _IMPLS[op]:
            raise KeyError(f"forced backend '{forced}' not registered for "
                           f"'{op}' (have {backends(op)})")
        impl = _IMPLS[op][forced]
        if impl.ok_for(*args, **kw):
            if current_tracker().active:
                _emit_decision(op, _bucket(args, kw), forced, {},
                               forced=True)
            return impl
        # forced backend cannot run these shapes (supports() rejected):
        # fall through to normal selection — forcing is a preference, the
        # call-site backend= arg is the hard requirement
    bucket = _bucket(args, kw)
    entry = _CACHE.get((op, bucket))
    if entry is None:
        if _has_tracer(args):           # cannot time under a jit trace
            for name in _STATIC_ORDER:
                impl = _IMPLS[op].get(name)
                if impl and impl.is_eligible() and impl.ok_for(*args, **kw):
                    _STATIC[(op, bucket)] = name
                    return impl
            raise RuntimeError(f"no eligible backend for kernel op '{op}' "
                               f"(registered: {backends(op)})")
        entry = _autotune(op, bucket, args, kw)
    impl = _IMPLS[op].get(entry.backend)
    if impl is None or not impl.ok_for(*args, **kw):
        cands = _candidates(op, args, kw)
        if not cands:
            raise RuntimeError(f"no eligible backend for kernel op '{op}'")
        impl = cands[0]
    return impl


def select_impl_for(op: str, *specs: "jax.ShapeDtypeStruct",
                    **kw: Any) -> KernelImpl:
    """:func:`select_impl` over shape/dtype specs instead of live arrays —
    for stage builders that need the winning backend cheaply on every cache
    lookup.  Specs carry .shape/.dtype, so the supports() checks and shape
    buckets work on them directly; dense zero arrays are synthesized ONLY
    when an autotune-cache miss actually needs something to time."""
    if op not in _IMPLS:
        raise KeyError(f"unknown kernel op '{op}'; have {available_ops()}")
    forced = _forced_backend(op)
    if forced is not None:
        if forced not in _IMPLS[op]:
            raise KeyError(f"forced backend '{forced}' not registered for "
                           f"'{op}' (have {backends(op)})")
        impl = _IMPLS[op][forced]
        if impl.ok_for(*specs, **kw):
            if current_tracker().active:
                _emit_decision(op, _bucket(specs, kw), forced, {},
                               forced=True)
            return impl                 # preference honored, no arrays built
    bucket = _bucket(specs, kw)
    entry = _CACHE.get((op, bucket))
    if entry is None:
        import jax.numpy as jnp
        args = tuple(jnp.zeros(s.shape, s.dtype) for s in specs)
        return select_impl(op, *args, **kw)
    impl = _IMPLS[op].get(entry.backend)
    if impl is None or not impl.ok_for(*specs, **kw):
        cands = _candidates(op, specs, kw)
        if not cands:
            raise RuntimeError(f"no eligible backend for kernel op '{op}'")
        impl = cands[0]
    return impl


def dispatch(op: str, *args: Any, backend: Optional[str] = None,
             **kw: Any) -> Any:
    """Run ``op`` on the chosen backend (autotuned unless ``backend`` or a
    force is in effect)."""
    if backend is not None:
        impls = _IMPLS.get(op, {})
        if backend not in impls:
            raise KeyError(f"backend '{backend}' not registered for '{op}' "
                           f"(have {backends(op)})")
        return impls[backend].fn(*args, **kw)
    return select_impl(op, *args, **kw).fn(*args, **kw)


def autotune_records() -> List[Dict[str, Any]]:
    """JSON-ready dump of the in-process autotune cache (one record per
    (op, bucket)): the selected backend plus per-backend timings.  Timing
    fields embed ``us_per_call`` so the bench-regression gate ignores them
    (machine-dependent); the selection itself is ignored via ``selected``."""
    records = []
    for (op, bucket), entry in sorted(_CACHE.items(), key=lambda x: x[0]):
        rec: Dict[str, Any] = {"op": op, "bucket": repr(bucket),
                               "num_backends": len(_IMPLS[op]),
                               "num_candidates_timed": len(entry.timings_us),
                               "backend_selected": entry.backend}
        for name, us in sorted(entry.timings_us.items()):
            rec[f"us_per_call_{name}"] = us
        records.append(rec)
    return records


def static_picks() -> List[Dict[str, Any]]:
    """The untimed picks made under a jit trace (one record per
    (op, bucket), in the order they were made): the backend the static
    preference order resolved to."""
    return [{"op": op, "bucket": repr(bucket), "backend_selected": backend}
            for (op, bucket), backend in _STATIC.items()]


def clear_autotune_cache() -> None:
    _CACHE.clear()
    _STATIC.clear()
    _EMITTED.clear()
