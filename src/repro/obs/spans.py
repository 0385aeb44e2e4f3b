"""Span tracing over dual clocks — where time goes inside a round.

The PR-6 tracker protocol streams *scalar* metrics (losses, bytes,
wall-clocks) but cannot say where a round's milliseconds went: gateway
stage vs cloud solve vs link transfer vs jit compile, in host wall time or
in the virtual edge clock.  A *span* is a named interval recorded on BOTH
clocks at once:

  * **wall** — host ``time.perf_counter()`` at open/close, always present;
  * **virtual** — the simulated edge time, present whenever a virtual
    clock is threaded in (:func:`use_virtual_clock` installs the event
    scheduler's ``lambda: scheduler.now`` for the block) or the caller
    stamps it explicitly (``t_virtual=`` on :func:`begin`/:func:`end`,
    :func:`record_span` for transfers whose duration is known up front).

Three entry points, all free on the noop path (an ``active`` check, and
for :func:`span` a check that the profiler is off):

  * ``with span(name, **tags): ...`` — nested lifetimes.  Spans opened
    inside run as children: each carries a ``path`` like
    ``"round/event_loop/gateway"`` built from the thread-local span stack,
    which is what the Perfetto export nests on and ``trace_diff`` aligns
    on.  An exception inside the block still closes the span (tagged
    ``error=<ExcType>``), restores the nesting depth, and re-raises.
  * ``h = begin(name, **tags)`` / ``end(h, **tags)`` — explicit handles
    for the event scheduler's NON-nested lifetimes (a dispatched task and
    the next dispatch overlap arbitrarily).  Flat spans take their path
    from the stack at ``begin`` but never push onto it, so they cannot
    corrupt the nesting of context-managed spans; the export renders them
    as async (overlap-safe) track events.
  * ``record_span(name, t0_virtual=, dur_virtual_s=, **tags)`` — a span
    whose interval is already known (the ``CommLedger``'s link transfers:
    virtual duration computed from bytes/bandwidth at record time).

While the JAX profiler records, a nested span also opens a
``jax.profiler.TraceAnnotation`` named ``repro/<name>`` with its tags as the
event's stats, tracker or no tracker: the span then sits in the profiler's
``.xplane.pb`` beside the device's operations and the harness's ``bench/``
spans, so an idle gap of the device can be put down to the host work that
was running.  With no active tracker and the profiler off, :func:`span`
returns a shared no-op context (two flag checks).  The flat ``begin`` /
``end`` spans and :func:`record_span` are virtual-time lifetimes of the
event scheduler and stay tracker-only.

Every close emits ONE ``kind="span"`` event through the active tracker's
``log_span`` — the jsonl / in-memory / composite sinks of ``repro.obs``
carry spans with no changes, and one ``.jsonl`` trace interleaves spans
with the PR-6 metric stream.  Reserved metric keys: ``name``, ``path``,
``depth``, ``flat``, ``t0_wall``, ``dur_wall_s``, ``t0_virtual``,
``dur_virtual_s``; everything else in the event is a caller tag (Perfetto
``args``).  Like ``repro.obs.tracker`` this module imports nothing from
the rest of ``repro`` — the scheduler, engines and kernel registry all
trace through it without cycles.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

from .tracker import TrackedEvent, Tracker, current_tracker

try:
    # the profiler's own "is a trace being recorded" flag (jax 0.9): one
    # C++ call, much cheaper than opening a TraceAnnotation that is off
    from jax._src.lib import _profiler
    _profiling: Callable[[], bool] = _profiler.TraceMe.is_enabled
except (ImportError, AttributeError):   # else always open the annotation
    def _profiling() -> bool:
        return True

# span-event keys that are structure, not caller tags
RESERVED_KEYS = ("name", "path", "depth", "flat", "t0_wall", "dur_wall_s",
                 "t0_virtual", "dur_virtual_s")

_STATE = threading.local()      # .stack: List[SpanHandle], .vclock: stack


def _stack() -> List["SpanHandle"]:
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    return stack


# ---------------------------------------------------------------------------
# virtual clock threading
# ---------------------------------------------------------------------------

def virtual_now() -> Optional[float]:
    """Current virtual time, or None when no virtual clock is installed."""
    clocks = getattr(_STATE, "vclock", None)
    return clocks[-1]() if clocks else None


@contextmanager
def use_virtual_clock(clock: Callable[[], float]) -> Iterator[None]:
    """Install ``clock`` (e.g. ``lambda: scheduler.now``) as the virtual
    timestamp source for spans opened in the block; contexts stack."""
    clocks = getattr(_STATE, "vclock", None)
    if clocks is None:
        clocks = _STATE.vclock = []
    clocks.append(clock)
    try:
        yield
    finally:
        clocks.pop()


# ---------------------------------------------------------------------------
# span lifecycle
# ---------------------------------------------------------------------------

@dataclass
class SpanHandle:
    """An open span: identity plus its open-time stamps.  The tracker is
    captured at open so a span closes into the sink it opened under even if
    the active tracker changes mid-flight."""
    name: str
    path: str
    depth: int
    t0_wall: float
    t0_virtual: Optional[float]
    tags: Dict[str, Any]
    tracker: Tracker
    flat: bool = False
    _extra: Dict[str, Any] = field(default_factory=dict)


def _emit(h: SpanHandle, t1_wall: float, t1_virtual: Optional[float]) -> None:
    metrics: Dict[str, Any] = {"name": h.name, "path": h.path,
                               "depth": h.depth,
                               "t0_wall": h.t0_wall,
                               "dur_wall_s": max(t1_wall - h.t0_wall, 0.0)}
    if h.flat:
        metrics["flat"] = True
    if h.t0_virtual is not None:
        metrics["t0_virtual"] = h.t0_virtual
        t1v = t1_virtual if t1_virtual is not None else h.t0_virtual
        metrics["dur_virtual_s"] = max(t1v - h.t0_virtual, 0.0)
    metrics.update(h.tags)
    metrics.update(h._extra)
    h.tracker.log_span(metrics)


def current_path() -> str:
    """The open nested-span path on this thread ("" at top level)."""
    stack = _stack()
    return stack[-1].path if stack else ""


# TraceMe encodes stats as ``name#k=v,k=v#``: these would split a value
_STAT_SEPARATORS = str.maketrans({",": ";", "=": ":", "#": " "})


def annotation(name: str, tags: Dict[str, Any]) -> TraceAnnotation:
    """The profiler event ``repro/<name>`` with ``tags`` as its stats."""
    return TraceAnnotation(
        f"repro/{name}",
        **{k: (v if isinstance(v, (int, float))
               else str(v).translate(_STAT_SEPARATORS))
           for k, v in tags.items()})


class _NoopSpan:
    """What :func:`span` returns when neither a tracker nor the profiler
    would see the span: one shared instance, nothing recorded."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NOOP_SPAN = _NoopSpan()


class _Span:
    """An open nested span: a tracker handle (when a tracker is active)
    and a profiler annotation (when the profiler records)."""

    __slots__ = ("tracker", "name", "tags", "t_virtual", "clock", "handle",
                 "annotation")

    def __init__(self, tracker: Tracker, name: str,
                 t_virtual: Optional[float],
                 clock: Optional[Callable[[], float]], tags: Dict[str, Any]):
        self.tracker, self.name, self.tags = tracker, name, tags
        self.t_virtual, self.clock = t_virtual, clock
        self.handle: Optional[SpanHandle] = None
        self.annotation = None

    def __enter__(self) -> Optional[SpanHandle]:
        if self.tracker.active:
            stack = _stack()
            parent = stack[-1].path if stack else ""
            t_virtual = self.t_virtual
            if t_virtual is None:
                t_virtual = (self.clock() if self.clock is not None
                             else virtual_now())
            self.handle = SpanHandle(
                name=self.name,
                path=f"{parent}/{self.name}" if parent else self.name,
                depth=len(stack), t0_wall=time.perf_counter(),
                t0_virtual=t_virtual, tags=dict(self.tags),
                tracker=self.tracker)
            stack.append(self.handle)
        if _profiling():
            self.annotation = annotation(self.name, self.tags)
            self.annotation.__enter__()
        return self.handle

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
        h = self.handle
        if h is not None:
            if exc_type is not None:
                h.tags.setdefault("error", exc_type.__name__)
            _stack().pop()
            _emit(h, time.perf_counter(),
                  self.clock() if self.clock is not None else virtual_now())
        return False


def span(name: str, *, t_virtual: Optional[float] = None,
         clock: Optional[Callable[[], float]] = None,
         **tags: Any) -> "_Span | _NoopSpan":
    """A nested span around a ``with`` block.  The block gets the tracker
    handle (None when no tracker is active); callers may add tags via
    ``handle.tags[...] = ...``.  ``clock`` is a per-span virtual clock (e.g.
    ``lambda: scheduler.now``) for call sites outside a
    :func:`use_virtual_clock` block.  Exceptions close the span with an
    ``error`` tag and re-raise.  While the profiler records, the block is
    also the annotation ``repro/<name>`` with ``tags`` as its stats."""
    tr = current_tracker()
    if not tr.active and not _profiling():
        return _NOOP_SPAN
    return _Span(tr, name, t_virtual, clock, tags)


def begin(name: str, *, t_virtual: Optional[float] = None,
          **tags: Any) -> Optional[SpanHandle]:
    """Open a *flat* span (non-nested lifetime) and return its handle, or
    None when no tracker is active (``end(None)`` is a no-op, so hot call
    sites need no guard of their own)."""
    tr = current_tracker()
    if not tr.active:
        return None
    stack = _stack()
    parent = stack[-1].path if stack else ""
    return SpanHandle(name=name,
                      path=f"{parent}/{name}" if parent else name,
                      depth=len(stack), t0_wall=time.perf_counter(),
                      t0_virtual=(t_virtual if t_virtual is not None
                                  else virtual_now()),
                      tags=dict(tags), tracker=tr, flat=True)


def end(handle: Optional[SpanHandle], *, t_virtual: Optional[float] = None,
        **tags: Any) -> None:
    """Close a span opened with :func:`begin`; extra ``tags`` are merged
    into the emitted event (e.g. the terminal outcome of a task)."""
    if handle is None:
        return
    handle._extra.update(tags)
    t1v = t_virtual if t_virtual is not None else virtual_now()
    _emit(handle, time.perf_counter(), t1v)


def record_span(name: str, *, t0_virtual: float, dur_virtual_s: float,
                **tags: Any) -> None:
    """Emit a span whose virtual interval is already known (link
    transfers): zero wall duration, stamped at the current wall clock."""
    tr = current_tracker()
    if not tr.active:
        return
    stack = _stack()
    parent = stack[-1].path if stack else ""
    now = time.perf_counter()
    h = SpanHandle(name=name,
                   path=f"{parent}/{name}" if parent else name,
                   depth=len(stack), t0_wall=now, t0_virtual=t0_virtual,
                   tags=dict(tags), tracker=tr, flat=True)
    _emit(h, now, t0_virtual + max(dur_virtual_s, 0.0))


# ---------------------------------------------------------------------------
# reading spans back out of a trace
# ---------------------------------------------------------------------------

def span_fields(event: TrackedEvent) -> Dict[str, Any]:
    """A span event's metrics with any scope prefix stripped — spans are
    normally emitted unscoped (via :func:`current_tracker`), but a span
    logged through a ``tracker.scope(...)`` view arrives with prefixed
    keys; this normalizes both so exporters/diff tools see one layout."""
    m = event.metrics
    if event.scope:
        prefix = event.scope + "/"
        m = {(k[len(prefix):] if k.startswith(prefix) else k): v
             for k, v in m.items()}
    return m


def span_tags(fields: Dict[str, Any]) -> Dict[str, Any]:
    """The caller-tag subset of normalized span fields (Perfetto args)."""
    return {k: v for k, v in fields.items() if k not in RESERVED_KEYS}


# package-level aliases: ``spans.begin``/``spans.end`` read naturally with
# the module prefix, ``begin_span``/``end_span`` without it
begin_span = begin
end_span = end

