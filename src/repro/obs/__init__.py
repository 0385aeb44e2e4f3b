"""Streaming observability: pluggable trackers, live metrics, span tracing.

See ``repro.obs.tracker`` for the protocol and the process-wide
:func:`current_tracker` context, ``repro.obs.jsonl`` for the append-only
file stream benches and CI consume, ``repro.obs.spans`` for dual-clock
(wall + virtual) span tracing that also reaches the JAX profiler's trace,
``repro.obs.compile_count`` for the process-wide compile counter, and
``repro.obs.perfetto`` for the Chrome trace-event export viewable in
Perfetto / ``chrome://tracing``.
"""
from . import spans
from .compile_count import compiles
from .jsonl import JsonlTracker, iter_trace, read_trace
from .spans import (begin_span, end_span, record_span, span, span_fields,
                    span_tags, use_virtual_clock, virtual_now)
from .tracker import (NOOP, CompositeTracker, InMemoryTracker, NoopTracker,
                      TrackedEvent, Tracker, current_tracker, use_tracker)

__all__ = [
    "NOOP", "CompositeTracker", "InMemoryTracker", "JsonlTracker",
    "NoopTracker", "TrackedEvent", "Tracker", "begin_span", "compiles",
    "current_tracker", "end_span", "iter_trace", "read_trace", "record_span",
    "span", "span_fields", "span_tags", "spans", "use_tracker",
    "use_virtual_clock", "virtual_now",
]
