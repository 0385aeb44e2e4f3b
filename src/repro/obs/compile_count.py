"""A process-wide count of XLA compiles, taken where JAX compiles.

One ``jax.monitoring`` listener, registered once when this module is first
imported, sees two events:

  * ``/jax/core/compile/backend_compile_duration`` -- a program was
    compiled, or loaded from the persistent compilation cache; JAX records
    it once per program, around both;
  * ``/jax/compilation_cache/cache_retrieval_time_sec`` -- that program
    came from the persistent cache (recorded inside the event above).

:func:`compiles` counts the first: each is one program the process had to
build before it could run it, whether by compiling or by loading.  The
second is not counted again.  While the profiler records, each of the two
drops a zero-length ``repro/compile`` annotation into the trace, with the
seconds and the event's name as stats, so a compile inside a traced window
sits on the device trace's clock under whichever span triggered it.
"""
from __future__ import annotations

import threading

import jax.monitoring

from .spans import _profiling, annotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_LOCK = threading.Lock()
_COUNT = 0


def compiles() -> int:
    """Programs compiled (or loaded from the persistent cache) by this
    process since ``repro.obs`` was imported."""
    return _COUNT


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    global _COUNT
    if event not in (COMPILE_EVENT, CACHE_EVENT):
        return
    if event == COMPILE_EVENT:
        with _LOCK:
            _COUNT += 1
    if _profiling():
        with annotation("compile", {"event": event, "seconds": seconds,
                                    **kwargs}):
            pass


jax.monitoring.register_event_duration_secs_listener(_on_duration)
