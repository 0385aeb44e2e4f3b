"""The program's own spans on the device trace's clock, for the per-layer
readers of the round engine.

While the profiler records, the program writes a host event
``repro/<name>`` for every span it opens (``repro.obs.spans``), with the
span's tags as the event's stats, and a zero-length ``repro/compile``
marker for every program it compiles or loads from the compile cache
(``repro.obs.compile_count``).  The harness's own spans are ``bench/...``.

This module finds a traced run's ``.xplane.pb`` (the newest under
``bench/out/<cell>.seed*/``), reads it once per process, and returns the
device-busy intervals of the first chip, the host spans and the compile
markers.  :func:`idle_split` puts every idle nanosecond of the traced
slice down to the innermost host span covering it: a ``repro/`` span, the
harness's ``bench/round`` outside any program span, or ``untraced``.  A
gap that crosses a span's edge is split at the edge.

The device's and the host's timestamps in a TPU trace disagree: on one
v5e the device plane read about 1.5 ms early, so that every program seemed
to start before the host had enqueued it.  The split therefore first
moves the device's intervals onto the host's clock.  Each run of a program
is an ``XLA Modules`` event on the device plane and a ``DoEnqueueProgram``
event on the host, both with the same ``run_id``; no run can start before
its enqueue began, so the shift is the largest lead of a run over its
enqueue (0 where the trace pairs none).

The slice is the harness's ``window_s`` (host clock), placed at the first
host span or (shifted) device operation: the harness opens ``bench/round``
right after starting the profiler.  So the split sums to
``device_idle.train`` up to the microseconds between the two.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import harness
from bench.trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                                _events, _union)

OUT = harness.OUT
PROGRAM = "repro/"
HARNESS = "bench/"
COMPILE = "repro/compile"
# one per program compiled or loaded; the cache-retrieval marker that can
# sit inside it is not a second compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
ENQUEUE = "DoEnqueueProgram"
UNTRACED = "untraced"


@dataclass
class ProgramSpans:
    busy: List[Tuple[int, int]]            # first chip, merged, host clock
    host: List[Tuple[int, int, str]]       # repro/ and bench/ spans
    compiles: List[int] = field(default_factory=list)   # marker times, ns
    shift_ns: int = 0                      # added to the device's times

    @property
    def has_program_spans(self) -> bool:
        return any(name.startswith(PROGRAM) for _, _, name in self.host)

    def slice(self, window_s: float) -> Tuple[int, int]:
        starts = [a for a, _, _ in self.host] + [a for a, _ in self.busy[:1]]
        t0 = min(starts) if starts else 0
        return t0, t0 + int(round(window_s * 1e9))


def read_planes(planes) -> ProgramSpans:
    """The intervals of one profile (``ProfileData.planes`` or objects of
    the same ``name`` / ``lines`` / ``events`` / ``stats`` shape)."""
    busy: List[Tuple[int, int]] = []
    host: List[Tuple[int, int, str]] = []
    compiles: List[int] = []
    runs: Dict[int, int] = {}           # run_id -> start on the device
    enqueues: Dict[int, int] = {}       # run_id -> enqueue start on the host
    chip = None
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            if chip is None:
                chip = int(plane.name.rsplit(":", 1)[1])
                busy = _union([(a, b) for _, a, b in
                               _events(plane, OPS_LINE)])
                runs = _run_starts(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == COMPILE:
                    if _stats(e).get("event") == COMPILE_EVENT:
                        compiles.append(int(e.start_ns))
                elif e.name.startswith((PROGRAM, HARNESS)):
                    a = int(e.start_ns)
                    host.append((a, a + int(e.duration_ns), e.name))
                elif e.name == ENQUEUE:
                    st = _stats(e)
                    if "run_id" in st and int(st.get("device_ordinal",
                                                     0)) == (chip or 0):
                        enqueues[int(st["run_id"])] = int(e.start_ns)
    leads = [enqueues[k] - a for k, a in runs.items() if k in enqueues]
    shift = max(0, max(leads, default=0))
    return ProgramSpans([(a + shift, b + shift) for a, b in busy],
                        sorted(host), sorted(compiles), shift)


def _stats(event) -> Dict[str, object]:
    return {k: v for k, v in event.stats}


def _run_starts(plane) -> Dict[int, int]:
    out = {}
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for e in line.events:
                run = _stats(e).get("run_id")
                if run is not None:
                    out[int(run)] = int(e.start_ns)
    return out


@functools.lru_cache(maxsize=4)
def _read_file(path: str, mtime_ns: int) -> ProgramSpans:
    from jax.profiler import ProfileData
    return read_planes(ProfileData.from_file(path).planes)


def newest_xplane(cell: str, out: Optional[Path] = None) -> Optional[Path]:
    files = [p for d in (out or OUT).glob(f"{cell}.seed*")
             for p in d.rglob("*.xplane.pb")]
    return max(files, key=lambda p: p.stat().st_mtime_ns, default=None)


def for_run(run) -> Optional[ProgramSpans]:
    """The spans of ``run``'s traced slice; None where the run was not
    traced or the program wrote no ``repro/`` span into the trace."""
    if run.trace is None:
        return None
    path = newest_xplane(run.cell.name)
    if path is None:
        return None
    spans = _read_file(str(path), path.stat().st_mtime_ns)
    return spans if spans.has_program_spans else None


# ------------------------------------------------------------------ idle split

def _label_segments(host: List[Tuple[int, int, str]], t0: int, t1: int
                    ) -> List[Tuple[int, int, str]]:
    """[t0, t1) cut into pieces, each labelled by the innermost host span
    covering it (the latest started; ``bench/`` spans only where no
    ``repro/`` span covers) or ``untraced``."""
    cuts = sorted({t0, t1} | {min(max(x, t0), t1)
                              for a, b, _ in host for x in (a, b)})
    by_start = sorted(host)
    out: List[Tuple[int, int, str]] = []
    active: List[Tuple[int, int, str]] = []
    i = 0
    for x, y in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][0] <= x:
            active.append(by_start[i])
            i += 1
        active = [s for s in active if s[1] > x]
        covering = [s for s in active if s[0] <= x and s[1] >= y]
        program = [s for s in covering if s[2].startswith(PROGRAM)]
        pick = program or covering
        label = max(pick, key=lambda s: (s[0], -s[1]))[2] if pick \
            else UNTRACED
        if out and out[-1][2] == label and out[-1][1] == x:
            out[-1] = (out[-1][0], y, label)
        else:
            out.append((x, y, label))
    return out


def _idle(busy: List[Tuple[int, int]], t0: int, t1: int
          ) -> List[Tuple[int, int]]:
    out, at = [], t0
    for a, b in busy:
        if b <= at:
            continue
        if a >= t1:
            break
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < t1:
        out.append((at, t1))
    return out


@dataclass
class IdleSplit:
    window_s: float
    by_label: Dict[str, float]                       # seconds
    gaps: List[Tuple[float, List[Tuple[str, float]]]]  # longest first

    def percent(self, prefix: str = "") -> float:
        return 100.0 * sum(s for k, s in self.by_label.items()
                           if k.startswith(prefix)) / self.window_s


def idle_split(spans: ProgramSpans, window_s: float) -> IdleSplit:
    """The slice's idle time on the first chip, split by innermost span;
    and each idle gap with its parts."""
    t0, t1 = spans.slice(window_s)
    segs = _label_segments(spans.host, t0, t1)
    by_label: Dict[str, float] = {}
    gaps = []
    j = 0
    for a, b in _idle(spans.busy, t0, t1):
        parts: Dict[str, float] = {}
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, label = segs[k]
            d = (min(b, s1) - max(a, s0)) * 1e-9
            if d > 0:
                parts[label] = parts.get(label, 0.0) + d
                by_label[label] = by_label.get(label, 0.0) + d
            k += 1
        gaps.append(((b - a) * 1e-9, sorted(parts.items(),
                                            key=lambda kv: -kv[1])))
    gaps.sort(key=lambda g: -g[0])
    return IdleSplit(window_s, by_label, gaps)


def compiles_in_slice(spans: ProgramSpans, window_s: float) -> int:
    t0, t1 = spans.slice(window_s)
    return sum(1 for t in spans.compiles if t0 <= t <= t1)


def log_split(split: IdleSplit, shift_ns: int, log=harness.log,
              top: int = 10) -> None:
    log(f"idle split of the {split.window_s:.3f} s slice "
        f"({split.percent():.3f}% idle; device times moved "
        f"{shift_ns * 1e-3:.1f} us onto the host's clock), "
        f"by innermost span:")
    for label, s in sorted(split.by_label.items(), key=lambda kv: -kv[1]):
        log(f"  {label}: {s * 1e3:.3f} ms "
            f"({100.0 * s / split.window_s:.3f}%)")
    log(f"longest {top} idle gaps:")
    for length, parts in split.gaps[:top]:
        log(f"  {length * 1e3:.3f} ms: " + ", ".join(
            f"{label} {s * 1e3:.3f} ms" for label, s in parts))
