"""Profiler trace (``.xplane.pb``) → device busy time, device time per
operation and per compiled program, and the longest idle gaps labelled by
what the harness was doing on the host.

Device planes are the ``/device:TPU:<n>`` planes.  Their ``XLA Modules``
line holds one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``; their ``XLA Ops`` line one event per
operation run, named by its HLO text (``%fusion.3 = ...``), which is cut
here to the operation's name (``fusion.3``).  An operation belongs to the
program run whose interval holds its start.  Busy time is the union of the
operations' intervals, averaged over the chips.  Host spans are the
harness's own ``jax.profiler.TraceAnnotation`` events, named ``bench/...``.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench/"
_OP_NAME = re.compile(r"^%?([^\s=]+)")


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` → ``fusion.3``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


@dataclass
class TraceSummary:
    window_s: float                      # the traced slice, host clock
    busy_s: float                        # mean over chips
    chips: int
    op_s: Dict[str, float] = field(default_factory=dict)      # by name
    op_calls: Dict[str, int] = field(default_factory=dict)
    module_s: Dict[str, float] = field(default_factory=dict)  # by program
    module_ops: Dict[str, List[str]] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def ops_matching(self, pattern: str) -> float:
        """Device seconds of the operations whose name matches."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def modules_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.module_s.items() if rx.search(name))

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                a = int(e.start_ns)
                yield e.name, a, a + int(e.duration_ns)


def reduce_planes(planes, window_s: float, top_gaps: int = 10
                  ) -> TraceSummary:
    """Reduce the planes of one profile (``ProfileData.planes`` or any
    objects with the same ``name`` / ``lines`` / ``events`` shape)."""
    planes = list(planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    op_s: Dict[str, float] = {}
    op_calls: Dict[str, int] = {}
    module_s: Dict[str, float] = {}
    module_ops: Dict[str, set] = {}
    busy = []
    first_busy: List[Tuple[int, int]] = []
    for i, plane in enumerate(devices):
        mods = sorted((a, b, name) for name, a, b in
                      _events(plane, MODULES_LINE))
        for a, b, name in mods:
            module_s[name] = module_s.get(name, 0.0) + (b - a) * 1e-9
        starts = [a for a, _, _ in mods]
        intervals = []
        for name, a, b in _events(plane, OPS_LINE):
            intervals.append((a, b))
            op = op_name(name)
            op_s[op] = op_s.get(op, 0.0) + (b - a) * 1e-9
            op_calls[op] = op_calls.get(op, 0) + 1
            j = bisect.bisect_right(starts, a) - 1
            if j >= 0 and a < mods[j][1]:
                module_ops.setdefault(mods[j][2], set()).add(op)
        merged = _union(intervals)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if i == 0:
            first_busy = merged
    chips = max(len(devices), 1)
    for k in op_s:
        op_s[k] /= chips
    for k in module_s:
        module_s[k] /= chips
    gaps = _label_gaps(first_busy, _host_spans(planes), top_gaps)
    return TraceSummary(
        window_s=window_s, busy_s=sum(busy) / chips if busy else 0.0,
        chips=len(devices), op_s=op_s, op_calls=op_calls, module_s=module_s,
        module_ops={k: sorted(v) for k, v in module_ops.items()},
        idle_gaps=gaps)


def _host_spans(planes) -> List[Tuple[int, int, str]]:
    spans = []
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    a = int(e.start_ns)
                    spans.append((a, a + int(e.duration_ns), e.name))
    return spans


def _label_gaps(busy: List[Tuple[int, int]],
                host: List[Tuple[int, int, str]], top: int
                ) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps between device operations on the first
    chip, each named by the innermost harness span covering its middle
    (``host: untraced`` where none does)."""
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])
                   if b[0] > a[1]), reverse=True)[:top]
    out = []
    for length, a, b in gaps:
        mid = (a + b) // 2
        covering = [(e - s, name) for s, e, name in host if s <= mid <= e]
        label = min(covering)[1] if covering else "host: untraced"
        out.append((label, length * 1e-9))
    return out


def reduce_file(path: Path, window_s: float) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes, window_s)


def reduce_run(xplane: Optional[Path], window_s: Optional[float]
               ) -> Optional[TraceSummary]:
    if xplane is None or window_s is None:
        return None
    return reduce_file(xplane, window_s)
