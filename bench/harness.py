"""The benchmark's harness: finds a cell's configuration, traffic mix, driver
and per-layer metric readers by the names in ``BENCHMARK.json``, guards the
device, keeps the compile cache, traces, and assembles the result line.

Everything one configuration, one traffic mix or one per-layer metric needs
lives in a file of its own, found by name:

  * ``bench/configs/<config>.json``   -- sizes as run, source, cuts
  * ``bench/traffic/<traffic>.json``  -- parameters of a traffic mix; its
    ``kind`` names the driver ``bench/drivers/<kind>.py`` that generates the
    mix from them and drives the window
  * ``bench/metrics/<metric>.py``     -- ``read(run) -> float | None``
  * ``bench/limits/<workload>.json``  -- the limit of each number the
    cell's correctness check compares, with the readings it was set from

So a later cell, configuration, mix or metric is new files plus a new entry
in ``BENCHMARK.json``: nothing here changes.  For a serving configuration
that holds where the plain reference knows its ``model_type``
(``bench/reference/moe_lm.py``); a new model type needs a change to the
reference.  ``bench/drivers/serve.py`` carries the configuration's
published choices to the program and refuses at set-up one that the
program cannot express.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
OUT = BENCH / "out"
CACHE_DIR = BENCH / ".jax_cache"


class BenchError(RuntimeError):
    """The benchmark cannot run as asked (missing file, unknown name, wrong
    device).  ``run.py`` turns it into a non-zero exit with no result."""


# ----------------------------------------------------------------- lookup

def load_json(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing benchmark file {path}") from e


def load_benchmark(root: Path = REPO) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]            # the configuration file's contents
    traffic: Dict[str, Any]           # the traffic file's contents
    end_to_end: List[Dict[str, Any]]  # metrics this cell reports, trace 0
    per_layer: List[Dict[str, Any]]   # metrics this cell reports, trace 1
    limits: Dict[str, float]          # bench/limits/<workload>.json

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: Dict[str, Any], name: str,
              root: Path = REPO) -> Cell:
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name} names unknown config "
                         f"{w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    limits = load_json(root / "bench" / "limits" / f"{name}.json")["limits"]
    return Cell(name, w, config, traffic, e2e, layer, limits)


def _load_module(path: Path, modname: str):
    if not path.exists():
        raise BenchError(f"missing benchmark module {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str, bench_dir: Path = BENCH):
    """``bench/drivers/<kind>.py``: ``run(ctx) -> Outcome``."""
    return _load_module(bench_dir / "drivers" / f"{kind}.py",
                        f"bench_driver_{kind}")


def load_metric(name: str, bench_dir: Path = BENCH
                ) -> Callable[["Run"], Optional[float]]:
    """``bench/metrics/<name>.py``'s ``read``."""
    mod = _load_module(bench_dir / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def peaks_for(kind: str) -> Dict[str, float]:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind`` ``kind``.  A
    kind missing from ``peaks.json`` is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


# ----------------------------------------------------------------- seeds

def seed_ints(seed: int, n: int) -> List[int]:
    """``n`` 32-bit integers derived from ``seed`` (any size): the seeds of
    every generator a run uses, so one ``--seed`` fixes all inputs."""
    import numpy as np
    ss = np.random.SeedSequence(int(seed))
    return [int(x) for x in ss.generate_state(n, dtype=np.uint32)]


# ----------------------------------------------------------------- device

def use_compile_cache() -> str:
    """JAX's persistent compile cache at one fixed path inside the checkout,
    ``bench/.jax_cache``, so only a cell's first run in a checkout compiles.
    The path is also put in ``JAX_COMPILATION_CACHE_DIR`` for program code
    that reads it.  (After ``benchmarks/common.py::use_compile_cache``,
    which keeps its own directory.)"""
    import jax
    path = str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however fast it compiled: a run's set-up should
    # compile nothing once the cell has run in this checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_tpu(chips: int):
    """The device guard: TPU only, at least ``chips`` chips.  There is no
    CPU path: anything else raises :class:`BenchError`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise BenchError(f"needs {chips} TPU chip(s); JAX reports "
                         f"{len(devs)} x {devs[0].platform} "
                         f"({devs[0].device_kind})")
    return devs[:chips]


def peak_bytes(devs=None) -> int:
    """``peak_bytes_in_use`` of the fullest chip so far (0 where the
    backend keeps no memory statistics).  Drivers read it once the window
    has closed and before the reference runs."""
    import jax
    peak = 0
    for d in devs or jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def device_info(devs, memory_peak_bytes: int) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak_bytes}


# ----------------------------------------------------------------- tracing

class Tracer:
    """Profiles one slice of a traced run's window.  The driver calls
    :meth:`start` and :meth:`stop` where the device is idle (after a
    round, between engine steps), so every call recorded inside the slice
    ran inside it.  Host spans of the harness's own calls go into the same
    trace through :meth:`annotate`."""

    def __init__(self, active: bool, out_dir: Path):
        self.active = active
        self.out_dir = out_dir
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    @property
    def running(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def start(self) -> None:
        if not self.active or self.t_start is not None:
            return
        import jax
        self.out_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if not self.running:
            return
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def annotate(self, name: str):
        import contextlib
        if not self.running:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @property
    def window_s(self) -> Optional[float]:
        if self.t_start is None or self.t_stop is None:
            return None
        return self.t_stop - self.t_start

    def xplane(self) -> Optional[Path]:
        files = sorted(self.out_dir.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        return files[-1] if files else None


# ----------------------------------------------------------------- outcome

@dataclass
class Check:
    """One number compared against its limit (``value <= limit`` passes)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value == self.value and self.value <= self.limit)


@dataclass
class Outcome:
    """What a driver hands back: end-to-end readings, the counters and
    shapes per-layer readers use, the checks, request counts and the
    device's peak memory after the window."""
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    counters: Dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: int = 0


@dataclass
class Run:
    """What a per-layer reader sees: the cell, the driver's counters, the
    reduced trace (None when no trace was read) and the chip's peaks."""
    cell: Cell
    counters: Dict[str, Any]
    trace: Optional[Any]
    peaks: Dict[str, float]
    device: Dict[str, Any]


@dataclass
class Context:
    """What a driver gets."""
    cell: Cell
    seed: int
    seconds: float
    tracer: Tracer
    t_process: float
    log: Callable[[str], None]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(outcome: Outcome, metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    line = {"correct": all(c.ok for c in outcome.checks) and bool(
                outcome.checks),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return json.dumps(line)
