"""The harness finds everything by name, and refuses to measure off the
TPU or outside a full checkout."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

REPO = harness.REPO
CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_by_name(name):
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, name)
    assert cell.kind and harness.load_driver(cell.kind).run
    assert cell.end_to_end and any(m["name"] == "setup_s"
                                   for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_metric(m["name"]))
        # the end-to-end metric it moves is reported in this cell
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    assert cell.limits


def test_unknown_names_are_errors():
    bench = harness.load_benchmark()
    with pytest.raises(harness.BenchError):
        harness.find_cell(bench, "no.such.cell")
    with pytest.raises(harness.BenchError):
        harness.load_metric("no_such_metric")
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v0")


def _digest(root: Path):
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell with a new configuration, traffic mix and per-layer metric
    is new files plus new entries in BENCHMARK.json: every file the
    benchmark already has stays as it is."""
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "bench", root / "bench", ignore=shutil.ignore_patterns(
        "out", ".jax_cache", "__pycache__", "tests"))
    before = _digest(root / "bench")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())

    cfg = json.loads((root / "bench/configs/olmoe-1b-7b.agg-shard.json")
                     .read_text())
    (root / "bench/configs/olmoe-1b-7b.agg-shard4.json").write_text(
        json.dumps({**cfg, "num_experts": 4}))
    tr = json.loads((root / "bench/traffic/fedround-p16.json").read_text())
    (root / "bench/traffic/fedround-p8.json").write_text(
        json.dumps({**tr, "clients": 8, "gateways": 2}))
    (root / "bench/metrics/solve_share.py").write_text(
        "def read(run):\n    return 42.0\n")
    (root / "bench/limits/fedround.olmoe-1b-7b.p8.json").write_text(
        json.dumps({"limits": {"G_rel_err": 1.0}}))
    bench["configs"].append({**bench["configs"][0],
                             "name": "olmoe-1b-7b.agg-shard4",
                             "file": "bench/configs/olmoe-1b-7b.agg-shard4.json"})
    bench["workloads"].append({"name": "fedround.olmoe-1b-7b.p8",
                               "config": "olmoe-1b-7b.agg-shard4",
                               "traffic": "fedround-p8", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "rounds_per_s":
            m["workloads"].append("fedround.olmoe-1b-7b.p8")
    bench["per_layer"].append({"name": "solve_share", "unit": "%",
                               "better": "lower", "source": "host_clock",
                               "layer": "whole round",
                               "moves": "rounds_per_s",
                               "workloads": ["fedround.olmoe-1b-7b.p8"]})

    cell = harness.find_cell(bench, "fedround.olmoe-1b-7b.p8", root=root)
    assert cell.config["num_experts"] == 4
    assert cell.traffic["clients"] == 8 and cell.kind == "fedround"
    assert [m["name"] for m in cell.per_layer] == ["solve_share"]
    assert [m["name"] for m in cell.end_to_end] == ["rounds_per_s",
                                                    "setup_s"]
    read = harness.load_metric("solve_share", bench_dir=root / "bench")
    assert read(None) == 42.0
    after = _digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def test_run_refuses_to_measure_off_the_tpu():
    r = _run(["--workload", CELLS[0], "--seed", str(2 ** 31 + 7),
              "--seconds", "1", "--trace", "0"], REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs 1 TPU chip" in r.stderr


def test_run_fails_in_a_checkout_of_only_the_benchmark(tmp_path):
    """With nothing but BENCHMARK.json and bench/, there is no system to
    measure: the run exits non-zero and prints no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    r = _run(["--workload", CELLS[0], "--seed", "3", "--seconds", "1"],
             tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    # past the device guard too: the driver finds no program to run
    code = ("import sys, time; sys.path.insert(0, '.'); "
            "from bench import harness; from bench.run import measure; "
            f"c = harness.find_cell(harness.load_benchmark(), {CELLS[0]!r}); "
            "print(measure(c, 3, 1.0, False, time.perf_counter(), "
            "device_guard=False))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "No module named 'repro'" in r.stderr
