"""Shared benchmark utilities: datasets, runners, CSV emission, and the
tracker hop that makes every bench's JSON a projection of its event trace.
"""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Callable, Dict, List

import jax
import numpy as np

from repro.data import make_femnist_like, make_mnist_like, make_synthetic
from repro.data.federated import FederatedDataset, make_federated
from repro.fl import ServerConfig, SimulationResult, run_simulation
from repro.models import get_model
from repro.models.config import ArchConfig
from repro.models.logistic import logistic_apply, logistic_loss
from repro.obs import current_tracker

ROWS: List[str] = []
REPO_ROOT = Path(__file__).resolve().parents[1]


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache at one fixed path inside the
    checkout, so every later run from it finds what earlier runs compiled.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def emit(name: str, us_per_call: float, derived: str) -> None:
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def publish_bench(results: Dict) -> None:
    """Stream a bench's JSON-ready results dict into the current tracker as
    marked summary events (``_bench_meta`` / ``_bench_record`` /
    ``_bench_block`` / ``_bench_list``) so ``bench_trace.derive_bench_json``
    can rebuild ``BENCH_<name>.json`` from the trace alone — the jsonl
    stream, not the returned dict, is what ``run.py --json`` commits."""
    tr = current_tracker()
    if not tr.active:
        return
    meta = {k: v for k, v in results.items()
            if not isinstance(v, (list, dict))}
    if meta:
        tr.log_summary({"_bench_meta": meta})
    for rec in results.get("records", []):
        tr.log_summary({"_bench_record": rec})
    for key, val in results.items():
        if key == "records" or not isinstance(val, (list, dict)):
            continue
        if isinstance(val, dict):
            tr.log_summary({"_bench_block": {"key": key, "value": val}})
        else:
            for item in val:
                tr.log_summary({"_bench_list": {"key": key, "value": item}})


def dataset(kind: str, seed: int = 0) -> FederatedDataset:
    """The paper's four datasets (procedural stand-ins, DESIGN.md §3)."""
    if kind == "mnist":
        x, y = make_mnist_like(4000, dim=64, num_classes=10, seed=seed)
        return make_federated(x, y, num_devices=30, num_classes=10,
                              concentration=0.2, seed=seed)
    if kind == "femnist":
        x, y = make_femnist_like(5000, dim=64, num_classes=62, seed=seed)
        return make_federated(x, y, num_devices=30, num_classes=62,
                              concentration=0.2, seed=seed)
    if kind == "synthetic_iid":
        xs, ys = make_synthetic(0.0, 0.0, num_devices=30,
                                samples_per_device=60, dim=60, iid=True,
                                seed=seed)
    elif kind == "synthetic_1_1":
        xs, ys = make_synthetic(1.0, 1.0, num_devices=30,
                                samples_per_device=60, dim=60, seed=seed)
    else:
        raise KeyError(kind)
    mask = np.ones(ys.shape, np.float32)
    tx = xs.reshape(-1, xs.shape[-1])[:400]
    ty = ys.reshape(-1)[:400]
    return FederatedDataset(xs, ys, mask, tx, ty, 10)


def run_fl(name: str, agg: str, ds: FederatedDataset, rounds: int,
           lr: float = 0.2, seed: int = 42, **kw) -> SimulationResult:
    cfg_model = ArchConfig(name="lr", family="logreg",
                           input_dim=ds.x.shape[-1],
                           num_classes=ds.num_classes)
    params = get_model(cfg_model).init(jax.random.PRNGKey(0))
    base = dict(num_devices=ds.num_devices, clients_per_round=10, lr=lr,
                batch_size=10, min_epochs=1, max_epochs=20)
    base.update(kw)
    cfg = ServerConfig(aggregator=agg, **base)
    return run_simulation(name, logistic_loss, logistic_apply, params, ds,
                          cfg, num_rounds=rounds, selection_seed=seed,
                          eval_every=1, collect_alpha=True)


def timeit(fn: Callable, iters: int = 20, warmup: int = 3) -> float:
    """Median wall time per call in microseconds."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)
