"""End-to-end FL simulation harness (reproduces the paper's experiments).

``run_simulation`` runs T synchronous rounds of a configured algorithm on a
:class:`FederatedDataset`, keeping ALL host-side randomness (device
selection, epoch heterogeneity) on a dedicated seed so different algorithms
see *identical* selections — exactly the paper's §IV-A3 protocol.

``run_async_simulation`` drives the same datasets/metrics through the
``repro.edge`` event-driven runtime: devices train at profile-dependent
speeds, updates arrive asynchronously, and the server aggregates buffered
(possibly stale) updates.  Both paths share the eval/metrics code, and the
async event stream is itself a pure function of (fleet, seed) — aggregation
choices never perturb timing — so algorithms remain comparable.

``run_hier_simulation`` runs synchronous rounds over a ``repro.hier``
multi-tier topology: the model broadcast flows down the tree, devices train,
each aggregation node waits for its members (timeout model: dropouts still
cost their partial time), summarizes, and ships the summary one hop up —
every hop is an event on the PR-1 scheduler, so round times are true
multi-hop critical paths and the per-tier byte ledger measures the uplink
saving the hierarchy exists for.  The per-round array math runs on the
fused engine (``repro.hier.fused``): flat (P, n) round matrices, one
shape-keyed jit call per tier node, Gram reductions through the
backend-aware kernel registry; ``HierSimulationResult.engine`` reports the
real wall-clock split (first-round compile vs steady-state).  With ``HierConfig.compress`` set (the
``hier_contextual_sketch`` aggregator), every summary uplink instead
carries an error-feedback-compressed payload (``repro.compress``): the
ledger records true serialized sizes, downstream solves consistently use
the decodes, and the cloud's γ stage runs on sketched cross-terms.
"""
from __future__ import annotations

import contextlib
import os
import time
from collections import deque
from dataclasses import dataclass, field, replace as dc_replace
from functools import lru_cache, partial
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..data.federated import FederatedDataset
from ..kernels.registry import force_backend
from ..obs import current_tracker, spans
from .client import client_update
from .metrics import evaluate_classifier, global_train_loss
from .server import RoundState, ServerConfig, build_round_fn, init_server, sample_round

Pytree = Any

# how much per-round α/γ history the result dataclasses retain:
#   True  — unbounded (the pre-tracker behavior; fine for short runs)
#   False — none (the tracker stream carries the per-round values instead)
#   int N — a rolling window of the last N entries (long fleet runs used to
#           OOM the host on P-vectors × thousands of rounds)
RecordHistory = Union[bool, int]


def _history_buffer(record_history: RecordHistory):
    """Backing store for a result's per-round history: a plain list when
    unbounded (or disabled), a ``deque(maxlen=N)`` for a rolling window —
    eviction is O(1) per append instead of the O(n) ``del hist[0]`` a list
    pays, which at fleet-scale round counts dominated history upkeep."""
    if record_history is True or record_history is False \
            or record_history == 0:
        return []
    return deque(maxlen=int(record_history))


def _history_push(hist, item: Any, record_history: RecordHistory) -> None:
    if record_history is False or record_history == 0:
        return
    hist.append(item)      # deque(maxlen) evicts the oldest entry itself
    if (record_history is not True and not isinstance(hist, deque)
            and len(hist) > int(record_history)):
        del hist[0]        # list fallback (caller skipped _history_buffer)


def _vec_stats(prefix: str, v) -> Dict[str, float]:
    """Flat summary stats of a weight vector for one tracker event (the full
    vector stays out of the stream unless the caller opted in)."""
    a = np.asarray(v, np.float64)
    if a.size == 0:
        return {}
    return {f"{prefix}_mean": float(a.mean()), f"{prefix}_std": float(a.std()),
            f"{prefix}_min": float(a.min()), f"{prefix}_max": float(a.max())}


# ---------------------------------------------------------------------------
# process-wide compile caches: repeated simulations with the same client
# hyper-parameters (tests, benchmark sweeps) reuse one compiled function
# instead of re-jitting a fresh closure per run
# ---------------------------------------------------------------------------

@lru_cache(maxsize=32)
def _client_update_fn(loss_fn: Callable, max_steps: int, batch_size: int,
                      lr: float, mu: float) -> Callable:
    """Jitted single-device ``client_update`` (async runtime)."""
    return jax.jit(partial(client_update, loss_fn, max_steps=max_steps,
                           batch_size=batch_size, lr=lr, mu=mu))


@lru_cache(maxsize=32)
def _batched_client_update_fn(loss_fn: Callable, max_steps: int,
                              batch_size: int, lr: float, mu: float,
                              mesh=None) -> Callable:
    """Jitted vmapped cohort ``client_update`` (hierarchical runtime).  A
    mesh with a ``'fleet'`` axis shard_maps the cohort over it (params
    replicated, per-device rows split)."""
    upd = partial(client_update, loss_fn, max_steps=max_steps,
                  batch_size=batch_size, lr=lr, mu=mu)

    def cohort(params, xs, ys, ms, ns, keys):
        return jax.vmap(lambda xx, yy, mm, n, k: upd(params, xx, yy, mm, n, k)
                        )(xs, ys, ms, ns, keys)

    if mesh is not None and "fleet" in mesh.shape:
        from ..sharding.specs import shard_cohort_fn
        return shard_cohort_fn(mesh, cohort, num_stacked_args=5)
    return jax.jit(cohort)


@lru_cache(maxsize=16)
def _batched_virtual_update_fn(loss_fn: Callable, max_steps: int,
                               batch_size: int, lr: float, mu: float,
                               dataset, mesh=None) -> Callable:
    """Jitted vmapped cohort ``client_update`` over a
    :class:`~repro.data.fleetgen.VirtualFleetDataset`: each device's shard is
    generated *inside* the jit boundary from its id (counter-based PRNG
    fold-in), so a fleet-scale cohort never materializes an (N, m, dim) host
    array.  ``dataset`` is identity-hashed (frozen, ``eq=False``).  A mesh
    with a ``'fleet'`` axis shard_maps the cohort over it — shard
    generation *and* training both run device-parallel."""
    shard = dataset.shard_fn()
    upd = partial(client_update, loss_fn, max_steps=max_steps,
                  batch_size=batch_size, lr=lr, mu=mu)

    def cohort(params, dev_ids, ns, keys):
        def one(d, n, k):
            xx, yy, mm = shard(d)
            return upd(params, xx, yy, mm, n, k)
        return jax.vmap(one)(dev_ids, ns, keys)

    if mesh is not None and "fleet" in mesh.shape:
        from ..sharding.specs import shard_cohort_fn
        return shard_cohort_fn(mesh, cohort, num_stacked_args=3)
    return jax.jit(cohort)


@lru_cache(maxsize=32)
def _round_fn_cached(loss_fn: Callable, cfg: ServerConfig,
                     samples_per_device: int) -> Callable:
    """One compiled round function per (loss, config, shard size)."""
    return build_round_fn(loss_fn, cfg, samples_per_device)


@dataclass
class SimulationResult:
    name: str
    train_loss: List[float] = field(default_factory=list)
    test_acc: List[float] = field(default_factory=list)
    test_nll: List[float] = field(default_factory=list)
    alpha_history: List[np.ndarray] = field(default_factory=list)
    wall_time: float = 0.0

    def rounds_to_accuracy(self, level: float) -> Optional[int]:
        """First round index whose test accuracy reaches ``level`` (fig. 6)."""
        for i, acc in enumerate(self.test_acc):
            if acc >= level:
                return i + 1
        return None

    def loss_volatility(self) -> float:
        """Mean |Δ loss| between consecutive rounds after round 5 — the
        robustness metric (paper: 'wide fluctuations, even in consecutive
        rounds')."""
        arr = np.asarray(self.train_loss[5:])
        if len(arr) < 2:
            return 0.0
        return float(np.mean(np.abs(np.diff(arr))))


def run_simulation(name: str, loss_fn: Callable, apply_fn: Callable,
                   init_params: Pytree, dataset: FederatedDataset,
                   cfg: ServerConfig, num_rounds: int,
                   selection_seed: int = 1234, eval_every: int = 1,
                   collect_alpha: bool = False,
                   record_history: RecordHistory = True) -> SimulationResult:
    round_fn = _round_fn_cached(loss_fn, cfg, dataset.samples_per_device)
    steps_per_epoch = max(dataset.samples_per_device // cfg.batch_size, 1)
    if (cfg.attack is not None and cfg.attack.corrupts_data
            and cfg.malicious):
        # label-flip adversaries poison their shards before the run; the
        # update-space attacks corrupt inside the compiled round instead
        from ..robust.attacks import poison_labels
        dataset = poison_labels(dataset, cfg.malicious)

    state = init_server(jax.tree_util.tree_map(jnp.asarray, init_params))
    data = (jnp.asarray(dataset.x), jnp.asarray(dataset.y),
            jnp.asarray(dataset.mask))
    sel_rng = np.random.RandomState(selection_seed)  # shared across algorithms
    key = jax.random.PRNGKey(selection_seed)

    tr = current_tracker().scope(f"sync/{name}")
    if tr.active:
        tr.jot(runtime="sync", run=name, aggregator=cfg.aggregator,
               num_rounds=num_rounds)
    result = SimulationResult(name=name)
    result.alpha_history = _history_buffer(record_history)
    t0 = time.time()
    for t in range(num_rounds):
        with spans.span("round", round=t):
            sel, grad_sel, num_steps = sample_round(sel_rng, cfg, steps_per_epoch)
            key, round_key = jax.random.split(key)
            # one jit call fuses the cohort's client updates with the
            # aggregation solve, so they share a span
            with spans.span("update_aggregate"):
                state, info = round_fn(state, data, jnp.asarray(sel),
                                       jnp.asarray(grad_sel),
                                       jnp.asarray(num_steps), round_key)
            if collect_alpha and "alpha" in info:
                _history_push(result.alpha_history, np.asarray(info["alpha"]),
                              record_history)
            event: Dict[str, Any] = {"round": t} if tr.active else {}
            if tr.active and "alpha" in info:
                event.update(_vec_stats("alpha", info["alpha"]))
            if (t + 1) % eval_every == 0 or t == num_rounds - 1:
                with spans.span("eval"):
                    loss = global_train_loss(loss_fn, state.params, data[0],
                                             data[1], data[2])
                    nll, acc = evaluate_classifier(
                        apply_fn, state.params, jnp.asarray(dataset.test_x),
                        jnp.asarray(dataset.test_y))
                result.train_loss.append(loss)
                result.test_acc.append(acc)
                result.test_nll.append(nll)
                if tr.active:
                    event.update(train_loss=loss, test_acc=acc, test_nll=nll)
            if tr.active:
                tr.log(event, step=t)
    result.wall_time = time.time() - t0
    if tr.active and result.train_loss:
        tr.log_summary({"final_train_loss": result.train_loss[-1],
                        "final_test_acc": result.test_acc[-1],
                        "wall_time_s": result.wall_time})
    return result


@dataclass
class AsyncSimulationResult:
    """Metrics of an async run, indexed by *virtual wall-clock* eval points."""
    name: str
    times: List[float] = field(default_factory=list)       # virtual seconds
    versions: List[int] = field(default_factory=list)      # model version
    train_loss: List[float] = field(default_factory=list)
    test_acc: List[float] = field(default_factory=list)
    test_nll: List[float] = field(default_factory=list)
    staleness_mean: List[float] = field(default_factory=list)  # per flush
    alpha_history: List[np.ndarray] = field(default_factory=list)
    updates_per_device: Optional[np.ndarray] = None   # arrivals aggregated
    dispatched: int = 0
    arrived: int = 0
    dropped: int = 0
    wall_time: float = 0.0                                 # real seconds

    def time_to_accuracy(self, level: float) -> Optional[float]:
        """First virtual time at which test accuracy reaches ``level``."""
        return self.to_curve().time_to_accuracy(level)

    def to_curve(self):
        from ..edge.wallclock import WallclockCurve
        return WallclockCurve(name=self.name, times=list(self.times),
                              test_acc=list(self.test_acc),
                              train_loss=list(self.train_loss))


def run_async_simulation(name: str, loss_fn: Callable, apply_fn: Callable,
                         init_params: Pytree, dataset: FederatedDataset,
                         cfg, fleet, num_aggregations: int,
                         selection_seed: int = 1234, eval_every: int = 1,
                         collect_alpha: bool = False,
                         record_history: RecordHistory = True,
                         attack=None, churn=None
                         ) -> AsyncSimulationResult:
    """Event-driven async FL (``cfg`` is a :class:`repro.edge.AsyncConfig`).

    The server keeps up to ``cfg.concurrency`` tasks in flight (default: one
    per device); devices without a task wait in a FIFO queue, so a
    concurrency cap rotates work across the whole fleet rather than pinning
    it to a fixed subset.  Each ARRIVAL is trained against the params it was
    *dispatched* with, buffered, and the buffer is flushed through the
    configured aggregator (``contextual_async`` / ``fedbuff`` /
    ``fedasync``) once ``cfg.buffer_size`` updates are present.  Dropouts
    lose their work; the freed slot goes to the next waiting device.  Runs
    until ``num_aggregations`` buffer flushes have been applied.

    ``attack`` (a :class:`repro.robust.AttackModel`) corrupts each arrival
    from a device in ``fleet.malicious`` before it enters the buffer
    (label-flip attacks poison the malicious shards up front instead);
    ``churn`` (a :class:`repro.robust.ChurnSchedule`) rides on the event
    scheduler, turning tasks dispatched inside an active wave into
    dropouts.
    """
    # Imported lazily: repro.edge imports repro.fl at module scope, so the
    # reverse edge must not exist at import time.
    from ..edge.async_server import AsyncBuffer, BufferedUpdate
    from ..edge.events import EventKind, EventScheduler
    from ..edge.wallclock import model_flops_per_step, model_payload_bytes

    if fleet.num_devices != cfg.num_devices:
        raise ValueError(f"fleet has {fleet.num_devices} devices, config "
                         f"expects {cfg.num_devices}")
    if dataset.num_devices < cfg.num_devices:
        raise ValueError(f"dataset has {dataset.num_devices} device shards, "
                         f"need {cfg.num_devices}")

    malicious = frozenset(getattr(fleet, "malicious", ()))
    if attack is not None and attack.corrupts_data and malicious:
        from ..robust.attacks import poison_labels
        dataset = poison_labels(dataset, malicious)
    live_attack = (attack if attack is not None
                   and not attack.corrupts_data and malicious else None)

    steps_per_epoch = max(dataset.samples_per_device // cfg.batch_size, 1)
    max_steps = cfg.max_epochs * steps_per_epoch
    upd = _client_update_fn(loss_fn, max_steps, cfg.batch_size, cfg.lr,
                           cfg.mu)

    params = jax.tree_util.tree_map(jnp.asarray, init_params)
    x = jnp.asarray(dataset.x)
    y = jnp.asarray(dataset.y)
    mask = jnp.asarray(dataset.mask)
    test_x, test_y = jnp.asarray(dataset.test_x), jnp.asarray(dataset.test_y)

    scheduler = EventScheduler(
        fleet, seed=selection_seed,
        flops_per_step=model_flops_per_step(params, cfg.batch_size),
        payload_bytes=model_payload_bytes(params), churn=churn)
    buffer = AsyncBuffer(cfg)
    epoch_rng = np.random.RandomState(selection_seed + 1)
    base_key = jax.random.PRNGKey(selection_seed)

    version = 0
    in_flight: Dict[int, tuple] = {}     # device_id -> (params snapshot, version)
    idle = deque(range(fleet.num_devices))   # devices waiting for a task

    def dispatch_next() -> None:
        device_id = idle.popleft()
        epochs = int(epoch_rng.randint(cfg.min_epochs, cfg.max_epochs + 1))
        scheduler.dispatch(device_id, epochs * steps_per_epoch, version)
        in_flight[device_id] = (params, version)

    concurrency = (fleet.num_devices if cfg.concurrency is None
                   else min(cfg.concurrency, fleet.num_devices))
    for _ in range(concurrency):
        dispatch_next()

    tr = current_tracker().scope(f"async/{name}")
    if tr.active:
        tr.jot(runtime="async", run=name, aggregator=cfg.aggregator,
               num_aggregations=num_aggregations,
               buffer_size=cfg.buffer_size)
    result = AsyncSimulationResult(
        name=name, updates_per_device=np.zeros(fleet.num_devices, np.int64))
    result.alpha_history = _history_buffer(record_history)
    max_events = 1000 + 50 * num_aggregations * cfg.buffer_size
    aggs = 0
    events_processed = 0
    t0 = time.time()
    with spans.use_virtual_clock(lambda: scheduler.now):
        while aggs < num_aggregations:
            if events_processed >= max_events:
                raise RuntimeError(f"exceeded {max_events} events before reaching "
                                   f"{num_aggregations} aggregations")
            events_processed += 1
            evt = scheduler.pop()
            if evt is None:
                raise RuntimeError("event queue exhausted before reaching "
                                   f"{num_aggregations} aggregations")
            disp_params, disp_version = in_flight.pop(evt.device_id)
            idle.append(evt.device_id)      # back of the queue either way
            if evt.kind == EventKind.DROPOUT:
                dispatch_next()             # lost work; slot goes to next waiter
                continue
            key = jax.random.fold_in(base_key, evt.seq)
            with spans.span("client_update", device=evt.device_id,
                            staleness=version - disp_version):
                delta, grad = upd(disp_params, x[evt.device_id],
                                  y[evt.device_id], mask[evt.device_id],
                                  jnp.int32(evt.num_steps), key)
            if live_attack is not None and evt.device_id in malicious:
                from ..robust.attacks import corrupt_one_jit
                delta, grad = corrupt_one_jit(
                    live_attack, delta, grad,
                    jax.random.fold_in(key, 0x0BAD))
            buffer.add(BufferedUpdate(delta, grad, disp_version, evt.device_id))
            result.updates_per_device[evt.device_id] += 1
            if buffer.ready():
                with spans.span("aggregate", flush=aggs + 1):
                    params, info = buffer.flush(params, version)
                version += 1
                aggs += 1
                stale = float(np.mean(info["staleness"]))
                result.staleness_mean.append(stale)
                if collect_alpha and "alpha" in info:
                    _history_push(result.alpha_history,
                                  np.asarray(info["alpha"]), record_history)
                event: Dict[str, Any] = {}
                if tr.active:
                    event = {"flush": aggs, "t_virtual": scheduler.now,
                             "version": version, "staleness_mean": stale,
                             "staleness_max": float(np.max(info["staleness"]))}
                    if "alpha" in info:
                        event.update(_vec_stats("alpha", info["alpha"]))
                if aggs % eval_every == 0 or aggs == num_aggregations:
                    with spans.span("eval"):
                        loss = global_train_loss(loss_fn, params, x, y, mask)
                        nll, acc = evaluate_classifier(apply_fn, params,
                                                       test_x, test_y)
                    result.times.append(scheduler.now)
                    result.versions.append(version)
                    result.train_loss.append(loss)
                    result.test_acc.append(acc)
                    result.test_nll.append(nll)
                    if tr.active:
                        event.update(train_loss=loss, test_acc=acc, test_nll=nll)
                if tr.active:
                    tr.log(event, step=aggs)
            dispatch_next()                 # fresh task on the freshest model
    result.wall_time = time.time() - t0
    result.dispatched = scheduler.stats.dispatched
    result.arrived = scheduler.stats.arrived
    result.dropped = scheduler.stats.dropped
    if tr.active:
        tr.log_summary({"dispatched": result.dispatched,
                        "arrived": result.arrived,
                        "dropped": result.dropped,
                        "t_virtual_end": scheduler.now,
                        "wall_time_s": result.wall_time})
    return result


@dataclass
class HierSimulationResult:
    """Metrics of a hierarchical run, indexed by virtual wall-clock."""
    name: str
    times: List[float] = field(default_factory=list)       # round-end seconds
    train_loss: List[float] = field(default_factory=list)
    test_acc: List[float] = field(default_factory=list)
    test_nll: List[float] = field(default_factory=list)
    gamma_history: List[np.ndarray] = field(default_factory=list)
    comm: Dict[str, Dict[str, float]] = field(default_factory=dict)
    cloud_uplink_bytes: float = 0.0
    total_bytes: float = 0.0
    dispatched: int = 0         # device tasks only (backhaul transfers are
    arrived: int = 0            # scheduler events but not counted here, so
    dropped: int = 0            # these match AsyncSimulationResult semantics)
    rounds_skipped: int = 0     # rounds where every participant dropped out
    wall_time: float = 0.0
    # engine stats: engine_name ("fused"|"streamed"), the memory model
    # (round_matrix_peak_bytes for the engine used vs what the dense (P, n)
    # matrices would cost, dense_round_matrix_bytes), and real wall-clock —
    # compile_wall_time_s (first round, pays the jit compiles),
    # steady_wall_time_per_round_s (median of the rest), rounds_wall_time_s
    engine: Dict[str, Any] = field(default_factory=dict)

    def time_to_accuracy(self, level: float) -> Optional[float]:
        return self.to_curve().time_to_accuracy(level)

    def to_curve(self):
        from ..edge.wallclock import WallclockCurve
        return WallclockCurve(name=self.name, times=list(self.times),
                              test_acc=list(self.test_acc),
                              train_loss=list(self.train_loss))


def run_hier_simulation(name: str, loss_fn: Callable, apply_fn: Callable,
                        init_params: Pytree, dataset: FederatedDataset,
                        cfg, topology, num_rounds: int,
                        selection_seed: int = 1234, eval_every: int = 1,
                        collect_gamma: bool = False,
                        engine: str = "auto",
                        stream_chunk: Optional[int] = None,
                        mesh=None,
                        record_history: RecordHistory = True,
                        attack=None, churn=None,
                        scheduler_mode: str = "auto",
                        rng_stream: str = "v1",
                        eval_device_cap: int = 4096,
                        cohort_chunk: Optional[int] = None,
                        publish_fn: Optional[Callable[[int, Pytree], None]]
                        = None) -> HierSimulationResult:
    """Synchronous rounds over a multi-tier topology (``cfg`` is a
    :class:`repro.hier.HierConfig`, ``topology`` a :class:`repro.hier.Topology`).

    Per round: the model broadcast flows down the backhaul links, every
    gateway's (fan-in-sampled) devices train at profile speed, each
    aggregation node completes when its last member's terminal event pops —
    dropouts lose their update but still gate the node (timeout model, as in
    the flat sync path) — then its summary rides the uplink as a scheduled
    multi-hop event.  The round ends when the cloud's last child reports; the
    cloud stage goes through the ``core.aggregation`` registry
    (``hier_contextual`` / ``hier_fedavg`` / ``hier_relay`` /
    ``hier_contextual_sketch``).  With ``cfg.compress`` set, summary uplinks
    carry EF-compressed payloads and the γ stage solves on sketched
    cross-terms (see the module docstring and ``repro.compress``).

    ``engine`` picks the round engine: ``"fused"`` (dense (P, n) round
    matrices, fastest at small width), ``"streamed"`` (chunked column
    passes, O(P·chunk) round-matrix memory — big models), or ``"auto"``
    (default): streamed when the dense footprint 2·P·n·4 bytes would exceed
    ``REPRO_DENSE_ROUND_BYTES`` (default 1 GiB).  Device-uplink compression
    needs the dense matrices and forces the fused engine.  ``stream_chunk``
    / ``mesh`` are forwarded to the streamed engine; a mesh with a
    ``'fleet'`` axis additionally shard_maps the cohort client update over
    it (params replicated, per-device rows split — see
    :func:`repro.sharding.specs.shard_cohort_fn`) and row-shards the
    streamed engine's (P, n) statistics pass
    (:func:`repro.sharding.specs.stream_round_shardings`).  Over a mesh of
    more than one device the round's kernel ops run on ``xla``: Pallas TPU
    kernels cannot be partitioned automatically, and the rows they would
    read are sharded.

    Fleet scale.  ``topology`` may be a :class:`repro.hier.StackedTopology`
    (array-native, no per-device nodes) and ``dataset`` a
    :class:`repro.data.VirtualFleetDataset` (shards generated inside the jit
    boundary from device ids; ``cohort_chunk`` bounds the in-jit shard
    buffer, ``eval_device_cap`` caps the materialized eval subsample — full
    coverage when the fleet fits the cap).  ``scheduler_mode``:

      * ``"event"``  — the per-device event path above;
      * ``"cohort"`` — no per-device Event objects at all: one vectorized
        batch dispatch, per-gateway completion = max member terminal time,
        gateways processed in completion order, backhaul transfers drained
        as events.  Virtual times and results match the event path exactly
        on two-tier trees (the cloud fires only after every gateway; on
        deeper trees transfer tie-breaking at *exactly* equal times may
        order seq numbers differently).  Incompatible with
        ``CompressConfig(device_uplink=True)`` (per-arrival error feedback
        needs per-device events);
      * ``"auto"``   — cohort from 4096 participants per round, else event.

    ``rng_stream`` picks the scheduler's RNG universe (``"v1"`` legacy
    sequential draws, ``"v2"`` counter-based — see
    :class:`repro.edge.EventScheduler`); both are deterministic, v2 is the
    one whose batch dispatch vectorizes.

    ``publish_fn(round, params)``, when given, is called with each round's
    aggregated params the moment the cloud stage applies them (inside the
    round's virtual-clock scope, so ``spans.virtual_now()`` is the round's
    completion time) — the train→serve hook that feeds
    :class:`repro.serve.ModelBus.publish` without the serving side polling
    the result object.  Skipped rounds (every participant dropped) publish
    nothing.
    """
    # Imported lazily: repro.hier imports repro.edge which imports repro.fl,
    # so the reverse edge must not exist at import time.
    from ..compress import ErrorFeedback, payload_gram
    from ..edge.events import EventKind, EventScheduler
    from ..edge.wallclock import model_flops_per_step, model_payload_bytes
    from ..hier.comm import (CommLedger, compressed_summary_bytes,
                             summary_bytes, update_bytes)
    from ..hier.fused import HierRoundEngine
    from ..hier.gateway import CompressedSummary, GatewaySummary
    from ..hier.hier_server import blockdiag_diagnostics
    from ..hier.streamed import StreamedRoundEngine, dense_round_bytes

    fleet = topology.fleet
    virtual = bool(getattr(dataset, "virtual", False))
    if dataset.num_devices < fleet.num_devices:
        raise ValueError(f"dataset has {dataset.num_devices} device shards, "
                         f"topology needs {fleet.num_devices}")

    # -- adversarial wiring (repro.robust): label_flip poisons shards up
    # front; update-space attacks corrupt the cohort's stacked rows after
    # local training, with a key stream independent of the honest fold_ins
    malicious = np.asarray(sorted(getattr(fleet, "malicious", ())), np.int64)
    if attack is not None and attack.corrupts_data and malicious.size:
        if virtual:
            raise ValueError(
                "data-poisoning attacks need materialized shards; a "
                "VirtualFleetDataset generates data inside the jit boundary "
                "(materialize() a subset, or use an update-space attack)")
        from ..robust.attacks import poison_labels
        dataset = poison_labels(dataset, malicious)
    live_attack = (attack if attack is not None
                   and not attack.corrupts_data and malicious.size else None)

    steps_per_epoch = max(dataset.samples_per_device // cfg.batch_size, 1)
    max_steps = cfg.max_epochs * steps_per_epoch
    params = jax.tree_util.tree_map(jnp.asarray, init_params)
    if virtual:
        batch_update = _batched_virtual_update_fn(
            loss_fn, max_steps, cfg.batch_size, cfg.lr, cfg.mu, dataset,
            mesh)
        # eval over a capped, evenly-strided materialized device subsample:
        # exact global loss whenever the fleet fits the cap (the fleet-vs-64
        # equivalence scenario), an unbiased O(cap) estimate beyond it
        from ..data.fleetgen import eval_device_ids
        ex, ey, em = dataset.materialize_arrays(
            eval_device_ids(fleet.num_devices, eval_device_cap))
        x, y, mask = jnp.asarray(ex), jnp.asarray(ey), jnp.asarray(em)
        tx, ty = dataset.test_set()
        test_x, test_y = jnp.asarray(tx), jnp.asarray(ty)
    else:
        batch_update = _batched_client_update_fn(loss_fn, max_steps,
                                                 cfg.batch_size, cfg.lr,
                                                 cfg.mu, mesh)
        x = jnp.asarray(dataset.x)
        y = jnp.asarray(dataset.y)
        mask = jnp.asarray(dataset.mask)
        test_x, test_y = (jnp.asarray(dataset.test_x),
                          jnp.asarray(dataset.test_y))

    n_model = sum(l.size for l in jax.tree_util.tree_leaves(params))
    mbytes = model_payload_bytes(params)
    scheduler = EventScheduler(
        fleet, seed=selection_seed,
        flops_per_step=model_flops_per_step(params, cfg.batch_size),
        payload_bytes=mbytes, churn=churn, rng_stream=rng_stream)
    tr = current_tracker().scope(f"hier/{name}")
    if tr.active:
        tr.jot(runtime="hier", run=name, aggregator=cfg.aggregator,
               depth=topology.depth, num_rounds=num_rounds)
    # the ledger streams every transfer it records (per-tier up/down bytes
    # stamped with the virtual clock) the moment it is recorded
    ledger = CommLedger(topology.depth, tracker=tr.scope("comm"),
                        clock=lambda: scheduler.now)
    sel_rng = np.random.RandomState(selection_seed)
    base_key = jax.random.PRNGKey(selection_seed)

    gateways = topology.gateways            # tier-1 nodes (the cloud, if star)
    solve_cfg = cfg.solve_config()
    relay = cfg.aggregator == "hier_relay"
    tier_mode = cfg.tier_mode
    cloud_kind = "fedavg" if cfg.aggregator == "hier_fedavg" else "combo"

    # -- round-engine selection (the per-round P is fixed by topology+fan_in)
    P_round = sum(min(cfg.fan_in, len(gw.children)) if cfg.fan_in is not None
                  else len(gw.children) for gw in gateways)
    dense_bytes = dense_round_bytes(P_round, n_model)
    if engine not in ("auto", "fused", "streamed"):
        raise ValueError(f"unknown engine '{engine}' (auto|fused|streamed)")
    device_decodes = cfg.compressing and cfg.compress.device_uplink
    if engine == "streamed" and device_decodes:
        # decoded device rows replace rows of the dense matrices; the
        # streamed statistics cannot absorb per-row substitutions — an
        # explicit request must fail loudly, not silently allocate (P, n)
        raise ValueError("engine='streamed' is incompatible with "
                         "CompressConfig(device_uplink=True): decoded "
                         "device rows need the dense round matrices "
                         "(use engine='fused' or 'auto')")
    if engine == "auto":
        budget = float(os.environ.get("REPRO_DENSE_ROUND_BYTES", 1 << 30))
        engine = ("fused" if device_decodes or dense_bytes <= budget
                  else "streamed")
    if scheduler_mode not in ("auto", "event", "cohort"):
        raise ValueError(f"unknown scheduler_mode '{scheduler_mode}' "
                         "(auto|event|cohort)")
    cohort_mode = (scheduler_mode == "cohort"
                   or (scheduler_mode == "auto" and P_round >= 4096))
    if cohort_mode and device_decodes:
        if scheduler_mode == "cohort":
            raise ValueError("scheduler_mode='cohort' is incompatible with "
                             "CompressConfig(device_uplink=True): per-arrival "
                             "error feedback needs per-device events")
        cohort_mode = False
    robust_cfg = getattr(cfg, "robust", None)
    if engine == "streamed":
        eng = StreamedRoundEngine(params, solve_cfg, tier_mode,
                                  cfg.gram_scope, chunk=stream_chunk,
                                  mesh=mesh, donate_params=True,
                                  robust=robust_cfg)
        # the streamed combine donates its params argument off-CPU, and
        # jnp.asarray above is a no-copy identity on jax arrays: copy once
        # so round 1 never invalidates the caller's init_params buffers
        if jax.default_backend() != "cpu":
            params = jax.tree_util.tree_map(jnp.array, params)
    else:
        # dense engine: summaries carry FLAT f32 vectors for ū/ĝ and every
        # tier stage is one shape-keyed jit call; only the final cloud
        # delta converts back to the parameter tree
        eng = HierRoundEngine(params, solve_cfg, tier_mode, cfg.gram_scope,
                              robust=robust_cfg)

    # Summary compression (repro.compress): every compressing sender keeps
    # per-sender error-feedback residuals that persist ACROSS rounds, and
    # linear sketches share one per-round seed so the cloud's Gram stage can
    # run in sketch space (payload_gram).  In a star topology summaries
    # never exist, so only the optional device-uplink compression applies.
    compressing = cfg.compressing
    if compressing:
        comp_u_c, comp_g_c = cfg.compress.build_pair(n_model)
        ef = ErrorFeedback(enabled=cfg.compress.error_feedback)
        compress_devices = cfg.compress.device_uplink

    # model-broadcast delay & per-link down-bytes from the cloud to each
    # gateway (device-tier downlink is inside DeviceProfile.task_time)
    def broadcast_path(gw):
        path, node = [], gw
        while node.parent is not None:
            path.append(node)
            node = topology.nodes[node.parent]
        return list(reversed(path))         # cloud-side hop first

    result = HierSimulationResult(name=name)
    result.gamma_history = _history_buffer(record_history)
    round_walls: List[float] = []
    t0 = time.time()
    kernels = (force_backend("xla") if mesh is not None and mesh.size > 1
               else contextlib.nullcontext())
    with spans.use_virtual_clock(lambda: scheduler.now), kernels:
        for t in range(num_rounds):
            with spans.span("round", round=t):
                round_t0 = time.perf_counter()
                round_start = scheduler.now
                # -- selection (identical-selection protocol: one shared RNG).
                # The cohort is flat arrays: per-gateway contiguous blocks of
                # participant rows (part_dev), O(gateways) Python + vectorized
                # numpy — no per-device tuples/dicts at any fleet size.
                groups: List[np.ndarray] = []
                for gw in gateways:
                    devs = np.asarray(gw.children, np.int64)
                    if cfg.fan_in is not None and cfg.fan_in < len(devs):
                        devs = np.sort(sel_rng.choice(devs, cfg.fan_in,
                                                      replace=False))
                    groups.append(devs)
                gw_sizes = np.asarray([len(g) for g in groups], np.int64)
                gw_start = np.zeros(len(groups), np.int64)
                np.cumsum(gw_sizes[:-1], out=gw_start[1:])
                part_dev = np.concatenate(groups)
                P = int(part_dev.size)
                epochs = sel_rng.randint(cfg.min_epochs, cfg.max_epochs + 1,
                                         size=P)
                num_steps = (epochs * steps_per_epoch).astype(np.int32)

                # -- downlink broadcast, then dispatch at each gateway's model-arrival
                down_delay = np.zeros(len(gateways))
                for gi, gw in enumerate(gateways):
                    delay = 0.0
                    for hop in broadcast_path(gw):
                        dl = hop.uplink.downlink_time(mbytes)
                        ledger.record_down(hop.tier, mbytes, dl)
                        delay += dl
                    down_delay[gi] = delay
                # one batched model-fetch record + one batched dispatch for
                # the whole cohort (same draws/trace as the per-device loop
                # under v1; see EventScheduler.dispatch_batch)
                ledger.record_down(0, mbytes, count=P)
                batch = scheduler.dispatch_batch(
                    part_dev, num_steps, version=t,
                    at=round_start + np.repeat(down_delay, gw_sizes),
                    enqueue=not cohort_mode)

                # -- local training for the whole cohort (vmap, one compile) --------
                keys = jax.vmap(jax.random.fold_in, (None, 0))(
                    base_key, jnp.arange(t * P, (t + 1) * P, dtype=jnp.uint32))
                ns_j = jnp.asarray(num_steps)
                with spans.span("client_update", participants=P):
                    if virtual:
                        dev_j = jnp.asarray(part_dev)
                        if cohort_chunk is None or P <= cohort_chunk:
                            deltas, grads = batch_update(params, dev_j, ns_j,
                                                         keys)
                        else:
                            # chunked: bounds the in-jit generated
                            # (chunk, m, dim) shard buffers at fleet scale
                            # (at most two compiled shapes: chunk, remainder)
                            cc = int(cohort_chunk)
                            parts = [batch_update(params, dev_j[s:s + cc],
                                                  ns_j[s:s + cc],
                                                  keys[s:s + cc])
                                     for s in range(0, P, cc)]
                            deltas = jax.tree_util.tree_map(
                                lambda *c: jnp.concatenate(c),
                                *[p[0] for p in parts])
                            grads = jax.tree_util.tree_map(
                                lambda *c: jnp.concatenate(c),
                                *[p[1] for p in parts])
                    else:
                        sel = jnp.asarray(part_dev)
                        deltas, grads = batch_update(params, x[sel], y[sel],
                                                     mask[sel], ns_j, keys)
                if live_attack is not None:
                    from ..robust.attacks import corrupt_stacked_jit
                    mal_mask = jnp.asarray(np.isin(part_dev, malicious))
                    if bool(np.any(np.asarray(mal_mask))):
                        akey = jax.random.fold_in(
                            jax.random.PRNGKey(selection_seed + 7919), t)
                        deltas, grads = corrupt_stacked_jit(
                            live_attack, deltas, grads, mal_mask, akey)
                # the round context is the engine's view of the cohort: the fused
                # engine flattens to (P, n) f32 matrices (cohort slicing is a single
                # in-jit gather per tier node), the streamed engine runs one chunked
                # column pass and keeps only (P, P) statistics — summaries then
                # carry symbolic row-mix refs instead of full-width vectors
                with spans.span("begin_round", engine=eng.name):
                    ctx = eng.begin_round(deltas, grads)

                # -- event loop: device terminals, then multi-hop transfers ---------
                # Contextual tiers run a gradient pre-pass: each gateway ships its
                # cohort ĝ_g up first (n floats), the cloud assembles the global ĝ
                # and broadcasts it back down, and only then do gateways solve and
                # ship (ū_g, G_g, c_g).  Total uplink is identical to packing ĝ_g
                # inside the summary — the pre-pass just reorders it — but every
                # tier's c-term is now priced against the *global* ∇f estimate; a
                # gateway cohort is a skewed sample of a non-IID fleet, and a solve
                # against the skewed local ĝ misweights the whole cohort in a way
                # the parent's γ rescale cannot repair.
                use_prepass = (topology.depth >= 2 and not relay
                               and tier_mode == "contextual"
                               and cfg.gateway_grad == "global")
                interior = [n for tier in range(2, topology.depth + 1)
                            for n in topology.tier_nodes(tier)]
                out_grad = {n.node_id: len(n.children) for n in interior}
                out_sum = {n.node_id: len(n.children) for n in interior}
                recv_grad: Dict[int, list] = {n.node_id: [] for n in interior}
                recv_sum: Dict[int, list] = {n.node_id: [] for n in interior}
                node_ghat: Dict[int, Pytree] = {}
                gw_idxs: Dict[int, np.ndarray] = {}
                meta: Dict[int, tuple] = {}          # event seq -> (kind, node, payload)
                ghat_global = None
                cloud_done = False
                round_info: Dict[str, Any] = {}
                if not cohort_mode:
                    # device id -> cohort row / gateway index, as flat arrays
                    idx_of = np.full(fleet.num_devices, -1, np.int64)
                    idx_of[part_dev] = np.arange(P)
                    part_gw = np.repeat(np.arange(len(gateways)), gw_sizes)
                    out_dev = {gw.node_id: int(gw_sizes[gi])
                               for gi, gw in enumerate(gateways)}
                    survivors: Dict[int, List[int]] = {
                        gw.node_id: [] for gw in gateways}

                def send_up(kind, node, payload, nbytes):
                    parent = topology.nodes[node.parent]
                    dt = node.uplink.uplink_time(nbytes)
                    ledger.record_up(parent.tier, nbytes, dt)
                    evt = scheduler.schedule(dt, node.node_id, version=t)
                    meta[evt.seq] = (kind, node.node_id, payload)

                def send_ghat_down(child_id, ghat):
                    child = topology.nodes[child_id]
                    nbytes = update_bytes(n_model)
                    dt = child.uplink.downlink_time(nbytes)
                    ledger.record_down(child.tier, nbytes, dt)
                    evt = scheduler.schedule(dt, child_id, version=t)
                    meta[evt.seq] = ("ghat", child_id, ghat)

                def gone_up(nid, out_map, complete_fn):
                    """Subtree has nothing to report: release the parent's count."""
                    pid = topology.nodes[nid].parent
                    out_map[pid] -= 1
                    if out_map[pid] == 0:
                        complete_fn(pid)

                def gateway_done(gid, idxs):
                    node = topology.nodes[gid]
                    idxs = np.sort(np.asarray(idxs, np.int64))  # stable order
                    gw_idxs[gid] = idxs
                    if node.parent is None:          # star: the cloud is the gateway
                        finish_cloud(idxs.tolist() if idxs.size else None)
                        return
                    if not idxs.size:
                        if use_prepass:
                            gone_up(gid, out_grad, on_grad_complete)
                        gone_up(gid, out_sum, on_sum_complete)
                        return
                    if relay:
                        send_up("summary", node, idxs.tolist(),
                                len(idxs) * update_bytes(n_model))
                    elif use_prepass:
                        ghat_g = ctx.mean_grad(idxs)
                        send_up("grad", node, (ghat_g, len(idxs)),
                                update_bytes(n_model))
                    else:   # no pre-pass: solve (or average) against the cohort's
                            # own ĝ_g, which rides up inside the summary
                        s = _gateway_summary(gid, idxs, None)
                        if compressing:
                            send_up("summary", node, *_compress_summary(s, gid))
                        else:
                            send_up("summary", node, s,
                                    summary_bytes(len(idxs), n_model,
                                                  include_grad=True))

                def _gateway_summary(gid, idxs, solve_grad):
                    # §III-C at the gateway tier: a fan-in-sampled cohort prices the
                    # pool it was drawn from, exactly like contextual_expected flat
                    pool = len(topology.nodes[gid].children)
                    pool_scale = ((pool - 1) / max(len(idxs) - 1, 1)
                                  if cfg.fan_in is not None and cfg.fan_in < pool
                                  and tier_mode == "contextual" else 1.0)
                    with spans.span("gateway", node=gid, members=len(idxs)):
                        out = ctx.gateway(idxs, solve_grad=solve_grad,
                                          pool_scale=pool_scale)
                    return GatewaySummary(
                        node_id=gid, num_updates=len(idxs),
                        member_ids=part_dev[np.asarray(idxs, np.int64)],
                        G=out["G"], c=out["c"], alpha=out["alpha"],
                        u_bar=out["u_bar"], grad_est=out["ghat"], info=out["info"])

                def _merge_summaries(nid, kids, solve_grad):
                    """Parent-tier merge over what actually arrived: the children's
                    ū refs become this node's members (mass-conserving Σγ=1 stage,
                    see ``hier.gateway.merge_summaries``); member vectors stack
                    inside the jit boundary (fused) or stay symbolic row-mixes
                    (streamed)."""
                    counts = np.asarray([s.num_updates for s in kids], np.float32)
                    with spans.span("merge", node=nid, children=len(kids)):
                        out = ctx.merge([s.u_bar for s in kids],
                                        [s.grad_est for s in kids], counts,
                                        solve_grad=solve_grad)
                    return GatewaySummary(
                        node_id=nid, num_updates=int(counts.sum()),
                        member_ids=np.asarray([s.node_id for s in kids], np.int64),
                        G=out["G"], c=out["c"], alpha=out["alpha"],
                        u_bar=out["u_bar"], grad_est=out["ghat"], info=out["info"])

                def _compress_summary(s, nid):
                    """EF-compress one summary's (ū, ĝ) for its uplink hop; returns
                    (payload, wire bytes).  The same per-round sketch seed is shared
                    by every node and both vectors, so sketched cross-terms compose
                    at the cloud; residual state is per (vector, node).  Under the
                    streamed engine this is where symbolic refs dense-ify: one
                    chunked combine per vector, right before the encode."""
                    comp_u, u_hat = ef.step(("u", nid), ctx.materialize(s.u_bar),
                                            comp_u_c, seed=t)
                    comp_g, g_hat = ef.step(("g", nid), ctx.materialize(s.grad_est),
                                            comp_g_c, seed=t)
                    decoded = dc_replace(s, u_bar=u_hat, grad_est=g_hat)
                    nbytes = compressed_summary_bytes(comp_u.nbytes + comp_g.nbytes)
                    return CompressedSummary(decoded, comp_u, comp_g), nbytes

                def on_grad_complete(nid):
                    nonlocal ghat_global
                    node = topology.nodes[nid]
                    entries = recv_grad[nid]         # [(sender, ĝ ref, count)]
                    if not entries:
                        if node.parent is not None:
                            gone_up(nid, out_grad, on_grad_complete)
                        return
                    counts = np.asarray([c for _, _, c in entries], np.float64)
                    ghat = ctx.compose_grads([g for _, g, _ in entries], counts)
                    if node.parent is None:          # cloud: broadcast the global ĝ
                        ghat_global = ghat
                        for sender, _, _ in entries:
                            send_ghat_down(sender, ghat)
                    else:
                        send_up("grad", node, (ghat, int(counts.sum())),
                                update_bytes(n_model))

                def on_ghat(nid, ghat):
                    node = topology.nodes[nid]
                    node_ghat[nid] = ghat
                    if node.tier == 1:               # gateway: solve and ship
                        idxs = gw_idxs[nid]
                        send_up("summary", node, _gateway_summary(nid, idxs, ghat),
                                summary_bytes(len(idxs), n_model))
                    else:                            # regional: fan the broadcast out
                        for sender, _, _ in recv_grad[nid]:
                            send_ghat_down(sender, ghat)

                def on_sum_complete(nid):
                    node = topology.nodes[nid]
                    kids = recv_sum[nid]
                    if node.parent is None:
                        if not kids:
                            finish_cloud(None)
                        else:
                            finish_cloud(sum(kids, []) if relay else kids)
                        return
                    if not kids:
                        gone_up(nid, out_sum, on_sum_complete)
                        return
                    if relay:
                        fwd = sum(kids, [])
                        send_up("summary", node, fwd,
                                len(fwd) * update_bytes(n_model))
                    elif compressing:
                        # merge over what actually arrived (the decodes), then
                        # re-compress with this node's own error-feedback state
                        s = _merge_summaries(nid, [p.summary for p in kids],
                                             node_ghat.get(nid))
                        send_up("summary", node, *_compress_summary(s, nid))
                    else:
                        s = _merge_summaries(nid, kids, node_ghat.get(nid))
                        send_up("summary", node, s,
                                summary_bytes(len(kids), n_model,
                                              include_grad=not use_prepass))

                def finish_cloud(payload):
                    nonlocal cloud_done, round_info, params
                    if payload is None:              # every participant dropped out
                        result.rounds_skipped += 1
                    else:
                        with spans.span("cloud"):
                            delta, round_info = _cloud_stage(payload)
                            params = ctx.apply(params, delta)
                        if publish_fn is not None:
                            # train→serve hop: hand the round's aggregated
                            # params to the serving side (e.g. ModelBus)
                            # the moment the cloud stage lands them
                            publish_fn(t, params)
                    cloud_done = True

                def _cloud_stage(payload):
                    if isinstance(payload, list) and isinstance(
                            payload[0], (int, np.integer)):
                        # raw updates (star / relay); a star cloud is the fleet's one
                        # gateway, so fan-in sampling prices its pool here too
                        pool = len(topology.nodes[topology.cloud_id].children)
                        scale = ((pool - 1) / max(len(payload) - 1, 1)
                                 if cfg.fan_in is not None and cfg.fan_in < pool
                                 and not relay and tier_mode == "contextual" else 1.0)
                        kind = ("fedavg" if cfg.aggregator == "hier_fedavg"
                                else "raw")
                        return ctx.cloud_raw(payload, kind, solve_scale=scale)
                    if compressing:                      # compressed child summaries
                        csums = payload
                        summaries = [p.summary for p in csums]
                        counts = [s.num_updates for s in summaries]
                        # the P×P stage runs on the sketched cross-terms, corrected
                        # for sketch distortion inside payload_gram; the combine
                        # applies the decodes, so solve and step stay consistent
                        G2c2 = payload_gram(comp_u_c,
                                            [p.comp_u for p in csums],
                                            [p.comp_g for p in csums],
                                            np.asarray(counts, np.float64))
                        ghat = ctx.compose_grads([s.grad_est for s in summaries],
                                                 counts)
                        # no blockdiag diagnostics: the K_g² Gram blocks stayed at
                        # the gateways — that is where the byte saving comes from
                        return ctx.cloud_combo([s.u_bar for s in summaries], counts,
                                               ghat, kind="combo", override=G2c2)
                    summaries = payload              # top-tier child summaries
                    counts = [s.num_updates for s in summaries]
                    ghat = (ghat_global if ghat_global is not None else
                            ctx.compose_grads([s.grad_est for s in summaries],
                                              counts))
                    delta, info = ctx.cloud_combo([s.u_bar for s in summaries],
                                                  counts, ghat, kind=cloud_kind)
                    info = dict(info)
                    info.update(blockdiag_diagnostics(summaries, info["gamma"],
                                                      cfg.smoothness))
                    return delta, info

                def on_transfer(kind, sender, payload):
                    if kind == "grad":
                        pid = topology.nodes[sender].parent
                        recv_grad[pid].append((sender,) + payload)
                        out_grad[pid] -= 1
                        if out_grad[pid] == 0:
                            on_grad_complete(pid)
                    elif kind == "ghat":
                        on_ghat(sender, payload)
                    else:                        # summary
                        pid = topology.nodes[sender].parent
                        recv_sum[pid].append(payload)
                        out_sum[pid] -= 1
                        if out_sum[pid] == 0:
                            on_sum_complete(pid)

                if cohort_mode:
                    # -- cohort device phase: zero per-device Event objects.
                    # Every gateway completes at its members' max terminal
                    # time (dropouts still gate — the timeout model); walk
                    # gateways in completion order, settle each cohort block
                    # vectorized, then drain the backhaul transfers as
                    # events.  The clock may legitimately rewind while
                    # draining transfers scheduled by earlier gateways.
                    max_events = 8 * len(topology.nodes) + 64
                    with spans.span("event_loop"):
                        t_complete = np.maximum.reduceat(batch.t_end, gw_start)
                        for gi in np.argsort(t_complete, kind="stable"):
                            scheduler.advance_to(float(t_complete[gi]))
                            s = int(gw_start[gi])
                            e = s + int(gw_sizes[gi])
                            alive = s + np.flatnonzero(~batch.dropped[s:e])
                            result.arrived += int(alive.size)
                            result.dropped += e - s - int(alive.size)
                            gid = gateways[int(gi)].node_id
                            ledger.record_up(topology.nodes[gid].tier,
                                             update_bytes(n_model),
                                             count=int(alive.size))
                            gateway_done(gid, alive)
                        scheduler.complete_batch(batch)
                        for _ in range(max_events):
                            if cloud_done:
                                break
                            evt = scheduler.pop()
                            if evt is None or evt.seq not in meta:
                                raise RuntimeError(
                                    f"round {t}: non-transfer event in the "
                                    "cohort drain")
                            on_transfer(*meta.pop(evt.seq))
                else:
                    max_events = 8 * (P + len(topology.nodes)) + 64
                    with spans.span("event_loop"):
                        for _ in range(max_events):
                            if cloud_done:
                                break
                            evt = scheduler.pop()
                            if evt is None:
                                raise RuntimeError(f"round {t}: event queue "
                                                   "exhausted before the "
                                                   "cloud completed")
                            if evt.seq in meta:      # backhaul transfer arrival
                                on_transfer(*meta.pop(evt.seq))
                            else:                    # device terminal event
                                pi = int(idx_of[evt.device_id])
                                gid = gateways[int(part_gw[pi])].node_id
                                if evt.kind == EventKind.ARRIVAL:
                                    survivors[gid].append(pi)
                                    result.arrived += 1
                                    if compressing and compress_devices:
                                        # per-device error feedback: the residual of every
                                        # round a device DID report persists on-device.
                                        # BOTH streams compress — the solves downstream
                                        # consume the gradient too, so an upload that only
                                        # shipped the update would be under-priced.  The
                                        # decoded rows enter the round context as ONE
                                        # gathered array update per cohort (fused engine;
                                        # the streamed engine defers to it for this config).
                                        comp_d, vhat = ef.step(
                                            ("dev", evt.device_id), ctx.D[pi],
                                            comp_u_c, seed=t)
                                        comp_dg, ghat = ef.step(
                                            ("devg", evt.device_id), ctx.GM[pi],
                                            comp_g_c, seed=t)
                                        ctx.add_decoded_row(pi, vhat, ghat)
                                        ledger.record_up(
                                            topology.nodes[gid].tier,
                                            comp_d.nbytes + comp_dg.nbytes)
                                    else:
                                        ledger.record_up(topology.nodes[gid].tier,
                                                         update_bytes(n_model))
                                else:
                                    result.dropped += 1
                                out_dev[gid] -= 1
                                if out_dev[gid] == 0:
                                    gateway_done(gid, survivors[gid])
                if not cloud_done:
                    raise RuntimeError(f"round {t}: exceeded {max_events} events")
                result.dispatched += P
                round_walls.append(time.perf_counter() - round_t0)

                if collect_gamma and "gamma" in round_info:
                    _history_push(result.gamma_history,
                                  np.asarray(round_info["gamma"]), record_history)
                event: Dict[str, Any] = {}
                if tr.active:
                    event = {"round": t, "t_virtual": scheduler.now,
                             "round_virtual_s": scheduler.now - round_start,
                             "round_wall_s": round_walls[-1], "participants": P,
                             "rounds_skipped": result.rounds_skipped}
                    if "gamma" in round_info:
                        event.update(_vec_stats("gamma", round_info["gamma"]))
                if (t + 1) % eval_every == 0 or t == num_rounds - 1:
                    with spans.span("eval"):
                        loss = global_train_loss(loss_fn, params, x, y, mask)
                        nll, acc = evaluate_classifier(apply_fn, params,
                                                       test_x, test_y)
                    result.times.append(scheduler.now)
                    result.train_loss.append(loss)
                    result.test_acc.append(acc)
                    result.test_nll.append(nll)
                    if tr.active:
                        event.update(train_loss=loss, test_acc=acc, test_nll=nll)
                if tr.active:
                    tr.log(event, step=t)
    result.wall_time = time.time() - t0
    result.comm = ledger.report()
    result.cloud_uplink_bytes = ledger.cloud_uplink_bytes
    result.total_bytes = ledger.total_bytes()
    # compressed summary tiers dense-ify above the encode hop: the largest
    # summary-level fan-in bounds the (members, n) stacks the streamed
    # engine's fused-fallback stages hold (0 when uncompressed / fused)
    dense_members = 0
    if compressing and eng.name == "streamed":
        dense_members = max((len(nd.children)
                             for tier in range(2, topology.depth + 1)
                             for nd in topology.tier_nodes(tier)), default=0)
    result.engine = {
        "engine_name": eng.name,
        # deterministic memory model of the engine actually used vs the
        # dense (P, n) footprint — THE acceptance metric for big models
        "round_matrix_peak_bytes": eng.peak_round_bytes(
            P_round, dense_fallback_members=dense_members),
        "dense_round_matrix_bytes": dense_bytes,
    }
    if round_walls:
        steady = round_walls[1:] if len(round_walls) > 1 else round_walls
        result.engine.update({
            "compile_wall_time_s": round_walls[0],
            "steady_wall_time_per_round_s": float(np.median(steady)),
            "rounds_wall_time_s": float(np.sum(round_walls)),
        })
    if tr.active:
        tr.log_summary({**result.engine,
                        "cloud_uplink_bytes": result.cloud_uplink_bytes,
                        "total_bytes": result.total_bytes,
                        "t_virtual_end": scheduler.now,
                        "wall_time_s": result.wall_time})
    return result
