"""Fleet-scale tests: vectorized batch dispatch parity with the per-device
scheduler (both RNG streams, churn included), batched comm-ledger
equivalence, array fleets vs object fleets, stacked topologies, virtual
datasets, cohort-vs-event simulation equality, bounded history windows,
and the device-axis shard_map parity (multi-device CPU subprocess)."""
import json
import os
import subprocess
import sys
import textwrap
from collections import deque

import jax
import numpy as np
import pytest

from repro.data import VirtualFleetDataset, eval_device_ids
from repro.edge import (EventScheduler, array_bimodal_fleet,
                        array_longtail_fleet, array_uniform_fleet,
                        bimodal_fleet, fleet_arrays, longtail_fleet,
                        uniform_fleet)
from repro.fl import run_hier_simulation
from repro.fl.simulation import _history_buffer, _history_push
from repro.hier import (CommLedger, HierConfig, StackedTopology,
                        stacked_two_tier, two_tier_topology)
from repro.hier.topology import TopoNode
from repro.models import get_model
from repro.models.config import ArchConfig
from repro.models.logistic import logistic_apply, logistic_loss
from repro.robust import ChurnSchedule, ChurnWave
from repro.robust.attacks import ByzantineGauss, assign_adversaries


# ---------------------------------------------------------------------------
# scheduler: batch dispatch vs per-device dispatch
# ---------------------------------------------------------------------------

def _drain(sched):
    while sched.pop() is not None:
        pass


def _trace_pair(fleet, rng_stream, churn=None):
    """Same cohort through dispatch_batch vs N dispatch() calls."""
    ids = np.arange(fleet.num_devices)
    steps = 5 + (ids % 7)
    batch_sched = EventScheduler(fleet, seed=9, flops_per_step=1e7,
                                 payload_bytes=1e5, churn=churn,
                                 rng_stream=rng_stream)
    batch_sched.dispatch_batch(ids, steps, version=0)
    _drain(batch_sched)
    seq_sched = EventScheduler(fleet, seed=9, flops_per_step=1e7,
                               payload_bytes=1e5, churn=churn,
                               rng_stream=rng_stream)
    for d in ids:
        seq_sched.dispatch(int(d), int(steps[d]), version=0)
    _drain(seq_sched)
    return batch_sched.trace_signature(), seq_sched.trace_signature()


@pytest.mark.parametrize("rng_stream", ["v1", "v2"])
@pytest.mark.parametrize("kind", ["uniform", "bimodal"])
def test_batch_dispatch_matches_per_device(rng_stream, kind):
    fleet = (uniform_fleet(64, dropout=0.1, jitter=0.2) if kind == "uniform"
             else bimodal_fleet(64, slowdown=10.0, dropout_slow=0.1, seed=0))
    batch, seq = _trace_pair(fleet, rng_stream)
    assert batch == seq


@pytest.mark.parametrize("rng_stream", ["v1", "v2"])
def test_batch_dispatch_matches_under_churn(rng_stream):
    fleet = bimodal_fleet(64, slowdown=10.0, dropout_slow=0.1, seed=0)
    churn = ChurnSchedule(64, (ChurnWave(0.0, 1e9, 0.3, seed=4),))
    batch, seq = _trace_pair(fleet, rng_stream, churn=churn)
    assert batch == seq
    # the wave actually bites: some device must have dropped
    kinds = {t[2] for t in batch}
    assert 2 in kinds            # EventKind.DROPOUT


def test_cohort_mode_conservation():
    fleet = uniform_fleet(32, dropout=0.2, jitter=0.1)
    sched = EventScheduler(fleet, seed=3, flops_per_step=1e7,
                           payload_bytes=1e5, rng_stream="v2")
    batch = sched.dispatch_batch(np.arange(32), 6, version=0, enqueue=False)
    assert batch.size == 32
    assert sched.conservation_ok()          # in-flight via _batch_inflight
    sched.advance_to(float(batch.t_end.max()))
    sched.complete_batch(batch)
    assert sched.conservation_ok()
    assert sched.stats.arrived + sched.stats.dropped == 32
    with pytest.raises(RuntimeError):
        sched.complete_batch(batch)          # double settle


def test_v2_scalar_dispatch_is_batch_special_case():
    fleet = bimodal_fleet(16, seed=0)
    a = EventScheduler(fleet, seed=5, flops_per_step=1e7, payload_bytes=1e5,
                       rng_stream="v2")
    b = EventScheduler(fleet, seed=5, flops_per_step=1e7, payload_bytes=1e5,
                       rng_stream="v2")
    for d in range(16):
        a.dispatch(d, 4, version=0)
    b.dispatch_batch(np.arange(16), 4, version=0)
    _drain(a), _drain(b)
    assert a.trace_signature() == b.trace_signature()


# ---------------------------------------------------------------------------
# comm ledger: batched record_* equivalence
# ---------------------------------------------------------------------------

def test_ledger_count_batching_matches_loop():
    loop, batched = CommLedger(depth=2), CommLedger(depth=2)
    for _ in range(37):
        loop.record_down(0, 1234.0, seconds=0.5)
        loop.record_up(1, 99.0, seconds=0.25)
    batched.record_down(0, 1234.0, seconds=0.5, count=37)
    batched.record_up(1, 99.0, seconds=0.25, count=37)
    batched.record_up(1, 5.0, count=0)       # no-op
    assert loop.report() == batched.report()


# ---------------------------------------------------------------------------
# array fleets / stacked topology / virtual dataset
# ---------------------------------------------------------------------------

def test_array_fleets_match_object_fleets():
    pairs = [
        (uniform_fleet(48, dropout=0.1, jitter=0.2),
         array_uniform_fleet(48, dropout=0.1, jitter=0.2)),
        (bimodal_fleet(48, slowdown=10.0, dropout_slow=0.05, seed=3),
         array_bimodal_fleet(48, slowdown=10.0, dropout_slow=0.05, seed=3)),
        (longtail_fleet(48, seed=3), array_longtail_fleet(48, seed=3)),
    ]
    for obj, arr in pairs:
        oa, aa = fleet_arrays(obj), fleet_arrays(arr)
        for a, b in zip(oa, aa):
            np.testing.assert_array_equal(a, b)
        assert arr[5].flops == obj[5].flops    # per-device profile view


def test_stacked_topology_validation():
    fleet = array_uniform_fleet(16)
    topo = stacked_two_tier(fleet, 4)
    assert isinstance(topo, StackedTopology)
    assert topo.num_devices == 16 and topo.depth == 2
    assert len(topo.gateways) == 4
    assert sum(len(g.children) for g in topo.gateways) == 16
    # a gateway that misses a device must be rejected
    nodes = {}
    truncated = False
    for nid, n in topo.nodes.items():
        if n.tier == 1 and not truncated:
            nodes[nid] = TopoNode(n.node_id, n.tier, n.parent,
                                  np.asarray(n.children[:-1], np.int32),
                                  n.uplink)
            truncated = True
        else:
            nodes[nid] = n
    with pytest.raises(ValueError):
        StackedTopology(topo.name, fleet, nodes, topo.cloud_id)


def test_virtual_dataset_shards_and_eval_ids():
    ds = VirtualFleetDataset(num_devices=32, samples_per_device=8, dim=6,
                             num_classes=3, seed=7)
    ids = np.array([0, 5, 31])
    x, y, m = ds.materialize_arrays(ids)
    assert x.shape == (3, 8, 6) and y.shape == (3, 8)
    # jit-boundary shard == materialized shard, bit for bit
    x5, y5, _ = jax.vmap(ds.shard_fn())(np.array([5]))
    np.testing.assert_array_equal(np.asarray(x5[0]), x[1])
    np.testing.assert_array_equal(np.asarray(y5[0]), y[1])
    # held-out test ids never overlap training ids
    fed = ds.materialize()
    assert fed.x.shape == (32, 8, 6)
    assert ds.test_set()[0].shape[0] == ds.test_devices * 8
    # strided eval subsample: full coverage under the cap, capped above
    np.testing.assert_array_equal(eval_device_ids(10, 64), np.arange(10))
    sub = eval_device_ids(1000, 64)
    assert sub.size <= 64 and sub[0] == 0 and np.all(np.diff(sub) > 0)


def test_churn_offline_mask_matches_scalar():
    sched = ChurnSchedule(100, (ChurnWave(1.0, 2.0, 0.4, seed=2),
                                ChurnWave(1.5, 3.0, 0.3, seed=3)))
    ids = np.arange(100)
    for t in (0.5, 1.2, 1.7, 2.5, 3.5):
        mask = sched.offline_mask(ids, np.full(100, t))
        scalar = np.array([sched.offline(int(d), t) for d in ids])
        np.testing.assert_array_equal(mask, scalar)


# ---------------------------------------------------------------------------
# bounded history windows
# ---------------------------------------------------------------------------

def test_history_buffer_window():
    full = _history_buffer(True)
    assert isinstance(full, list)
    for i in range(10):
        _history_push(full, i, True)
    assert full == list(range(10))

    window = _history_buffer(3)
    assert isinstance(window, deque) and window.maxlen == 3
    for i in range(10):
        _history_push(window, i, 3)
    assert list(window) == [7, 8, 9]

    off = _history_buffer(False)
    _history_push(off, 1, False)
    assert list(off) == []


# ---------------------------------------------------------------------------
# end-to-end: cohort mode vs event mode, virtual vs materialized
# ---------------------------------------------------------------------------

def _hier_kw(rounds=3):
    return dict(num_rounds=rounds, selection_seed=42, eval_every=1,
                rng_stream="v2")


def _cfg(**kw):
    base = dict(aggregator="hier_contextual", lr=0.1, mu=0.0, batch_size=8,
                min_epochs=1, max_epochs=2)
    base.update(kw)
    return HierConfig(**base)


def _params(dim=10, classes=3):
    return get_model(ArchConfig(name="lr", family="logreg", input_dim=dim,
                                num_classes=classes)
                     ).init(jax.random.PRNGKey(0))


def _run_pair(attack=None, frac=0.0, churn=None):
    ds = VirtualFleetDataset(num_devices=64, samples_per_device=16, dim=10,
                             num_classes=3, seed=3)
    params = _params()
    obj_fleet = bimodal_fleet(64, slowdown=10.0, dropout_slow=0.05, seed=0)
    arr_fleet = array_bimodal_fleet(64, slowdown=10.0, dropout_slow=0.05,
                                    seed=0)
    if frac:
        obj_fleet = assign_adversaries(obj_fleet, frac, seed=5)
        arr_fleet = assign_adversaries(arr_fleet, frac, seed=5)
    kw = _hier_kw()
    ev = run_hier_simulation(
        "ev", logistic_loss, logistic_apply, params, ds.materialize(),
        _cfg(), two_tier_topology(obj_fleet, 4), scheduler_mode="event",
        attack=attack, churn=churn, **kw)
    co = run_hier_simulation(
        "co", logistic_loss, logistic_apply, params, ds,
        _cfg(), stacked_two_tier(arr_fleet, 4), scheduler_mode="cohort",
        attack=attack, churn=churn, **kw)
    return ev, co


def _assert_equivalent(ev, co, tol=1e-5):
    assert co.times == ev.times                  # virtual clock, exactly
    assert co.cloud_uplink_bytes == ev.cloud_uplink_bytes
    assert co.total_bytes == ev.total_bytes
    assert (co.arrived, co.dropped) == (ev.arrived, ev.dropped)
    assert max(abs(a - b) for a, b in
               zip(ev.train_loss, co.train_loss)) < tol
    assert max(abs(a - b) for a, b in zip(ev.test_acc, co.test_acc)) <= tol


def test_cohort_mode_matches_event_mode():
    _assert_equivalent(*_run_pair())


def test_cohort_mode_matches_under_attack_and_churn():
    churn = ChurnSchedule(64, (ChurnWave(0.0, 1e9, 0.2, seed=4),))
    ev, co = _run_pair(attack=ByzantineGauss(scale=10.0), frac=0.25,
                       churn=churn)
    _assert_equivalent(ev, co)
    assert ev.dropped > 0                        # the wave actually bit


def test_cohort_mode_rejects_device_uplink_compression():
    from repro.compress import CompressConfig
    ds = VirtualFleetDataset(num_devices=16, samples_per_device=16, dim=10,
                             num_classes=3, seed=3)
    topo = stacked_two_tier(array_uniform_fleet(16), 4)
    cfg = _cfg(aggregator="hier_contextual_sketch",
               compress=CompressConfig(scheme="signsketch", ratio=4,
                                       device_uplink=True))
    with pytest.raises(ValueError):
        run_hier_simulation("c", logistic_loss, logistic_apply, _params(),
                            ds, cfg, topo, scheduler_mode="cohort",
                            **_hier_kw(rounds=1))


def test_virtual_dataset_rejects_data_poisoning():
    from repro.robust.attacks import LabelFlip
    ds = VirtualFleetDataset(num_devices=16, samples_per_device=16, dim=10,
                             num_classes=3, seed=3)
    fleet = assign_adversaries(array_uniform_fleet(16), 0.25, seed=1)
    with pytest.raises(ValueError):
        run_hier_simulation("p", logistic_loss, logistic_apply, _params(),
                            ds, _cfg(), stacked_two_tier(fleet, 4),
                            attack=LabelFlip(), **_hier_kw(rounds=1))


def test_cohort_chunking_matches_unchunked():
    ds = VirtualFleetDataset(num_devices=48, samples_per_device=16, dim=10,
                             num_classes=3, seed=3)
    params = _params()
    topo = stacked_two_tier(array_uniform_fleet(48), 4)
    a = run_hier_simulation("a", logistic_loss, logistic_apply, params, ds,
                            _cfg(), topo, scheduler_mode="cohort",
                            **_hier_kw())
    b = run_hier_simulation("b", logistic_loss, logistic_apply, params, ds,
                            _cfg(), topo, scheduler_mode="cohort",
                            cohort_chunk=16, **_hier_kw())
    assert a.times == b.times
    assert max(abs(x - y) for x, y in
               zip(a.train_loss, b.train_loss)) < 1e-5


# ---------------------------------------------------------------------------
# device-axis sharding (multi-device CPU subprocess)
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.data.fleetgen import VirtualFleetDataset
    from repro.fl.simulation import _batched_virtual_update_fn
    from repro.models import get_model
    from repro.models.config import ArchConfig
    from repro.models.logistic import logistic_loss
    from repro.sharding.specs import fleet_mesh, stream_round_shardings

    assert jax.device_count() == 8
    ds = VirtualFleetDataset(num_devices=64, samples_per_device=16, dim=8,
                             num_classes=3, seed=3)
    params = get_model(ArchConfig(name="lr", family="logreg", input_dim=8,
                                  num_classes=3)).init(jax.random.PRNGKey(0))
    mesh = fleet_mesh()
    B = 20                                   # 20 % 8 != 0: exercises padding
    ids = jnp.arange(B)
    ns = jnp.full((B,), 4, jnp.int32)
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(7), i)
                    )(jnp.arange(B, dtype=jnp.uint32))
    plain = _batched_virtual_update_fn(logistic_loss, 4, 8, 0.1, 0.0, ds)
    shard = _batched_virtual_update_fn(logistic_loss, 4, 8, 0.1, 0.0, ds,
                                       mesh)
    o1, o2 = plain(params, ids, ns, keys), shard(params, ids, ns, keys)
    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in
               zip(jax.tree_util.tree_leaves(o1),
                   jax.tree_util.tree_leaves(o2)))
    sh = stream_round_shardings(mesh, {"m": jnp.zeros((16, 32)),
                                       "v": jnp.zeros((16,))})
    print(json.dumps({"diff": diff,
                      "m_spec": str(sh["m"].spec),
                      "v_spec": str(sh["v"].spec)}))
""")


def test_fleet_axis_shard_map_parity():
    # JAX_PLATFORMS=cpu pinned explicitly: a parent jax import exports
    # TPU_LIBRARY_PATH into os.environ, and a child that merely unsets
    # JAX_PLATFORMS hangs probing the TPU plugin on TPU-less hosts
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["diff"] < 1e-5
    assert result["m_spec"] == "PartitionSpec('fleet', None)"
    assert result["v_spec"] == "PartitionSpec('fleet',)"


_MESH_KERNEL_SCRIPT = textwrap.dedent("""
    import json
    import jax
    from repro.data.fleetgen import VirtualFleetDataset
    from repro.edge import array_bimodal_fleet
    from repro.fl import run_hier_simulation
    from repro.hier import HierConfig, stacked_two_tier
    from repro.kernels import registry
    from repro.models import get_model
    from repro.models.config import ArchConfig
    from repro.models.logistic import logistic_apply, logistic_loss
    from repro.obs import InMemoryTracker, use_tracker
    from repro.sharding.specs import fleet_mesh

    ds = VirtualFleetDataset(num_devices=64, samples_per_device=16, dim=8,
                             num_classes=3, seed=3)
    params = get_model(ArchConfig(name="lr", family="logreg", input_dim=8,
                                  num_classes=3)).init(jax.random.PRNGKey(0))
    cfg = HierConfig(aggregator="hier_contextual", lr=0.1, batch_size=8,
                     min_epochs=1, max_epochs=1)
    topo = stacked_two_tier(array_bimodal_fleet(64), 4)
    picks = {}
    # pallas everywhere the caller can reach; the sharded run must still
    # keep its round kernels off it
    with registry.force_backend("pallas"):
        for name, mesh in (("sharded", fleet_mesh()), ("single", None)):
            mem = InMemoryTracker()
            with use_tracker(mem):
                run_hier_simulation(
                    name, logistic_loss, logistic_apply, params, ds, cfg,
                    topo, num_rounds=1, eval_every=1,
                    scheduler_mode="cohort", rng_stream="v2", mesh=mesh)
            picks[name] = sorted({e.metrics["kernels/autotune/backend"]
                                  for e in mem.metrics_events()
                                  if "kernels/autotune/op" in e.metrics})
    print(json.dumps(picks))
""")


def test_multi_device_mesh_keeps_round_kernels_off_pallas():
    """Pallas TPU kernels cannot be partitioned over a mesh: a round whose
    cohort rows are sharded over several devices runs its kernel ops on
    xla, even where the caller forced pallas."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", _MESH_KERNEL_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr
    picks = json.loads(out.stdout.strip().splitlines()[-1])
    assert picks == {"sharded": ["xla"], "single": ["pallas"]}


def test_stream_round_shardings_backcompat_without_fleet_axis():
    from jax.sharding import Mesh
    from repro.sharding.specs import (stream_column_shardings,
                                      stream_round_shardings)
    import jax.numpy as jnp
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    stacked = {"m": jnp.zeros((4, 8)), "v": jnp.zeros((4,))}
    a = stream_column_shardings(mesh, stacked)
    b = stream_round_shardings(mesh, stacked)
    assert {k: s.spec for k, s in a.items()} == \
        {k: s.spec for k, s in b.items()}
