"""Streaming telemetry: tracker protocol, jsonl stream, sim instrumentation.

Covers the observability layer end to end: unit behavior of the tracker
implementations (scoping, fan-out, per-scope monotone steps, jsonl round
trip), the bench trace → BENCH JSON derivation, and the live events the
three simulation loops emit — including ordering under the async/hier
virtual clock and the guarantee that instrumentation never perturbs
results.
"""
import io
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.edge import AsyncConfig, bimodal_fleet
from repro.fl import (ServerConfig, run_async_simulation, run_hier_simulation,
                      run_simulation)
from repro.hier import HierConfig, two_tier_topology
from repro.models.logistic import logistic_apply, logistic_loss
from repro.obs import (NOOP, CompositeTracker, InMemoryTracker, JsonlTracker,
                       NoopTracker, current_tracker, iter_trace, read_trace,
                       spans, use_tracker, use_virtual_clock)
from repro.obs.spans import span_fields, span_tags

import repro.edge.async_server  # noqa: F401  (registers async aggregators)
import repro.hier.hier_server  # noqa: F401  (registers hier aggregators)


# ---------------------------------------------------------------------------
# tracker protocol units
# ---------------------------------------------------------------------------

def test_default_tracker_is_inactive_noop():
    assert current_tracker() is NOOP
    assert not NOOP.active
    assert NOOP.scope("a").scope("b") is NOOP      # no per-scope allocation
    NOOP.log({"x": 1}, step=3)                      # all swallowed
    NOOP.log_summary({"x": 1})
    NOOP.jot(run="r")


def test_use_tracker_stacks_and_restores():
    t1, t2 = InMemoryTracker(), InMemoryTracker()
    with use_tracker(t1):
        assert current_tracker() is t1
        with use_tracker(t2):
            assert current_tracker() is t2
        assert current_tracker() is t1
        current_tracker().log({"a": 1})
    assert current_tracker() is NOOP
    assert t1.series("a") == [1]
    assert t2.events == []


def test_scope_prefixes_keys_and_threads_scope_path():
    tr = InMemoryTracker()
    tr.scope("hier").scope("gw3").log({"bytes": 7}, step=2)
    (e,) = tr.events
    assert e.metrics == {"hier/gw3/bytes": 7}
    assert e.scope == "hier/gw3"
    assert e.step == 2 and e.kind == "metrics" and e.t_wall > 0
    scoped = tr.scope("x")
    assert scoped.active == tr.active


def test_composite_fans_out_every_event():
    a, b = InMemoryTracker(), InMemoryTracker()
    comp = CompositeTracker([a, b])
    assert comp.active
    comp.scope("s").log({"v": 1}, step=0)
    comp.log_summary({"done": True})
    comp.jot(name="run")
    for t in (a, b):
        assert [e.kind for e in t.events] == ["metrics", "summary", "tags"]
        assert t.series("s/v") == [1]
    assert not CompositeTracker([NoopTracker()]).active


# ---------------------------------------------------------------------------
# jsonl stream
# ---------------------------------------------------------------------------

def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with use_tracker(JsonlTracker(path)) as tr:
        tr.scope("run").log({"loss": 0.5, "vec": np.arange(3)}, step=0)
        tr.scope("run").log({"loss": np.float32(0.25)}, step=1)
        tr.log_summary({"final": 0.25})
    events = read_trace(path)
    assert [e.kind for e in events] == ["metrics", "metrics", "summary"]
    assert events[0].metrics == {"run/loss": 0.5, "run/vec": [0, 1, 2]}
    assert events[1].metrics["run/loss"] == pytest.approx(0.25)
    assert events[0].scope == "run" and events[2].scope == ""
    # stepless events inherit their own scope's latest step (root is at 0)
    assert [e.step for e in events] == [0, 1, 0]
    assert read_trace(path, kind="summary")[0].metrics == {"final": 0.25}
    # every line is valid json with the stream fields (tailable live)
    for line in open(path):
        obj = json.loads(line)
        assert set(obj) == {"step", "t_wall", "kind", "scope", "metrics"}


def test_jsonl_step_monotone_per_scope():
    tr = JsonlTracker(io.StringIO())
    a, b = tr.scope("runA"), tr.scope("runB")
    a.log({"x": 1}, step=5)
    b.log({"x": 1}, step=0)         # independent scope restarts at 0: fine
    a.log({"x": 1}, step=5)         # repeat is fine
    with pytest.raises(ValueError, match="non-monotonic step"):
        a.log({"x": 1}, step=4)
    b.log({"x": 1}, step=1)         # runB unaffected by runA's violation


def test_jsonl_rejects_unserializable():
    tr = JsonlTracker(io.StringIO())
    with pytest.raises(TypeError, match="not JSON-serializable"):
        tr.log({"fn": lambda: None})


def test_jsonl_flush_every_batches_and_finish_flushes(tmp_path):
    path = str(tmp_path / "batched.jsonl")
    tr = JsonlTracker(path, flush_every=100)
    for i in range(3):
        tr.log({"x": i}, step=i)
    # nothing reached disk yet: flushes are batched
    assert open(path).read() == ""
    tr.finish()
    assert [e.metrics["x"] for e in read_trace(path)] == [0, 1, 2]
    with pytest.raises(ValueError, match="flush_every"):
        JsonlTracker(str(tmp_path / "bad.jsonl"), flush_every=0)


def test_use_tracker_finishes_jsonl_when_body_raises(tmp_path):
    path = str(tmp_path / "crash.jsonl")
    with pytest.raises(RuntimeError, match="boom"):
        with use_tracker(JsonlTracker(path, flush_every=1000)) as tr:
            tr.log({"x": 1}, step=0)
            raise RuntimeError("boom")
    # finish() ran on the way out: the pending tail reached disk
    assert [e.metrics["x"] for e in read_trace(path)] == [1]


def test_iter_trace_is_lazy_read_trace_materializes(tmp_path):
    path = str(tmp_path / "lazy.jsonl")
    with use_tracker(JsonlTracker(path)) as tr:
        tr.log({"x": 1}, step=0)
        tr.log_summary({"done": True})
    it = iter_trace(path)
    assert iter(it) is it                       # generator, not a list
    assert next(it).metrics == {"x": 1}
    assert [e.kind for e in read_trace(path)] == ["metrics", "summary"]
    assert len(read_trace(path, kind="summary")) == 1


# ---------------------------------------------------------------------------
# spans: dual-clock intervals through the tracker protocol
# ---------------------------------------------------------------------------

def test_span_nesting_paths_and_dual_clock():
    mem = InMemoryTracker()
    vt = [10.0]
    with use_tracker(mem, finish=False), use_virtual_clock(lambda: vt[0]):
        with spans.span("round", round=3):
            with spans.span("solve", K=4) as h:
                h.tags["extra"] = "yes"
                vt[0] = 12.5
    fields = [span_fields(e) for e in mem.span_events()]
    assert [f["path"] for f in fields] == ["round/solve", "round"]
    solve, rnd = fields
    assert solve["depth"] == 1 and rnd["depth"] == 0
    assert rnd["t0_virtual"] == 10.0
    assert rnd["dur_virtual_s"] == pytest.approx(2.5)
    assert solve["dur_wall_s"] >= 0
    assert span_tags(solve) == {"K": 4, "extra": "yes"}
    assert span_tags(rnd) == {"round": 3}


def test_span_error_path_closes_and_restores_depth():
    mem = InMemoryTracker()
    with use_tracker(mem, finish=False):
        with pytest.raises(RuntimeError):
            with spans.span("outer"):
                with spans.span("inner"):
                    raise RuntimeError("bang")
        # depth restored: a fresh span is top-level again
        with spans.span("after"):
            pass
    fields = [span_fields(e) for e in mem.span_events()]
    assert [f["path"] for f in fields] == ["outer/inner", "outer", "after"]
    assert fields[0]["error"] == "RuntimeError"
    assert fields[1]["error"] == "RuntimeError"
    assert "error" not in fields[2]
    assert fields[2]["depth"] == 0


def test_flat_spans_do_not_corrupt_nesting():
    mem = InMemoryTracker()
    with use_tracker(mem, finish=False):
        with spans.span("round"):
            h1 = spans.begin("task", t_virtual=1.0, device=7)
            h2 = spans.begin("task", t_virtual=2.0, device=8)
            with spans.span("solve"):       # nests under round, not task
                pass
            spans.end(h2, t_virtual=6.0, outcome="arrival")
            spans.end(h1, t_virtual=9.0, outcome="dropout")
    fields = [span_fields(e) for e in mem.span_events()]
    by_path = [f["path"] for f in fields]
    assert by_path == ["round/solve", "round/task", "round/task", "round"]
    tasks = [f for f in fields if f["name"] == "task"]
    assert all(f["flat"] for f in tasks)
    assert {f["outcome"] for f in tasks} == {"arrival", "dropout"}
    assert sorted(f["dur_virtual_s"] for f in tasks) == [4.0, 8.0]


def test_spans_are_free_on_the_noop_path():
    assert current_tracker() is NOOP
    # profiler off, no tracker: one shared no-op, nothing recorded
    assert spans.span("x") is spans.span("w", round=3)
    with spans.span("x") as h:
        assert h is None
    assert spans.begin("y") is None
    spans.end(None, outcome="ignored")          # no-op, no error
    spans.record_span("z", t0_virtual=0.0, dur_virtual_s=1.0)
    assert spans.current_path() == ""


def _profile(tmp_path, body):
    """Run ``body`` under the JAX profiler as the benchmark harness records
    (host tracer level 1) and return the host events named ``repro/...``
    as (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro/"):
                    a = int(e.start_ns)
                    out.append((e.name, a, a + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda ev: ev[1])


def test_span_reaches_the_profiler_trace_without_a_tracker(tmp_path):
    assert current_tracker() is NOOP

    def body():
        with spans.span("outer", P=16, src="delta") as h:
            assert h is None                    # no tracker handle
            with spans.span("inner", round=3):
                pass

    events = _profile(tmp_path, body)
    assert [e[0] for e in events] == ["repro/outer", "repro/inner"]
    (_, a0, b0, outer), (_, a1, b1, inner) = events
    assert outer == {"P": 16, "src": "delta"} and inner == {"round": 3}
    assert a0 <= a1 <= b1 <= b0                 # nested spans nest in time


def test_span_feeds_tracker_and_trace_at_once(tmp_path):
    mem = InMemoryTracker()

    def body():
        with use_tracker(mem, finish=False):
            with spans.span("solve", K=4):
                pass

    events = _profile(tmp_path, body)
    assert [(e[0], e[3]) for e in events] == [("repro/solve", {"K": 4})]
    (f,) = [span_fields(e) for e in mem.span_events()]
    assert f["path"] == "solve" and span_tags(f) == {"K": 4}


def test_compile_counter_and_its_trace_marker(tmp_path):
    from repro.obs import compiles
    x = jnp.ones((3,))                          # compiled before counting
    fn = jax.jit(lambda v: v * 2 + 1)
    before = compiles()
    fn(x)
    assert compiles() == before + 1
    fn(x)
    assert compiles() == before + 1             # cached: no compile

    events = _profile(tmp_path, lambda: jax.jit(lambda v: v - 3)(x))
    marks = [e for e in events if e[0] == "repro/compile"]
    # a marker, not an interval: it holds no work of its own
    assert marks and all(b - a < 1e6 for _, a, b, _ in marks)
    stats = [m[3] for m in marks]
    assert any(st["event"] == "/jax/core/compile/backend_compile_duration"
               and st["seconds"] >= 0 for st in stats)


def test_streamed_round_spans_share_a_round_id():
    from repro.core.solve import SolveConfig
    from repro.hier.streamed import StreamedRoundEngine
    P = 4
    key = jax.random.PRNGKey(0)
    D = {"w": jax.random.normal(key, (P, 3, 5)),
         "b": jax.random.normal(key, (P, 5))}
    GM = jax.tree_util.tree_map(lambda v: -0.5 * v, D)
    params = jax.tree_util.tree_map(lambda v: v[0], D)
    eng = StreamedRoundEngine(params, SolveConfig(beta=5.0, ridge=1e-6),
                              "contextual", chunk=8)

    def one_round():
        ctx = eng.begin_round(D, GM)
        sums = [ctx.gateway(c) for c in ([0, 1], [2, 3])]
        ghat = ctx.compose_grads([s["ghat"] for s in sums], [2.0, 2.0])
        merged = ctx.merge([s["u_bar"] for s in sums],
                           [s["ghat"] for s in sums], [2.0, 2.0])
        ctx.cloud_raw([0, 1, 2, 3], "raw")
        delta, _ = ctx.cloud_combo([s["u_bar"] for s in sums],
                                   [2.0, 2.0], ghat)
        assert merged["u_bar"].w.shape == (P,)
        return ctx.apply(params, delta)

    mem = InMemoryTracker()
    with use_tracker(mem, finish=False):
        one_round()
        one_round()
    fields = [span_fields(e) for e in mem.span_events()]
    table = {"stream_views", "stream_plan", "stream_accumulate",
             "stream_gateway", "stream_compose", "stream_merge",
             "stream_cloud_raw", "stream_cloud", "stream_apply"}
    assert table <= {f["name"] for f in fields}
    by_round = {}
    for f in fields:
        by_round.setdefault(f["round"], set()).add(f["name"])
    assert sorted(by_round) == [0, 1]
    assert by_round[0] == by_round[1] and table <= by_round[0]
    assert eng.rounds == 2


def test_record_span_emits_known_virtual_interval():
    mem = InMemoryTracker()
    with use_tracker(mem, finish=False):
        spans.record_span("link/up", t0_virtual=5.0, dur_virtual_s=0.25,
                          tier=2, bytes=1024.0)
    (f,) = [span_fields(e) for e in mem.span_events()]
    assert f["t0_virtual"] == 5.0 and f["dur_virtual_s"] == 0.25
    assert f["dur_wall_s"] == 0.0 and f["flat"]
    assert span_tags(f) == {"tier": 2, "bytes": 1024.0}


def test_span_reserved_keys_match_stdlib_mirror():
    sys.path.insert(0, "benchmarks")
    try:
        import bench_trace
    finally:
        sys.path.pop(0)
    assert tuple(bench_trace.SPAN_RESERVED) == tuple(spans.RESERVED_KEYS)


# ---------------------------------------------------------------------------
# bench trace → BENCH_*.json derivation
# ---------------------------------------------------------------------------

def test_publish_bench_derives_identical_json(tmp_path):
    sys.path.insert(0, "benchmarks")
    try:
        from bench_trace import derive_bench_json
        from common import publish_bench
    finally:
        sys.path.pop(0)
    results = {"benchmark": "toy", "rounds": 3,
               "records": [{"method": "a", "final_loss": 0.5},
                           {"method": "b", "final_loss": 0.25}],
               "acceptance": {"meets_target": True},
               "autotune": [{"op": "gram"}, {"op": "colsum"}]}
    path = str(tmp_path / "BENCH_toy.jsonl")
    with use_tracker(JsonlTracker(path)) as tr:
        # live telemetry interleaves with the published results
        tr.scope("sim").log({"train_loss": 1.0}, step=0)
        publish_bench(results)
    assert derive_bench_json(path) == results


# ---------------------------------------------------------------------------
# simulation instrumentation (shared tiny problem from conftest)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny(tiny_edge_problem):
    ds, params, _ = tiny_edge_problem
    return ds, params


def _sync(ds, params, **kw):
    cfg = ServerConfig(aggregator="contextual", num_devices=ds.num_devices,
                       clients_per_round=6, lr=0.2, batch_size=10,
                       min_epochs=1, max_epochs=4)
    base = dict(num_rounds=4, selection_seed=11, eval_every=2,
                collect_alpha=True)
    base.update(kw)
    return run_simulation("t", logistic_loss, logistic_apply, params, ds,
                          cfg, **base)


def _hier(ds, params, **kw):
    fleet = bimodal_fleet(ds.num_devices, slowdown=4.0, dropout_slow=0.2,
                          seed=0)
    topo = two_tier_topology(fleet, 3)
    cfg = HierConfig(aggregator="hier_contextual", lr=0.2, batch_size=10,
                     min_epochs=1, max_epochs=4)
    base = dict(num_rounds=4, selection_seed=11, eval_every=2)
    base.update(kw)
    return run_hier_simulation("t", logistic_loss, logistic_apply, params,
                               ds, cfg, topo, **base)


def test_sync_sim_streams_rounds_and_summary(tiny):
    ds, params = tiny
    mem = InMemoryTracker()
    with use_tracker(mem):
        r = _sync(ds, params)
    rounds = [e for e in mem.metrics_events() if "sync/t/round" in e.metrics]
    assert [e.metrics["sync/t/round"] for e in rounds] == [0, 1, 2, 3]
    assert [e.step for e in rounds] == [0, 1, 2, 3]
    assert mem.series("sync/t/alpha_mean")      # α stage weights streamed
    losses = mem.series("sync/t/train_loss")
    assert losses == pytest.approx(r.train_loss)
    (summary,) = [e for e in mem.events if e.kind == "summary"]
    assert summary.metrics["sync/t/final_train_loss"] == \
        pytest.approx(r.train_loss[-1])
    tags = [e for e in mem.events if e.kind == "tags"]
    assert tags and tags[0].metrics["sync/t/runtime"] == "sync"


def test_async_sim_event_order_under_virtual_clock(tiny):
    ds, params = tiny
    cfg = AsyncConfig(aggregator="contextual_async",
                      num_devices=ds.num_devices, buffer_size=3, lr=0.2,
                      batch_size=10, min_epochs=1, max_epochs=4)
    fleet = bimodal_fleet(ds.num_devices, slowdown=8.0, dropout_slow=0.2,
                          seed=0)
    mem = InMemoryTracker()
    with use_tracker(mem):
        r = run_async_simulation("t", logistic_loss, logistic_apply, params,
                                 ds, cfg, fleet, num_aggregations=6,
                                 selection_seed=11, eval_every=2)
    flushes = [e for e in mem.metrics_events()
               if "async/t/flush" in e.metrics]
    assert [e.metrics["async/t/flush"] for e in flushes] == [1, 2, 3, 4, 5, 6]
    tv = [e.metrics["async/t/t_virtual"] for e in flushes]
    assert all(b >= a for a, b in zip(tv, tv[1:]))   # virtual clock monotone
    assert all(e.metrics["async/t/staleness_mean"] >= 0 for e in flushes)
    (summary,) = [e for e in mem.events if e.kind == "summary"]
    assert summary.metrics["async/t/dispatched"] == r.dispatched
    assert summary.metrics["async/t/t_virtual_end"] >= tv[-1]


def test_hier_sim_streams_comm_ledger_and_engine(tiny):
    ds, params = tiny
    mem = InMemoryTracker()
    with use_tracker(mem):
        r = _hier(ds, params)
    rounds = [e for e in mem.metrics_events() if "hier/t/round" in e.metrics]
    assert [e.metrics["hier/t/round"] for e in rounds] == [0, 1, 2, 3]
    tv = [e.metrics["hier/t/t_virtual"] for e in rounds]
    assert all(b >= a for a, b in zip(tv, tv[1:]))
    # CommLedger transfers streamed as recorded, virtual-clock stamped and
    # ordered within the round structure
    comm = [e for e in mem.metrics_events()
            if "hier/t/comm/bytes" in e.metrics]
    assert comm
    ctv = [e.metrics["hier/t/comm/t_virtual"] for e in comm]
    assert all(b >= a for a, b in zip(ctv, ctv[1:]))
    assert sum(e.metrics["hier/t/comm/bytes"] for e in comm) == \
        pytest.approx(r.total_bytes)
    assert {e.metrics["hier/t/comm/tier"] for e in comm} <= {0, 1, 2}
    # fused engine stage builds announced on cache miss
    (summary,) = [e for e in mem.events if e.kind == "summary"]
    assert summary.metrics["hier/t/engine_name"] == "fused"
    assert summary.metrics["hier/t/cloud_uplink_bytes"] == \
        pytest.approx(r.cloud_uplink_bytes)


def test_hier_sim_emits_nested_and_flat_spans(tiny):
    ds, params = tiny
    mem = InMemoryTracker()
    with use_tracker(mem):
        r = _hier(ds, params)
    fields = [span_fields(e) for e in mem.span_events()]
    paths = {f["path"] for f in fields}
    # the whole round path shows up, nested
    assert {"round", "round/client_update", "round/begin_round",
            "round/event_loop"} <= paths
    assert any(p.startswith("round/event_loop/gateway") for p in paths)
    assert any(p.startswith("round/event_loop/cloud") for p in paths)
    # round spans carry both clocks; virtual duration matches the scheduler
    rounds = [f for f in fields if f["path"] == "round"]
    assert len(rounds) == 4
    assert [f["round"] for f in rounds] == [0, 1, 2, 3]
    assert all(f["dur_virtual_s"] > 0 and f["dur_wall_s"] > 0
               for f in rounds)
    assert sum(f["dur_virtual_s"] for f in rounds) == \
        pytest.approx(r.times[-1])
    # engine stages trace under their tier node (the compile-vs-steady
    # naming itself is unit-tested below — this process's stage cache may
    # already be warm from earlier tests)
    names = {f["name"] for f in fields}
    assert any(n.startswith("stage_") for n in names)
    # scheduler task lifetimes: flat, virtual-stamped, outcome-tagged
    tasks = [f for f in fields if f["name"] == "sched/task"]
    assert len(tasks) == r.dispatched
    assert all(f["flat"] and f["t0_virtual"] >= 0 for f in tasks)
    outcomes = {f["outcome"] for f in tasks}
    assert outcomes <= {"arrival", "dropout"} and "arrival" in outcomes
    # link transfers land as virtual-time spans with byte tags
    links = [f for f in fields if f["name"].startswith("link/")]
    assert links and all(f["dur_virtual_s"] > 0 and f["dur_wall_s"] == 0.0
                         for f in links)
    assert {f["name"] for f in links} == {"link/up", "link/down"}


def test_async_sim_emits_spans_under_virtual_clock(tiny):
    ds, params = tiny
    cfg = AsyncConfig(aggregator="contextual_async",
                      num_devices=ds.num_devices, buffer_size=3, lr=0.2,
                      batch_size=10, min_epochs=1, max_epochs=4)
    fleet = bimodal_fleet(ds.num_devices, slowdown=8.0, dropout_slow=0.2,
                          seed=0)
    mem = InMemoryTracker()
    with use_tracker(mem):
        run_async_simulation("t", logistic_loss, logistic_apply, params,
                             ds, cfg, fleet, num_aggregations=4,
                             selection_seed=11, eval_every=2)
    fields = [span_fields(e) for e in mem.span_events()]
    aggs = [f for f in fields if f["name"] == "aggregate"]
    assert [f["flush"] for f in aggs] == [1, 2, 3, 4]
    tv = [f["t0_virtual"] for f in aggs]
    assert all(b >= a for a, b in zip(tv, tv[1:]))
    assert all(f["name"] in ("client_update", "aggregate", "eval",
                             "sched/task") for f in fields)
    assert any(f["name"] == "client_update" and "staleness" in f
               for f in fields)


def test_traced_stage_splits_compile_from_steady_state():
    from repro.hier.fused import _traced_stage
    calls = []
    stage = _traced_stage("summary", K=4, n=100, backend="xla",
                          stage=lambda v: calls.append(v) or v * 2)
    mem = InMemoryTracker()
    with use_tracker(mem, finish=False):
        assert stage(1) == 2 and stage(2) == 4 and stage(3) == 6
    names = [span_fields(e)["name"] for e in mem.span_events()]
    assert names == ["stage_summary_compile", "stage_summary",
                     "stage_summary"]
    assert calls == [1, 2, 3]
    # an untracked first call still consumes the compile slot silently
    stage2 = _traced_stage("cloud", K=2, n=10, backend="xla",
                           stage=lambda v: v)
    stage2(0)                                   # no tracker: no span, no cost
    with use_tracker(mem, finish=False):
        stage2(0)
    assert span_fields(mem.span_events()[-1])["name"] == "stage_cloud"


def test_instrumentation_does_not_perturb_results(tiny):
    """Same seeds with and without a live tracker → identical trajectories
    (the telemetry layer only observes)."""
    ds, params = tiny
    r_silent = _hier(ds, params)
    with use_tracker(InMemoryTracker()):
        r_traced = _hier(ds, params)
    assert r_traced.train_loss == r_silent.train_loss
    assert r_traced.times == r_silent.times
    assert r_traced.total_bytes == r_silent.total_bytes


def test_record_history_caps_alpha_history(tiny):
    ds, params = tiny
    full = _sync(ds, params)
    assert len(full.alpha_history) == 4            # True: unbounded (default)
    capped = _sync(ds, params, record_history=2)
    assert len(capped.alpha_history) == 2          # rolling last-2 window
    np.testing.assert_allclose(capped.alpha_history[-1],
                               full.alpha_history[-1])
    off = _sync(ds, params, record_history=False)
    assert off.alpha_history == []
    assert off.train_loss == full.train_loss       # knob only affects history


def test_record_history_caps_gamma_history(tiny):
    ds, params = tiny
    capped = _hier(ds, params, collect_gamma=True, record_history=1)
    assert len(capped.gamma_history) == 1
    off = _hier(ds, params, collect_gamma=True, record_history=0)
    assert off.gamma_history == []
