"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference one precision step down, in the program's
place) fails the cell's limits.  Runs drive the real harness on the CPU at
a small size with the device guard off."""
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.drivers import fedround as F
from bench.run import measure

V5E = harness.load_json(harness.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]
FR = "fedround.olmoe-1b-7b.p16"
SEED = 2 ** 31 + 2 ** 30 + 77


def _fedround_cell():
    cell = harness.find_cell(harness.load_benchmark(), FR)
    cell.config = {**cell.config, "hidden_size": 128,
                   "intermediate_size": 64, "num_attention_heads": 4,
                   "num_key_value_heads": 4, "head_dim": 32,
                   "num_experts": 2, "router_outputs": 8}
    cell.traffic = {**cell.traffic, "chunk_cols": 4096, "trace_s": 0.2}
    return cell


def _run(cell, seconds=0.5):
    line = measure(cell, SEED, seconds, False, time.perf_counter(),
                   device_guard=False, peaks=V5E)
    return json.loads(line)


def test_fedround_sound_run_is_correct():
    out = _run(_fedround_cell())
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


def _unchanged(monkeypatch):
    from repro.hier import streamed
    monkeypatch.setattr(streamed.StreamedRoundContext, "apply",
                        lambda self, params, delta: params)


def _half_the_clients(monkeypatch):
    from repro.hier import streamed
    orig = streamed.StreamedRoundContext.compose_grads

    def half(self, refs, counts):
        refs, counts = list(refs), list(counts)
        k = len(refs) // 2
        return orig(self, refs[:k], counts[:k])
    monkeypatch.setattr(streamed.StreamedRoundContext, "compose_grads", half)


def _altered_statistics(monkeypatch):
    from repro.hier import streamed
    orig = streamed.StreamedRoundEngine.begin_round

    def altered(self, d, g):
        ctx = orig(self, d, g)
        ctx.G = ctx.G.at[0, 0].multiply(1.001)
        return ctx
    monkeypatch.setattr(streamed.StreamedRoundEngine, "begin_round", altered)


def _altered_weights(monkeypatch):
    from repro.hier import streamed
    orig = streamed.StreamedRoundContext.cloud_combo

    def altered(self, *a, **kw):
        mix, info = orig(self, *a, **kw)
        return streamed.RowMix(mix.w.at[0].multiply(1.001), mix.src), info
    monkeypatch.setattr(streamed.StreamedRoundContext, "cloud_combo",
                        altered)


@pytest.mark.parametrize("fault", [_unchanged, _half_the_clients,
                                   _altered_statistics, _altered_weights])
def test_fedround_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _run(_fedround_cell())
    assert not out["correct"], out["checks"]


def test_fedround_control_fails_the_limits(monkeypatch):
    """The control (the reference one precision step down) in the
    program's place, through the harness's own run and comparison."""
    cell = _fedround_cell()
    tr = cell.traffic

    def control(eng, params, D, GM, groups, precision):
        c = F.control_round(D, GM, params, groups, tr["beta"], tr["ridge"])
        return (SimpleNamespace(G=c["G"], C=c["C"]),
                SimpleNamespace(w=c["eff"]), c["params"], {})
    monkeypatch.setattr(F, "round_once", control)
    monkeypatch.setattr(harness, "load_driver", lambda kind: F)
    out = _run(cell)
    assert not out["correct"], out["checks"]
    assert set(out["checks"]) == set(cell.limits)


def test_bfloat16_solves_alone_fail_the_weights_limit():
    """The weights number separates on its own: float32 statistics with
    the solves one step down (bfloat16 operands) read above its limit."""
    from bench.reference import fedround as ref
    cell = _fedround_cell()
    tr = cell.traffic
    shapes = F.leaf_shapes(cell.config)
    D, GM, _ = F.make_inputs(shapes, 16, 5, float(tr["update_scale"]))
    names = sorted(D)
    G, C, _ = ref.statistics([D[k] for k in names], [GM[k] for k in names])
    groups = F.cohorts(16, 4)
    want = ref.solve_round(G, C, groups, tr["beta"], tr["ridge"])["eff"]
    got = ref.solve_round(G, C, groups, tr["beta"], tr["ridge"],
                          low=True)["eff"]
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err > cell.limits["weights_rel_err"], err
