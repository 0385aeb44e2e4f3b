"""The program's spans on the device trace's clock (``bench/program_spans.py``)
and the four readers built on them: on planes built by hand, where every
number is known, and on a small trace recorded on one TPU v5e (two
streamed rounds at a small width, ``data/record_round_spans.py``)."""
import json
import math
import shutil
from pathlib import Path

import pytest

from bench import harness, program_spans as S, trace_reduce
from bench.tests.test_bench_trace import Ev, Line, Plane

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "v5e_round_spans.xplane.pb"
FLASH = DATA / "v5e_flash_decode.xplane.pb"
CELL = "fedround.olmoe-1b-7b.p16"
READERS = ("combine_roofline", "flat_view_ms", "engine_dispatch_idle",
           "window_compiles")


def _compile(t, event=S.COMPILE_EVENT):
    return Ev(S.COMPILE, t, 0, [("event", event), ("seconds", 0.5)])


def _planes():
    """Ops [0,100) [300,400) [700,800) [1000,1100) on chip 0, inside one
    ``bench/round`` [0,1200); views [90,250) holding plan [150,200); a
    gateway [500,750) that a gap crosses; the slice is [0,1300)."""
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("%fusion.1 = f32[] fusion(...)", 0, 100),
        Ev("%fusion.2 = f32[] fusion(...)", 300, 100),
        Ev("%copy.3 = f32[] copy(...)", 700, 100),
        Ev("%fusion.1 = f32[] fusion(...)", 1000, 100)])])
    other_chip = Plane("/device:TPU:1", [Line("XLA Ops", [
        Ev("%x = ...", 0, 1300)])])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench/round", 0, 1200),
        Ev("repro/stream_views", 90, 160),
        Ev("repro/stream_plan", 150, 50),
        Ev("repro/stream_gateway", 500, 250),
        _compile(600), _compile(650, "/jax/compilation_cache/"
                                     "cache_retrieval_time_sec"),
        _compile(1250), _compile(5000)])])
    return [dev, other_chip, host]


def test_idle_split_by_innermost_span():
    spans = S.read_planes(_planes())
    assert spans.busy == [(0, 100), (300, 400), (700, 800), (1000, 1100)]
    assert spans.has_program_spans
    split = S.idle_split(spans, window_s=1300e-9)
    want = {"repro/stream_views": 100e-9, "repro/stream_plan": 50e-9,
            "bench/round": 450e-9, "repro/stream_gateway": 200e-9,
            S.UNTRACED: 100e-9}
    assert split.by_label == pytest.approx(want)
    # every idle nanosecond of the slice is put down somewhere
    assert split.percent() == pytest.approx(100.0 * 900 / 1300)
    assert split.percent("repro/stream_") == pytest.approx(
        100.0 * 350 / 1300)
    # the gap [400, 700) crosses the gateway's start: split at the edge
    lengths = [g[0] for g in split.gaps]
    assert lengths == pytest.approx([300e-9, 200e-9, 200e-9, 200e-9])
    (crossing,) = [p for length, p in split.gaps
                   if dict(p).get("repro/stream_gateway")]
    assert dict(crossing) == pytest.approx(
        {"repro/stream_gateway": 200e-9, "bench/round": 100e-9})


def test_device_times_move_onto_the_host_clock():
    """Two runs whose device starts lead their enqueues by 150 and 120 ns:
    the device's intervals move by the larger lead, so that no run starts
    before the host began to enqueue it."""
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_a(1)", 0, 100, [("run_id", 7)]),
                             Ev("jit_b(2)", 300, 100, [("run_id", 8)])]),
        Line("XLA Ops", [Ev("fusion.1", 0, 100), Ev("fusion.2", 300, 100)])])
    host = Plane("/host:CPU", [
        Line("python", [Ev("bench/round", 100, 500),
                        Ev("repro/stream_views", 120, 20)]),
        Line("main", [Ev(S.ENQUEUE, 150, 10, [("run_id", 7),
                                              ("device_ordinal", 0)]),
                      Ev(S.ENQUEUE, 420, 10, [("run_id", 8),
                                              ("device_ordinal", 0)]),
                      Ev(S.ENQUEUE, 10, 10, [("run_id", 9),
                                             ("device_ordinal", 1)])])])
    spans = S.read_planes([dev, host])
    assert spans.shift_ns == 150
    assert spans.busy == [(150, 250), (450, 550)]
    split = S.idle_split(spans, window_s=500e-9)      # slice [100, 600)
    assert split.by_label == pytest.approx({
        "bench/round": 280e-9, "repro/stream_views": 20e-9})


def test_compile_markers_count_inside_the_slice_only():
    spans = S.read_planes(_planes())
    # 600 and 1250 are compiles inside [0, 1300); 650 is the cache
    # retrieval inside the first, 5000 lies outside
    assert spans.compiles == [600, 1250, 5000]
    assert S.compiles_in_slice(spans, window_s=1300e-9) == 2


def test_a_trace_without_program_spans_reads_nothing():
    planes = [p for p in _planes() if not p.name.startswith("/host")]
    planes.append(Plane("/host:CPU", [Line("python", [
        Ev("bench/round", 0, 1200)])]))
    spans = S.read_planes(planes)
    assert not spans.has_program_spans
    split = S.idle_split(spans, window_s=1300e-9)
    assert split.by_label == pytest.approx({"bench/round": 800e-9,
                                            S.UNTRACED: 100e-9})


def _run(tmp_path, monkeypatch, xplane, window_s, counters, peaks):
    """The harness's view of a traced run whose profile is ``xplane``."""
    monkeypatch.setattr(S, "OUT", tmp_path)
    dst = tmp_path / f"{CELL}.seed1" / "plugins" / "profile" / "x"
    dst.mkdir(parents=True)
    shutil.copy(xplane, dst / "t.xplane.pb")
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    summary = trace_reduce.reduce_file(xplane, window_s)
    return harness.Run(cell, counters, summary, peaks, {})


def test_readers_on_the_recorded_v5e_rounds(tmp_path, monkeypatch, capsys):
    meta = json.loads((DATA / "v5e_round_spans.json").read_text())
    peaks = harness.peaks_for(meta["device_kind"])
    run = _run(tmp_path, monkeypatch, RECORDED, meta["window_s"],
               meta["counters"], peaks)
    values = {name: harness.load_metric(name)(run) for name in READERS}
    assert all(v is not None and math.isfinite(v) for v in values.values())
    assert 0 < values["combine_roofline"] <= 100
    assert values["flat_view_ms"] > 0
    assert 0 < values["engine_dispatch_idle"] < 100
    assert values["window_compiles"] == 1     # the recorder's fresh jit
    # the split accounts for all of device_idle.train
    idle = harness.load_metric("device_idle.train")(run)
    split = S.idle_split(S.for_run(run), meta["window_s"])
    assert split.percent() == pytest.approx(idle, abs=0.01)
    # the device plane of this recording reads 1.5 ms ahead of the host
    assert S.for_run(run).shift_ns > 1_000_000
    labels = set(split.by_label)
    assert {"repro/stream_views", "repro/stream_apply"} <= labels
    assert labels <= {"bench/round", S.UNTRACED} | {
        k for k in labels if k.startswith("repro/")}
    err = capsys.readouterr().err
    assert "idle split" in err and "longest 10 idle gaps" in err


def test_readers_read_nothing_without_the_programs(tmp_path, monkeypatch):
    """A trace of a program without the round's names and spans (here, a
    decode kernel): every new reader returns None and none raises."""
    run = _run(tmp_path, monkeypatch, FLASH, 0.01,
               {"traced_rounds": 3, "P": 16, "n": 1000, "itemsize": 2},
               harness.peaks_for("TPU v5 lite"))
    assert {name: harness.load_metric(name)(run) for name in READERS} == {
        name: None for name in READERS}
