"""The trace reduction, on a small trace recorded on one TPU v5e (a jitted
matmul-reduce and the Pallas ``flash_decode`` kernel, three times each,
inside ``bench/round`` and ``bench/submit`` host spans), and on planes
built by hand where every number is known."""
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import pytest

from bench import trace_reduce as T

RECORDED = Path(__file__).parent / "data" / "v5e_flash_decode.xplane.pb"


def test_recorded_v5e_trace():
    s = T.reduce_file(RECORDED, window_s=0.01)
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    # one program per jitted function, named jit_<fn>(<fingerprint>)
    names = {k.split("(")[0] for k in s.module_s}
    assert names == {"jit__lambda", "jit_flash_decode_pallas"}
    kernel_mod = [k for k in s.module_s if "flash_decode" in k][0]
    assert any(op.startswith("flash_decode_pallas")
               for op in s.module_ops[kernel_mod])
    assert s.op_calls[[op for op in s.op_calls
                       if op.startswith("flash_decode_pallas")][0]] == 3
    assert 0 < s.ops_matching(r"flash_decode") <= s.modules_matching(
        r"flash_decode")
    # busy time is the union of the ops, never more than their sum
    assert s.busy_s <= sum(s.op_s.values()) + 1e-12
    labels = {label for label, _ in s.idle_gaps}
    assert labels & {"bench/round", "bench/submit"}
    bd = s.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: List = field(default_factory=list)


@dataclass
class Line:
    name: str
    events: List[Ev]


@dataclass
class Plane:
    name: str
    lines: List[Line]


def test_hand_built_planes():
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step(1)", 0, 100),
                             Ev("jit_step(1)", 300, 100)]),
        Line("XLA Ops", [Ev("%fusion.1 = f32[] fusion(...)", 0, 60),
                         Ev("%fusion.2 = f32[] fusion(...)", 40, 60),
                         Ev("%fusion.1 = f32[] fusion(...)", 300, 50),
                         Ev("%copy.3 = f32[] copy(...)", 350, 50)]),
        Line("Async XLA Ops", [Ev("%copy-start = ...", 100, 200)])])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench/engine.step", 90, 220), Ev("bench/submit", 150, 20)])])
    other = Plane("/device:TPU:0 SparseCore", [Line("XLA Ops", [
        Ev("%x = ...", 0, 10_000)])])
    s = T.reduce_planes([dev, host, other], window_s=1e-6)
    assert s.chips == 1
    assert s.busy_s == pytest.approx(200e-9)          # [0,100) + [300,400)
    assert s.op_s == pytest.approx({"fusion.1": 110e-9, "fusion.2": 60e-9,
                                    "copy.3": 50e-9})
    assert s.op_calls == {"fusion.1": 2, "fusion.2": 1, "copy.3": 1}
    assert s.module_s == pytest.approx({"jit_step(1)": 200e-9})
    assert s.module_ops == {"jit_step(1)": ["copy.3", "fusion.1",
                                            "fusion.2"]}
    # the one gap [100, 300): its middle (200) lies in engine.step only
    assert s.idle_gaps == [("bench/engine.step", pytest.approx(200e-9))]


def test_op_names():
    assert T.op_name("%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop") == \
        "fusion.3"
    assert T.op_name("flash_decode_pallas.9") == "flash_decode_pallas.9"
