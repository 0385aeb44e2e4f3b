"""Record ``v5e_round_spans.xplane.pb``: a few streamed rounds at a small
width, traced as the harness traces the fedround cell, for the tests of
``bench/program_spans.py`` and the readers built on it.

    python3 bench/tests/data/record_round_spans.py [--out-dir DIR]

Runs on one TPU chip.  Three of the fedround configuration's leaves at a
small width (a projection, a norm and the experts' up projection: hidden
256, 2 heads, 2 experts), P = 16 clients under 4 gateways;
two warm-up rounds, then ``--rounds`` rounds inside ``bench/round`` spans,
then one fresh compile inside the slice, so that the trace holds a
``repro/compile`` marker.  Writes the whole trace,
``v5e_round_spans.full.xplane.pb``, and ``v5e_round_spans.json`` (the
slice's host-clock length and the counters the readers use).

    python3 bench/tests/data/record_round_spans.py --strip FULL

cuts a whole trace down to what the readers read (no chip needed; reads
the proto with the ``xplane_pb2`` module that TensorFlow ships): the
device planes' ``XLA Ops`` and ``XLA Modules`` lines, each operation named
by its HLO name alone and each program run keeping only its ``run_id``,
and the host's ``repro/`` and ``bench/`` events with their stats and
``DoEnqueueProgram`` events with their ``run_id`` and ``device_ordinal``.
It writes ``v5e_round_spans.xplane.pb``, the file the tests read.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import harness  # noqa: E402

SMALL = {"hidden_size": 256, "num_attention_heads": 2,
         "num_key_value_heads": 2, "head_dim": 128,
         "intermediate_size": 128, "num_experts": 2, "router_outputs": 64}
LEAVES = ("attn/wq", "ln1", "moe/w_up")
P, GATEWAYS, CHUNK = 16, 4, 1 << 14


def _xplane_pb2():
    """TensorFlow's ``xplane_pb2``, loaded from its file alone (importing
    the ``tensorflow`` package would load all of TensorFlow)."""
    import importlib.util
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise SystemExit("--strip needs TensorFlow's xplane_pb2 module")
    path = (Path(spec.submodule_search_locations[0])
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def strip(src: Path, dst: Path) -> None:
    from bench.program_spans import ENQUEUE
    from bench.trace_reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE, op_name
    pb2 = _xplane_pb2()
    space = pb2.XSpace()
    space.ParseFromString(src.read_bytes())
    out = pb2.XSpace()
    for plane in space.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        names = {i: m.name for i, m in plane.event_metadata.items()}
        stat_names = {i: m.name for i, m in plane.stat_metadata.items()}
        kept = out.planes.add(id=plane.id, name=plane.name)
        used, used_stats = {}, set()
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [e for e in line.events if device or names.get(
                e.metadata_id, "").startswith(("repro/", "bench/", ENQUEUE))]
            if not events:
                continue
            new = kept.lines.add(id=line.id, display_id=line.display_id,
                                 name=line.name,
                                 timestamp_ns=line.timestamp_ns,
                                 duration_ps=line.duration_ps)
            for e in events:
                name = names.get(e.metadata_id, "")
                ne = new.events.add(metadata_id=e.metadata_id,
                                    offset_ps=e.offset_ps,
                                    duration_ps=e.duration_ps)
                # a span keeps all its stats (its tags), a run its ids
                wanted = ({"run_id"} if device else
                          {"run_id", "device_ordinal"} if name == ENQUEUE
                          else None)
                for st in e.stats:
                    if wanted is None or stat_names.get(
                            st.metadata_id) in wanted:
                        ne.stats.add().CopyFrom(st)
                        used_stats.add(st.metadata_id)
                        if st.HasField("ref_value"):
                            used_stats.add(st.ref_value)
                used[e.metadata_id] = (op_name(name) if line.name == OPS_LINE
                                       else name)
        for i, name in used.items():
            kept.event_metadata[i].id = i
            kept.event_metadata[i].name = name
        for i in used_stats:
            if i in plane.stat_metadata:
                kept.stat_metadata[i].CopyFrom(plane.stat_metadata[i])
    dst.write_bytes(out.SerializeToString())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out-dir", type=Path, default=HERE)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--strip", type=Path, default=None)
    args = ap.parse_args(argv)
    args.out_dir.mkdir(parents=True, exist_ok=True)
    if args.strip is not None:
        strip(args.strip, args.out_dir / "v5e_round_spans.xplane.pb")
        return 0

    import jax
    import jax.numpy as jnp
    from repro.core.solve import SolveConfig
    from repro.hier.streamed import StreamedRoundEngine

    devs = harness.require_tpu(1)
    drv = harness.load_driver("fedround")
    shapes = {k: v for k, v in drv.leaf_shapes(SMALL).items()
              if k in LEAVES}
    D, GM, params = drv.make_inputs(shapes, P, 7, 0.01)
    eng = StreamedRoundEngine(params, SolveConfig(beta=5.0, ridge=1e-6),
                              "contextual", chunk=CHUNK)
    groups = drv.cohorts(P, GATEWAYS)
    for _ in range(2):
        jax.block_until_ready(drv.round_once(eng, params, D, GM, groups,
                                             "float32")[2])
    x = jnp.ones((8, 128), jnp.float32)
    jax.block_until_ready(x)

    tmp = harness.OUT / "record_round_spans"
    shutil.rmtree(tmp, ignore_errors=True)
    tracer = harness.Tracer(True, tmp)
    tracer.start()
    for _ in range(args.rounds):
        with tracer.annotate("bench/round"):
            jax.block_until_ready(drv.round_once(eng, params, D, GM, groups,
                                                 "float32")[2])
    jax.block_until_ready(jax.jit(lambda v: v * 3.0 - 1.0)(x))
    time.sleep(0.001)
    tracer.stop()

    shutil.copy(tracer.xplane(),
                args.out_dir / "v5e_round_spans.full.xplane.pb")
    n = sum(int(v.size) for v in params.values())
    meta = {"window_s": tracer.window_s, "device_kind": devs[0].device_kind,
            "counters": {"traced_rounds": args.rounds, "P": P, "n": n,
                         "itemsize": 2}}
    (args.out_dir / "v5e_round_spans.json").write_text(
        json.dumps(meta, indent=1) + "\n")
    print(json.dumps(meta))
    return 0


if __name__ == "__main__":
    sys.exit(main())
