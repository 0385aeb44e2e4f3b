"""The TPU bring-up guards that need no chip.

* Every kept Pallas kernel compiles for a TPU v5e at the widths the main
  path gives it: the TPU compiler is installed here and compiles for a
  described (not attached) ``v5e:2x2`` topology.  Interpret-mode tests
  cannot see a block that is not tile-aligned or a tile that overflows
  VMEM; this compiler refuses both.  The topology is described inside a
  module fixture, never at import: only one process may load the TPU
  library, and every test worker imports this file.
* ``chip_smoke.py``'s phases, run small on the CPU, and its device guard,
  which refuses the CPU.
"""
import functools
import importlib.util
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.combine import combine_pallas
from repro.kernels.decode_attn import flash_decode_pallas
from repro.kernels.gram import gram_block_pallas, gram_pallas
from repro.kernels.rng_sketch import rng_sketch_pallas
from repro.kernels.sketch import sketch_apply_pallas
from repro.kernels.stream import pallas_tile, stream_stats_pallas

ROOT = Path(__file__).resolve().parents[1]
N = 1 << 20                      # aggregation kernels: 2**20 columns
CHUNK = 1 << 18                  # the streamed engine's column chunk


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# (kernel, static kwargs, argument shapes/dtypes) at the main path's widths
KERNELS = {
    "gram": (gram_pallas, {}, [((16, N), jnp.float32), ((N,), jnp.float32)]),
    # the fused engine's cohort Grams at logreg width (K and n unaligned),
    # up to the largest cohort the op's supports() lets the kernel take
    "gram_cohort": (gram_pallas, {},
                    [((5, 610), jnp.float32), ((610,), jnp.float32)]),
    "gram_cohort_largest": (gram_pallas, {},
                            [((1440, 68), jnp.float32),
                             ((68,), jnp.float32)]),
    "gram_block": (gram_block_pallas, {},
                   [((16, N), jnp.float32), ((16, N), jnp.float32),
                    ((N,), jnp.float32)]),
    "sketch": (sketch_apply_pallas, {},
               [((16, N), jnp.float32), ((64, N), jnp.float32)]),
    "combine": (combine_pallas, {},
                [((N,), jnp.float32), ((16, N), jnp.float32),
                 ((16,), jnp.float32)]),
    "sign_sketch": (rng_sketch_pallas, {"m": 64},
                    [((16, N), jnp.float32), ((), jnp.uint32)]),
    # the streamed engine hands its chunk over; the kernel tiles it to VMEM
    "stream_stats": (stream_stats_pallas, {"block_n": CHUNK},
                     [((16, 1 << 22), jnp.bfloat16),
                      ((16, 1 << 22), jnp.bfloat16)]),
    # qwen3-14b decode: B=8 slots, KV=8, G=5, hd=128, S=2048, bf16
    "flash_decode": (flash_decode_pallas, {"block_s": 512},
                     [((8, 8, 5, 128), jnp.bfloat16),
                      ((8, 2048, 8, 128), jnp.bfloat16),
                      ((8, 2048, 8, 128), jnp.bfloat16),
                      ((8,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernel_compiles_for_v5e(name, one_chip):
    fn, kw, shapes = KERNELS[name]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False, **kw)).lower(
        *specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gram_supports_rejects_cohorts_beyond_vmem():
    from repro.kernels.ops import _gram_pallas_ok
    g = jax.ShapeDtypeStruct((68,), jnp.float32)
    assert _gram_pallas_ok(jax.ShapeDtypeStruct((1440, 68), jnp.float32), g)
    assert not _gram_pallas_ok(jax.ShapeDtypeStruct((2000, 68), jnp.float32),
                               g)


def test_stream_stats_tile_is_decoupled_from_chunk():
    """The engine's 2**18-column chunk overflows VMEM as one bf16 block at
    P=16; the kernel's tile is capped below it and shrinks for wider rows."""
    assert pallas_tile(16, jnp.bfloat16, CHUNK) == 1 << 16
    assert pallas_tile(16, jnp.float32, CHUNK) == 1 << 15
    assert pallas_tile(16, jnp.bfloat16, 2048) == 2048
    assert pallas_tile(1000, jnp.float32, CHUNK) == 512


def test_combine_reads_stacked_leaves_in_their_layout_on_v5e(one_chip):
    """The streamed combine (``jit_apply_mix``) at OLMoE widths with two
    experts: on the TPU's tiled layout no whole-leaf ``copy`` (a relayout)
    or ``convert`` (an f32 copy of the bf16 updates) runs outside a fusion,
    and the program holds no scratch the size of a stacked leaf."""
    from repro.hier import streamed
    P, shapes = 16, {"experts": (2, 2048, 1024), "attn": (2048, 2048),
                     "norm": (2048,)}
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for k, s in shapes.items()}
    stacked = {k: jax.ShapeDtypeStruct((P,) + s, jnp.bfloat16,
                                       sharding=one_chip)
               for k, s in shapes.items()}
    w = jax.ShapeDtypeStruct((P,), jnp.float32, sharding=one_chip)
    compiled = streamed._apply_fn(False).lower(params, stacked, w).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert not re.findall(r"= \S+ (?:copy|convert)\(", entry)
    leaf_bytes = P * 2 * 2048 * 1024 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes


# ------------------------------------------------------ chip_smoke on the CPU

@pytest.fixture(scope="module")
def chip_smoke():
    return _load_chip_smoke()


def test_chip_smoke_serve_phase_small(chip_smoke):
    from repro.configs import get_config
    cfg = get_config("qwen3-14b").with_overrides(
        num_layers=2, d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
        d_ff=256, vocab_size=512)
    out = chip_smoke.phase_serve(cfg, slots=4, requests=4, prompt_len=16,
                                 new_tokens=8)
    assert out["all_finite"] == 1.0
    assert out["max_trail_share"] <= chip_smoke.GREEDY_SLACK
    assert out["flash_decode_max_abs_err"] <= chip_smoke.DECODE_KERNEL_TOL
    assert out["steady_s"] > 0 and out["cold_s"] > 0


def test_chip_smoke_train_phase_small(chip_smoke):
    out = chip_smoke.phase_train(rounds=2)
    assert out["fused_losses"][-1] < out["fused_losses"][0]
    assert out["loss_gap_streamed_vs_fused"] < 1e-3


def test_chip_smoke_aggregate_phase_small(chip_smoke):
    out = chip_smoke.phase_aggregate(shape=(128, 256, 1), P=8, chunk=1 << 12)
    assert out["G_rel_err_vs_xla"] < 1e-4 and out["C_rel_err_vs_xla"] < 1e-4


def test_chip_smoke_fleet_phase_small(chip_smoke):
    out = chip_smoke.phase_fleet4(n_dev=64, rounds=2)
    assert out["loss_gap"] <= chip_smoke.FLEET4_LOSS_TOL
    assert out["devices"] == len(jax.devices())


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_device_guard_refuses_cpu(chip_smoke, chips, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(["--chips", str(chips)])
    assert exc.value.code != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "cpu" in out.err


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_script_fails_without_chip(alone, tmp_path):
    """Run as a script on a machine without a TPU: from the checkout, and
    alone in a directory without the rest of the repo.  Both exit non-zero
    and print no result."""
    script = ROOT / "chip_smoke.py"
    cwd = ROOT
    if alone:
        cwd = tmp_path
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
