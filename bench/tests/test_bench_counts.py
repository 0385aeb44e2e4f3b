"""The FLOP and byte counts behind every roofline and mfu share count no
more than the program's own work, so no share can pass 100%."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, harness
from bench.drivers import fedround as F

V5E = harness.load_json(harness.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]


def _cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    return c["flops"], c["bytes accessed"]


@pytest.mark.parametrize("P,n", [(8, 1024), (16, 4096)])
def test_stream_stats_counts_within_the_programs_work(P, n):
    from repro.kernels.stream import stream_stats_xla
    d = jnp.ones((P, n), jnp.bfloat16)
    flops, nbytes = counts.stream_stats_work(P, n, 2)
    # one column window: no loop, so the compiler counts every op
    c_flops, c_bytes = _cost(lambda a, b: stream_stats_xla(a, b, block_n=n),
                             d, d)
    assert flops <= c_flops * (1 + 1e-6)
    assert nbytes <= c_bytes


def test_round_counts_within_the_inputs():
    cfg = {"hidden_size": 256, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "head_dim": 64, "num_experts": 2, "router_outputs": 8}
    shapes = F.leaf_shapes(cfg)
    n = sum(int(np.prod(s)) for s in shapes.values())
    P = 16
    flops, nbytes = counts.round_work(P, n, 2)
    # D and GM read by the statistics pass, D again by the combine, the
    # f32 parameters read and written: what the round's arrays hold
    held = 2 * P * n * 2 + P * n * 2 + 2 * n * 4
    assert nbytes == held
    assert flops == 4 * P * P * n


def test_min_time_takes_the_larger_bound():
    t, which = counts.min_time_s(197e12, 1.0, V5E)
    assert which == "flops" and t == pytest.approx(1.0)
    t, which = counts.min_time_s(1.0, 819e9, V5E)
    assert which == "bytes" and t == pytest.approx(1.0)
