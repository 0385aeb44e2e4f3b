"""Inputs come from the seed alone: the same seed gives the same inputs,
another seed other values of the same sizes."""
import jax
import numpy as np

from bench import harness
from bench.drivers import fedround as F

BIG = 2 ** 31 + 2 ** 30 + 12345          # beyond 32 signed bits


def test_seed_ints_take_any_whole_number():
    a = harness.seed_ints(BIG, 3)
    assert a == harness.seed_ints(BIG, 3)
    assert a != harness.seed_ints(BIG + 1, 3)
    assert all(0 <= x < 2 ** 32 for x in a)
    assert harness.seed_ints(2 ** 40, 1) != harness.seed_ints(2 ** 40 + 1, 1)


def test_round_inputs_reproduce_from_the_seed():
    shapes = {"a": (4, 8), "b": (16,)}
    x = F.make_inputs(shapes, 4, 99, 0.01)
    y = F.make_inputs(shapes, 4, 99, 0.01)
    z = F.make_inputs(shapes, 4, 98, 0.01)
    leaves = jax.tree_util.tree_leaves
    assert all(np.array_equal(np.asarray(p), np.asarray(q))
               for p, q in zip(leaves(x), leaves(y)))
    assert not np.array_equal(np.asarray(x[0]["a"]), np.asarray(z[0]["a"]))
