"""Hierarchical multi-pod contextual aggregation (DESIGN.md §3) on a
simulated 2x2x2 (pod, data, model) mesh of host devices.

Shows the two-stage combine: contextual aggregation of cohort updates
WITHIN each pod, then a second contextual combine ACROSS pods — the
collective schedule the 2x16x16 dry-run lowers at scale.

  python examples/multipod_hierarchical.py        # (sets its own XLA_FLAGS)
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.distributed import (contextual_combine_sharded,
                                    hierarchical_contextual_combine)


def main():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    n = 1024           # parameter slice per example
    beta = 10.0
    key = jax.random.PRNGKey(0)
    # 4 cohorts (2 pods x 2 data) each with an update; sharded over model
    g = jax.random.normal(key, (n,), jnp.float32)
    updates = -0.1 * (g[None, None, :] +
                      0.5 * jax.random.normal(jax.random.fold_in(key, 1),
                                              (2, 2, n)))

    @jax.jit
    def run(updates, g):
        def body(u_shard, g_shard):
            u = u_shard[0, 0]           # this cohort's slice
            gs = g_shard
            flat, alpha = contextual_combine_sharded(u, gs, beta,
                                                     data_axis="data",
                                                     model_axis="model")
            hier, a_intra, a_pods = hierarchical_contextual_combine(
                u, gs, beta)
            return (flat[None, None], hier[None, None],
                    alpha[None, None], a_pods[None, None])
        return jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=(P("pod", "data", "model"), P(None, None, "model")
                      if False else P("model")),
            out_specs=(P("pod", "data", "model"), P("pod", "data", "model"),
                       P("pod", "data", None), P("pod", "data", None)),
        )(updates, g)

    flat, hier, alpha, a_pods = run(updates, g)
    print("mesh:", dict(mesh.shape))
    print("intra-pod alpha (per pod):", np.asarray(alpha)[:, 0])
    print("cross-pod alpha:", np.asarray(a_pods)[0, 0])
    # both combines live in span(updates); hierarchical applies a second
    # contextual reweighting across pods
    print("flat combine norm:   ", float(jnp.linalg.norm(flat[0, 0])))
    print("hierarchical norm:   ", float(jnp.linalg.norm(hier[0, 0])))
    assert np.isfinite(np.asarray(hier)).all()
    print("ok: two-stage (pod -> cross-pod) contextual aggregation ran on a "
          "multi-pod mesh")


if __name__ == "__main__":
    main()
