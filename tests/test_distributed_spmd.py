"""SPMD integration tests — run in a SUBPROCESS with 8 forced host devices
(the main test process must keep the default single device).

Marked ``slow``: each subprocess compiles a full sharded train step on an
emulated pod mesh (~8 min apiece on this CPU container — they dominated the
old ~26-min tier-1 wall-clock).  The default run skips them; CI's full
-coverage leg (and any local ``pytest -m ""``) still runs everything."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_contextual_combine_matches_reference():
    """shard_map gram/solve/combine on a (2,2,2) pod mesh == local math."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.distributed import contextual_combine_sharded
        from repro.core import gram_and_cross, solve_alpha_simple

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        K, n, beta = 4, 64, 8.0
        key = jax.random.PRNGKey(0)
        U = jax.random.normal(key, (K, n), jnp.float32)
        g = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)

        def body(u, gs):
            comb, alpha = contextual_combine_sharded(u[0], gs, beta, 1e-6)
            return comb[None], alpha[None]

        comb, alpha = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("data", "model"), P("model")),
            out_specs=(P("data", "model"), P("data", None)),
            check_vma=False))(U, g)

        G, c = gram_and_cross(U, g)
        alpha_ref = solve_alpha_simple(G, c, beta, 1e-6)
        comb_ref = U.T @ alpha_ref

        ok_alpha = bool(np.allclose(np.asarray(alpha[0]), np.asarray(alpha_ref),
                                    rtol=1e-4, atol=1e-4))
        ok_comb = bool(np.allclose(np.asarray(comb[0]), np.asarray(comb_ref),
                                   rtol=1e-4, atol=1e-4))
        print(json.dumps({"ok_alpha": ok_alpha, "ok_comb": ok_comb}))
    """)
    res = _run_subprocess(code)
    assert res["ok_alpha"] and res["ok_comb"], res


def test_spmd_train_step_contextual_vs_singlehost():
    """The pjit FL train step on a (4,2) mesh computes the same new params
    as an equivalent single-device cohort loop (paper semantics preserved
    under sharding)."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch.shapes import InputShape
        from repro.launch.steps import build_train_step
        from repro.models import get_model

        cfg = get_config("qwen3-14b").reduced().with_overrides(
            num_layers=1, d_model=64, d_ff=128, vocab_size=128,
            num_heads=2, num_kv_heads=2, head_dim=32)
        bundle = get_model(cfg)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = InputShape("t", "train", 16, 8)
        step = build_train_step(cfg, mesh, shape, aggregator="contextual",
                                lr=0.05, remat=False)
        params = bundle.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128)
        with mesh:
            new_params, metrics = jax.jit(step)(params, {"tokens": tokens})

        # single-host reference: 4 cohorts of batch 2
        C = 4
        loss = lambda p, b: bundle.train_loss(p, b)[0]
        cb = tokens.reshape(C, 2, 16)
        grads = jax.vmap(lambda b: jax.grad(loss)(params, {"tokens": b}))(cb)
        deltas = jax.tree_util.tree_map(lambda g: -0.05 * g, grads)
        flat = [l.reshape(C, -1) for p, l in
                jax.tree_util.tree_flatten_with_path(deltas)[0]
                if "lm_head" in str(p) or "final_norm" in str(p)]
        U = jnp.concatenate(flat, axis=1).astype(jnp.float32)
        gvec = -jnp.mean(U, 0) / 0.05
        from repro.core import solve_alpha_simple
        alpha = solve_alpha_simple(U @ U.T, U @ gvec, 1.0 / 0.05, 1e-6)
        ref = jax.tree_util.tree_map(
            lambda p, u: p + jnp.einsum("k,k...->...", alpha, u), params, deltas)

        errs = [float(np.max(np.abs(np.asarray(a, np.float32) -
                                    np.asarray(b, np.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(new_params),
                                jax.tree_util.tree_leaves(ref))]
        ok_alpha = bool(np.allclose(np.asarray(metrics["alpha"]),
                                    np.asarray(alpha), rtol=1e-3, atol=1e-4))
        print(json.dumps({"max_err": max(errs), "ok_alpha": ok_alpha}))
    """)
    res = _run_subprocess(code)
    assert res["ok_alpha"], res
    assert res["max_err"] < 5e-4, res


def test_dryrun_entrypoint_one_combo():
    """The dry-run CLI itself (512 devices, 16×16 mesh) works end to end."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "rwkv6-1.6b",
         "--shape", "decode_32k", "--mesh", "single"],
        env=env, capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[ok  ] rwkv6-1.6b|decode_32k|single" in out.stdout
