"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because smoke tests run with 1 CPU
device while the dry-run forces 512 host devices via XLA_FLAGS.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod slice: 16×16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ('data', 'model') single-pod — FL cohorts live on 'data',
    tensor/expert parallelism on 'model'; multi-pod prepends 'pod'
    (hierarchical FL: contextual aggregation within a pod, second-stage
    combine across pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"production mesh needs {need} devices, have {len(devices)} — "
            "run through launch/dryrun.py (it forces 512 host devices)")
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist, as a 1×N ('data','model') mesh — used by CPU
    integration tests and the quickstart example."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
