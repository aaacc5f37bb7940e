"""Pipeline parallelism: shard_map schedule == sequential stage application.

Runs in a subprocess with 4 host placeholder devices so the main test process
keeps the single real CPU device (the dry-run owns the 512-device setting).
"""

import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from repro.parallel.pipeline_par import pipeline_forward

mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
S, D = 4, 16
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.normal(0, 0.5, (S, D, D)).astype(np.float32))

def stage_fn(w, x):
    return jnp.tanh(x @ w)

x = jnp.asarray(rng.normal(0, 1, (8, D)).astype(np.float32))
out = pipeline_forward(stage_fn, ws, x, mesh=mesh, n_microbatches=4)

ref = x
for s in range(S):
    ref = stage_fn(ws[s], ref)
err = float(jnp.abs(out - ref).max())
assert err < 1e-5, err
print("PIPELINE_OK", err)
"""


def test_pipeline_matches_sequential():
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300, env=env)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
