"""Pipeline-parallel schedule tests (multi-device via subprocess)."""

import subprocess
import sys

from repro.training.pipeline import bubble_fraction

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.training.pipeline import make_pipeline_forward

mesh = make_mesh((4,), ("pipe",))
S, n_micro, d = 4, 6, 8

# stage s applies y = x @ W_s (W stacked over stages)
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (S, d, d)) / np.sqrt(d)

def stage_fn(w_local, x, sid):
    return x @ w_local[0]

f = make_pipeline_forward(stage_fn, mesh, n_micro=n_micro, axis="pipe")
xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, 2, d))
out = f(W, xs)

ref = xs
for s in range(S):
    ref = jnp.einsum("mbd,de->mbe", ref, W[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                           rtol=1e-4)
print("PIPELINE_OK")
"""


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 12) - 3 / 15) < 1e-12
    assert bubble_fraction(8, 8) == 7 / 15


def test_pipeline_forward_matches_sequential():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
