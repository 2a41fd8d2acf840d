"""GPipe pipeline parallelism: semantics vs sequential execution."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.sharding.pipeline import bubble_fraction, gpipe_apply

ROOT = Path(__file__).resolve().parent.parent

_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.sharding.pipeline import gpipe_apply

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("pipe", "data"))
rng = np.random.default_rng(0)
L, S, d = 8, 4, 16            # 8 layers over 4 stages
W = jnp.asarray(rng.normal(size=(L, d, d)).astype(np.float32) * 0.3)
x = jnp.asarray(rng.normal(size=(8, d)).astype(np.float32))

def layer(w, h):
    return jnp.tanh(h @ w)

# sequential reference
ref = x
for i in range(L):
    ref = layer(W[i], ref)

stage_params = W.reshape(4, 2, d, d)
with mesh:
    out = gpipe_apply(layer, stage_params, x, mesh=mesh, microbatches=4)
err = float(jnp.abs(out - ref).max())
assert err < 1e-5, err

# gradients flow through the pipeline
def loss_pp(Wf):
    return jnp.sum(gpipe_apply(layer, Wf.reshape(4, 2, d, d), x,
                               mesh=mesh, microbatches=4) ** 2)
def loss_seq(Wf):
    h = x
    for i in range(L):
        h = layer(Wf[i], h)
    return jnp.sum(h ** 2)
with mesh:
    g_pp = jax.grad(loss_pp)(W)
g_seq = jax.grad(loss_seq)(W)
gerr = float(jnp.abs(g_pp - g_seq).max() / (jnp.abs(g_seq).max() + 1e-9))
assert gerr < 1e-4, gerr
print("PIPELINE_OK", err, gerr)
"""


def test_bubble_fraction():
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(1, 8) == 0.0


def test_gpipe_single_stage_identity(rng):
    """stages=1 degenerates to a plain scan (runs on the real 1-CPU mesh)."""
    mesh = make_mesh((1,), ("pipe",))
    W = jnp.asarray(rng.normal(size=(4, 8, 8)).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))

    def layer(w, h):
        return jnp.tanh(h @ w)

    ref = x
    for i in range(4):
        ref = layer(W[i], ref)
    with mesh:
        out = gpipe_apply(layer, W.reshape(1, 4, 8, 8), x, mesh=mesh,
                          microbatches=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.slow
def test_gpipe_multistage_subprocess():
    """4-stage pipeline on 8 forced host devices: forward + grad parity."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c", _DRIVER], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PIPELINE_OK" in r.stdout
