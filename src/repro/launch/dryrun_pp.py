import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Pipeline-parallel dry-run: compile a GPipe'd dense stack on the 512-chip
mesh re-axed as (pipe=8, data=64) — the PP strategy proof of DESIGN.md §5.

    PYTHONPATH=src python -m repro.launch.dryrun_pp
"""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.analysis.hlo_cost import analyze
from repro.launch.mesh import make_mesh
from repro.sharding.pipeline import bubble_fraction, gpipe_apply


def main():
    mesh = make_mesh((8, 64), ("pipe", "data"))
    d, d_ff = 1024, 2816                 # qwen1.5-0.5b-scale dense layer
    L, stages = 24, 8
    B, S = 256, 512                      # microbatched 8x inside the pipe

    def layer(p, h):
        w1, w2 = p
        return h + jnp.tanh(h @ w1) @ w2

    params = (
        jax.ShapeDtypeStruct((stages, L // stages, d, d_ff), jnp.bfloat16),
        jax.ShapeDtypeStruct((stages, L // stages, d_ff, d), jnp.bfloat16),
    )
    x = jax.ShapeDtypeStruct((B, S, d), jnp.bfloat16)

    def step(params, x):
        def lf(p, h):
            return layer(p, h)
        return gpipe_apply(lf, params, x, mesh=mesh, microbatches=4,
                           batch_axis="data")

    t0 = time.time()
    with mesh:
        lowered = jax.jit(step).lower(params, x)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    rec = {
        "mesh": {"pipe": 8, "data": 64},
        "layers": L, "stages": stages, "microbatches": 4,
        "bubble_fraction": bubble_fraction(stages, 4),
        "compile_s": round(time.time() - t0, 1),
        "hlo_cost": analyze(hlo),
        "status": "ok",
    }
    n_perm = rec["hlo_cost"]["collectives"].get("collective-permute",
                                                {"count": 0})
    out = Path("results/dryrun/pp__dense24__pipe8.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    print(f"[dryrun_pp] ok compile={rec['compile_s']}s "
          f"bubble={rec['bubble_fraction']:.2f} "
          f"collective-permutes={n_perm['count']}")


if __name__ == "__main__":
    main()
