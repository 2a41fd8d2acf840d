"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Alternative to FSDPxTP for very deep models / cross-pod meshes: layers are
split into S contiguous stages along a mesh axis; microbatches stream
through stages with `jax.lax.ppermute` handing activations to the next
stage. The classic GPipe schedule executes S + M - 1 ticks (M microbatches),
bubble fraction (S-1)/(S+M-1).

`gpipe_apply` is deliberately generic: it takes ONE layer function and the
per-stage stacked parameters, so any scanned stack from
models/transformer.py (a Segment's repeats split across stages) can run
under it. Backward works through jax.grad (ppermute is differentiable).

This is the optional PP strategy of DESIGN.md §5; the dry-run proof lives in
tests/test_pipeline.py (subprocess with forced host devices) and can be
driven on the production mesh via launch/dryrun_pp.py.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_apply(layer_fn, stage_params, x, *, mesh, stage_axis: str = "pipe",
                microbatches: int = 4, batch_axis: str | None = None):
    """Run a stacked layer function as a pipeline over `stage_axis`.

    layer_fn(params_slice, x) -> x       one layer
    stage_params: pytree stacked as (n_stages, layers_per_stage, ...) and
        sharded dim0 over `stage_axis`.
    x: (batch, ...) global batch (microbatched internally).
    Returns y with x's shape.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = sizes[stage_axis]
    B = x.shape[0] // (sizes[batch_axis] if batch_axis else 1)   # local batch
    assert B % microbatches == 0
    mb = B // microbatches
    ticks = n_stages + microbatches - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_body(params, xs):
        # params: (1, layers_per_stage, ...) local slice; xs: full batch copy
        params = jax.tree.map(lambda p: p[0], params)
        sid = jax.lax.axis_index(stage_axis)

        def run_stage(h):
            def body(c, lp):
                return layer_fn(lp, c), None
            out, _ = jax.lax.scan(body, h, params)
            return out

        xs_mb = xs.reshape(microbatches, mb, *xs.shape[1:])
        buf = jnp.zeros((mb,) + xs.shape[1:], xs.dtype)   # inter-stage wire
        outs = jnp.zeros_like(xs_mb)

        def tick(carry, t):
            buf, outs = carry
            feed = jnp.clip(t, 0, microbatches - 1)
            # stage 0 consumes microbatch t from the input; others consume
            # the activation handed over by the previous stage
            h_in = jax.lax.cond(sid == 0, lambda: xs_mb[feed], lambda: buf)
            live = (t - sid >= 0) & (t - sid < microbatches)
            h_out = jax.lax.cond(live, run_stage, lambda h: h, h_in)
            # last stage records its finished microbatch
            done_idx = jnp.clip(t - (n_stages - 1), 0, microbatches - 1)
            record = live & (sid == n_stages - 1)
            outs = jax.lax.cond(
                record,
                lambda: outs.at[done_idx].set(h_out),
                lambda: outs)
            buf = jax.lax.ppermute(h_out, stage_axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them to all
        # stages so the result is replicated over the pipe axis
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs.reshape(xs.shape)

    x_spec = P(batch_axis, *([None] * (x.ndim - 1)))
    in_specs = (
        jax.tree.map(lambda _: P(stage_axis), stage_params),
        x_spec,
    )
    out_specs = x_spec
    fn = jax.shard_map(stage_body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return fn(stage_params, x)


def bubble_fraction(n_stages: int, microbatches: int) -> float:
    return (n_stages - 1) / (n_stages + microbatches - 1)
