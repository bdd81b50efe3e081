"""GPipe-style pipeline parallelism over a mesh axis.

`pipeline_apply` runs `stage_fn` across S stages (devices along the
"stage" axis) on M microbatches with the classic (M + S − 1)-tick
schedule: on every tick each stage processes the microbatch it holds and
`ppermute`s its activations to the next stage — compute and the
stage-to-stage transfer overlap across ticks, which is the
distributed-optimization trick PP brings (bubble fraction (S−1)/(M+S−1)).

Each device holds only its own stage's parameters (the stacked stage
params are sharded over the axis), so PP composes with DP/TP on the other
mesh axes. The dry-run meshes use DP×TP; PP is exercised by
tests/test_pipeline.py and examples/pipeline_mlp.py, and is available to
the launcher via --pipeline-stages.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P
from jax import shard_map


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   n_microbatches: int, axis: str = "stage"):
    """Run microbatched pipeline-parallel forward.

    stage_fn(params_for_stage, x_micro) -> y_micro (same shape).
    stage_params: pytree with leading axis = n_stages.
    x: (global_batch, ...) — split into n_microbatches on axis 0.
    Returns y with x's shape.
    """
    n_stages = mesh.shape[axis]
    gb = x.shape[0]
    assert gb % n_microbatches == 0
    mb = gb // n_microbatches
    xs = x.reshape((n_microbatches, mb) + x.shape[1:])

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)
    def run(params_s, xs_rep):
        my_params = jax.tree.map(lambda a: a[0], params_s)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_microbatches + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        buf = jnp.zeros((mb,) + xs_rep.shape[2:], xs_rep.dtype)
        outs = jnp.zeros_like(xs_rep)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if available)
            feed = xs_rep[jnp.clip(t, 0, n_microbatches - 1)]
            buf = jnp.where(stage == 0,
                            jnp.where(t < n_microbatches, feed, buf), buf)
            y = stage_fn(my_params, buf)
            # last stage retires microbatch t-(S-1)
            done_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (done_idx >= 0)
            outs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (jnp.maximum(done_idx, 0), 0)
                    + (0,) * (y.ndim - 1)),
                lambda o: o, outs)
            # hand activations to the next stage
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # only the last stage wrote non-zeros; psum broadcasts its results
        outs = jax.lax.psum(outs, axis)
        return outs

    ys = run(stage_params, xs)
    return ys.reshape((gb,) + x.shape[1:])
