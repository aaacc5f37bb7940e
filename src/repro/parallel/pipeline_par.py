"""Pipeline parallelism: GPipe-style microbatch schedule over a 'stage' mesh
axis with collective_permute handoffs.

shard_map over the stage axis: each device owns one pipeline stage's layer
block; microbatches stream through with a rotating buffer.  The schedule runs
S + M - 1 ticks (S stages, M microbatches); each tick every stage processes
the microbatch it holds and `ppermute`s activations to its successor, so the
steady state keeps all stages busy — the standard bubble fraction
(S-1)/(S+M-1) shrinks with M.

This is the feature path for depth-dominant models at >16-way sharding; the
production dry-run mesh keeps (pod, data, model) per the assignment, and PP
is exercised by tests/test_pipeline.py on a host-device mesh and selectable
via Layout in the autotuner.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, params_per_stage, x, *, mesh, n_microbatches,
                     stage_axis: str = "stage"):
    """Run x (B, ...) through `n_stages` stage_fns pipelined over microbatches.

    params_per_stage: pytree with leading stage axis, sharded over
    `stage_axis`.  x is split into n_microbatches along batch.
    """
    n_stages = mesh.shape[stage_axis]
    m = n_microbatches
    assert x.shape[0] % m == 0

    def per_stage(params, xs):
        # params: this stage's params (leading axis 1); xs: (M, mb, ...)
        params = jax.tree.map(lambda t: t[0], params)
        stage_id = jax.lax.axis_index(stage_axis)
        mb = xs.shape[1]
        # mark carries as stage-varying (shard_map vma typing): the loop body
        # writes stage-dependent values into them
        buf = jax.lax.pcast(jnp.zeros((mb,) + xs.shape[2:], xs.dtype),
                    (stage_axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), (stage_axis,), to="varying")

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (if in range); others use buf
            inject = jnp.where(t < m, t, m - 1)
            x_in = jnp.where(stage_id == 0, xs[inject], buf)
            y = stage_fn(params, x_in)
            # last stage records output for microbatch (t - (S-1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            record = (stage_id == n_stages - 1) & (t >= n_stages - 1)
            outs = jnp.where(record, outs.at[out_idx].set(y), outs)
            # hand activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_stages + m - 1, tick, (buf, outs))
        # every stage's `outs` is only valid on the last stage; broadcast it
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    shmapped = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(stage_axis), P(None)),
        out_specs=P(None),
    )
    xs = x.reshape(m, x.shape[0] // m, *x.shape[1:])
    out = shmapped(params_per_stage, xs)
    return out.reshape(x.shape)
