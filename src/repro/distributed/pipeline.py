"""Pipeline parallelism: GPipe schedule via shard_map + collective_permute.

Stages hold contiguous layer groups (params stacked on a leading 'stage'
axis); microbatches ripple through the ring. Used for deployments deeper
than the DP x TP mesh handles (DESIGN.md §4); correctness-tested against
the unpipelined forward on a host mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _varying(x, axis: str):
    return jax.lax.pcast(x, (axis,), to="varying")


def pipeline_forward(stage_fn: Callable, stage_params, x_microbatches,
                     mesh: Mesh, axis: str = "stage"):
    """Run ``stage_fn(params_for_stage, x) -> x`` over a pipeline.

    stage_params: pytree with leading stage axis (sharded over ``axis``).
    x_microbatches: (n_micro, mb, ...) inputs (replicated).
    Returns (n_micro, mb, ...) outputs from the final stage (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]
    total = n_micro + n_stages - 1

    def per_device(params_local, xs):
        # params_local: stage slice (leading axis 1) ; xs: all microbatches
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        # mark buffers as stage-varying from the start (VMA-stable carry)
        buf = _varying(jnp.zeros(mb_shape, xs.dtype), axis)
        outs = _varying(jnp.zeros((n_micro,) + mb_shape, xs.dtype), axis)

        def step(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any); others use the ring input
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            inp = jnp.where(stage == 0, _varying(feed, axis), buf)
            out = stage_fn(params_local, inp)
            # final stage commits microbatch (t - n_stages + 1)
            commit = t - (n_stages - 1)
            do_commit = jnp.logical_and(stage == n_stages - 1, commit >= 0)
            idx = jnp.clip(commit, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(do_commit, out, cur), idx, axis=0)
            # ring-shift activations to the next stage
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs)

        buf, outs = jax.lax.fori_loop(0, total, step, (buf, outs))
        # replicate final-stage outputs to every device
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params,
                             is_leaf=lambda x: hasattr(x, "shape")), P())
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P())
    return fn(stage_params, x_microbatches)
