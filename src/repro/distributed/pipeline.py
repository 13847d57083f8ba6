"""Pipeline parallelism (GPipe-style) over a mesh "pipe" axis.

Implemented with shard_map + collective_permute: each device holds one
stage's params; microbatches stream through the ring with a `lax.scan` over
(num_micro + num_stages - 1) ticks.  Bubble fraction = (S-1)/(M+S-1).

This is the optional PP dimension (DESIGN.md §6) — the default production
mesh is (data, model); PP composes for >2-axis deployments and is validated
by tests/test_pipeline.py on a host-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS
from jax import shard_map


def pipeline_forward(stage_fn, stage_params, x_micro, *, mesh: Mesh,
                     axis: str = "pipe"):
    """Run microbatches through a ring of pipeline stages.

    stage_fn(params, x) -> x        — one stage's computation
    stage_params: pytree whose leaves have leading dim = num_stages
    x_micro: (num_micro, micro_batch, ...) input microbatches
    Returns (num_micro, micro_batch, ...) outputs (after the last stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def body(params_local, xm):
        # shard_map leaves a leading stage dim of 1 on the params — strip it
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        rank = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if any) — others take the ring input
            inject = jnp.where(t < n_micro, jnp.minimum(t, n_micro - 1), 0)
            x_in = jnp.where(rank == 0, xm[inject], buf)
            y = stage_fn(params_local, x_in)
            # pass activation to the next stage
            buf = jax.lax.ppermute(
                y, axis,
                perm=[(j, (j + 1) % n_stages) for j in range(n_stages)])
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(rank == n_stages - 1, out_idx >= 0)
            safe_idx = jnp.clip(out_idx, 0, n_micro - 1)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, safe_idx, 0),
                lambda o: o, outs)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (zero, outs), jnp.arange(ticks))
        # broadcast the last stage's outputs to every stage (replicated out)
        outs = jax.lax.psum(
            jnp.where(rank == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(PS(axis), PS()),       # params sharded by stage, x replicated
        out_specs=PS(),
        check_vma=False)
    return fn(stage_params, x_micro)
