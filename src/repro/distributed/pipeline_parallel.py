"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The production default for this framework is FSDP x TP (it dry-runs clean
at 512 chips), but >1T-param or cross-DCN deployments want PP on the slow
axis.  This module implements the schedule generically: stage-stacked
block params live on a ``stage`` mesh axis; microbatches stream through
with ppermute handoffs; the bubble is the standard (S-1)/(M+S-1).

The block function is user-supplied (h, block_params) -> h, so any of the
model families' scanned blocks can be pipelined without modification.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_apply(
    block_fn: Callable[[jax.Array, PyTree], jax.Array],
    stage_params: PyTree,  # leaves [S, ...] (stage-major)
    x: jax.Array,  # [M, mb, ...] microbatched input
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Run M microbatches through S pipeline stages.  Returns [M, mb, ...]."""
    s = mesh.shape[axis]
    m = x.shape[0]
    perm_fwd = [(i, (i + 1) % s) for i in range(s)]

    def stage_program(params, xs):
        # params arrive with a local size-1 stage dim — strip it
        params = jax.tree.map(lambda w: w[0], params)
        # xs: [M, mb, ...] — only stage 0 consumes real input
        idx = jax.lax.axis_index(axis)
        mb = xs.shape[1:]
        # mark carries stage-varying up front (shard_map vma typing)
        buf = jax.lax.pcast(jnp.zeros(mb, xs.dtype), (axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros((m,) + mb, xs.dtype), (axis,),
                             to="varying")

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any left)
            take = jnp.clip(t, 0, m - 1)
            buf = jnp.where(idx == 0, jnp.where(t < m, xs[take], buf), buf)
            # every stage computes its block
            buf = block_fn(buf, params)
            # last stage emits microbatch t - (s - 1)
            out_t = t - (s - 1)
            ot = jnp.clip(out_t, 0, m - 1)
            emit = (idx == s - 1) & (out_t >= 0) & (out_t < m)
            cur = jax.lax.dynamic_index_in_dim(outs, ot, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, buf, cur), ot, 0
            )
            # hand off to the next stage
            buf = jax.lax.ppermute(buf, axis, perm_fwd)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, m + s - 1, tick, (buf, outs))
        # deliver outputs from the last stage to everyone (results replicated)
        outs = jax.lax.psum(
            jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    pspecs = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        stage_program,
        mesh=mesh,
        in_specs=(pspecs, P()),
        out_specs=P(),
    )
    return fn(stage_params, x)
