"""Pipeline parallelism (GPipe schedule) via shard_map + collective_permute.

For the deep-narrow archs (granite-34b: 88 layers) a 'pipe' mesh axis can
replace part of the model axis. Implementation: layers are stacked and
sharded over 'pipe' (each rank holds n_layers/S contiguous stages);
microbatches stream through a lax.scan over M + S - 1 ticks; activations
hop stages with lax.ppermute. Reverse-mode autodiff of the scanned
schedule yields the standard GPipe backward (reverse hops) for free.

This is the forward/loss building block: `pipeline_forward` is exact —
tested equal to the sequential stack (value AND gradients) on a host mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def pipeline_forward(
    stage_params: Any,
    x_microbatches: jax.Array,  # (M, mb, ...) microbatched inputs
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    *,
    mesh: Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run x through S pipeline stages; returns (M, mb, ...) outputs.

    stage_params: pytree whose leaves have a leading dim == S (sharded over
    ``axis``); stage_fn(params_slice, x) -> y applies ONE stage.
    """
    s_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]
    ticks = m + s_stages - 1

    def per_rank(params_local, x_local):
        # params_local: leaves (1, ...) — this rank's stage
        params_one = jax.tree.map(lambda p: p[0], params_local)
        rank = jax.lax.axis_index(axis)
        mb_shape = x_local.shape[1:]
        buf_out = jnp.zeros((m,) + mb_shape, x_local.dtype)

        def tick(carry, t):
            held, buf = carry
            # stage 0 injects microbatch t (if in range); others use held
            inject = jnp.where(t < m, t, 0)
            x_in = jnp.where(rank == 0, x_local[inject], held)
            y = stage_fn(params_one, x_in)
            # pass to next stage; last stage's output is collected
            fwd = [(i, (i + 1) % s_stages) for i in range(s_stages)]
            passed = jax.lax.ppermute(y, axis, fwd)
            out_t = t - (s_stages - 1)
            write = jnp.where(out_t >= 0, out_t, 0)
            is_out = jnp.logical_and(rank == s_stages - 1, out_t >= 0)
            buf = jax.lax.cond(
                is_out,
                lambda b: jax.lax.dynamic_update_index_in_dim(b, y, write, 0),
                lambda b: b,
                buf,
            )
            return (passed, buf), None

        held0 = jnp.zeros(mb_shape, x_local.dtype)
        (_, buf_out), _ = jax.lax.scan(tick, (held0, buf_out), jnp.arange(ticks))
        # buf_out is zeros on every rank but the last (is_out guard), so a
        # psum over 'pipe' broadcasts the result to all ranks.
        return jax.lax.psum(buf_out, axis)

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(),  # microbatches replicated into every rank (stage 0 reads them)
    )
    fn = jax.shard_map(
        per_rank, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )
    return fn(stage_params, x_microbatches)


def sequential_reference(
    stage_params: Any,
    x_microbatches: jax.Array,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
) -> jax.Array:
    """Oracle: apply all stages in order to each microbatch."""
    s_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def one_mb(x):
        for i in range(s_stages):
            p_i = jax.tree.map(lambda p: p[i], stage_params)
            x = stage_fn(p_i, x)
        return x

    return jax.vmap(one_mb)(x_microbatches)
