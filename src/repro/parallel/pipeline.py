"""Pipeline parallelism: GPipe-style microbatch pipeline over a 'stage'
mesh axis using shard_map + collective_permute.

The production mesh for this assignment is (data x model) — DP x TP — so PP
is provided as an optional composition for deployments that add a 'stage'
axis (e.g. (stage, data, model) across pod slices).  The schedule is the
classic GPipe flush: M microbatches flow through S stages in S + M - 1
ticks; bubble fraction (S - 1) / (S + M - 1).

``pipeline_apply`` is deliberately layer-agnostic: it pipelines any
``block_fn(params_stage, x) -> x`` where each stage holds its slice of the
stacked layer parameters.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(block_fn, params_stacked, x_microbatches, mesh: Mesh,
                   stage_axis: str = "stage"):
    """Run microbatches through pipeline stages.

    params_stacked: pytree with leading dim = n_stages (sharded over
    ``stage_axis``); x_microbatches: (M, mb, ...) microbatches (replicated).
    Returns (M, mb, ...) outputs.
    """
    s = mesh.shape[stage_axis]

    def staged(params_local, xs):
        # params_local: stage slice (1, ...); xs: (M, mb, d) replicated
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(stage_axis)
        m = xs.shape[0]
        ticks = s + m - 1

        def tick(carry, t):
            outputs, inflight = carry
            # which microbatch enters stage 0 at tick t
            mb_idx = jnp.clip(t, 0, m - 1)
            feed = jnp.where(t < m, xs[mb_idx], jnp.zeros_like(xs[0]))
            # stage receives from the previous stage (or the feed at stage 0)
            recv = jax.lax.ppermute(
                inflight, stage_axis,
                [(i, (i + 1) % s) for i in range(s)])
            x_in = jnp.where(stage == 0, feed, recv)
            active = (t - stage >= 0) & (t - stage < m)
            y = block_fn(params_local, x_in)
            y = jnp.where(active, y, x_in)
            # last stage writes its completed microbatch
            done_idx = t - (s - 1)
            is_done = (stage == s - 1) & (done_idx >= 0) & (done_idx < m)
            outputs = jax.lax.cond(
                is_done,
                lambda o: o.at[jnp.clip(done_idx, 0, m - 1)].set(y),
                lambda o: o, outputs)
            return (outputs, y), None

        outputs0 = jnp.zeros_like(xs)
        (outputs, _), _ = jax.lax.scan(
            tick, (outputs0, jnp.zeros_like(xs[0])), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them back
        outputs = jax.lax.psum(
            jnp.where(stage == s - 1, outputs, jnp.zeros_like(outputs)),
            stage_axis)
        return outputs

    in_specs = (jax.tree.map(lambda _: P(stage_axis), params_stacked),
                P())
    return jax.shard_map(staged, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(params_stacked, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_stages + n_microbatches - 1)
