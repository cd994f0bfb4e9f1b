"""GPipe-style pipeline parallelism building block (shard_map + ppermute).

An optional parallelism dimension for depth-dominated models at >512-chip
scale: stage s holds 1/S of the layer stack; microbatches stream through
stages with `jax.lax.ppermute` handoffs; the schedule runs M + S - 1
ticks (fill + drain bubble). Composes with the data/model axes (the
"pipe" axis is just another mesh axis).

Used by tests and available to launch/train.py via --pipeline-stages;
the default production mesh keeps pipeline off (FSDP+TP covers the
assigned shapes), so this module is a first-class but opt-in feature.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, n_stages: int, axis: str = "pipe"):
    """Build a per-device pipelined forward for shard_map.

    stage_fn(stage_params, x) -> x, applied by every device to each
    microbatch passing through. Input x: (M, mb, ...) microbatched on the
    leading axis; every device receives the same x but only stage 0's
    injections matter — outputs are collected from the last stage and
    broadcast back.
    """

    def run(stage_params, x):
        idx = jax.lax.axis_index(axis)
        m = x.shape[0]
        ticks = m + n_stages - 1
        buf = jnp.zeros_like(x[0])  # in-flight activation on this stage
        outs = jnp.zeros_like(x)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when in range)
            inject = jnp.where(t < m, t, m - 1)
            x_in = jnp.where(idx == 0, x[inject],
                             jnp.zeros_like(x[0]) + buf)
            y = stage_fn(stage_params, x_in)
            # pass to the next stage; last stage's output wraps to 0
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf_next = jax.lax.ppermute(y, axis, perm)
            # last stage writes microbatch t - (S - 1)
            out_t = t - (n_stages - 1)
            take = jnp.logical_and(out_t >= 0, idx == 0)
            # the value arriving at stage 0 via the wrap IS the final
            # output of microbatch out_t
            idx_w = jnp.where(out_t >= 0, out_t, 0)
            outs = jnp.where(
                take,
                outs.at[idx_w].set(buf_next),
                outs)
            return (buf_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # outs is only populated on stage 0 — broadcast it everywhere so
        # the shard_map output is legitimately replicated
        return jax.lax.all_gather(outs, axis)[0]

    return run


def make_pipelined(mesh: Mesh, stage_fn: Callable, n_stages: int,
                   axis: str = "pipe"):
    """jit-wrapped shard_map pipeline. stage_params stacked (S, ...)."""
    run = pipeline_forward(stage_fn, n_stages, axis)
    mapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(axis), P()),  # params sharded by stage, x replicated
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(mapped)
