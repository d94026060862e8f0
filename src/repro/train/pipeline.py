"""Pipeline parallelism over a mesh axis (GPipe schedule, shard_map).

Cross-pod staging (DESIGN.md §4): the ``pod`` axis carries only stage
boundary activations (one ppermute per tick) instead of per-layer gradient
traffic — the paper's "concentrate all-lane traffic in one narrow unit"
applied to the slowest interconnect tier.

Mechanics: stage s of S holds a contiguous slice of layers (stage-stacked
params sharded on the axis). Microbatches m=0..M-1 enter stage 0 on ticks
t=m; stage s computes microbatch t-s on tick t; outputs leave stage S-1 on
ticks t>=S-1. Everything is one shard_map with a lax.scan over
M+S-1 ticks and a ppermute shift per tick — jax.grad differentiates
through it, producing the mirrored backward pipeline automatically.

Bubble fraction = (S-1)/(M+S-1), the classic GPipe overhead; reported by
``bubble_fraction`` and asserted in tests.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as PS


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, mesh,
                   axis: str):
    """Run microbatches through a stage pipeline.

    stage_fn(params_one_stage, x) -> y     (same shape as x)
    stage_params: pytree, every leaf with leading dim == n_stages
                  (sharded over ``axis``)
    x_micro: (M, mb, ...) microbatched inputs (replicated over ``axis``)
    Returns (M, mb, ...) outputs of the last stage (replicated).
    """
    n_stages = mesh.shape[axis]
    m_micro = x_micro.shape[0]
    n_ticks = m_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def device_fn(params_local, x_all):
        # params_local leaves: (1, ...) — this device's stage slice
        params_me = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = x_all.shape[1:]

        def tick(carry, t):
            recv, outs = carry
            # stage 0 injects microbatch t (clamped; masked when t >= M)
            inject = jax.lax.dynamic_index_in_dim(
                x_all, jnp.minimum(t, m_micro - 1), axis=0, keepdims=False)
            x_in = jnp.where(stage == 0, inject, recv)
            active = (t >= stage) & (t - stage < m_micro)
            y = stage_fn(params_me, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # collect finished microbatch t-(S-1) from the last stage
            out_idx = t - (n_stages - 1)
            is_out = (stage == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                is_out,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), axis=0),
                lambda o: o, outs)
            recv = jax.lax.ppermute(y, axis, perm)
            return (recv, outs), None

        outs0 = jnp.zeros((m_micro,) + mb_shape, x_all.dtype)
        recv0 = jnp.zeros(mb_shape, x_all.dtype)
        (_, outs), _ = jax.lax.scan(tick, (recv0, outs0),
                                    jnp.arange(n_ticks, dtype=jnp.int32))
        # only the last stage holds real outputs; broadcast via psum
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    in_specs = (jax.tree_util.tree_map(lambda _: PS(axis), stage_params),
                PS())
    return shard_map(device_fn, mesh=mesh, in_specs=in_specs,
                     out_specs=PS(), check_vma=False)(
        stage_params, x_micro)


def pipeline_loss(stage_fn, loss_fn, stage_params, x_micro, y_micro, mesh,
                  axis: str):
    """Mean loss over microbatches through the pipeline (differentiable:
    jax.grad produces the mirrored backward schedule)."""
    outs = pipeline_apply(stage_fn, stage_params, x_micro, mesh, axis)
    return loss_fn(outs, y_micro)
