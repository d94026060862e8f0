"""Compute/communication overlap (vector chaining at mesh scale).

Ara's chaining overlaps a consumer FU with a producer at element
granularity (§III-E3). At mesh scale the analogue is overlapping collective
steps with partial compute: ring variants of all-gather/reduce-scatter
matmuls built from shard_map + ppermute, so each ICI hop is hidden behind
one shard's matmul. These are the beyond-paper §Perf levers for
collective-bound cells.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as PS


def _check_divisible(fn: str, what: str, dim: int, by: int, why: str):
    """Ring collectives move fixed-size shards: a ragged dimension would
    either crash deep inside the scan (shard_map refuses the split) or
    silently drop the remainder rows (a floor-divided slice). Fail up
    front with the shapes in the message instead."""
    if by < 1 or dim % by:
        raise ValueError(
            f"{fn}: {what}={dim} is not divisible by {why}={by}; "
            f"ring steps move fixed-size shards, so ragged shapes "
            f"cannot be scattered exactly — pad {what} to a multiple "
            f"of {by}")


def all_gather_matmul(x, w, mesh, axis: str, group: int = 1):
    """y = all_gather(x, axis) @ w, overlapped.

    x: (m, k) sharded on ``axis`` along m; w: (k, n) replicated.
    Computes x @ w without first materializing the gathered x on any
    device: each step multiplies the shard(s) it holds while ppermuting
    the next in. Returns (m, n) sharded like an all-gather result.
    Requires ``m % n_dev == 0`` (validated up front — shard_map cannot
    split a ragged row dimension).

    ``group`` is the ring's LMUL analogue (register grouping, §IV): the
    steady-state loop moves a ``group``-shard buffer per ppermute and runs
    one (group*m_local, k) matmul per hop — n_dev/group collective
    launches instead of n_dev, each hiding a ``group``× longer compute
    chain, exactly how grouped vector registers amortize the issue
    interval. A short fill phase of ``group - 1`` single-shard hops plays
    the operand-queue warm-up. Requires ``n_dev % group == 0`` (the
    grouped ring's step permutation i -> i+group only closes a cycle
    that visits every shard owner when group divides the ring).
    """
    n_dev = mesh.shape[axis]
    _check_divisible("all_gather_matmul", "m", x.shape[0], n_dev,
                     f"mesh axis '{axis}' size")
    _check_divisible("all_gather_matmul", "n_dev", n_dev, group, "group")
    if x.shape[1] != w.shape[0]:
        raise ValueError(
            f"all_gather_matmul: contraction mismatch x{tuple(x.shape)} "
            f"@ w{tuple(w.shape)}")

    def device_fn(x_loc, w_loc):
        idx = jax.lax.axis_index(axis)
        m_loc = x_loc.shape[0]
        n_out = w_loc.shape[1]
        out = jnp.zeros((n_dev * m_loc, n_out), x_loc.dtype)
        perm1 = [(i, (i + 1) % n_dev) for i in range(n_dev)]

        # fill: assemble the group buffer [idx, idx-1, ..., idx-group+1]
        big0 = jnp.zeros((group * m_loc, x_loc.shape[1]), x_loc.dtype)
        big0 = jax.lax.dynamic_update_slice(big0, x_loc, (0, 0))

        def fill(j, carry):
            big, cur = carry
            cur = jax.lax.ppermute(cur, axis, perm1)
            row = ((j + 1) * m_loc).astype(jnp.int32)
            big = jax.lax.dynamic_update_slice(big, cur,
                                               (row, jnp.int32(0)))
            return (big, cur)

        big, _ = jax.lax.fori_loop(0, group - 1, fill, (big0, x_loc))

        perm_g = [(i, (i + group) % n_dev) for i in range(n_dev)]

        def body(s, carry):
            big, out = carry
            # one long chain per hop: (group*m_loc, k) @ (k, n)
            part = jnp.dot(big, w_loc, preferred_element_type=jnp.float32)

            def put(j, out):
                src = (idx - s * group - j) % n_dev   # shard owner
                blk = jax.lax.dynamic_slice(
                    part, ((j * m_loc).astype(jnp.int32), jnp.int32(0)),
                    (m_loc, n_out))
                return jax.lax.dynamic_update_slice(
                    out, blk.astype(out.dtype),
                    ((src * m_loc).astype(jnp.int32), jnp.int32(0)))

            out = jax.lax.fori_loop(0, group, put, out)
            big = jax.lax.ppermute(big, axis, perm_g)
            return (big, out)

        big, out = jax.lax.fori_loop(0, n_dev // group, body, (big, out))
        return out

    return shard_map(device_fn, mesh=mesh,
                     in_specs=(PS(axis, None), PS(None, None)),
                     out_specs=PS(None, None), check_vma=False)(x, w)


def matmul_reduce_scatter(x, w, mesh, axis: str):
    """y = reduce_scatter(x @ w_sharded, axis), overlapped.

    x: (m, k) sharded on k; w: (k, n) sharded on k. The full (m, n)
    partial product never materializes per device: accumulate
    ring-style, each device ends with its (m/n_dev, n) slice of the
    sum. Requires ``k % n_dev == 0`` (the shard split) and
    ``m % n_dev == 0`` (the scatter slices) — both validated up front;
    the old floor-divided slice silently DROPPED the trailing
    ``m % n_dev`` rows instead of failing.
    """
    n_dev = mesh.shape[axis]
    _check_divisible("matmul_reduce_scatter", "k", x.shape[1], n_dev,
                     f"mesh axis '{axis}' size")
    _check_divisible("matmul_reduce_scatter", "m", x.shape[0], n_dev,
                     f"mesh axis '{axis}' size")
    if x.shape[1] != w.shape[0]:
        raise ValueError(
            f"matmul_reduce_scatter: contraction mismatch "
            f"x{tuple(x.shape)} @ w{tuple(w.shape)}")

    def device_fn(x_loc, w_loc):
        idx = jax.lax.axis_index(axis)
        m = x_loc.shape[0]
        m_loc = m // n_dev
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        acc0 = jnp.zeros((m_loc, w_loc.shape[1]), jnp.float32)

        def body(i, acc):
            # contribute the chunk that reaches its owner after the
            # remaining n-1-i hops: owner = idx + (n-1-i)
            chunk = (idx + n_dev - 1 - i) % n_dev
            xs = jax.lax.dynamic_slice(x_loc, (chunk * m_loc, 0),
                                       (m_loc, x_loc.shape[1]))
            part = jnp.dot(xs, w_loc, preferred_element_type=jnp.float32)
            acc = jax.lax.ppermute(acc, axis, perm) + part
            return acc

        acc = jax.lax.fori_loop(0, n_dev, body, acc0)
        return acc.astype(x_loc.dtype)

    return shard_map(device_fn, mesh=mesh,
                     in_specs=(PS(None, axis), PS(axis, None)),
                     out_specs=PS(axis, None), check_vma=False)(x, w)
