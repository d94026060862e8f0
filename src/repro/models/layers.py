"""Param templates + common neural net ops.

A model is described by a *template* tree (nested dicts of ``P`` leaves).
From one template we derive: concrete init, ShapeDtypeStruct stand-ins
(dry-run; no allocation), and PartitionSpecs (logical->mesh axes).
This single-source design keeps init/sharding/abstract-eval in sync.

Sharding follows the Ara lane model (DESIGN.md §2): the "model" mesh axis
is the lane axis; TP-sharded logical axes keep chained ops lane-local.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

# ---------------------------------------------------------------------------
# Param template
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class P:
    """One parameter: shape + logical axes (+ init law)."""
    shape: tuple
    axes: tuple                      # logical axis name (or None) per dim
    init: str = "normal"             # normal | zeros | ones | fan_in
    std: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _leaves(tree, path=()):
    if isinstance(tree, P):
        yield path, tree
    elif isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], path + (k,))
    else:
        raise TypeError(f"bad template node at {path}: {type(tree)}")


def _map_template(tree, fn):
    if isinstance(tree, P):
        return fn(tree)
    return {k: _map_template(v, fn) for k, v in tree.items()}


def init_params(template, key, dtype=jnp.float32):
    """Concrete init. Deterministic per-leaf key from the leaf path."""
    def init_one(path, p: P):
        if p.init == "zeros":
            return jnp.zeros(p.shape, dtype)
        if p.init == "ones":
            return jnp.ones(p.shape, dtype)
        leaf_key = jax.random.fold_in(key, zlib_hash(path))
        if p.init == "fan_in":
            std = 1.0 / math.sqrt(max(_fan_in(p), 1))
        else:
            std = p.std
        return (jax.random.normal(leaf_key, p.shape, jnp.float32) * std).astype(dtype)

    out: dict = {}
    for path, p in _leaves(template):
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = init_one(path, p)
    return out


def _fan_in(p: P) -> int:
    """Contracted size of a projection weight: the dims before its output,
    after any leading stack axis. The output is the last dim, or the
    (heads, head_dim) pair of a q/k/v projection; an output projection
    contracts its (heads, head_dim) pair."""
    if len(p.shape) < 2:
        return p.shape[-1]
    n_out = 2 if p.axes[-1] == "head_dim" else 1
    n_in = 2 if p.axes[-n_out - 1] == "head_dim" else 1
    return math.prod(p.shape[-n_out - n_in:-n_out])


def zlib_hash(path) -> int:
    import zlib
    return zlib.crc32("/".join(map(str, path)).encode()) & 0x7FFFFFFF


def abstract_params(template, dtype=jnp.float32):
    """ShapeDtypeStructs for AOT lowering — no memory is allocated."""
    return _map_template(template, lambda p: jax.ShapeDtypeStruct(p.shape, dtype))


@dataclasses.dataclass(frozen=True)
class Rules:
    """Logical-axis -> mesh-axis mapping (the lane-assignment policy)."""
    mapping: tuple                    # tuple of (logical, mesh_axis_or_tuple)
    mesh_shape: tuple                 # tuple of (mesh_axis, size)

    def mesh_size(self, axis) -> int:
        if isinstance(axis, (tuple, list)):
            return int(np.prod([self.mesh_size(a) for a in axis]))
        return dict(self.mesh_shape).get(axis, 1)

    def spec_for(self, p: P) -> PartitionSpec:
        m = dict(self.mapping)
        used = set()
        out = []
        for dim, ax in zip(p.shape, p.axes):
            mesh_ax = m.get(ax)
            if mesh_ax is None:
                out.append(None)
                continue
            flat = tuple(mesh_ax) if isinstance(mesh_ax, (tuple, list)) else (mesh_ax,)
            if any(a in used for a in flat):
                out.append(None)  # a mesh axis may shard only one dim
                continue
            # shard only when it divides or the dim is large enough that
            # GSPMD padding waste is acceptable (dim >= axis size)
            size = self.mesh_size(mesh_ax)
            if dim >= size and size > 1:
                used.update(flat)
                out.append(mesh_ax if not isinstance(mesh_ax, list) else tuple(mesh_ax))
            else:
                out.append(None)
        return PartitionSpec(*out)


def param_specs(template, rules: Rules):
    return _map_template(template, rules.spec_for)


def tree_size_bytes(tree):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Common ops
# ---------------------------------------------------------------------------


def rms_norm(x, gamma, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * gamma.astype(dt)


def layer_norm(x, gamma, beta, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dt) \
        * gamma.astype(dt) + beta.astype(dt)


def activation_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def rotary_embedding(positions, head_dim, theta, yarn=None):
    """positions (...,) int -> cos/sin (..., head_dim/2); ``yarn`` (a
    ``configs.base.YarnConfig``) scales the frequencies and the tables."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    scale = 1.0
    if yarn is not None:
        freqs = freqs * _yarn_freq_scale(yarn, head_dim, theta)
        scale = yarn.attention_factor
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return cos, sin


def _yarn_freq_scale(yarn, head_dim, theta):
    """Per-frequency factor of YaRN: 1/factor (interpolated) below the
    correction range, 1 (extrapolated) above it, a linear ramp between;
    the range is where a dimension turns ``beta_fast`` .. ``beta_slow``
    times within the original context, floored and ceiled."""
    half = head_dim // 2

    def dim_of(rotations):
        return head_dim * math.log(yarn.original_max_position_embeddings
                                   / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    extrapolated = 1.0 - ramp
    return jnp.asarray(extrapolated + (1.0 - extrapolated) / yarn.factor,
                       jnp.float32)


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin (..., S, D/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # broadcast over heads axis
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def shard(x, *axes):
    """with_sharding_constraint by raw PartitionSpec entries."""
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*axes))


def repeat_kv(k, n_rep: int):
    """(B,S,Hkv,D) -> (B,S,Hkv*n_rep,D) by head repetition (GQA broadcast)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d))
    return k.reshape(b, s, h * n_rep, d)
