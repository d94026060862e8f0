"""Mixture-of-Experts with two dispatch strategies.

1. ``moe_held`` — the experts this chip holds: experts [0, n_experts) of
   a router ``router_width`` wide (one rank of an expert-parallel
   deployment, or every expert when the router is no wider). Every token
   is routed over the whole router; the (token, expert) pairs of held
   experts are sorted by expert, run through grouped matrix products
   (``megablox.gmm``, whose grid visits the held groups' row tiles only),
   and added back with their gates. The sorted buffer has a row for every
   one of the T*k pairs, so no routing can drop a token; pairs of experts
   held elsewhere are left out, since their part belongs to other chips.
   Used on one device, below ``DENSE_PATH_MAX_TOKENS`` on a mesh (as
   ``moe_held_mesh``, a shard_map: XLA cannot partition the Pallas calls),
   and as the oracle the EP path is tested against.

2. ``moe_ep_shard_map`` — production expert parallelism: experts sharded over
   the ``model`` (lane) axis; tokens routed with an explicit all_to_all,
   computed by the owning lane, and returned. Dispatch is strip-mined
   (the paper's ``setvl`` concept) so transient buffers stay bounded
   regardless of tokens-per-device.

Both paths use top-k softmax routing with renormalized gates and return a
load-balance aux loss (Switch-style, over the whole router). DeepSeek-V3's
sigmoid+bias aux-free router is approximated by this classic router.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.sharding import PartitionSpec as PS

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models.layers import P, activation_fn
from repro.models.sharding import MeshCtx

DENSE_PATH_MAX_TOKENS = 16384   # below this, the held path runs on a mesh
EP_CHUNK_TOKENS = 8192          # strip-mine unit for the EP a2a pipeline
GMM_ROW_TILE = 512              # row tile of the grouped products
COUNTERS = ("moe_held_pairs", "moe_load_max_over_mean", "moe_dropped")


def moe_template(cfg: ArchConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    ep = m.n_experts_padded
    e_axis = "experts" if (m.expert_parallel or m.pad_experts_to) \
        else "experts_np"
    t = {
        "router": P((d, m.router_width), ("embed", None), "fan_in"),
        "w_gate": P((ep, d, m.expert_d_ff), (e_axis, "embed", "experts_ffn"), "fan_in"),
        "w_up": P((ep, d, m.expert_d_ff), (e_axis, "embed", "experts_ffn"), "fan_in"),
        "w_down": P((ep, m.expert_d_ff, d), (e_axis, "experts_ffn", "embed"), "fan_in"),
    }
    if m.n_shared_experts:
        ff = m.expert_d_ff * m.n_shared_experts
        t["shared"] = {
            "w_gate": P((d, ff), ("embed", "ffn"), "fan_in"),
            "w_up": P((d, ff), ("embed", "ffn"), "fan_in"),
            "w_down": P((ff, d), ("ffn", "embed2"), "fan_in"),
        }
    return t


def _route(x_tokens, router_w, top_k: int, n_experts: int, axes=()):
    """x (T,d) -> gates (T,k), ids (T,k), aux loss scalar. ``axes``: the
    mesh axes the tokens are split over (inside a shard_map), over which
    the aux loss's f and P are taken."""
    logits = jnp.einsum("td,de->te", x_tokens.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e f_e * P_e
    f = jnp.zeros((n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    p_mean = probs.mean(0)
    if axes:
        f = jax.lax.psum(f, axes)
        p_mean = jax.lax.pmean(p_mean, axes)
    f = f / jnp.maximum(f.sum(), 1.0)
    aux = n_experts * jnp.sum(f * p_mean)
    return gates, ids, aux


# ---------------------------------------------------------------------------
# Path 1: held experts, sorted pairs through grouped matmuls
# ---------------------------------------------------------------------------


def _gmm_tiling(m: int, k: int, n: int):
    """(rows, contracted, output) tile of one grouped product: rows in
    ``GMM_ROW_TILE`` (the pair buffer is padded to it); a width up to 1024
    whole, a wider one in its largest divisor of 128s up to 1024 (768 of
    Mellum2's 2304), so that the tiles and the fp32 accumulator stay well
    inside a v5e's default scoped VMEM."""
    def tile(x):
        if x <= 1024:
            return x
        return max((t for t in range(128, 1025, 128) if x % t == 0),
                   default=128)
    return min(GMM_ROW_TILE, m), tile(k), tile(n)


def _grouped(lhs, rhs, group_sizes, first=None):
    """lhs (R,k) rows sorted by group, rhs (G,k,n) groups [first,
    first + G) (``None``: from 0) -> (R,n); rows outside those groups
    come back zero."""
    return gmm(lhs, rhs.astype(lhs.dtype), group_sizes, lhs.dtype,
               _gmm_tiling, first, None, False, kops._default_interpret())


def moe_held(cfg: ArchConfig, p: dict, x_tokens):
    """x_tokens (T, d) -> (T, d), aux, counters. The held experts' part of
    the layer: pairs routed to experts [0, n_experts) of the router's
    ``router_width``; no capacity, nothing dropped."""
    held = cfg.moe.n_experts
    return _held(cfg, x_tokens, p["router"], p["w_gate"][:held],
                 p["w_up"][:held], p["w_down"][:held])


def _held(cfg: ArchConfig, x_tokens, router, w_gate, w_up, w_down,
          first=None, token_axes=(), lane_axis=None):
    """``moe_held``'s body. On one device the tables are the held experts.
    Inside ``moe_held_mesh``'s shard_map the tables hold this lane's
    experts from expert ``first`` on, or a slice of every held expert's
    hidden width, and ``lane_axis`` sums the lanes' parts; ``token_axes``
    are the mesh axes the tokens are split over, which the aux loss and
    the counters are taken over."""
    m = cfg.moe
    t_len, d = x_tokens.shape
    k, held, width = m.top_k, m.n_experts, m.router_width
    act = activation_fn(cfg.activation)
    with jax.named_scope("moe.route"):
        gates, ids, aux = _route(x_tokens, router, k, width, token_axes)
    with jax.named_scope("moe.dispatch"):
        pairs = t_len * k
        rows = -(-pairs // GMM_ROW_TILE) * GMM_ROW_TILE
        eid = ids.reshape(pairs)
        order = jnp.argsort(eid, stable=True)       # by expert, held first
        tok = order // k                            # pair t*k+j -> token t
        # one group per routed (or padded) expert and a last one for the
        # padding rows, so rows of unheld experts and padding are never
        # multiplied
        group_sizes = jnp.concatenate([
            jnp.bincount(eid, length=max(width, m.n_experts_padded)),
            jnp.full((1,), rows - pairs)]).astype(jnp.int32)
        xs = jnp.pad(x_tokens[tok], ((0, rows - pairs), (0, 0)))
    with jax.named_scope("moe.experts"):
        gate_h = _grouped(xs, w_gate, group_sizes, first)
        up_h = _grouped(xs, w_up, group_sizes, first)
        out = _grouped(act(gate_h) * up_h, w_down, group_sizes, first)
    with jax.named_scope("moe.combine"):
        weight = jnp.where(eid < held, gates.reshape(pairs), 0.0)[order]
        y = jnp.zeros((t_len, d), out.dtype).at[tok].add(
            out[:pairs] * weight[:, None].astype(out.dtype))
        if lane_axis is not None:
            y = jax.lax.psum(y, lane_axis)
    sizes = group_sizes[:held].astype(jnp.float32)
    # tokens sent to a held expert that got nothing back: a row the
    # grouped products skipped or a pair left out of the buffer
    lost = jnp.sum(jnp.any(ids < held, axis=-1)
                   & jnp.all(y == 0, axis=-1)).astype(jnp.float32)
    if token_axes:
        sizes = jax.lax.psum(sizes, token_axes)
        lost = jax.lax.psum(lost, token_axes)
    counters = {
        "moe_held_pairs": jnp.sum(sizes),
        "moe_load_max_over_mean": jnp.max(sizes)
        / jnp.maximum(jnp.mean(sizes), 1e-9),
        "moe_dropped": lost,
    }
    return y, aux, counters


def held_lane_split(cfg: ArchConfig, n_lanes: int) -> Optional[str]:
    """How ``moe_held_mesh`` splits the held experts over the lanes, as
    ``sharding.make_rules`` lays the tables out: "experts" (each lane its
    own experts), "ffn" (each lane a slice of every expert's hidden
    width), or None where the lanes do not divide them (every lane holds
    every held expert)."""
    m = cfg.moe
    if n_lanes <= 1:
        return None
    if m.expert_parallel and m.n_experts_padded % n_lanes == 0:
        return "experts"
    if not m.expert_parallel and m.expert_d_ff % n_lanes == 0:
        return "ffn"
    return None


def moe_held_mesh(cfg: ArchConfig, p: dict, x_tokens, ctx: MeshCtx):
    """``moe_held`` on a mesh, as a shard_map (XLA cannot partition the
    grouped products' Pallas calls). The tokens split over the batch axes
    that divide them, and over the lanes too where the lanes cannot split
    the experts; otherwise the lanes split the held experts or their
    hidden width (``held_lane_split``) and sum their parts."""
    m = cfg.moe
    sizes = ctx.axis_sizes
    lane = ctx.model_axis
    split = held_lane_split(cfg, ctx.n_lanes)
    token_axes = tuple(a for a in ctx.batch_axes if a in sizes)
    if split is None and ctx.n_lanes > 1:
        token_axes += (lane,)
    while token_axes and x_tokens.shape[0] % math.prod(
            sizes[a] for a in token_axes):
        token_axes = token_axes[:-1]
    tables = (p["w_gate"], p["w_up"], p["w_down"])
    if split == "experts":
        w_specs = (PS(lane, None, None),) * 3
        e_loc = m.n_experts_padded // ctx.n_lanes
    else:
        tables = tuple(w[:m.n_experts] for w in tables)
        w_specs = ((PS(None, None, lane),) * 2 + (PS(None, lane, None),)
                   if split == "ffn" else (PS(),) * 3)

    def body(x, router, w_gate, w_up, w_down):
        first = None
        if split == "experts":
            first = (jax.lax.axis_index(lane) * e_loc).astype(jnp.int32)
        return _held(cfg, x, router, w_gate, w_up, w_down, first,
                     token_axes, lane if split else None)

    tok_spec = PS(token_axes or None, None)
    return shard_map(
        body, mesh=ctx.mesh,
        in_specs=(tok_spec, PS()) + w_specs,
        out_specs=(tok_spec, PS(), {k: PS() for k in COUNTERS}),
        check_vma=False,
    )(x_tokens, p["router"], *tables)


# ---------------------------------------------------------------------------
# Path 2: expert-parallel all_to_all (shard_map)
# ---------------------------------------------------------------------------


def _ep_device_fn(cfg: ArchConfig, n_lanes: int, model_axis: str,
                  all_axes: tuple,
                  x_loc, router_w, w_gate, w_up, w_down):
    """Per-device body. x_loc (T_loc, d); w_* (E_loc, ...)."""
    m = cfg.moe
    act = activation_fn(cfg.activation)
    t_loc, d = x_loc.shape
    e_loc = m.n_experts_padded // n_lanes   # dead padded experts own slots
    k = m.top_k

    gates, ids, aux = _route(x_loc, router_w, k, m.router_width)

    chunk = min(EP_CHUNK_TOKENS, t_loc)
    n_chunks = -(-t_loc // chunk)
    assert n_chunks * chunk == t_loc, (t_loc, chunk)
    cap_send = max(int(chunk * k * m.capacity_factor / n_lanes), k)
    cap_local = max(int(n_lanes * cap_send * 2 / e_loc), 1)

    def one_chunk(carry, xs):
        xc, idc, gc = xs                            # (chunk,d),(chunk,k),(chunk,k)
        pairs = chunk * k
        pair_tok = jnp.repeat(jnp.arange(chunk, dtype=jnp.int32), k)
        eid = idc.reshape(pairs)
        gval = gc.reshape(pairs)
        dest = eid // e_loc                         # destination lane
        local_e = eid % e_loc

        lane_oh = jax.nn.one_hot(dest, n_lanes, dtype=jnp.int32)
        pos = (jnp.cumsum(lane_oh, axis=0) - lane_oh)
        pos = jnp.sum(lane_oh * pos, axis=-1)       # slot within dest lane
        keep = pos < cap_send
        pos_c = jnp.where(keep, pos, cap_send)      # overflow -> scratch row

        send = jnp.zeros((n_lanes, cap_send + 1, d), x_loc.dtype)
        send = send.at[dest, pos_c].set(xc[pair_tok])[:, :cap_send]
        send_e = jnp.full((n_lanes, cap_send + 1), 0, jnp.int32)
        send_e = send_e.at[dest, pos_c].set(local_e)[:, :cap_send]

        recv = jax.lax.all_to_all(send, model_axis, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, model_axis, 0, 0, tiled=False)

        pr = n_lanes * cap_send
        xr = recv.reshape(pr, d)
        er = recv_e.reshape(pr)
        e_oh = jax.nn.one_hot(er, e_loc, dtype=jnp.int32)
        pos2 = jnp.sum(e_oh * (jnp.cumsum(e_oh, axis=0) - e_oh), axis=-1)
        keep2 = pos2 < cap_local
        pos2_c = jnp.where(keep2, pos2, cap_local)

        buf = jnp.zeros((e_loc, cap_local + 1, d), x_loc.dtype)
        buf = buf.at[er, pos2_c].set(xr)[:, :cap_local]

        gh = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
        uh = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
        ob = jnp.einsum("ecf,efd->ecd", act(gh) * uh, w_down.astype(buf.dtype))

        out_pairs = ob[er, pos2_c % cap_local] * keep2[:, None].astype(ob.dtype)
        back = out_pairs.reshape(n_lanes, cap_send, d)
        got = jax.lax.all_to_all(back, model_axis, 0, 0, tiled=False)

        mine = got[dest, pos_c % cap_send] * keep[:, None].astype(got.dtype)
        yc = jnp.zeros((chunk, d), x_loc.dtype)
        yc = yc.at[pair_tok].add(mine * gval[:, None].astype(mine.dtype))
        return carry, yc

    xcs = x_loc.reshape(n_chunks, chunk, d)
    idcs = ids.reshape(n_chunks, chunk, k)
    gcs = gates.reshape(n_chunks, chunk, k)
    _, ys = jax.lax.scan(one_chunk, 0, (xcs, idcs, gcs))
    aux = jax.lax.pmean(aux, all_axes)
    return ys.reshape(t_loc, d), aux


def moe_ep_shard_map(cfg: ArchConfig, p: dict, x_tokens, ctx: MeshCtx):
    """x_tokens (T, d) -> (T, d), aux. Experts sharded over the lane axis."""
    mesh = ctx.mesh
    all_axes = tuple(mesh.axis_names)
    n_lanes = ctx.n_lanes
    # tokens sharded over every mesh axis (lanes included) so routing work
    # is not duplicated; divisibility is guaranteed by moe_block's guard.
    fn = functools.partial(_ep_device_fn, cfg, n_lanes, ctx.model_axis,
                           all_axes)
    y, aux = shard_map(
        fn, mesh=mesh,
        in_specs=(PS(all_axes, None), PS(None, None),
                  PS(ctx.model_axis, None, None), PS(ctx.model_axis, None, None),
                  PS(ctx.model_axis, None, None)),
        out_specs=(PS(all_axes, None), PS()),
        check_vma=False,
    )(x_tokens, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


# ---------------------------------------------------------------------------
# Public block
# ---------------------------------------------------------------------------


def uses_ep(cfg: ArchConfig, n_tokens: int,
            ctx: Optional[MeshCtx]) -> bool:
    """Whether ``moe_block`` takes the EP all_to_all path: a mesh with
    lanes that divide the expert table, every expert held on the mesh,
    and enough tokens to split evenly."""
    m = cfg.moe
    if ctx is None or ctx.mesh is None:
        return False
    n_dev = math.prod(ctx.axis_sizes.values())
    ep_capable = m.expert_parallel or m.pad_experts_to > 0
    return (ep_capable and m.router_width == m.n_experts
            and m.n_experts_padded % max(ctx.n_lanes, 1) == 0
            and ctx.n_lanes > 1 and n_tokens >= DENSE_PATH_MAX_TOKENS
            and n_tokens % n_dev == 0)


def moe_block(cfg: ArchConfig, p: dict, x, ctx: Optional[MeshCtx] = None):
    """x (B,S,d) -> (B,S,d), aux_loss, counters (``COUNTERS`` on the held
    path; none on the EP path)."""
    b, s, d = x.shape
    x_tokens = x.reshape(b * s, d)
    if uses_ep(cfg, b * s, ctx):
        y, aux = moe_ep_shard_map(cfg, p, x_tokens, ctx)
        counters = {}
    elif ctx is not None and ctx.mesh is not None:
        y, aux, counters = moe_held_mesh(cfg, p, x_tokens, ctx)
    else:
        y, aux, counters = moe_held(cfg, p, x_tokens)
    y = y.reshape(b, s, d)
    if "shared" in p:
        from repro.models.mlp import mlp
        y = y + mlp(p["shared"], x, "silu")
    return y, aux, counters
