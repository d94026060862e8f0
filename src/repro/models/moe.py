"""Mixture-of-Experts with two dispatch strategies.

1. ``moe_dense_dispatch`` — GShard-style one-hot capacity dispatch (einsum).
   Used for small token counts (decode), for expert-TP configs whose expert
   count does not divide the lane axis (granite: 40 experts / 16 lanes), and
   as the single-device oracle the EP path is tested against.

2. ``moe_ep_shard_map`` — production expert parallelism: experts sharded over
   the ``model`` (lane) axis; tokens routed with an explicit all_to_all,
   computed by the owning lane, and returned. Dispatch is strip-mined
   (DESIGN.md: the paper's ``setvl`` concept) so transient buffers stay
   bounded regardless of tokens-per-device.

Both paths use top-k softmax routing with renormalized gates and return a
load-balance aux loss (Switch-style). DeepSeek-V3's sigmoid+bias aux-free
router is approximated by this classic router; deviation noted in DESIGN.md.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as PS

from repro.configs.base import ArchConfig
from repro.models.layers import P, activation_fn
from repro.models.sharding import MeshCtx

DENSE_PATH_MAX_TOKENS = 16384   # below this, one-hot dispatch is cheaper
EP_CHUNK_TOKENS = 8192          # strip-mine unit for the EP a2a pipeline


def moe_template(cfg: ArchConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    ep = m.n_experts_padded
    e_axis = "experts" if (m.expert_parallel or m.pad_experts_to) \
        else "experts_np"
    t = {
        "router": P((d, m.n_experts), ("embed", None), "fan_in"),
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


def _route(x_tokens, router_w, top_k: int, n_experts: int):
    """x (T,d) -> gates (T,k), ids (T,k), aux loss scalar."""
    logits = jnp.einsum("td,de->te", x_tokens.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e f_e * P_e
    f = jnp.zeros((n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    p_mean = probs.mean(0)
    aux = n_experts * jnp.sum(f * p_mean)
    return gates, ids, aux


# ---------------------------------------------------------------------------
# Path 1: one-hot capacity dispatch (GShard einsum)
# ---------------------------------------------------------------------------


def _dispatch_combine(ids, gates, group_len, top_k, n_experts, capacity):
    """Build (Sg, E, C) dispatch (bool-ish) and combine (gated) tensors."""
    sel = jax.nn.one_hot(ids, n_experts, dtype=jnp.float32)     # (Sg,k,E)
    flat = sel.reshape(group_len * top_k, n_experts)            # slot-major
    pos = jnp.cumsum(flat, axis=0) - flat                       # (P,E)
    pos_sel = jnp.sum(flat * pos, axis=-1).astype(jnp.int32)    # (P,)
    keep = (pos_sel < capacity)
    slot_oh = jax.nn.one_hot(pos_sel, capacity, dtype=jnp.float32)
    disp_pairs = flat[:, :, None] * slot_oh[:, None, :] * keep[:, None, None]
    disp = disp_pairs.reshape(group_len, top_k, n_experts, capacity)
    dispatch = disp.sum(1)                                      # (Sg,E,C)
    combine = (disp * gates.reshape(group_len, top_k)[:, :, None, None]).sum(1)
    return dispatch, combine


def moe_dense_dispatch(cfg: ArchConfig, p: dict, x_tokens, *,
                       group_size: Optional[int] = None):
    """x_tokens (T, d) -> (T, d), aux. Grouped one-hot dispatch."""
    m = cfg.moe
    t_len, d = x_tokens.shape
    act = activation_fn(cfg.activation)
    gates, ids, aux = _route(x_tokens, p["router"], m.top_k, m.n_experts)

    sg = group_size or min(t_len, 64 if t_len > DENSE_PATH_MAX_TOKENS else t_len)
    n_groups = -(-t_len // sg)
    assert n_groups * sg == t_len, (t_len, sg)
    capacity = max(int(sg * m.top_k * m.capacity_factor / m.n_experts), m.top_k)

    xg = x_tokens.reshape(n_groups, sg, d)
    idsg = ids.reshape(n_groups, sg, m.top_k)
    gatesg = gates.reshape(n_groups, sg, m.top_k)

    dispatch, combine = jax.vmap(
        lambda i, g: _dispatch_combine(i, g, sg, m.top_k, m.n_experts, capacity)
    )(idsg, gatesg)
    dispatch = dispatch.astype(x_tokens.dtype)
    combine = combine.astype(x_tokens.dtype)

    w_gate = p["w_gate"][:m.n_experts]
    w_up = p["w_up"][:m.n_experts]
    w_down = p["w_down"][:m.n_experts]
    buf = jnp.einsum("gsec,gsd->gecd", dispatch, xg)            # (G,E,C,d)
    gate_h = jnp.einsum("gecd,edf->gecf", buf, w_gate.astype(buf.dtype))
    up_h = jnp.einsum("gecd,edf->gecf", buf, w_up.astype(buf.dtype))
    hidden = act(gate_h) * up_h
    out_buf = jnp.einsum("gecf,efd->gecd", hidden, w_down.astype(buf.dtype))
    y = jnp.einsum("gsec,gecd->gsd", combine, out_buf)
    return y.reshape(t_len, d), aux


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

    gates, ids, aux = _route(x_loc, router_w, k, m.n_experts)

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


def moe_block(cfg: ArchConfig, p: dict, x, ctx: Optional[MeshCtx] = None):
    """x (B,S,d) -> (B,S,d), aux_loss."""
    m = cfg.moe
    b, s, d = x.shape
    x_tokens = x.reshape(b * s, d)
    n_dev = math.prod(ctx.axis_sizes.values()) if ctx and ctx.mesh else 1
    ep_capable = m.expert_parallel or m.pad_experts_to > 0
    use_ep = (
        ctx is not None and ctx.mesh is not None and ep_capable
        and m.n_experts_padded % max(ctx.n_lanes, 1) == 0 and ctx.n_lanes > 1
        and b * s >= DENSE_PATH_MAX_TOKENS
        and (b * s) % n_dev == 0 and (b * s) // n_dev >= 1
    )
    if use_ep:
        y, aux = moe_ep_shard_map(cfg, p, x_tokens, ctx)
    else:
        y, aux = moe_dense_dispatch(cfg, p, x_tokens)
    y = y.reshape(b, s, d)
    if "shared" in p:
        from repro.models.mlp import mlp
        y = y + mlp(p["shared"], x, "silu")
    return y, aux
