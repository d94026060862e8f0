"""Model zoo: one template+forward covering all assigned families.

Families: dense (GQA), moe (GQA or MLA router blocks), ssm (mLSTM), hybrid
(Mamba2 + shared attn), vlm (cross-attn every k layers), audio (enc-dec).

Homogeneous layer stacks are scanned (jax.lax.scan over stacked params) —
one layer is compiled once regardless of depth, which also keeps the
512-device dry-run compile tractable. Remat wraps the scan body; the
named policies ("dots", "dots_no_batch", ...) are shared with the
per-q-block checkpoint knob of the blockwise attention path
(models.attention.checkpoint_policy), so layer-level and attention-level
rematerialization speak one vocabulary. Training attention routes through
chunked_attention — and from there the Pallas flash kernel when
cfg.attn_flash allows (see models/attention.py, kernels/attention.py).

Decode uses per-sequence KV caches (see attention.py) or recurrent states
(ssm.py); ``init_cache``/``input_specs`` build matching ShapeDtypeStructs
for the no-allocation dry-run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import (checkpoint_policy as attn_checkpoint_policy,
                                    cross_attention, gqa_attention,
                                    gqa_template, mla_attention, mla_template)
from repro.models.layers import P, rms_norm
from repro.models.mlp import mlp, mlp_template
from repro.models.moe import COUNTERS, moe_block, moe_template, uses_ep
from repro.models.sharding import MeshCtx

# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------


def _stack(tmpl, n: int):
    """Add a leading stacked-layers dim to every leaf."""
    def add(p: P) -> P:
        return P((n,) + p.shape, ("layers",) + p.axes, p.init, p.std)
    if isinstance(tmpl, P):
        return add(tmpl)
    return {k: _stack(v, n) for k, v in tmpl.items()}


def _attn_layer_template(cfg: ArchConfig, cross=False) -> dict:
    t = {"ln1": P((cfg.d_model,), ("embed",), "ones")}
    if cfg.use_mla:
        t["attn"] = mla_template(cfg)
    else:
        t["attn"] = gqa_template(cfg, cross=cross)
    return t


def _dense_layer_template(cfg: ArchConfig) -> dict:
    t = _attn_layer_template(cfg)
    t["ln2"] = P((cfg.d_model,), ("embed",), "ones")
    t["mlp"] = mlp_template(cfg.d_model, cfg.d_ff, cfg.activation)
    return t


def _moe_layer_template(cfg: ArchConfig) -> dict:
    t = _attn_layer_template(cfg)
    t["ln2"] = P((cfg.d_model,), ("embed",), "ones")
    t["moe"] = moe_template(cfg)
    return t


def model_template(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    t: dict = {
        "embed": P((cfg.vocab_size, d), ("vocab", "embed"), "normal", 0.02),
        "final_norm": P((d,), ("embed",), "ones"),
    }
    if not cfg.tie_embeddings:
        t["unembed"] = P((d, cfg.vocab_size), ("embed", "vocab"), "normal", 0.02)

    fam = cfg.family
    if fam == "dense":
        t["layers"] = _stack(_dense_layer_template(cfg), cfg.n_layers)
    elif fam == "moe":
        m = cfg.moe
        n_moe = cfg.n_layers - m.n_dense_layers
        if m.n_dense_layers:
            dense_cfg = dataclasses.replace(cfg, d_ff=m.dense_d_ff or cfg.d_ff)
            t["dense_layers"] = _stack(_dense_layer_template(dense_cfg),
                                       m.n_dense_layers)
        t["layers"] = _stack(_moe_layer_template(cfg), n_moe)
        if cfg.mtp_depth:
            t["mtp"] = {
                "proj": P((2 * d, d), (None, "embed"), "fan_in"),
                "norm_h": P((d,), ("embed",), "ones"),
                "norm_e": P((d,), ("embed",), "ones"),
                "layer": _dense_layer_template(
                    dataclasses.replace(cfg, use_mla=False,
                                        d_ff=cfg.moe.dense_d_ff or cfg.d_ff)),
            }
    elif fam == "ssm":
        layer = {"ln1": P((d,), ("embed",), "ones"),
                 "mix": ssm_mod.mlstm_template(cfg)}
        t["layers"] = _stack(layer, cfg.n_layers)
    elif fam == "hybrid":
        layer = {"ln1": P((d,), ("embed",), "ones"),
                 "mix": ssm_mod.mamba2_template(cfg)}
        t["layers"] = _stack(layer, cfg.n_layers)
        t["shared_attn"] = _dense_layer_template(cfg)
    elif fam == "vlm":
        assert cfg.n_layers % cfg.cross_attn_every == 0
        t["layers"] = _stack(_dense_layer_template(cfg), cfg.n_layers)
        n_cross = cfg.n_layers // cfg.cross_attn_every
        xt = _attn_layer_template(cfg, cross=True)
        xt["ln2"] = P((d,), ("embed",), "ones")
        xt["mlp"] = mlp_template(d, cfg.d_ff, cfg.activation)
        t["cross_layers"] = _stack(xt, n_cross)
    elif fam == "audio":
        t["enc_layers"] = _stack(_dense_layer_template(cfg),
                                 cfg.n_encoder_layers)
        t["enc_norm"] = P((d,), ("embed",), "ones")
        dec = _dense_layer_template(cfg)
        dec["ln_x"] = P((d,), ("embed",), "ones")
        dec["xattn"] = gqa_template(cfg)
        t["layers"] = _stack(dec, cfg.n_layers)
    else:
        raise ValueError(fam)
    return t


# ---------------------------------------------------------------------------
# Blocks (forward)
# ---------------------------------------------------------------------------


def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    # named policies share models.attention's vocabulary; "dots" keeps its
    # historical meaning (no-batch-dims dots, the scan-body default)
    name = "dots_no_batch" if cfg.remat == "dots" else cfg.remat
    return jax.checkpoint(fn, policy=attn_checkpoint_policy(name))


_PREFILL_FROM_ZERO = False


def set_prefill_hint(value: bool):
    """Static hint from the serving layer: the incoming cache is fresh
    (lengths==0, prompt fills it end-to-end), so prefill attention may walk
    the causal triangle only."""
    global _PREFILL_FROM_ZERO
    _PREFILL_FROM_ZERO = value


def _attn_block(cfg, p, x, positions, cache=None, causal=True, window=None,
                yarn=None):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = mla_attention(cfg, p["attn"], h, positions,
                                     cache=cache,
                                     prefill_from_zero=_PREFILL_FROM_ZERO)
    else:
        a, new_cache = gqa_attention(cfg, p["attn"], h, positions,
                                     cache=cache, causal=causal,
                                     prefill_from_zero=_PREFILL_FROM_ZERO,
                                     window=window, yarn=yarn)
    return a, h, new_cache


def dense_block(cfg, p, x, positions, cache=None, causal=True, memory=None):
    with jax.named_scope("attn"):
        a, h, new_cache = _attn_block(cfg, p, x, positions, cache, causal)
    if cfg.parallel_block:
        with jax.named_scope("mlp"):
            return x + a + mlp(p["mlp"], h, cfg.activation), new_cache
    x = x + a
    if memory is not None and "xattn" in p:
        hx = rms_norm(x, p["ln_x"], cfg.norm_eps)
        x = x + cross_attention(cfg, p["xattn"], hx, memory)
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp(p["mlp"], h2, cfg.activation)
    return x, new_cache


def moe_layer(cfg, p, x, positions, ctx, cache=None, kind="full"):
    """One MoE layer; returns (x, {"aux", counters...}, new_cache). A
    ``kind`` "sliding" layer attends within ``cfg.sliding_window`` with
    plain RoPE, a full one with ``cfg.yarn``'s; the attention scope names
    the kind (``attn.window`` / ``attn.full``) when the config has kinds."""
    sliding = kind == "sliding"
    scope = ("attn." + ("window" if sliding else "full")) \
        if cfg.layer_kinds else "attn"
    with jax.named_scope(scope):
        a, _, new_cache = _attn_block(
            cfg, p, x, positions, cache,
            window=cfg.sliding_window if sliding else None,
            yarn=None if sliding else cfg.yarn)
    x = x + a
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, aux, counters = moe_block(cfg, p["moe"], h2, ctx)
    return x + y, dict(counters, aux=aux), new_cache


def mix_layer(cfg, p, x, state=None):
    """ssm/hybrid mixing layer (mamba2 or mlstm)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.ssm.kind == "mamba2":
        y, new_state = ssm_mod.mamba2_block(cfg, p["mix"], h, state)
    else:
        y, new_state = ssm_mod.mlstm_block(cfg, p["mix"], h, state)
    return x + y, new_state


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------


def _scan_layers(cfg, stacked_params, body, x, cache_xs=None, aux0=None,
                 remat=True):
    """Scan ``body`` over stacked layer params (+ optional stacked cache).

    body(params_i, x, cache_i) -> (x, new_cache_i, aux_i); ``aux0`` is
    the zero of the aux tree (a float32 scalar when None), summed leaf by
    leaf. ``remat=False`` leaves the body as it is (it rematerializes its
    own parts). Returns (x, new_cache_stacked, aux_sum).
    """
    aux0 = jnp.float32(0.0) if aux0 is None else aux0

    def scan_fn(carry, xs):
        x, aux = carry
        p_i, c_i = xs
        x, new_c, a = body(p_i, x, c_i)
        return (x, jax.tree_util.tree_map(jnp.add, aux, a)), new_c

    fn = _maybe_remat(scan_fn, cfg) if remat else scan_fn
    if cfg.scan_layers:
        (x, aux), new_cache = jax.lax.scan(
            fn, (x, aux0), (stacked_params, cache_xs))
        return x, new_cache, aux
    # unrolled (smoke tests): index the stacked params
    n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    aux = aux0
    new_caches = []
    for i in range(n):
        p_i = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
        c_i = None if cache_xs is None \
            else jax.tree_util.tree_map(lambda a: a[i], cache_xs)
        (x, aux), nc = fn((x, aux), (p_i, c_i))
        new_caches.append(nc)
    if new_caches and new_caches[0] is not None:
        new_cache = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *new_caches)
    else:
        new_cache = None
    return x, new_cache, aux


def _scan_periods(cfg, stacked_params, kinds, layer, x, cache_xs, aux0):
    """Scan over periods of ``len(kinds)`` layers: one compiled body holds
    a whole period, each layer of it ``layer(p_i, x, c_i, kind=...)``
    rematerialized on its own, so the backward pass rebuilds one layer's
    activations at a time. Same contract as ``_scan_layers``."""
    period = len(kinds)

    def split(a):
        return a.reshape((a.shape[0] // period, period) + a.shape[1:])

    steps = [_maybe_remat(functools.partial(layer, kind=kind), cfg)
             for kind in kinds]

    def body(p_g, x, c_g):
        aux, new_c = aux0, []
        for j, step in enumerate(steps):
            pick = functools.partial(jax.tree_util.tree_map,
                                     lambda a: a[j])
            x, nc, a = step(pick(p_g), x, None if c_g is None else pick(c_g))
            aux = jax.tree_util.tree_map(jnp.add, aux, a)
            new_c.append(nc)
        if new_c[0] is None:
            return x, None, aux
        return x, jax.tree_util.tree_map(lambda *c: jnp.stack(c), *new_c), aux

    x, nc, aux = _scan_layers(
        cfg, jax.tree_util.tree_map(split, stacked_params), body, x,
        None if cache_xs is None else jax.tree_util.tree_map(split, cache_xs),
        aux0, remat=False)
    if nc is not None:
        nc = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), nc)
    return x, nc, aux


def forward(cfg: ArchConfig, params: dict, tokens, *,
            ctx: Optional[MeshCtx] = None,
            cache: Optional[dict] = None,
            frontend_emb=None,
            head_fn=None):
    """Shared forward. tokens (B,S) int32.

    cache=None  -> full causal forward (training / scoring), returns
                   (logits, aux, extras)
    cache=dict  -> prefill (lengths=0, S=prompt) or decode (S small);
                   returns (logits, aux, new_cache)
    head_fn     -> optional ``(x, unembed) -> logits`` replacing the final
                   einsum — the serving degrade ladder routes the logits
                   matmul through the Policy Pallas kernels here
                   (``kernels.ops.lm_head``).
    """
    ctx = ctx or MeshCtx(mesh=None)
    from repro.models import attention as attn_mod
    attn_mod.set_mesh_ctx(ctx if ctx.mesh is not None else None)
    b, s = tokens.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)

    if cache is not None:
        lengths = cache["lengths"]
        positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    else:
        lengths = None
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))

    fam = cfg.family
    aux = jnp.float32(0.0)
    new_cache: dict = {} if cache is not None else None
    extras: dict = {}

    if fam in ("dense", "vlm"):
        if fam == "vlm":
            memory = frontend_emb.astype(compute_dtype)
            k_every = cfg.cross_attn_every
            n_groups = cfg.n_layers // k_every
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape((n_groups, k_every) + a.shape[1:]),
                params["layers"])

            def group_body(p_g, x, c_g):
                self_p, cross_p = p_g
                sub_c = None if c_g is None else c_g
                x, nc, _ = _scan_layers(
                    cfg, self_p,
                    lambda p_i, xx, ci: dense_block(cfg, p_i, xx, positions,
                                                    cache=ci) + (jnp.float32(0),),
                    x, cache_xs=sub_c)
                x2, _ = _cross_block(cfg, cross_p, x, memory)
                return x2, nc, jnp.float32(0.0)

            pairs = (grouped, params["cross_layers"])
            c_xs = None if cache is None else {"k": cache["k"].reshape(
                (n_groups, k_every) + cache["k"].shape[1:]),
                "v": cache["v"].reshape((n_groups, k_every) + cache["v"].shape[1:]),
                "lengths": jnp.broadcast_to(lengths, (n_groups, k_every, b))}
            x, nc, _ = _scan_layers(cfg, pairs, group_body, x, cache_xs=c_xs)
            if cache is not None:
                new_cache = {"k": nc["k"].reshape((-1,) + nc["k"].shape[2:]),
                             "v": nc["v"].reshape((-1,) + nc["v"].shape[2:])}
        else:
            def body(p_i, x, c_i):
                x, nc = dense_block(cfg, p_i, x, positions, cache=c_i)
                return x, nc, jnp.float32(0.0)
            c_xs = _layer_cache_xs(cache, cfg.n_layers, lengths, b)
            x, nc, _ = _scan_layers(cfg, params["layers"], body, x, c_xs)
            if cache is not None:
                new_cache = {"k": nc["k"], "v": nc["v"]}

    elif fam == "moe":
        m = cfg.moe
        n_dense = m.n_dense_layers
        kv_keys = ("c_kv", "k_rope") if cfg.use_mla else ("k", "v")
        if n_dense:
            dense_cfg = dataclasses.replace(cfg, d_ff=m.dense_d_ff or cfg.d_ff)

            def dbody(p_i, x, c_i):
                x, nc = dense_block(dense_cfg, p_i, x, positions, cache=c_i)
                return x, nc, jnp.float32(0.0)
            c_xs = _moe_cache_xs(cache, "dense_", kv_keys, n_dense, lengths, b)
            x, nc_d, _ = _scan_layers(cfg, params["dense_layers"], dbody, x, c_xs)
        n_moe = cfg.n_layers - n_dense
        kinds = cfg.layer_kinds or ("full",)
        counters = () if uses_ep(cfg, b * s, ctx) else COUNTERS
        aux0 = {k: jnp.float32(0.0) for k in ("aux",) + counters}

        def mbody(p_i, x, c_i, kind=kinds[0]):
            x, a, nc = moe_layer(cfg, p_i, x, positions, ctx, cache=c_i,
                                 kind=kind)
            return x, nc, a
        c_xs = _moe_cache_xs(cache, "", kv_keys, n_moe, lengths, b)
        if len(kinds) == 1:
            x, nc_m, aux = _scan_layers(cfg, params["layers"], mbody, x,
                                        c_xs, aux0)
        else:
            x, nc_m, aux = _scan_periods(cfg, params["layers"], kinds, mbody,
                                         x, c_xs, aux0)
        extras["moe"] = dict(aux)
        aux = extras["moe"].pop("aux")
        if counters:
            # per step: pairs and drops summed over the layers, the load
            # ratio averaged over them
            extras["moe"]["moe_load_max_over_mean"] /= n_moe
        if cache is not None:
            new_cache = {k: nc_m[k] for k in kv_keys}
            if n_dense:
                for k in kv_keys:
                    new_cache["dense_" + k] = nc_d[k]

    elif fam == "ssm":
        def body(p_i, x, st_i):
            x, ns = mix_layer(cfg, p_i, x, st_i)
            return x, ns, jnp.float32(0.0)
        st_xs = None if cache is None else {"conv": cache["conv"],
                                            "ssm": cache["ssm"]}
        x, ns, _ = _scan_layers(cfg, params["layers"], body, x, st_xs)
        if cache is not None:
            new_cache = {"conv": ns["conv"], "ssm": ns["ssm"]}

    elif fam == "hybrid":
        x, new_cache = _hybrid_forward(cfg, params, x, positions, cache,
                                       lengths, b)

    elif fam == "audio":
        # decode (single token) reads the encoder memory from the cache;
        # prefill / full forward runs the encoder and stores it.
        if cache is not None and "memory" in cache and s == 1:
            memory = cache["memory"].astype(compute_dtype)
        else:
            memory = frontend_emb.astype(compute_dtype)
            enc_pos = jnp.broadcast_to(
                jnp.arange(memory.shape[1], dtype=jnp.int32)[None, :],
                memory.shape[:2])

            def ebody(p_i, x, _):
                x, _ = dense_block(cfg, p_i, x, enc_pos, causal=False)
                return x, None, jnp.float32(0.0)
            memory, _, _ = _scan_layers(cfg, params["enc_layers"], ebody, memory)
            memory = rms_norm(memory, params["enc_norm"], cfg.norm_eps)

        def dbody(p_i, x, c_i):
            x, nc = dense_block(cfg, p_i, x, positions, cache=c_i,
                                memory=memory)
            return x, nc, jnp.float32(0.0)
        c_xs = _layer_cache_xs(cache, cfg.n_layers, lengths, b)
        x, nc, _ = _scan_layers(cfg, params["layers"], dbody, x, c_xs)
        if cache is not None:
            new_cache = {"k": nc["k"], "v": nc["v"], "memory": memory}
    else:
        raise ValueError(fam)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    with jax.named_scope("head"):
        if head_fn is not None:
            logits = head_fn(x, unembed.astype(compute_dtype))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                unembed.astype(compute_dtype))

    if cache is not None:
        new_cache["lengths"] = lengths + s
        return logits, aux, new_cache
    extras["final_hidden"] = x
    return logits, aux, extras


def _cross_block(cfg, p, x, memory):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + cross_attention(cfg, p["attn"], h, memory)
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + mlp(p["mlp"], h2, cfg.activation)
    return x, None


def _layer_cache_xs(cache, n_layers, lengths, b):
    if cache is None:
        return None
    return {"k": cache["k"], "v": cache["v"],
            "lengths": jnp.broadcast_to(lengths, (n_layers, b))}


def _moe_cache_xs(cache, prefix, kv_keys, n_layers, lengths, b):
    if cache is None:
        return None
    out = {k: cache[prefix + k] for k in kv_keys}
    out["lengths"] = jnp.broadcast_to(lengths, (n_layers, b))
    return out


def _hybrid_forward(cfg, params, x, positions, cache, lengths, b):
    """zamba2: groups of ``attn_every`` mamba layers + shared attn block."""
    k_every = cfg.attn_every
    n_groups = cfg.n_layers // k_every
    n_tail = cfg.n_layers - n_groups * k_every
    shared = params["shared_attn"]

    grouped = jax.tree_util.tree_map(
        lambda a: a[:n_groups * k_every].reshape(
            (n_groups, k_every) + a.shape[1:]), params["layers"])
    tail = jax.tree_util.tree_map(lambda a: a[n_groups * k_every:],
                                  params["layers"])

    def group_body(p_g, x, c_g):
        mamba_c = None if c_g is None else {"conv": c_g["conv"],
                                            "ssm": c_g["ssm"]}

        def mbody(p_i, xx, st_i):
            xx, ns = mix_layer(cfg, p_i, xx, st_i)
            return xx, ns, jnp.float32(0.0)
        x, ns, _ = _scan_layers(cfg, p_g, mbody, x, mamba_c)
        attn_c = None if c_g is None else {"k": c_g["k"], "v": c_g["v"],
                                           "lengths": c_g["lengths"]}
        x, nc_attn = dense_block(cfg, shared, x, positions, cache=attn_c)
        new_c = None
        if c_g is not None:
            new_c = {"conv": ns["conv"], "ssm": ns["ssm"],
                     "k": nc_attn["k"], "v": nc_attn["v"]}
        return x, new_c, jnp.float32(0.0)

    c_xs = None
    if cache is not None:
        c_xs = {
            "conv": cache["conv"][:n_groups * k_every].reshape(
                (n_groups, k_every) + cache["conv"].shape[1:]),
            "ssm": cache["ssm"][:n_groups * k_every].reshape(
                (n_groups, k_every) + cache["ssm"].shape[1:]),
            "k": cache["attn_k"], "v": cache["attn_v"],
            "lengths": jnp.broadcast_to(lengths, (n_groups, b)),
        }
    x, nc, _ = _scan_layers(cfg, grouped, group_body, x, c_xs)

    new_cache = None
    tail_states = None
    if n_tail:
        def tbody(p_i, xx, st_i):
            xx, ns = mix_layer(cfg, p_i, xx, st_i)
            return xx, ns, jnp.float32(0.0)
        tail_c = None
        if cache is not None:
            tail_c = {"conv": cache["conv"][n_groups * k_every:],
                      "ssm": cache["ssm"][n_groups * k_every:]}
        x, tail_states, _ = _scan_layers(cfg, tail, tbody, x, tail_c)

    if cache is not None:
        conv = nc["conv"].reshape((-1,) + nc["conv"].shape[2:])
        ssm_s = nc["ssm"].reshape((-1,) + nc["ssm"].shape[2:])
        if n_tail:
            conv = jnp.concatenate([conv, tail_states["conv"]], 0)
            ssm_s = jnp.concatenate([ssm_s, tail_states["ssm"]], 0)
        new_cache = {"conv": conv, "ssm": ssm_s,
                     "attn_k": nc["k"], "attn_v": nc["v"]}
    return x, new_cache


# ---------------------------------------------------------------------------
# Losses / steps-facing API
# ---------------------------------------------------------------------------


def lm_loss(cfg: ArchConfig, params, batch, ctx: Optional[MeshCtx] = None):
    """Next-token CE (+ MoE aux + optional MTP). batch={"tokens","labels",...}."""
    logits, aux, extras = forward(cfg, params, batch["tokens"], ctx=ctx,
                                  frontend_emb=batch.get("frontend_emb"))
    loss = _ce(logits, batch["labels"])
    total = loss + 0.01 * aux
    metrics = {"ce": loss, "aux": aux, **extras.get("moe", {})}
    if cfg.mtp_depth and "mtp" in params:
        mtp_loss = _mtp_loss(cfg, params, batch, extras["final_hidden"])
        total = total + 0.3 * mtp_loss
        metrics["mtp"] = mtp_loss
    return total, metrics


def _ce(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _mtp_loss(cfg, params, batch, hidden):
    """DeepSeek MTP (depth 1): predict t+2 from [h_t ; emb(label_t)]."""
    p = params["mtp"]
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    emb = jnp.take(params["embed"], batch["labels"], axis=0).astype(compute_dtype)
    h = jnp.concatenate([rms_norm(hidden, p["norm_h"], cfg.norm_eps),
                         rms_norm(emb, p["norm_e"], cfg.norm_eps)], -1)
    h = jnp.einsum("bsk,kd->bsd", h, p["proj"].astype(compute_dtype))
    b, s = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    mtp_cfg = dataclasses.replace(cfg, use_mla=False,
                                  d_ff=cfg.moe.dense_d_ff or cfg.d_ff)
    h, _ = dense_block(mtp_cfg, p["layer"], h, positions)
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(compute_dtype))
    # labels shifted one more step: predict labels[t+1] at position t
    mtp_labels = jnp.concatenate([batch["labels"][:, 1:],
                                  batch["labels"][:, -1:]], axis=1)
    return _ce(logits, mtp_labels)


# ---------------------------------------------------------------------------
# Cache init + input specs
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, abstract=False,
               cache_dtype=jnp.bfloat16):
    """Decode cache tree (zeros or ShapeDtypeStructs)."""
    mk = (jax.ShapeDtypeStruct if abstract
          else lambda sh, dt: jnp.zeros(sh, dt))
    hd = cfg.resolved_head_dim
    fam = cfg.family
    c: dict = {"lengths": mk((batch,), jnp.int32)}
    if fam in ("dense", "vlm", "audio"):
        c["k"] = mk((cfg.n_layers, batch, max_seq, cfg.n_kv_heads, hd), cache_dtype)
        c["v"] = mk((cfg.n_layers, batch, max_seq, cfg.n_kv_heads, hd), cache_dtype)
        if fam == "audio":
            c["memory"] = mk((batch, cfg.frontend_seq,
                              cfg.frontend_dim or cfg.d_model), jnp.float32)
    elif fam == "moe":
        n_dense = cfg.moe.n_dense_layers
        n_moe = cfg.n_layers - n_dense
        if cfg.use_mla:
            m = cfg.mla
            shapes = {"c_kv": (max_seq, m.kv_lora_rank),
                      "k_rope": (max_seq, m.qk_rope_head_dim)}
        else:
            shapes = {"k": (max_seq, cfg.n_kv_heads, hd),
                      "v": (max_seq, cfg.n_kv_heads, hd)}
        for key, sh in shapes.items():
            c[key] = mk((n_moe, batch) + sh, cache_dtype)
            if n_dense:
                c["dense_" + key] = mk((n_dense, batch) + sh, cache_dtype)
    elif fam == "ssm":
        s = cfg.ssm
        di = s.expansion * cfg.d_model
        dqk = int(di * s.qk_dim_factor)
        nh = cfg.n_heads
        c["conv"] = mk((cfg.n_layers, batch, s.conv_width - 1, di), cache_dtype)
        c["ssm"] = mk((cfg.n_layers, batch, nh, dqk // nh, di // nh + 1),
                      jnp.float32)
    elif fam == "hybrid":
        s = cfg.ssm
        di = s.expansion * cfg.d_model
        nh = di // s.head_dim
        n_groups = cfg.n_layers // cfg.attn_every
        c["conv"] = mk((cfg.n_layers, batch, s.conv_width - 1,
                        di + 2 * s.state_dim), cache_dtype)
        c["ssm"] = mk((cfg.n_layers, batch, nh, s.state_dim, s.head_dim),
                      jnp.float32)
        c["attn_k"] = mk((n_groups, batch, max_seq, cfg.n_kv_heads, hd),
                         cache_dtype)
        c["attn_v"] = mk((n_groups, batch, max_seq, cfg.n_kv_heads, hd),
                         cache_dtype)
    return c


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    tok = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32)
    f32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float32)
    front = {}
    if cfg.frontend_seq:
        front["frontend_emb"] = f32(b, cfg.frontend_seq,
                                    cfg.frontend_dim or cfg.d_model)
    if shape.kind == "train":
        return {"tokens": tok(b, s), "labels": tok(b, s), **front}
    if shape.kind == "prefill":
        return {"tokens": tok(b, s), **front}
    # decode / long_decode: one new token against a cache of size s
    return {"tokens": tok(b, 1),
            "cache": init_cache(cfg, b, s, abstract=True), **front}
