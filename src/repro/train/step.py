"""Train / prefill / decode step builders with full sharding specs.

``make_train_step`` returns (step_fn, state_specs, batch_specs) ready for
``jax.jit(step_fn, in_shardings=..., out_shardings=...)`` — used identically
by the real trainer and by the AOT dry-run (ShapeDtypeStructs in, compiled
HLO out). Grad accumulation strip-mines the batch through a lax.scan
(the paper's setvl loop — core/stripmine.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import transformer as tf
from repro.models.layers import abstract_params, param_specs
from repro.models.sharding import MeshCtx, kv_cache_rules, make_rules
from repro.optim import adamw


# ---------------------------------------------------------------------------
# Spec plumbing
# ---------------------------------------------------------------------------


def batch_pspecs(cfg: ArchConfig, shape_kind: str, ctx: MeshCtx) -> dict:
    b_axes = tuple(ctx.batch_axes)
    specs = {"tokens": PS(b_axes, None)}
    if shape_kind == "train":
        specs["labels"] = PS(b_axes, None)
    if cfg.frontend_seq:
        specs["frontend_emb"] = PS(b_axes, None, None)
    return specs


def cache_pspecs(cfg: ArchConfig, ctx: MeshCtx) -> dict:
    """PartitionSpecs matching init_cache's tree."""
    rules = kv_cache_rules(cfg, ctx)
    b = PS(tuple(ctx.batch_axes))

    def spec(axes):
        from repro.models.layers import P as PT
        return rules.spec_for(PT(tuple(1000 for _ in axes), tuple(axes)))

    fam = cfg.family
    c = {"lengths": b}
    kv_axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    if fam in ("dense", "vlm", "audio"):
        c["k"] = spec(kv_axes)
        c["v"] = spec(kv_axes)
        if fam == "audio":
            c["memory"] = spec(("batch", "seq", "embed"))
    elif fam == "moe":
        keys = ("c_kv", "k_rope") if cfg.use_mla else ("k", "v")
        axes = {"c_kv": ("layers", "batch", "kv_seq", "kv_lora"),
                "k_rope": ("layers", "batch", "kv_seq", "kv_lora"),
                "k": kv_axes, "v": kv_axes}
        for k in keys:
            c[k] = spec(axes[k])
            if cfg.moe.n_dense_layers:
                c["dense_" + k] = spec(axes[k])
    elif fam == "ssm":
        c["conv"] = spec(("layers", "batch", "seq", "d_inner"))
        c["ssm"] = spec(("layers", "batch", "heads", "ssm_state", "head_dim"))
    elif fam == "hybrid":
        c["conv"] = spec(("layers", "batch", "seq", "d_inner"))
        c["ssm"] = spec(("layers", "batch", "heads", "ssm_state", "head_dim"))
        c["attn_k"] = spec(("groups", "batch", "kv_seq", "kv_heads", "head_dim"))
        c["attn_v"] = spec(("groups", "batch", "kv_seq", "kv_heads", "head_dim"))
    return c


def named(tree, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, PS))


def sanitize_specs(spec_tree, aval_tree, mesh):
    """jit in_/out_shardings require even tiling: drop mesh axes from dims
    they don't divide (e.g. batch=1 over data=16, 24 heads over 16 lanes).
    Replication is the correct conservative fallback; EXPERIMENTS.md notes
    where it costs performance."""
    import math
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix(spec, aval):
        if spec is None or not isinstance(spec, PS):
            return spec
        entries = list(spec)
        new = []
        for i, entry in enumerate(entries):
            if entry is None or i >= len(aval.shape):
                new.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            prod = math.prod(sizes.get(a, 1) for a in axes)
            new.append(entry if prod > 0 and aval.shape[i] % prod == 0
                       else None)
        return PS(*new)

    return jax.tree_util.tree_map(
        fix, spec_tree, aval_tree,
        is_leaf=lambda x: x is None or isinstance(x, PS))


def named_for(spec_tree, aval_tree, mesh):
    return named(sanitize_specs(spec_tree, aval_tree, mesh), mesh)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnOverrides:
    """Per-run attention-path overrides (long-context training knobs).

    Each field, when set, replaces the matching ArchConfig field before the
    step closes over it: ``flash`` routes chunked_attention through the
    Pallas kernel ("auto" | "on" | "off"), ``chunk`` sets the KV chunk of
    the blockwise scan, ``threshold`` caps the materialized quadratic
    fast path, ``block_remat`` names the per-q-block jax.checkpoint
    policy (see models.attention.checkpoint_policy)."""
    flash: Optional[str] = None
    chunk: Optional[int] = None
    threshold: Optional[int] = None
    block_remat: Optional[str] = None


def apply_attn_overrides(cfg: ArchConfig,
                         attn: Optional[AttnOverrides]) -> ArchConfig:
    """cfg with any set AttnOverrides fields swapped in (frozen-safe)."""
    if attn is None:
        return cfg
    upd = {}
    if attn.flash is not None:
        upd["attn_flash"] = attn.flash
    if attn.chunk is not None:
        upd["attn_chunk"] = attn.chunk
    if attn.threshold is not None:
        upd["attn_threshold"] = attn.threshold
    if attn.block_remat is not None:
        upd["attn_block_remat"] = attn.block_remat
    return dataclasses.replace(cfg, **upd) if upd else cfg


@dataclasses.dataclass(frozen=True)
class TrainStepBundle:
    step_fn: object          # (state, batch) -> (state, metrics)
    state_specs: dict        # PartitionSpec tree for state
    batch_specs: dict
    abstract_state: dict     # ShapeDtypeStruct tree (dry-run / init shapes)


def make_train_state_abstract(cfg: ArchConfig, opt_cfg: adamw.OptConfig):
    tmpl = tf.model_template(cfg)
    aparams = abstract_params(tmpl, jnp.dtype(cfg.param_dtype))
    return {"params": aparams, "opt": adamw.abstract_state(opt_cfg, aparams)}


def train_state_specs(cfg: ArchConfig, ctx: MeshCtx) -> dict:
    tmpl = tf.model_template(cfg)
    rules = make_rules(cfg, ctx)
    pspecs = param_specs(tmpl, rules)
    return {"params": pspecs,
            "opt": {"m": pspecs, "v": pspecs, "step": PS()}}


def make_train_step(cfg: ArchConfig, opt_cfg: adamw.OptConfig, ctx: MeshCtx,
                    grad_accum: int = 1,
                    attn: Optional[AttnOverrides] = None) -> TrainStepBundle:
    cfg = apply_attn_overrides(cfg, attn)

    def loss_fn(params, batch):
        loss, metrics = tf.lm_loss(cfg, params, batch, ctx=ctx)
        return loss, metrics

    def train_step(state, batch):
        params = state["params"]
        if grad_accum > 1:
            from repro.core.stripmine import stripmined_grads
            (loss, metrics), grads = stripmined_grads(
                loss_fn, params, batch, grad_accum)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw.update(
                opt_cfg, grads, state["opt"], params)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return {"params": new_params, "opt": new_opt}, metrics

    specs = train_state_specs(cfg, ctx)
    bspecs = batch_pspecs(cfg, "train", ctx)
    astate = make_train_state_abstract(cfg, opt_cfg)
    return TrainStepBundle(train_step, specs, bspecs, astate)


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ArchConfig, ctx: MeshCtx, max_seq: int):
    """(params, tokens[, frontend_emb]) -> (logits_last, cache)."""
    def prefill(params, batch):
        b = batch["tokens"].shape[0]
        cache = tf.init_cache(cfg, b, max_seq)
        tf.set_prefill_hint(True)
        try:
            logits, _, cache = tf.forward(
                cfg, params, batch["tokens"], ctx=ctx, cache=cache,
                frontend_emb=batch.get("frontend_emb"))
        finally:
            tf.set_prefill_hint(False)
        return logits[:, -1], cache
    return prefill


def make_decode_step(cfg: ArchConfig, ctx: MeshCtx):
    """(params, cache, tokens) -> (logits, new_cache)."""
    def decode(params, cache, batch):
        logits, _, cache = tf.forward(cfg, params, batch["tokens"], ctx=ctx,
                                      cache=cache,
                                      frontend_emb=batch.get("frontend_emb"))
        return logits[:, -1], cache
    return decode
