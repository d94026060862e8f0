"""Attention blocks: GQA (+RoPE), MLA (DeepSeek), cross-attention.

Long sequences use a chunked online-softmax formulation — blockwise-
parallel attention: queries are split into row blocks, each block scans
only its causal prefix of KV chunks (lax.scan), and a per-q-block
``jax.checkpoint`` policy bounds the residuals, so training memory is
O(S·D) instead of O(S²). The Pallas kernel in repro/kernels/attention.py
is the fused per-chip version of the same math WITH a custom-VJP backward;
``chunked_attention`` routes through it when the shapes allow (causal
triangular training, or pure kv_valid-masked cross attention) and falls
back to the jnp scan otherwise. Routing: ``REPRO_FLASH_ATTENTION=1/0``
overrides; default is kernel-on-TPU, scan elsewhere (interpret mode is a
correctness tool, not a perf path).

Convention (shared with the kernel and ref oracle): rows with NO valid
key — e.g. cross-attention against fully-padded memory — output zeros.

KV-cache decode supports per-sequence lengths (continuous batching) via
row-wise dynamic_update_slice.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ArchConfig
from repro.models.layers import (P, apply_rope, repeat_kv, rms_norm,
                                 rotary_embedding)

NEG_INF = -1e30
CHUNK_THRESHOLD = 2048  # use chunked attention when kv_len exceeds this
KV_CHUNK = 1024

# Mesh context hint set by transformer.forward: lets the MLA chunked loop
# run as an explicit lane-local shard_map. REFUTED alternative
# (EXPERIMENTS.md §Perf): with_sharding_constraint on the scan carries —
# GSPMD then fights its own layouts and reshards every iteration (measured
# 8x regression). Taking the partitioner out of the loop is deterministic.
_MESH_CTX = None


def set_mesh_ctx(ctx):
    global _MESH_CTX
    _MESH_CTX = ctx


def flash_route_enabled(mode: str = "auto") -> bool:
    """Should attention route through the Pallas flash kernel?

    ``mode`` is the config knob ("auto" | "on" | "off").  The
    ``REPRO_FLASH_ATTENTION`` env var (1/0) overrides; "auto" means
    kernel on TPU, jnp blockwise scan elsewhere (the interpreted kernel
    is a correctness tool — its grid unrolls at trace time)."""
    env = os.environ.get("REPRO_FLASH_ATTENTION", "").strip().lower()
    if env in ("1", "on", "true"):
        return True
    if env in ("0", "off", "false"):
        return False
    if mode == "on":
        return True
    if mode == "off":
        return False
    return jax.default_backend() == "tpu"


_CKPT_POLICIES = {
    "everything": "everything_saveable",
    "nothing": "nothing_saveable",
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
}


def checkpoint_policy(name: str):
    """Named jax.checkpoint policy for the per-q-block triangular loop
    (the blockwise-parallel-transformer knob). "none" -> no checkpoint."""
    if name in (None, "none", ""):
        return None
    try:
        return getattr(jax.checkpoint_policies, _CKPT_POLICIES[name])
    except KeyError:
        raise ValueError(
            f"unknown checkpoint policy {name!r}; pick one of "
            f"{['none', *_CKPT_POLICIES]}") from None


def _flash_attention(q, k, v, kv_valid, causal: bool, window=None):
    """(B,S,H,D)-layout adapter around kernels.ops.flash_attention.

    Under a mesh the kernel runs per lane-local shard (batch on the data
    axes, heads on the lane axis): GSPMD cannot partition a Pallas TPU
    kernel, so callers route here only when :func:`_flash_fits_mesh`."""
    from repro.kernels import ops as kops

    def local(q, k, v, kv_valid):
        out = kops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), kv_valid=kv_valid, causal=causal,
            window=window)
        return out.transpose(0, 2, 1, 3)

    ctx = _MESH_CTX
    if ctx is None:
        return local(q, k, v, kv_valid)
    from jax.sharding import PartitionSpec as PS
    b_axes = tuple(ctx.batch_axes)
    qkv = PS(b_axes, None, ctx.model_axis, None)
    return shard_map(local, mesh=ctx.mesh,
                     in_specs=(qkv, qkv, qkv, PS(b_axes, None)),
                     out_specs=qkv, check_vma=False)(q, k, v, kv_valid)


def _flash_fits_mesh(batch: int, heads: int) -> bool:
    """No mesh, or one the kernel can run lane-local on."""
    return _MESH_CTX is None or _lane_local_ok(batch, heads)


def _lane_local_ok(batch: int, heads: int) -> bool:
    """True when heads divide the lane axis and batch divides the data axes
    — the MLA chunked loop then runs as an explicit shard_map."""
    ctx = _MESH_CTX
    if ctx is None or ctx.mesh is None:
        return False
    import math as _math
    b_div = _math.prod(ctx.axis_sizes.get(a, 1) for a in ctx.batch_axes)
    return heads % max(ctx.n_lanes, 1) == 0 and batch % max(b_div, 1) == 0


# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------


def gqa_template(cfg: ArchConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hp, hkv = cfg.n_heads_padded, cfg.n_kv_heads
    t = {
        "wq": P((d, hp, hd), ("embed", "heads", "head_dim"), "fan_in"),
        "wk": P((d, hkv, hd), ("embed", "kv_heads", "head_dim"), "fan_in"),
        "wv": P((d, hkv, hd), ("embed", "kv_heads", "head_dim"), "fan_in"),
        "wo": P((hp, hd, d), ("heads", "head_dim", "embed"), "fan_in"),
    }
    if cross:
        t["q_norm"] = P((d,), ("embed",), "ones")
        t["gate"] = P((), (), "zeros")  # tanh-gated cross-attn (llama3.2-V)
    return t


def mla_template(cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": P((d, m.q_lora_rank), ("embed", "q_lora"), "fan_in"),
        "q_norm": P((m.q_lora_rank,), ("q_lora",), "ones"),
        "wq_b": P((m.q_lora_rank, h, qk), ("q_lora", "heads", "head_dim"), "fan_in"),
        "wkv_a": P((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "kv_lora"), "fan_in"),
        "kv_norm": P((m.kv_lora_rank,), ("kv_lora",), "ones"),
        "wkv_b": P((m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim),
                   ("kv_lora", "heads", "head_dim"), "fan_in"),
        "wo": P((h, m.v_head_dim, d), ("heads", "head_dim", "embed"), "fan_in"),
    }


# ---------------------------------------------------------------------------
# Core attention math (shared by all variants)
# ---------------------------------------------------------------------------


def _masked_softmax_attn(q, k, v, mask):
    """Single-block attention. q (B,S,H,D), k/v (B,T,H,D), mask (B,1,S,T).
    Rows with no valid key output zeros (softmax over an all-NEG_INF row
    would otherwise emit uniform garbage)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32)
    s = jnp.where(mask, s * scale, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(axis=-1, keepdims=True), p, 0.0).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", p, v)


def chunked_attention(q, k, v, q_pos, kv_valid, kv_offset=0, chunk=KV_CHUNK,
                      triangular=False, threshold=None, use_flash="auto",
                      block_remat="none", window=None):
    """Blockwise online-softmax attention over KV chunks.

    q: (B,S,H,D); k,v: (B,T,H,D); q_pos: (B,S) absolute positions;
    kv_valid: (B,T) bool; kv positions are kv_offset + arange(T).
    Causal: kv_pos <= q_pos AND kv_valid; with a ``window`` also
    q_pos - kv_pos < window (a sliding-window layer).

    ``triangular=True`` (training: S==T, q_pos==arange, kv_offset==0)
    splits queries into blocks and runs each block only against its causal
    prefix of KV chunks — ~2x less score compute and traffic than the
    rectangular loop (fully-masked blocks never run). When the flash route
    is enabled (``use_flash``/REPRO_FLASH_ATTENTION, see
    flash_route_enabled), this path dispatches to the Pallas kernel — same
    math, fused, with its custom-VJP backward. Otherwise ``block_remat``
    names the per-q-block jax.checkpoint policy ("none" | "everything" |
    "nothing" | "dots" | "dots_no_batch") bounding training residuals.

    ``threshold`` caps the materialized quadratic fast path (defaults to
    CHUNK_THRESHOLD); sequences at or below it take one masked softmax.
    """
    b, s_len, h, d = q.shape
    t_len = k.shape[1]
    kv_pos = kv_offset + jnp.arange(t_len, dtype=jnp.int32)
    if threshold is None:
        threshold = CHUNK_THRESHOLD

    tri = triangular and s_len == t_len and kv_offset == 0
    if tri and flash_route_enabled(use_flash) and _flash_fits_mesh(b, h):
        # q_pos is arange(S) by the triangular contract, so the kernel's
        # index-vs-index causal mask is exactly this mask
        return _flash_attention(q, k, v, kv_valid, causal=True,
                                window=window)

    def visible(kv_pos):
        """(B,1,S,T) causal (and band) mask of q_pos against kv_pos."""
        mask = kv_pos[None, None, None, :] <= q_pos[:, None, :, None]
        if window is not None:
            mask = mask & (q_pos[:, None, :, None]
                           - kv_pos[None, None, None, :] < window)
        return mask

    if t_len <= max(chunk, threshold):
        mask = visible(kv_pos) & kv_valid[:, None, None, :]
        return _masked_softmax_attn(q, k, v, mask)

    if tri and s_len % chunk == 0:
        blk = functools.partial(chunked_attention, kv_offset=kv_offset,
                                chunk=chunk, threshold=threshold,
                                window=window)
        policy = checkpoint_policy(block_remat)
        if block_remat not in (None, "none", ""):
            blk = jax.checkpoint(blk, policy=policy)
        outs = []
        for i in range(s_len // chunk):
            q_blk = q[:, i * chunk:(i + 1) * chunk]
            pos_blk = q_pos[:, i * chunk:(i + 1) * chunk]
            t_hi = (i + 1) * chunk
            outs.append(blk(q_blk, k[:, :t_hi], v[:, :t_hi], pos_blk,
                            kv_valid[:, :t_hi]))
        return jnp.concatenate(outs, axis=1)

    n_chunks = -(-t_len // chunk)
    pad = n_chunks * chunk - t_len
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=2**30)

    scale = 1.0 / math.sqrt(d)

    # scan over chunk *indices*, slicing K/V in place: no stacked/transposed
    # copy of the KV tensor, so GSPMD keeps the head sharding through the
    # loop (a transpose-stacked copy used to force a full all-gather)
    def body(carry, c_idx):
        acc, m_run, l_run = carry
        start = c_idx * chunk
        kb = jax.lax.dynamic_slice_in_dim(k, start, chunk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, start, chunk, axis=1)
        validb = jax.lax.dynamic_slice_in_dim(kv_valid, start, chunk, axis=1)
        posb = jax.lax.dynamic_slice_in_dim(kv_pos, start, chunk, axis=0)
        sc = jnp.einsum("bshd,bthd->bhst", q, kb,
                        preferred_element_type=jnp.float32) * scale
        mask = visible(posb) & validb[:, None, None, :]
        sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m_run, sc.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        # dead rows (m_new still NEG_INF): exp(sc - m_new) would be
        # exp(0)=1 garbage — rebase those rows at 0 so exp(-1e30) -> 0
        m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
        p = jnp.exp(sc - m_safe[..., None])
        l_new = l_run * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhst,bthd->bshd", p.astype(vb.dtype), vb)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, s_len, h, d), jnp.float32)
    m0 = jnp.full((b, h, s_len), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_len), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  jnp.arange(n_chunks, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def update_cache(cache_k, cache_v, k_new, v_new, lengths):
    """Scatter new KV rows at per-sequence write positions.

    cache_k/v: (B, Smax, Hkv, D); k/v_new: (B, S_new, Hkv, D); lengths: (B,)
    """
    def upd_row(ck, cv, kn, vn, ln):
        ck = jax.lax.dynamic_update_slice(ck, kn.astype(ck.dtype), (ln, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, vn.astype(cv.dtype), (ln, 0, 0))
        return ck, cv
    return jax.vmap(upd_row)(cache_k, cache_v, k_new, v_new, lengths)


# ---------------------------------------------------------------------------
# GQA attention (train / prefill / decode)
# ---------------------------------------------------------------------------


def gqa_attention(cfg: ArchConfig, p: dict, x, positions, *,
                  cache: Optional[dict] = None, kv_valid=None, causal=True,
                  prefill_from_zero=False, window=None, yarn=None):
    """x (B,S,d); positions (B,S) absolute. cache = {"k","v","lengths"} or None.
    ``window``: a sliding-window layer's width; ``yarn``: YaRN rotary
    scaling (a full layer's, ``cfg.yarn``).

    Returns (out (B,S,d), new_cache_entries or None).
    """
    h, hkv, hd = cfg.n_heads_padded, cfg.n_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))

    cos, sin = rotary_embedding(positions, hd, cfg.rope_theta, yarn)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    new_cache = None
    if cache is not None:
        ck, cv = update_cache(cache["k"], cache["v"], k, v, cache["lengths"])
        new_cache = {"k": ck, "v": cv}
        t_len = ck.shape[1]
        kv_valid = jnp.arange(t_len, dtype=jnp.int32)[None, :] \
            <= positions[:, -1:]  # rows written so far (incl. current step)
        k_full, v_full = ck.astype(x.dtype), cv.astype(x.dtype)
    else:
        k_full, v_full = k, v
        if kv_valid is None:
            kv_valid = jnp.ones(k.shape[:2], bool)

    k_full = repeat_kv(k_full, h // hkv)
    v_full = repeat_kv(v_full, h // hkv)
    mask_pos = positions if causal else jnp.full_like(positions, 2**29)
    # triangular only for the no-cache (training) path: measured on the
    # dry-run profiler, the q-block loop over a repeat_kv'd cache reshards
    # at every block boundary and regresses GQA prefill 3.8x (§Perf)
    out = chunked_attention(q, k_full, v_full, mask_pos, kv_valid,
                            triangular=causal and cache is None,
                            chunk=getattr(cfg, "attn_chunk", KV_CHUNK),
                            threshold=getattr(cfg, "attn_threshold", 0)
                            or None,
                            use_flash=getattr(cfg, "attn_flash", "auto"),
                            block_remat=getattr(cfg, "attn_block_remat",
                                                "none"),
                            window=window)
    out = _mask_pad_heads(cfg, out)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_cache


def _mask_pad_heads(cfg: ArchConfig, out):
    """Zero the padded heads' outputs so wq/wo pad blocks receive zero
    gradient — padding stays model-equivalent through training.

    GQA grouping: repeat_kv assigns q head h to kv group h // (Hp/hkv), so
    the live heads are the first H/hkv slots of each group — the q<->kv
    pairing of the unpadded model is preserved."""
    hp, h, hkv = cfg.n_heads_padded, cfg.n_heads, cfg.n_kv_heads
    if hp == h:
        return out
    per_group_pad = hp // hkv
    per_group_live = h // hkv
    head_live = (jnp.arange(hp) % per_group_pad) < per_group_live
    return out * head_live.astype(out.dtype)[None, None, :, None]


def cross_attention(cfg: ArchConfig, p: dict, x, memory, memory_valid=None):
    """Cross-attn to encoder/vision memory (B,T,d). Tanh-gated if gate in p."""
    h, hkv, hd = cfg.n_heads_padded, cfg.n_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", memory, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", memory, p["wv"].astype(x.dtype))
    k = repeat_kv(k, h // hkv)
    v = repeat_kv(v, h // hkv)
    b, t = memory.shape[:2]
    if memory_valid is None:
        memory_valid = jnp.ones((b, t), bool)
    if flash_route_enabled(getattr(cfg, "attn_flash", "auto")) \
            and _flash_fits_mesh(b, h):
        # pure kv_valid masking (no causal term) is exactly the kernel's
        # non-causal mode; fully-padded memory rows output zeros either way
        out = _flash_attention(q, k, v, memory_valid, causal=False)
    else:
        mask = memory_valid[:, None, None, :]
        out = _masked_softmax_attn(q, k, v, mask)
    out = _mask_pad_heads(cfg, out)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if "gate" in p:
        out = jnp.tanh(p["gate"]).astype(x.dtype) * out
    return out


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ---------------------------------------------------------------------------


def _mla_triangular(cfg, q_nope, q_rope, c_kv, k_rope, wkv_b, q_pos,
                    chunk=KV_CHUNK, lane_local=False):
    """Causal-triangle q-block loop around mla_chunked (training/prefill:
    S == T, positions == arange): ~2x less score work than rectangular."""
    s_len = q_nope.shape[1]
    if s_len % chunk or s_len <= chunk:
        return mla_chunked(cfg, q_nope, q_rope, c_kv, k_rope, wkv_b, q_pos,
                           jnp.ones(c_kv.shape[:2], bool), chunk,
                           lane_local=lane_local)
    outs = []
    for i in range(s_len // chunk):
        sl = slice(i * chunk, (i + 1) * chunk)
        t_hi = (i + 1) * chunk
        outs.append(mla_chunked(
            cfg, q_nope[:, sl], q_rope[:, sl], c_kv[:, :t_hi],
            k_rope[:, :t_hi], wkv_b, q_pos[:, sl],
            jnp.ones((c_kv.shape[0], t_hi), bool), chunk,
            lane_local=lane_local))
    return jnp.concatenate(outs, axis=1)


def mla_chunked(cfg, q_nope, q_rope, c_kv, k_rope, wkv_b, q_pos, kv_valid,
                chunk=KV_CHUNK, lane_local=False):
    """Dispatcher: explicit lane-local shard_map when the mesh allows
    (heads on lanes, batch on data — zero collectives inside the loop;
    the Ara lane principle applied to attention). Inference-only: through
    jax.grad the shard_map boundary makes GSPMD replicate the full-batch
    cotangents (measured 2x train regression — §Perf), so training uses
    the GSPMD in-place-slice loop."""
    ctx = _MESH_CTX
    if not (lane_local and _lane_local_ok(q_nope.shape[0], q_nope.shape[2])):
        return _mla_chunked(cfg, q_nope, q_rope, c_kv, k_rope, wkv_b,
                            q_pos, kv_valid, chunk)
    import functools
    from jax.sharding import PartitionSpec as PS
    b_axes = tuple(ctx.batch_axes)
    fn = functools.partial(_mla_chunked, cfg, chunk=chunk)
    return shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(PS(b_axes, None, ctx.model_axis, None),   # q_nope
                  PS(b_axes, None, ctx.model_axis, None),   # q_rope
                  PS(b_axes, None, None),                   # c_kv
                  PS(b_axes, None, None),                   # k_rope
                  PS(None, ctx.model_axis, None),           # wkv_b
                  PS(b_axes, None),                         # q_pos
                  PS(b_axes, None)),                        # kv_valid
        out_specs=PS(b_axes, None, ctx.model_axis, None),
        check_vma=False,
    )(q_nope, q_rope, c_kv, k_rope, wkv_b, q_pos, kv_valid)


def _mla_chunked(cfg, q_nope, q_rope, c_kv, k_rope, wkv_b, q_pos, kv_valid,
                 chunk=KV_CHUNK):
    """Chunked MLA attention without materializing expanded K/V.

    The (B,T,H,192) expanded key concat(k_nope, broadcast(k_rope)) defeats
    GSPMD head-sharding propagation (the dry-run showed a 103 GB/layer
    all-gather). Instead: expand KV per chunk inside the scan from the
    compressed cache (FlashMLA-style) and keep the rope term as a separate
    head-free einsum. q_nope (B,S,H,nope); q_rope (B,S,H,rope);
    c_kv (B,T,kv_lora); k_rope (B,T,rope) [already rotary-encoded].
    """
    m = cfg.mla
    nope, rope, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    b, s_len, h, _ = q_nope.shape
    t_len = c_kv.shape[1]
    scale = 1.0 / math.sqrt(nope + rope)
    kv_pos = jnp.arange(t_len, dtype=jnp.int32)

    chunk = min(chunk, t_len)
    n_chunks = -(-t_len // chunk)
    pad = n_chunks * chunk - t_len
    if pad:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
        kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=2**30)

    def body(carry, c_idx):
        acc, m_run, l_run = carry
        start = c_idx * chunk
        ckv_b = jax.lax.dynamic_slice_in_dim(c_kv, start, chunk, 1)
        ckr_b = jax.lax.dynamic_slice_in_dim(k_rope, start, chunk, 1)
        validb = jax.lax.dynamic_slice_in_dim(kv_valid, start, chunk, 1)
        posb = jax.lax.dynamic_slice_in_dim(kv_pos, start, chunk, 0)
        kv_b = jnp.einsum("btr,rhk->bthk", ckv_b, wkv_b)
        k_nope_b, v_b = kv_b[..., :nope], kv_b[..., nope:]
        sc = jnp.einsum("bshk,bthk->bhst", q_nope, k_nope_b,
                        preferred_element_type=jnp.float32)
        sc += jnp.einsum("bshk,btk->bhst", q_rope, ckr_b,
                         preferred_element_type=jnp.float32)
        sc *= scale
        mask = (posb[None, None, None, :] <= q_pos[:, None, :, None]) \
            & validb[:, None, None, :]
        sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m_run, sc.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)  # dead rows -> 0
        p = jnp.exp(sc - m_safe[..., None])
        l_new = l_run * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhst,bthd->bshd", p.astype(v_b.dtype), v_b)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, s_len, h, dv), jnp.float32)
    m0 = jnp.full((b, h, s_len), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_len), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  jnp.arange(n_chunks, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q_nope.dtype)


def mla_attention(cfg: ArchConfig, p: dict, x, positions, *,
                  cache: Optional[dict] = None, prefill_from_zero=False):
    """Multi-head Latent Attention.

    Prefill/train: expanded form. Decode (cache): absorbed form — scores and
    values computed directly in the compressed kv_lora space, so the cache is
    (B, Smax, kv_lora) + (B, Smax, rope) regardless of head count.
    """
    m = cfg.mla
    h = cfg.n_heads
    nope, rope, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q_lat = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype)),
                     p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"].astype(x.dtype))
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c_kv = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:]

    cos, sin = rotary_embedding(positions, rope, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]

    if cache is None:
        out = _mla_triangular(cfg, q_nope, q_rope, c_kv, k_rope,
                              p["wkv_b"].astype(x.dtype), positions)
        new_cache = None
    else:
        wkv_b = p["wkv_b"].astype(x.dtype)
        w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
        lengths = cache["lengths"]

        def upd(c, n, ln):
            return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (ln, 0))
        ckv = jax.vmap(upd)(cache["c_kv"], c_kv, lengths)
        ckr = jax.vmap(upd)(cache["k_rope"], k_rope, lengths)
        new_cache = {"c_kv": ckv, "k_rope": ckr}
        t_len = ckv.shape[1]
        kv_valid = jnp.arange(t_len, dtype=jnp.int32)[None, :] <= positions[:, -1:]

        if x.shape[1] > 1:
            # prefill: chunked attention over the updated compressed cache;
            # from-zero prefill walks the causal triangle only
            if prefill_from_zero and x.shape[1] == ckv.shape[1]:
                out = _mla_triangular(cfg, q_nope, q_rope,
                                      ckv.astype(x.dtype),
                                      ckr.astype(x.dtype), wkv_b, positions,
                                      lane_local=True)
            else:
                out = mla_chunked(cfg, q_nope, q_rope, ckv.astype(x.dtype),
                                  ckr.astype(x.dtype), wkv_b, positions,
                                  kv_valid, lane_local=True)
        else:
            # absorbed single-token decode: O(kv_lora) per cached token
            q_c = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)  # absorb W_UK
            s = jnp.einsum("bshr,btr->bhst", q_c, ckv.astype(x.dtype),
                           preferred_element_type=jnp.float32)
            s += jnp.einsum("bshk,btk->bhst", q_rope, ckr.astype(x.dtype),
                            preferred_element_type=jnp.float32)
            s *= 1.0 / math.sqrt(nope + rope)
            s = jnp.where(kv_valid[:, None, None, :], s, NEG_INF)
            pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            o_c = jnp.einsum("bhst,btr->bshr", pr, ckv.astype(x.dtype))
            out = jnp.einsum("bshr,rhk->bshk", o_c, w_uv)

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_cache
