"""Public jit'd wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; the
kernel body then runs as the Pallas interpreter, validating semantics) and
False on TPU where the compiled kernel is the fast path.

Multi-precision: every wrapper takes ``policy`` (core.precision.Policy) —
inputs are cast to ``policy.compute_dtype`` before the kernel, so bf16/f16
compute with fp32 in-kernel accumulation is one kwarg away. This is the
same Policy the analytical perf model consults, keeping the TPU kernels
and the Ara datapath-split model on one source of per-precision truth.
``policy.lmul`` likewise flows into the matmul/axpy block-shape pick
(core.stripmine.lmul_tile) unless the caller passes ``lmul=`` explicitly —
register grouping and element width travel together, as in vsetvl.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import Policy
from repro.kernels.attention import flash_attention as _flash
from repro.kernels.axpy import axpy as _axpy
from repro.kernels.conv import conv2d_direct as _conv
from repro.kernels.matmul import matmul as _matmul
from repro.kernels.matmul import matmul_int8 as _matmul_int8
from repro.kernels.ssm_scan import ssm_scan as _ssm


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _cast(policy, *arrays):
    if policy is None:
        return arrays
    dt = jnp.dtype(policy.compute_dtype)
    return tuple(a.astype(dt) for a in arrays)


def matmul(a, b, *, policy: Policy | None = None, **kw):
    kw.setdefault("interpret", _default_interpret())
    if policy is not None:
        kw.setdefault("lmul", policy.lmul)
    a, b = _cast(policy, a, b)
    return _matmul(a, b, **kw)


def matmul_int8(a, b, *, policy: Policy | None = None, **kw):
    """SEW=8 route: int8 inputs, int32 accumulation, optional int8
    requantize (``out_dtype=jnp.int8, shift=``). No dtype cast here —
    int8 operands are the caller's quantization decision."""
    kw.setdefault("interpret", _default_interpret())
    if policy is not None:
        kw.setdefault("lmul", policy.lmul)
    return _matmul_int8(a, b, **kw)


def axpy(alpha, x, y, *, policy: Policy | None = None, **kw):
    kw.setdefault("interpret", _default_interpret())
    if policy is not None:
        kw.setdefault("lmul", policy.lmul)
    x, y = _cast(policy, x, y)
    return _axpy(alpha, x, y, **kw)


def conv2d(x, w, *, policy: Policy | None = None, **kw):
    kw.setdefault("interpret", _default_interpret())
    x, w = _cast(policy, x, w)
    return _conv(x, w, **kw)


def flash_attention(q, k, v, *, policy: Policy | None = None, **kw):
    """Blockwise flash attention with a training-grade VJP (see
    kernels/attention.py). ``policy.attn_bq``/``attn_bk`` pick the block
    shapes (None: ``attention.default_block``); ``kv_valid`` passes
    through uncast (it is a mask, not data)."""
    kw.setdefault("interpret", _default_interpret())
    if policy is not None:
        kw.setdefault("bq", policy.attn_bq)
        kw.setdefault("bk", policy.attn_bk)
    q, k, v = _cast(policy, q, k, v)
    return _flash(q, k, v, **kw)


def ssm_scan(q, k, v, log_decay, scale, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _ssm(q, k, v, log_decay, scale, **kw)


# ---------------------------------------------------------------------------
# Serving logits head (Policy-routed degrade ladder)
# ---------------------------------------------------------------------------


def _mxu_tiles(m: int, k: int, n: int, b: int = 128) -> bool:
    """True when (m,k)@(k,n) tiles the Pallas matmul's MXU blocks."""
    return all(d % min(b, d) == 0 for d in (m, k, n))


def lm_head_route(m: int, k: int, n: int, compute_dtype: str) -> str:
    """Which path :func:`lm_head` takes for an (m,k)@(k,n) head at a given
    compute dtype — host-side, so the serving engine can log the route."""
    if compute_dtype in ("float32", "float64"):
        return "einsum-fp32"
    if not _mxu_tiles(m, k, n):
        return "einsum-fallback"
    return "pallas-int8" if compute_dtype == "int8" \
        else f"pallas-{jnp.dtype(compute_dtype).name}"


def lm_head(x, w, *, compute_dtype: str = "float32", interpret=None):
    """Logits head ``x (B,S,D) @ w (D,V) -> (B,S,V) float32``, routed by
    compute dtype — the serving degrade ladder's consumer of the PR-1/PR-5
    Policy kernels, so the quantized datapath actually carries traffic:

    - ``float32``: plain einsum (the exact path).
    - ``bfloat16``/``float16``: the Pallas :func:`matmul` kernel at the
      narrow width with fp32 VMEM accumulation (§III-E4's 2x rate).
    - ``int8``: dynamic symmetric per-tensor quantization of both
      operands through :func:`matmul_int8` (int32 accumulation, the 8x
      Ara rung / TPU 394-TOPS mode), dequantized to fp32 logits.

    Shapes that don't tile the MXU blocks fall back to an einsum at the
    requested width (``lm_head_route`` reports which path ran).
    """
    b, s, d = x.shape
    d2, v = w.shape
    assert d == d2, (x.shape, w.shape)
    route = lm_head_route(b * s, d, v, compute_dtype)
    x2 = x.reshape(b * s, d)
    if route == "einsum-fp32":
        out = jnp.einsum("md,dv->mv", x2.astype(jnp.float32),
                         w.astype(jnp.float32))
    elif route == "pallas-int8":
        sx = jnp.max(jnp.abs(x2.astype(jnp.float32))) / 127.0 + 1e-8
        sw = jnp.max(jnp.abs(w.astype(jnp.float32))) / 127.0 + 1e-8
        qx = jnp.clip(jnp.round(x2.astype(jnp.float32) / sx),
                      -127, 127).astype(jnp.int8)
        qw = jnp.clip(jnp.round(w.astype(jnp.float32) / sw),
                      -127, 127).astype(jnp.int8)
        acc = matmul_int8(qx, qw)                    # exact int32
        out = acc.astype(jnp.float32) * (sx * sw)
    elif route == "einsum-fallback":
        dt = jnp.dtype("bfloat16" if compute_dtype == "int8"
                       else compute_dtype)
        out = jnp.einsum("md,dv->mv", x2.astype(dt), w.astype(dt),
                         preferred_element_type=jnp.float32)
    else:
        dt = jnp.dtype(compute_dtype)
        kw = {"out_dtype": jnp.float32}
        if interpret is not None:
            kw["interpret"] = interpret
        out = matmul(x2.astype(dt), w.astype(dt), **kw)
    return out.astype(jnp.float32).reshape(b, s, v)
