"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Tests sweep shapes/dtypes and assert_allclose(kernel(interpret=True), ref).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul_ref(a, b):
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)) \
        .astype(a.dtype)


def axpy_ref(alpha, x, y):
    return (jnp.asarray(alpha, x.dtype) * x + y).astype(x.dtype)


def conv2d_ref(x, w):
    """x (C,H,W); w (OC,C,KH,KW) -> (OC,H-KH+1,W-KW+1), fp32 accum."""
    out = jax.lax.conv_general_dilated(
        x[None].astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return out[0].astype(x.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True, kv_valid=None,
                        window=None):
    """q (B,H,Sq,D); k,v (B,H,Sk,D); kv_valid (B,Sk) bool or None.

    Pins the kernel's conventions: causal masking compares raw row/column
    indices (with a ``window``, the band ``0 <= q - k < window``), and rows
    with NO valid key output ZEROS (never the uniform softmax garbage a
    -1e30 fill produces)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    sq, sk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((q.shape[0], 1, sq, sk), bool)
    if causal:
        rel = jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :]
        mask = mask & (rel >= 0)
        if window is not None:
            mask = mask & (rel < window)
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def ssm_scan_ref(q, k, v, log_decay, scale):
    """Sequential recurrence oracle. Shapes as kernels/ssm_scan.ssm_scan."""
    bh, s, n = q.shape
    p_dim = v.shape[-1]

    def step(state, xs):
        qt, kt, vt, ldt, sct = xs
        state = state * jnp.exp(ldt.astype(jnp.float32))[:, None, None] \
            + sct.astype(jnp.float32)[:, None, None] \
            * (kt.astype(jnp.float32)[:, :, None]
               * vt.astype(jnp.float32)[:, None, :])
        y = jnp.einsum("bn,bnp->bp", qt.astype(jnp.float32), state)
        return state, y

    xs = (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
          log_decay.transpose(1, 0), scale.transpose(1, 0))
    _, ys = jax.lax.scan(step, jnp.zeros((bh, n, p_dim), jnp.float32), xs)
    return ys.transpose(1, 0, 2).astype(v.dtype)
