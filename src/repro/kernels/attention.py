"""Blockwise flash attention: fused online-softmax forward AND backward.

The per-chip hot spot behind models/attention.chunked_attention: KV blocks
stream through VMEM while the running max/denominator carry in scratch —
the same operand-queue streaming discipline as Ara's chained VFMA, applied
to the softmax recurrence. Nothing O(Sq*Sk) is ever materialized: the
forward saves only the per-row log-sum-exp, and the backward re-computes
each probability block (recompute-p) while accumulating dQ / dK / dV in
fp32 VMEM scratch, so bf16 training holds sequence lengths the quadratic
path cannot.

Contract (normative — see docs/kernels.md):

- ``flash_attention(q, k, v, kv_valid=, causal=, bq=, bk=)`` with
  q (B,H,Sq,D), k/v (B,H,Sk,D), optional kv_valid (B,Sk) bool. Sq/Sk are
  padded internally to block multiples (padded keys are masked, padded
  query rows are sliced off) — ragged lengths are first-class, and
  genuinely unsupported inputs raise ``ValueError`` naming the shapes.
- Block shapes left as None follow ``default_block``: the largest of
  1024, 512, 256, 128 that pads the length no further than a multiple
  of 128 does and whose (block, D) tile fits 512 KiB, the length itself
  below 128. A grid step costs fixed pipeline time, so the largest tile
  that adds no padding runs fastest.
- Causal masking compares raw row/column indices (``q_pos >= k_pos``),
  matching ``ref.flash_attention_ref``.
- Causal block-skip is real: KV blocks strictly above the diagonal issue
  NO MXU work (``pl.when`` around the whole block body), and
  ``flash_attention_probe`` returns the per-(batch*head, q-block) count of
  blocks that did issue — the triangular case provably runs O(n_k/2)
  iterations per q row-block (asserted in tests). The index maps clamp a
  skipped step onto the block already resident, so it fetches nothing.
- A static ``window`` (causal only) adds the band's lower edge: q sees k
  iff ``0 <= q - k < window``, in the skip predicate, the masks and the
  index maps of all three kernels, so blocks below the band are neither
  computed nor fetched (15 of 64 live at 8192 keys, 1024 blocks and
  window 1024, against 36 causal). ``window=None`` traces the causal
  program unchanged.
- Fully-masked rows (every key invalid — e.g. cross-attention padding)
  output ZEROS, with lse pinned to NEG_INF and zero gradients; never
  ``acc / max(l, eps)`` garbage.
- ``jax.grad`` works through it: a ``jax.custom_vjp`` pairs the forward
  with two Pallas backward kernels (dQ; dK+dV), both skipping
  fully-masked blocks, both accumulating in fp32 regardless of input
  dtype. Block sizes ride on ``core.precision.Policy`` (``attn_bq`` /
  ``attn_bk``, None for the rule above) through
  ``kernels.ops.flash_attention``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# rows whose running max never left NEG_INF saw no valid key; exp() against
# a 0.0 stand-in underflows every masked score to exactly 0 instead of the
# exp(NEG_INF - NEG_INF) == 1 garbage the old kernel produced
_DEAD_ROW = NEG_INF * 0.5


def _causal_need(qb, kb, bq: int, bk: int, window=None):
    """Traced predicate: does KV block kb intersect the causal triangle of
    q row-block qb (and, with a ``window``, the band q - k < window)?
    False means every (q, k) pair in the tile is masked — the block must
    issue no MXU work."""
    need = kb * bk <= qb * bq + bq - 1
    if window is not None:
        need = need & (kb * bk + bk - 1 > qb * bq - window)
    return need


def _kv_index(causal: bool, bq: int, bk: int, window=None):
    """KV block index map of the forward and dQ grids. Under the causal
    skip a step past q row-block qb's last needed KV block names that
    block again, and a step before its first needed block (the window's
    lower edge) names that one; the pipeline, which fetches only when an
    index changes, copies nothing for either."""
    if not causal:
        return lambda qb, kb: kb
    if window is None:
        return lambda qb, kb: jnp.minimum(kb, (qb * bq + bq - 1) // bk)
    return lambda qb, kb: jnp.clip(
        kb, jnp.maximum(qb * bq - window + 1, 0) // bk,
        (qb * bq + bq - 1) // bk)


def _q_index(causal: bool, bq: int, bk: int, n_q: int, window=None):
    """q row-block index map of the dK/dV grid. Under the causal skip a
    step before KV block kb's first needed row-block names that block,
    and with a ``window`` a step past its last needed one (the band's
    edge) names that one; capped at the last row-block for a KV block no
    row needs (Sk > Sq)."""
    if not causal:
        return lambda kb, qb: qb
    if window is None:
        return lambda kb, qb: jnp.minimum(jnp.maximum(qb, (kb * bk) // bq),
                                          n_q - 1)
    return lambda kb, qb: jnp.minimum(
        jnp.clip(qb, (kb * bk) // bq, (kb * bk + bk + window - 2) // bq),
        n_q - 1)


def _mask(mask, qb, kb, *, causal: bool, bq: int, bk: int, window):
    """The key mask of a (bq, bk) tile with the causal edge and, with a
    ``window``, the band's lower edge: q sees k iff 0 <= q - k < window."""
    if not causal:
        return mask
    q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = mask & (q_pos >= k_pos)
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    return mask


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, kvm_ref, o_ref, lse_ref, probe_ref,
                m_ref, l_ref, acc_ref, *,
                scale: float, causal: bool, bq: int, bk: int, n_k: int,
                window):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        probe_ref[...] = jnp.zeros_like(probe_ref)

    def _work():
        q = q_ref[0]                       # (bq, d)
        k = k_ref[0]                       # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask(kvm_ref[0] != 0, qb, kb, causal=causal, bq=bq, bk=bk,
                     window=window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # dead rows keep m_new == NEG_INF; exp() against 0.0 underflows all
        # their (masked) scores to 0 instead of exp(0) == 1
        m_safe = jnp.where(m_new > _DEAD_ROW, m_new, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_safe)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha \
            + jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        probe_ref[...] += 1

    if causal:
        pl.when(_causal_need(qb, kb, bq, bk, window))(_work)
        kb_last = jnp.minimum(n_k - 1, (qb * bq + bq - 1) // bk)
    else:
        _work()
        kb_last = n_k - 1

    @pl.when(kb == kb_last)
    def _done():
        l = l_ref[...]
        live = l > 0.0
        l_safe = jnp.where(live, l, 1.0)
        o_ref[0] = jnp.where(live, acc_ref[...] / l_safe, 0.0) \
            .astype(o_ref.dtype)
        lse_ref[0] = jnp.where(live, m_ref[...] + jnp.log(l_safe), NEG_INF)


def _fwd_call(qf, kf, vf, kvm, *, causal: bool, bq: int, bk: int,
              interpret: bool, window=None):
    """Padded flat call: qf (G,Sq,D), kf/vf (G,Sk,D), kvm (G,1,Sk) int32.
    Returns (out (G,Sq,D), lse (G,Sq,1) f32, probe (G,n_q,1,1) int32).

    Every block's last two dims are full array dims or (8, 128)-aligned,
    as the TPU lowering requires: the key mask is a row per group
    (block (1,1,bk)), the per-row stats are columns (block (1,bq,1), the
    same (bq, 1) shape as the m/l scratch), and the probe carries two unit
    trailing dims so each (group, q-block) counter is a whole block."""
    g, sq, d = qf.shape
    sk = kf.shape[1]
    n_q, n_k = sq // bq, sk // bk
    scale = 1.0 / math.sqrt(d)
    kv = _kv_index(causal, bq, bk, window)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, n_k=n_k, window=window),
        grid=(g, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda gi, qb, kb: (gi, qb, 0)),
            pl.BlockSpec((1, bk, d), lambda gi, qb, kb: (gi, kv(qb, kb), 0)),
            pl.BlockSpec((1, bk, d), lambda gi, qb, kb: (gi, kv(qb, kb), 0)),
            pl.BlockSpec((1, 1, bk), lambda gi, qb, kb: (gi, 0, kv(qb, kb))),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda gi, qb, kb: (gi, qb, 0)),
            pl.BlockSpec((1, bq, 1), lambda gi, qb, kb: (gi, qb, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda gi, qb, kb: (gi, qb, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, sq, d), qf.dtype),
            jax.ShapeDtypeStruct((g, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((g, n_q, 1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, kvm)


# ---------------------------------------------------------------------------
# Backward kernels (recompute-p)
# ---------------------------------------------------------------------------


def _recompute_p(q_ref, k_ref, kvm_ref, lse_ref, qb, kb, *,
                 scale: float, causal: bool, bq: int, bk: int, window):
    """Rebuild the (bq, bk) probability block from q, k and the saved lse.
    Masked positions and dead rows come back exactly 0."""
    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _mask(kvm_ref[0] != 0, qb, kb, causal=causal, bq=bq, bk=bk,
                 window=window)
    lse = lse_ref[0]                       # (bq, 1)
    lse_safe = jnp.where(lse > _DEAD_ROW, lse, 0.0)
    return jnp.where(mask, jnp.exp(s - lse_safe), 0.0)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *,
                   scale: float, causal: bool, bq: int, bk: int, n_k: int,
                   window):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _work():
        p = _recompute_p(q_ref, k_ref, kvm_ref, lse_ref, qb, kb,
                         scale=scale, causal=causal, bq=bq, bk=bk,
                         window=window)
        do = do_ref[0]
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_need(qb, kb, bq, bk, window))(_work)
    else:
        _work()

    @pl.when(kb == n_k - 1)
    def _done():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, bq: int, bk: int, n_q: int,
                    window):
    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _work():
        p = _recompute_p(q_ref, k_ref, kvm_ref, lse_ref, qb, kb,
                         scale=scale, causal=causal, bq=bq, bk=bk,
                         window=window)
        do = do_ref[0]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_need(qb, kb, bq, bk, window))(_work)
    else:
        _work()

    @pl.when(qb == n_q - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp core (operates on padded, flattened operands)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(qf, kf, vf, kvm, causal, bq, bk, interpret, window):
    out, _, _ = _fwd_call(qf, kf, vf, kvm, causal=causal, bq=bq, bk=bk,
                          interpret=interpret, window=window)
    return out


def _flash_core_fwd(qf, kf, vf, kvm, causal, bq, bk, interpret, window):
    out, lse, _ = _fwd_call(qf, kf, vf, kvm, causal=causal, bq=bq, bk=bk,
                            interpret=interpret, window=window)
    return out, (qf, kf, vf, kvm, out, lse)


def _flash_core_bwd(causal, bq, bk, interpret, window, res, dout):
    qf, kf, vf, kvm, out, lse = res
    g, sq, d = qf.shape
    sk = kf.shape[1]
    n_q, n_k = sq // bq, sk // bk
    scale = 1.0 / math.sqrt(d)
    # D_i = sum_j dO_ij * O_ij, shared by both backward kernels
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    common = dict(scale=scale, causal=causal, bq=bq, bk=bk, window=window)
    kv = _kv_index(causal, bq, bk, window)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, **common),
        grid=(g, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda gi, qb, kb: (gi, qb, 0)),
            pl.BlockSpec((1, bk, d), lambda gi, qb, kb: (gi, kv(qb, kb), 0)),
            pl.BlockSpec((1, bk, d), lambda gi, qb, kb: (gi, kv(qb, kb), 0)),
            pl.BlockSpec((1, 1, bk), lambda gi, qb, kb: (gi, 0, kv(qb, kb))),
            pl.BlockSpec((1, bq, d), lambda gi, qb, kb: (gi, qb, 0)),
            pl.BlockSpec((1, bq, 1), lambda gi, qb, kb: (gi, qb, 0)),
            pl.BlockSpec((1, bq, 1), lambda gi, qb, kb: (gi, qb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda gi, qb, kb: (gi, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((g, sq, d), qf.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, kvm, dout, lse, delta)
    qi = _q_index(causal, bq, bk, n_q, window)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, **common),
        grid=(g, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda gi, kb, qb: (gi, qi(kb, qb), 0)),
            pl.BlockSpec((1, bk, d), lambda gi, kb, qb: (gi, kb, 0)),
            pl.BlockSpec((1, bk, d), lambda gi, kb, qb: (gi, kb, 0)),
            pl.BlockSpec((1, 1, bk), lambda gi, kb, qb: (gi, 0, kb)),
            pl.BlockSpec((1, bq, d), lambda gi, kb, qb: (gi, qi(kb, qb), 0)),
            pl.BlockSpec((1, bq, 1), lambda gi, kb, qb: (gi, qi(kb, qb), 0)),
            pl.BlockSpec((1, bq, 1), lambda gi, kb, qb: (gi, qi(kb, qb), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda gi, kb, qb: (gi, kb, 0)),
            pl.BlockSpec((1, bk, d), lambda gi, kb, qb: (gi, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, sk, d), kf.dtype),
            jax.ShapeDtypeStruct((g, sk, d), vf.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, kvm, dout, lse, delta)
    return dq, dk, dv, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# Public entry points (validation, padding, flattening)
# ---------------------------------------------------------------------------


def _validate(q, k, v, kv_valid, causal=True, window=None):
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"flash_attention: window={window} needs causal=True and a "
            f"window of at least 1")
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(
            f"flash_attention expects rank-4 (B,H,S,D) operands, got "
            f"q{tuple(q.shape)} k{tuple(k.shape)} v{tuple(v.shape)}")
    if k.shape != v.shape:
        raise ValueError(
            f"flash_attention: k{tuple(k.shape)} and v{tuple(v.shape)} "
            f"must match")
    if q.shape[:2] != k.shape[:2] or q.shape[3] != k.shape[3]:
        raise ValueError(
            f"flash_attention: q{tuple(q.shape)} is incompatible with "
            f"k{tuple(k.shape)} (batch/head/head_dim must match)")
    if kv_valid is not None and tuple(kv_valid.shape) != (q.shape[0],
                                                          k.shape[2]):
        raise ValueError(
            f"flash_attention: kv_valid{tuple(kv_valid.shape)} must be "
            f"(B, Sk) = {(q.shape[0], k.shape[2])}")


_BLOCKS = (1024, 512, 256, 128)
# one (block, D) operand tile at most: 1024 x 128 at fp32 or 1024 x 256 at
# bf16 still fit the default scoped VMEM of a v5e, 1024 x 256 at fp32 not
_TILE_BYTES = 512 * 1024


def default_block(length: int, row_bytes: int) -> int:
    """The block a length gets when the caller names none: the largest of
    ``_BLOCKS`` whose padded length is no longer than the length padded
    to a multiple of 128 and whose tile of ``row_bytes``-byte rows (D x
    itemsize) fits ``_TILE_BYTES``; the length itself below 128. Each
    grid step costs fixed pipeline time, so fewer, larger tiles win until
    they add padding (a 1601-key memory keeps 128: 256 would pad 128 keys
    more) or outgrow the fast memory."""
    if length < 128:
        return length
    floor = -(-length // 128) * 128
    return next((b for b in _BLOCKS if -(-length // b) * b <= floor
                 and b * row_bytes <= _TILE_BYTES), _BLOCKS[-1])


def _block_geometry(sq: int, sk: int, bq: int | None, bk: int | None,
                    row_bytes: int):
    """Pick blocks (``default_block`` where None), clamp them to the
    (unpadded) lengths, then round lengths UP to block multiples — the
    padded tail is masked, never asserted away."""
    bq = default_block(sq, row_bytes) if bq is None else bq
    bk = default_block(sk, row_bytes) if bk is None else bk
    bq = max(1, min(bq, sq))
    bk = max(1, min(bk, sk))
    sq_p = -(-sq // bq) * bq
    sk_p = -(-sk // bk) * bk
    return bq, bk, sq_p, sk_p


def _prepare(q, k, v, kv_valid, bq, bk):
    """Pad to block multiples and flatten (B,H) -> G. Returns the flat
    operands plus the geometry needed to undo it."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk, sq_p, sk_p = _block_geometry(sq, sk, bq, bk,
                                         d * q.dtype.itemsize)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    valid = jnp.arange(sk_p, dtype=jnp.int32) < sk          # (sk_p,)
    if kv_valid is None:
        kvm = jnp.broadcast_to(valid[None, :], (b, sk_p))
    else:
        kvm = jnp.pad(kv_valid.astype(bool), ((0, 0), (0, sk_p - sk))) \
            & valid[None, :]
    kvm = jnp.broadcast_to(kvm[:, None, :], (b, h, sk_p)) \
        .reshape(b * h, 1, sk_p).astype(jnp.int32)
    qf = q.reshape(b * h, sq_p, d)
    kf = k.reshape(b * h, sk_p, d)
    vf = v.reshape(b * h, sk_p, d)
    return qf, kf, vf, kvm, bq, bk


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret",
                                    "window"))
def _flash_padded(q, k, v, kv_valid, *, causal, bq, bk, interpret,
                  window=None):
    b, h, sq, d = q.shape
    qf, kf, vf, kvm, bq, bk = _prepare(q, k, v, kv_valid, bq, bk)
    out = _flash_core(qf, kf, vf, kvm, causal, bq, bk, interpret, window)
    return out[:, :sq].reshape(b, h, sq, d)


def flash_attention(q, k, v, *, kv_valid=None, causal: bool = True,
                    window: int | None = None,
                    bq: int | None = None, bk: int | None = None,
                    interpret: bool = False):
    """Blockwise attention with a training-grade VJP.

    q (B,H,Sq,D); k,v (B,H,Sk,D); kv_valid (B,Sk) bool or None ->
    (B,H,Sq,D). Differentiable w.r.t. q, k, v. Ragged Sq/Sk are padded to
    block multiples internally; rows with no valid key return zeros.
    bq/bk None take ``default_block`` of Sq/Sk and the head row's bytes.
    ``window`` (causal only) keeps the band 0 <= q - k < window: blocks
    outside it are neither computed nor fetched.
    """
    _validate(q, k, v, kv_valid, causal, window)
    return _flash_padded(q, k, v, kv_valid, causal=causal, bq=bq, bk=bk,
                         interpret=interpret, window=window)


def flash_attention_probe(q, k, v, *, kv_valid=None, causal: bool = True,
                          window: int | None = None,
                          bq: int | None = None, bk: int | None = None,
                          interpret: bool = False):
    """Forward pass plus the block-skip witness.

    Returns (out, probe) where probe (B*H, n_q_blocks) int32 counts the KV
    block iterations that actually issued MXU work for each q row-block.
    The causal guarantee is ``probe[g, qb] == min(n_k, qb*bq//bk + 1)``
    rather than n_k — O(n_k/2) summed over the triangle.
    """
    _validate(q, k, v, kv_valid, causal, window)
    b, h, sq, d = q.shape
    qf, kf, vf, kvm, bq, bk = _prepare(q, k, v, kv_valid, bq, bk)
    out, _, probe = _fwd_call(qf, kf, vf, kvm, causal=causal, bq=bq, bk=bk,
                              interpret=interpret, window=window)
    return out[:, :sq].reshape(b, h, sq, d), probe.reshape(probe.shape[:2])
