"""Blockwise/flash attention lane (pytest -m attention).

The training-grade contract of kernels/attention.py and the model-layer
routing in models/attention.py:

- custom-VJP backward vs the jnp oracle's jax.grad across causal x dtype
  x ragged lengths (tol 1e-5 fp32 / 2e-2 bf16),
- the causal block-skip probe (fully masked KV blocks issue no work) and
  the index maps that fetch nothing for a skipped block, with and
  without a sliding window's lower edge,
- the block rule for callers that name no block shape,
- internal pad-to-block-multiple instead of the old bare assert, with
  ValueError naming the shapes for genuinely unsupported inputs,
- the zeros-for-dead-rows convention (output AND gradients) on every
  path: kernel, oracle, quadratic softmax, blockwise scan,
- forced flash routing == the jnp scan path at the model layer, and the
  Policy/config knobs that pick block shapes and checkpoint policies.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision import Policy
from repro.kernels import ops, ref
from repro.kernels import attention as K
from repro.kernels.attention import flash_attention_probe
from repro.models import attention as A

pytestmark = pytest.mark.attention

GRAD_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _mk(rng, shape, dtype):
    return jnp.asarray(rng.randn(*shape), dtype)


# ---------------------------------------------------------------------------
# Backward: custom VJP vs the oracle's jax.grad
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk,bq,bk", [
    (64, 64, 16, 16),      # block-aligned square
    (48, 80, 16, 16),      # ragged: pad-to-block both sides, sq != sk
    (33, 33, 16, 8),       # odd lengths, mixed block shapes
])
def test_flash_grads_match_ref(dtype, causal, sq, sk, bq, bk, rng):
    if causal and sq != sk:
        pytest.skip("causal contract requires square q/k here")
    b, h, d = 2, 2, 16
    q = _mk(rng, (b, h, sq, d), dtype)
    k = _mk(rng, (b, h, sk, d), dtype)
    v = _mk(rng, (b, h, sk, d), dtype)
    kv_valid = jnp.asarray(rng.rand(b, sk) < 0.9)

    def l_kernel(q, k, v):
        o = ops.flash_attention(q, k, v, kv_valid=kv_valid, causal=causal,
                                bq=bq, bk=bk, interpret=True)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    def l_ref(q, k, v):
        o = ref.flash_attention_ref(q, k, v, causal=causal,
                                    kv_valid=kv_valid)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    gk = jax.grad(l_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(l_ref, argnums=(0, 1, 2))(q, k, v)
    tol = GRAD_TOL[dtype]
    for name, a, b_ in zip("qkv", gk, gr):
        assert a.dtype == b_.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   rtol=tol, atol=tol * 4,
                                   err_msg=f"d{name}")


def test_flash_grad_under_jit_and_vjp_composition(rng):
    """The custom VJP must survive jit and double application (value+grad)."""
    b, h, s, d = 1, 2, 32, 8
    q = _mk(rng, (b, h, s, d), jnp.float32)

    @jax.jit
    def f(q):
        o = ops.flash_attention(q, q, q, causal=True, bq=8, bk=8,
                                interpret=True)
        return jnp.sum(o ** 2)

    val, grad = jax.value_and_grad(f)(q)
    assert np.isfinite(float(val))
    assert grad.shape == q.shape and bool(jnp.any(grad != 0))


@pytest.mark.parametrize("length,row_bytes,block", [
    (4096, 256, 1024),     # the train cell: D 128 at bf16
    (1024, 256, 1024),
    (1536, 256, 512),      # 1024 would pad 512 keys
    (700, 256, 256),
    (1601, 256, 128),      # a vision memory: 256 would pad 128 keys
    (64, 256, 64),         # below 128: the length itself
    (4096, 512, 1024),     # D 256 at bf16
    (4096, 1024, 512),     # D 256 at fp32: a 1024-row tile is 1 MiB
    (4096, 4096, 128),     # D 1024 at fp32: no tile fits, the smallest
])
def test_default_block_rule(length, row_bytes, block):
    """No named block: the largest of 1024/512/256/128 that pads no
    further than the 128-multiple and whose tile fits 512 KiB, the
    length itself below 128."""
    assert K.default_block(length, row_bytes) == block


@pytest.mark.parametrize("s,causal,dead_row", [
    (2048, True, False),   # a 2x2 grid of 1024 blocks
    (700, True, True),     # 256 blocks, ragged tail, one batch row dead
    (700, False, True),
])
def test_flash_default_blocks_match_ref(s, causal, dead_row, rng):
    """Forward and grads at the blocks the kernel picks for itself."""
    b, h, d = 2, 1, 16
    q, k, v = (_mk(rng, (b, h, s, d), jnp.float32) for _ in range(3))
    kv_valid = jnp.asarray(rng.rand(b, s) < 0.9)
    if dead_row:
        kv_valid = kv_valid.at[0].set(False)

    def run(fn, **kw):
        def loss(q, k, v):
            o = fn(q, k, v, kv_valid=kv_valid, causal=causal, **kw)
            return jnp.sum(jnp.sin(o)), o
        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        return o, g

    o_k, g_k = run(ops.flash_attention, interpret=True)
    o_r, g_r = run(ref.flash_attention_ref)
    tol = GRAD_TOL[jnp.float32]
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=tol, atol=tol * 4)
    if dead_row:
        assert float(jnp.abs(o_k[0]).max()) == 0.0
    for name, a, b_ in zip("qkv", g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=tol, atol=tol * 4,
                                   err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# Causal block-skip probe
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,s,d,blk", [
    (2, 3, 128, 16, 16),
    (1, 2, 1536, 8, None),     # default blocks: 512, a 3x3 grid
])
def test_causal_skip_triangular_iterations(b, h, s, d, blk, rng):
    """Causal grids issue exactly n_k*(n_k+1)/2 block iterations per
    (batch*head) — the docstring's skip promise, counted in-kernel."""
    q = _mk(rng, (b, h, s, d), jnp.float32)
    out, probe = flash_attention_probe(q, q, q, causal=True, bq=blk, bk=blk,
                                       interpret=True)
    n = s // (blk or K.default_block(s, d * 4))
    assert int(probe.sum()) == b * h * n * (n + 1) // 2
    # per q-block: block i visits exactly i+1 KV blocks
    per_block = np.asarray(probe).reshape(b * h, n)
    assert (per_block == np.arange(1, n + 1)).all()
    # and the skip is not changing the math
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.flash_attention_ref(q, q, q)),
        rtol=1e-5, atol=1e-5)


def _fetches(indices):
    """Copies a pipeline that fetches only on a change of index makes."""
    return sum(1 for i, x in enumerate(indices) if i == 0
               or x != indices[i - 1])


@pytest.mark.parametrize("sq,sk,bq,bk", [
    (1024, 1024, 128, 128), (1024, 1024, 256, 128), (1024, 1024, 128, 256),
    (512, 1024, 128, 128),     # Sk > Sq: some KV blocks no row needs
])
def test_causal_index_maps_fetch_only_live_blocks(sq, sk, bq, bk):
    """A step the causal skip drops names the block already resident: the
    forward/dQ grid fetches K/V once per live block of each q row-block,
    the dK/dV grid Q/dO/lse/delta once per live block of each KV block
    (at least once: a block is resident at every step), and every live
    step names its own block."""
    n_q, n_k = sq // bq, sk // bk
    kv = K._kv_index(True, bq, bk)
    qi = K._q_index(True, bq, bk, n_q)
    live = [[bool(K._causal_need(qb, kb, bq, bk)) for kb in range(n_k)]
            for qb in range(n_q)]
    for qb in range(n_q):
        idx = [int(kv(qb, kb)) for kb in range(n_k)]
        assert all(idx[kb] == kb for kb in range(n_k) if live[qb][kb])
        assert _fetches(idx) == sum(live[qb])
    for kb in range(n_k):
        idx = [int(qi(kb, qb)) for qb in range(n_q)]
        assert all(0 <= i < n_q for i in idx)
        assert all(idx[qb] == qb for qb in range(n_q) if live[qb][kb])
        assert _fetches(idx) == max(1, sum(row[kb] for row in live))


@pytest.mark.parametrize("window", [128, 1024])
def test_window_default_blocks_match_ref(window, rng):
    """Forward and grads of a sliding-window layer (q sees k iff
    0 <= q - k < window) at the blocks the kernel picks for 2048 keys."""
    b, h, s, d = 1, 2, 2048, 16
    q, k, v = (_mk(rng, (b, h, s, d), jnp.float32) for _ in range(3))

    def run(fn, **kw):
        def loss(q, k, v):
            o = fn(q, k, v, causal=True, window=window, **kw)
            return jnp.sum(jnp.sin(o)), o
        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        return o, g

    o_k, g_k = run(ops.flash_attention, interpret=True)
    o_r, g_r = run(ref.flash_attention_ref)
    causal = ref.flash_attention_ref(q, k, v, causal=True)
    assert float(jnp.abs(o_r - causal).max()) > 1e-3   # the band bites
    tol = GRAD_TOL[jnp.float32]
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=tol, atol=tol * 4)
    for name, a, b_ in zip("qkv", g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=tol, atol=tol * 4,
                                   err_msg=f"d{name}")


def test_window_skip_counts_live_blocks(rng):
    """8192 keys in 1024 blocks with a 1024 window: each q row-block
    visits its diagonal block and the one before it, 15 of the grid's 64
    (36 causal)."""
    q = _mk(rng, (1, 1, 8192, 128), jnp.float32)
    _, probe = flash_attention_probe(q, q, q, causal=True, window=1024,
                                     interpret=True)
    assert K.default_block(8192, 128 * 4) == 1024
    assert np.asarray(probe).reshape(-1).tolist() == [1] + [2] * 7
    assert int(probe.sum()) == 15


@pytest.mark.parametrize("s,bq,bk,window", [
    (1024, 128, 128, 128), (1024, 128, 128, 300), (1024, 256, 128, 200),
    (1024, 128, 256, 129), (1024, 128, 128, 2048),
])
def test_window_index_maps_fetch_only_live_blocks(s, bq, bk, window):
    """The window's lower edge in the index maps: a step below the band
    names the first live block, so both grids fetch each live block once
    and nothing else, and every live step names its own block."""
    n_q, n_k = s // bq, s // bk
    kv = K._kv_index(True, bq, bk, window)
    qi = K._q_index(True, bq, bk, n_q, window)
    live = [[bool(K._causal_need(qb, kb, bq, bk, window))
             for kb in range(n_k)] for qb in range(n_q)]
    rel = np.arange(s)[:, None] - np.arange(s)[None, :]
    band = ((rel >= 0) & (rel < window)).reshape(n_q, bq, n_k, bk)
    assert live == band.any(axis=(1, 3)).tolist()
    for qb in range(n_q):
        idx = [int(kv(qb, kb)) for kb in range(n_k)]
        assert all(idx[kb] == kb for kb in range(n_k) if live[qb][kb])
        assert _fetches(idx) == sum(live[qb])
    for kb in range(n_k):
        idx = [int(qi(kb, qb)) for qb in range(n_q)]
        assert all(0 <= i < n_q for i in idx)
        assert all(idx[qb] == qb for qb in range(n_q) if live[qb][kb])
        assert _fetches(idx) == max(1, sum(row[kb] for row in live))


def test_window_needs_causal(rng):
    q = _mk(rng, (1, 1, 16, 8), jnp.float32)
    with pytest.raises(ValueError, match="window"):
        ops.flash_attention(q, q, q, causal=False, window=4, interpret=True)


def test_non_causal_runs_full_grid(rng):
    q = _mk(rng, (1, 2, 64, 8), jnp.float32)
    _, probe = flash_attention_probe(q, q, q, causal=False, bq=16, bk=16,
                                     interpret=True)
    n = 64 // 16
    assert int(probe.sum()) == 1 * 2 * n * n


# ---------------------------------------------------------------------------
# Shape handling: internal padding + ValueError for real misuse
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sq,sk", [(20, 20), (130, 70), (7, 128)])
def test_non_multiple_shapes_pad_internally(sq, sk, rng):
    """Shapes that don't tile the blocks pad internally (the old kernel
    asserted) and still match the oracle."""
    causal = sq == sk
    q = _mk(rng, (1, 2, sq, 16), jnp.float32)
    k = _mk(rng, (1, 2, sk, 16), jnp.float32)
    v = _mk(rng, (1, 2, sk, 16), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)


def test_bad_shapes_raise_valueerror_naming_shapes(rng):
    q3 = jnp.zeros((2, 16, 8))
    with pytest.raises(ValueError, match="rank-4"):
        ops.flash_attention(q3, q3, q3, interpret=True)
    q = jnp.zeros((1, 2, 16, 8))
    k = jnp.zeros((1, 2, 16, 8))
    v = jnp.zeros((1, 2, 24, 8))
    with pytest.raises(ValueError, match=r"24"):
        ops.flash_attention(q, k, v, interpret=True)
    kv = jnp.zeros((1, 7), bool)
    with pytest.raises(ValueError, match="kv_valid"):
        ops.flash_attention(q, k, k, kv_valid=kv, interpret=True)


# ---------------------------------------------------------------------------
# Dead rows: zeros out, zero gradients — every path agrees
# ---------------------------------------------------------------------------


def test_dead_rows_zero_output_and_grads(rng):
    """Rows with no valid key (fully padded cross-attention memory) emit
    zeros and receive/propagate zero gradients — not softmax garbage."""
    b, h, s, d = 2, 2, 32, 8
    q = _mk(rng, (b, h, s, d), jnp.float32)
    k = _mk(rng, (b, h, s, d), jnp.float32)
    v = _mk(rng, (b, h, s, d), jnp.float32)
    kv_valid = jnp.ones((b, s), bool).at[0].set(False)  # seq 0: all padding

    def l(q, k, v):
        o = ops.flash_attention(q, k, v, kv_valid=kv_valid, causal=False,
                                bq=8, bk=8, interpret=True)
        return o

    out = l(q, k, v)
    assert float(jnp.abs(out[0]).max()) == 0.0
    assert float(jnp.abs(out[1]).max()) > 0.0
    gq, gk, gv = jax.grad(
        lambda *a: jnp.sum(l(*a)), argnums=(0, 1, 2))(q, k, v)
    assert float(jnp.abs(gq[0]).max()) == 0.0
    assert float(jnp.abs(gk[0]).max()) == 0.0
    assert float(jnp.abs(gv[0]).max()) == 0.0


def test_dead_rows_agree_across_paths(rng):
    """Kernel, oracle, quadratic softmax, and the blockwise scan all pin
    the same convention."""
    b, s, h, d = 2, 64, 2, 8
    q = _mk(rng, (b, s, h, d), jnp.float32)
    k = _mk(rng, (b, s, h, d), jnp.float32)
    v = _mk(rng, (b, s, h, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    kv_valid = jnp.asarray(rng.rand(b, s) < 0.5).at[0].set(False)
    outs = {
        "quadratic": A.chunked_attention(q, k, v, pos, kv_valid,
                                         triangular=True, use_flash="off"),
        "blockwise": A.chunked_attention(q, k, v, pos, kv_valid,
                                         triangular=True, use_flash="off",
                                         threshold=8, chunk=16),
        "kernel": A.chunked_attention(q, k, v, pos, kv_valid,
                                      triangular=True, use_flash="on"),
    }
    for name, o in outs.items():
        assert float(jnp.abs(o[0]).max()) == 0.0, name
    base = np.asarray(outs["quadratic"])
    for name in ("blockwise", "kernel"):
        np.testing.assert_allclose(np.asarray(outs[name]), base,
                                   rtol=2e-5, atol=1e-4, err_msg=name)


# ---------------------------------------------------------------------------
# Model-layer routing
# ---------------------------------------------------------------------------


def test_forced_flash_route_matches_scan(rng, monkeypatch):
    """REPRO_FLASH_ATTENTION=1 swaps in the kernel without changing the
    math (fwd + grads), including ragged kv_valid."""
    monkeypatch.delenv("REPRO_FLASH_ATTENTION", raising=False)
    b, s, h, d = 2, 48, 4, 16
    q = _mk(rng, (b, s, h, d), jnp.float32)
    k = _mk(rng, (b, s, h, d), jnp.float32)
    v = _mk(rng, (b, s, h, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    kv_valid = jnp.asarray(rng.rand(b, s) < 0.9)

    def run(flag):
        def loss(q, k, v):
            o = A.chunked_attention(q, k, v, pos, kv_valid, triangular=True,
                                    use_flash=flag)
            return jnp.sum(o * jnp.cos(o))
        return (A.chunked_attention(q, k, v, pos, kv_valid, triangular=True,
                                    use_flash=flag),
                jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    o_off, g_off = run("off")
    o_on, g_on = run("on")
    np.testing.assert_allclose(np.asarray(o_on), np.asarray(o_off),
                               rtol=2e-5, atol=1e-4)
    for a, b_ in zip(g_on, g_off):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_env_var_overrides_config(monkeypatch):
    monkeypatch.setenv("REPRO_FLASH_ATTENTION", "0")
    assert not A.flash_route_enabled("on")
    monkeypatch.setenv("REPRO_FLASH_ATTENTION", "1")
    assert A.flash_route_enabled("off")
    monkeypatch.delenv("REPRO_FLASH_ATTENTION")
    assert A.flash_route_enabled("on")
    assert not A.flash_route_enabled("off")
    # auto == backend routing (cpu here)
    assert A.flash_route_enabled("auto") == (jax.default_backend() == "tpu")


def test_block_remat_preserves_values_and_grads(rng):
    """Per-q-block jax.checkpoint changes memory, never math."""
    b, s, h, d = 1, 64, 2, 8
    q = _mk(rng, (b, s, h, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = jnp.ones((b, s), bool)

    def loss(q, remat):
        o = A.chunked_attention(q, q, q, pos, valid, triangular=True,
                                use_flash="off", threshold=8, chunk=16,
                                block_remat=remat)
        return jnp.sum(o ** 2)

    for policy in ("everything", "nothing", "dots", "dots_no_batch"):
        np.testing.assert_allclose(
            np.asarray(jax.grad(loss)(q, policy)),
            np.asarray(jax.grad(loss)(q, "none")),
            rtol=1e-5, atol=1e-5, err_msg=policy)
    with pytest.raises(ValueError, match="checkpoint policy"):
        A.checkpoint_policy("bogus")


def test_policy_block_knobs_flow_through(rng):
    """Policy.attn_bq/attn_bk pick the kernel's block shapes (observable
    via the probe's grid: 32-blocks -> 2x2 grid on seq 64)."""
    pol = Policy(compute_dtype="float32", attn_bq=32, attn_bk=32)
    q = _mk(rng, (1, 1, 64, 8), jnp.float32)
    out = ops.flash_attention(q, q, q, policy=pol, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.flash_attention_ref(q, q, q)),
                               rtol=1e-5, atol=1e-5)
    _, probe = flash_attention_probe(q, q, q, causal=True,
                                     bq=pol.attn_bq, bk=pol.attn_bk,
                                     interpret=True)
    assert probe.shape == (1, 2)          # g=1, n_q = 64/32
    assert int(probe.sum()) == 3          # 2*(2+1)/2 triangular


def test_attn_overrides_thread_into_train_step():
    from repro.train import step as step_lib
    from repro.configs import get_config, reduced
    cfg = reduced(get_config("tinyllama-1.1b"))
    out = step_lib.apply_attn_overrides(
        cfg, step_lib.AttnOverrides(flash="off", chunk=256,
                                    block_remat="dots"))
    assert (out.attn_flash, out.attn_chunk, out.attn_block_remat) == \
        ("off", 256, "dots")
    assert step_lib.apply_attn_overrides(cfg, None) is cfg
    # frozen config untouched
    assert (cfg.attn_flash, cfg.attn_chunk) == ("auto", 1024)


def test_cross_attention_flash_route_matches(rng, monkeypatch):
    """cross_attention: kernel route == masked softmax, incl. a fully
    padded memory row (gated zeros, not garbage)."""
    from repro.configs import get_config, reduced
    from repro.models.layers import init_params
    cfg = reduced(get_config("tinyllama-1.1b"))
    tmpl = A.gqa_template(cfg)  # no tanh gate: zeros-init would hide diffs
    params = init_params({"attn": tmpl}, jax.random.PRNGKey(0))["attn"]
    x = _mk(rng, (2, 8, cfg.d_model), jnp.float32)
    mem = _mk(rng, (2, 12, cfg.d_model), jnp.float32)
    mv = jnp.asarray(rng.rand(2, 12) < 0.8).at[1].set(False)
    monkeypatch.setenv("REPRO_FLASH_ATTENTION", "0")
    off = A.cross_attention(cfg, params, x, mem, mv)
    monkeypatch.setenv("REPRO_FLASH_ATTENTION", "1")
    on = A.cross_attention(cfg, params, x, mem, mv)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               rtol=2e-5, atol=1e-4)
