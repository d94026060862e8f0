"""Assigned-architecture smoke tests: reduced same-family configs run one
forward + one train step on CPU, asserting shapes and no NaNs; plus the
prefill/decode == full-forward equivalence property for every family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, reduced, SHAPES
from repro.models.layers import init_params
from repro.models import transformer as tf
from repro.models.sharding import MeshCtx
from repro.optim import adamw
from repro.train import step as step_lib

B, S = 2, 16


def _setup(name, **over):
    cfg = reduced(get_config(name), **over)
    params = init_params(tf.model_template(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    kw = {}
    if cfg.frontend_seq:
        kw["frontend_emb"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (B, cfg.frontend_seq, cfg.frontend_dim or cfg.d_model))
    return cfg, params, toks, kw


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_no_nan(name):
    cfg, params, toks, kw = _setup(name)
    logits, aux, _ = tf.forward(cfg, params, toks, **kw)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_train_step(name):
    cfg, params, toks, kw = _setup(name)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1), **kw}
    ctx = MeshCtx(mesh=None)
    bundle = step_lib.make_train_step(cfg, adamw.OptConfig(), ctx)
    state = {"params": params, "opt": adamw.init(adamw.OptConfig(), params)}
    new_state, metrics = jax.jit(bundle.step_fn)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    delta = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()),
        state["params"], new_state["params"])
    assert max(jax.tree_util.tree_leaves(delta)) > 0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_prefill_decode_equals_full_forward(name):
    over = {"mtp_depth": 0}
    cfg, params, toks, kw = _setup(name, **over)
    if cfg.is_moe:  # capacity drops differ between prefix/full; disable
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    full, _, _ = tf.forward(cfg, params, toks, **kw)
    cache = tf.init_cache(cfg, B, S, cache_dtype=jnp.float32)
    pre, _, cache = tf.forward(cfg, params, toks[:, :8], cache=cache, **kw)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :8]),
                               rtol=1e-4, atol=1e-4)
    for t in range(8, S):
        lg, _, cache = tf.forward(cfg, params, toks[:, t:t + 1],
                                  cache=cache, **kw)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.attention
def test_long_context_train_step():
    """A train step well past the single-softmax threshold: the blockwise
    q-block loop with per-block checkpointing carries it (the full-length
    version — 4x the quadratic ceiling — runs in benchmarks/
    attention_long.py's long_train_step gate)."""
    seq = 256
    cfg = reduced(get_config("tinyllama-1.1b"))
    params = init_params(tf.model_template(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, seq), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    attn = step_lib.AttnOverrides(flash="auto", chunk=64, threshold=32,
                                  block_remat="dots")
    bundle = step_lib.make_train_step(cfg, adamw.OptConfig(),
                                      MeshCtx(mesh=None), attn=attn)
    state = {"params": params, "opt": adamw.init(adamw.OptConfig(), params)}
    _, metrics = jax.jit(bundle.step_fn)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0


def test_long_context_rule():
    """long_500k runs only for sub-quadratic archs (assignment rule)."""
    sub = {n for n in ARCH_NAMES if get_config(n).subquadratic}
    assert sub == {"xlstm-1.3b", "zamba2-7b"}
    long = SHAPES["long_500k"]
    for n in ARCH_NAMES:
        assert get_config(n).supports_shape(long) == (n in sub)


def test_param_counts_in_range():
    """Declared model scales roughly match the configs (sanity on 6ND)."""
    expect = {"tinyllama-1.1b": (0.9e9, 1.4e9), "llama3-8b": (7e9, 9e9),
              "starcoder2-3b": (2.5e9, 3.6e9),
              "deepseek-v3-671b": (6e11, 7.4e11),
              "stablelm-1.6b": (1.3e9, 2.0e9)}
    for name, (lo, hi) in expect.items():
        n = get_config(name).param_count()
        assert lo < n < hi, (name, n)
    ds = get_config("deepseek-v3-671b")
    assert 3e10 < ds.active_param_count() < 4.5e10


def test_cache_specs_match_cache_tree():
    """cache_pspecs tree structure must match init_cache for every arch."""
    for name in ARCH_NAMES:
        cfg = get_config(name)
        cache = tf.init_cache(cfg, 4, 32, abstract=True)
        specs = step_lib.cache_pspecs(cfg, MeshCtx(mesh=None))
        assert set(cache) == set(specs), (name, set(cache) ^ set(specs))


def test_head_padding_model_equivalent():
    """pad_heads_to: padded model == unpadded with shared live weights
    (group-aware mapping), dead heads receive zero gradients."""
    import copy
    import dataclasses
    cfg0 = reduced(get_config("starcoder2-3b"))       # 4 heads, kv=2
    cfg0 = dataclasses.replace(cfg0, pad_heads_to=0)
    cfg1 = dataclasses.replace(cfg0, pad_heads_to=8)
    p1 = init_params(tf.model_template(cfg1), jax.random.PRNGKey(0))
    p0 = copy.deepcopy(p1)
    live = np.array([0, 1, 4, 5])   # first 2 slots of each 4-slot group
    p0["layers"]["attn"]["wq"] = p1["layers"]["attn"]["wq"][:, :, live, :]
    p0["layers"]["attn"]["wo"] = p1["layers"]["attn"]["wo"][:, live, :, :]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg0.vocab_size)
    l1, _, _ = tf.forward(cfg1, p1, toks)
    l0, _, _ = tf.forward(cfg0, p0, toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=1e-5, atol=1e-5)

    def loss(params):
        lg, _, _ = tf.forward(cfg1, params, toks)
        return jnp.mean(lg.astype(jnp.float32) ** 2)
    g = jax.grad(loss)(p1)
    dead = np.array([2, 3, 6, 7])
    assert float(jnp.abs(g["layers"]["attn"]["wq"][:, :, dead, :]).max()) == 0
    assert float(jnp.abs(g["layers"]["attn"]["wo"][:, dead]).max()) == 0


@pytest.mark.parametrize("path,fan_in", [
    (("layers", "attn", "wq"), "d_model"),
    (("layers", "attn", "wk"), "d_model"),
    (("layers", "attn", "wo"), "heads*head_dim"),
    (("layers", "mlp", "w_up"), "d_model"),
    (("layers", "mlp", "w_down"), "d_ff"),
])
def test_fan_in_init_scales_by_contracted_size(path, fan_in):
    """fan_in weights have std 1/sqrt(contracted size): d_model for the
    q/k/v projections (not n_heads), heads*head_dim for the output
    projection (not head_dim). At the old scale stablelm-1.6b's attention
    logits had std ~64 and its random model was chaotic under bf16."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), n_layers=1,
                              vocab_size=256, d_ff=1024)
    size = {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "heads*head_dim": cfg.n_heads * cfg.resolved_head_dim}[fan_in]
    params = init_params(tf.model_template(cfg), jax.random.PRNGKey(0))
    w = params
    for k in path:
        w = w[k]
    np.testing.assert_allclose(float(jnp.std(w)), size ** -0.5, rtol=0.02)
