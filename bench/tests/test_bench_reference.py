"""The benchmark's plain reference against the program at tiny widths on
the CPU: forward logits, loss and gradients, one AdamW step, and the
control's departure. Both tiny configurations come from
``tiny_catalog``: parallel block with gated SiLU and an untied head, and
sequential block with tanh-GELU, tied embeddings and padded dead heads."""
from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import tiny_catalog  # noqa: E402
from harness import flat_shapes  # noqa: E402
from refs import dense  # noqa: E402

from repro.configs.base import ArchConfig  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.layers import abstract_params  # noqa: E402
from repro.optim import adamw  # noqa: E402

CONFIGS = sorted(tiny_catalog.CONFIGS)


def _tokens(seed, b, s, v):
    return jnp.asarray(np.random.default_rng(seed).integers(0, v, (b, s)),
                       jnp.int32)


@pytest.mark.parametrize("name", CONFIGS)
def test_layout_matches_program(name):
    arch = tiny_catalog.CONFIGS[name]
    prog = flat_shapes(abstract_params(tf.model_template(ArchConfig(**arch))))
    assert dense.check_layout(arch, prog) is None
    wrong = dict(arch, d_ff=arch["d_ff"] * 2)
    assert dense.check_layout(wrong, prog) is not None


@pytest.mark.parametrize("name", CONFIGS)
def test_forward_matches_program_fp32(name):
    arch = tiny_catalog.CONFIGS[name]
    cfg = ArchConfig(**arch)
    params = dense.make_params(arch, 7)
    toks = _tokens(1, 2, 32, arch["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want, _, _ = tf.forward(dataclasses.replace(cfg, attn_flash="off"),
                                params, toks)
    got = dense.logits(arch, params, dense.hidden(arch, params, toks,
                                                  q_block=8))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", CONFIGS)
def test_loss_and_grad_match_program_fp32(name):
    arch = tiny_catalog.CONFIGS[name]
    cfg = dataclasses.replace(ArchConfig(**arch), attn_flash="off")
    params = dense.make_params(arch, 3)
    toks = _tokens(2, 2, 33, arch["vocab_size"])
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with jax.default_matmul_precision("highest"):
        (want, _), want_g = jax.value_and_grad(
            lambda p: tf.lm_loss(cfg, p, batch), has_aux=True)(params)
    got, got_g = dense.loss_and_grad(arch, params, batch["tokens"],
                                     batch["labels"])
    assert abs(float(got) - float(want)) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_dead_heads_get_no_gradient():
    arch = tiny_catalog.CONFIGS["tiny-padded"]
    params = dense.make_params(arch, 5)
    toks = _tokens(3, 1, 17, arch["vocab_size"])
    _, g = dense.loss_and_grad(arch, params, toks[:, :-1], toks[:, 1:])
    live = dense.live_heads(arch)
    dead = np.setdiff1d(np.arange(arch["pad_heads_to"]), live)
    assert len(dead) == arch["pad_heads_to"] - arch["n_heads"]
    assert float(jnp.abs(g["layers"]["attn"]["wq"][:, :, dead]).max()) == 0
    assert float(jnp.abs(g["layers"]["attn"]["wq"][:, :, live]).max()) > 0


def test_adamw_step_matches_program():
    arch = tiny_catalog.CONFIGS["tiny-parallel"]
    opt = tiny_catalog.CELLS["tiny.train"][2]["opt"]
    params = dense.make_params(arch, 11)
    grads = jax.tree_util.tree_map(lambda p: 3.0 * jnp.sin(p * 50.0), params)
    cfg = adamw.OptConfig(**opt)
    want, want_state, _ = adamw.update(cfg, grads, adamw.init(cfg, params),
                                       params)
    got, got_state, _ = dense.adamw_step(opt, params, grads,
                                         dense.adamw_init(params))
    for a, b in zip(jax.tree_util.tree_leaves((got, got_state["m"])),
                    jax.tree_util.tree_leaves((want, want_state["m"]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-9)


def test_int8_control_departs_from_fp32():
    arch = tiny_catalog.CONFIGS["tiny-parallel"]
    params = dense.make_params(arch, 2)
    toks = _tokens(4, 1, 32, arch["vocab_size"])
    exact = dense.logits(arch, params, dense.hidden(arch, params, toks))
    ctl = dense.logits(arch, params, dense.hidden(arch, params, toks,
                                                  quant=True), quant=True)
    rel = float(jnp.linalg.norm(ctl - exact) / jnp.linalg.norm(exact))
    assert 1e-3 < rel < 0.5
