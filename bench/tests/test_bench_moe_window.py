"""The mixture-of-experts reference with sliding-window and full layers
(``refs/moe_window.py``) against the program at tiny widths on the CPU,
and a tiny training cell of that kind run end to end through the
harness, added as new files and entries the way ``tiny_catalog`` adds
its cells."""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import flops_moe  # noqa: E402
import tiny_catalog  # noqa: E402
from harness import flat_shapes  # noqa: E402
from refs import moe_window  # noqa: E402

from repro.configs.base import ArchConfig  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.layers import abstract_params  # noqa: E402

SEED = 2 ** 31 + 1613

# two periods of (sliding, sliding, sliding, full) with window 8; a YaRN
# ramp that spans dimensions 2-6 of the 8 rotated pairs; 4 of 16 experts
# held, top 4
ARCH = {
    "name": "tiny-moe-window", "family": "moe", "n_layers": 8, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab_size": 256,
    "head_dim": 16, "rope_theta": 10000.0, "norm_eps": 1e-6,
    "activation": "silu", "layer_kinds": ["sliding"] * 3 + ["full"],
    "sliding_window": 8,
    "yarn": {"factor": 16.0, "original_max_position_embeddings": 4096,
             "beta_fast": 32.0, "beta_slow": 1.0, "attention_factor": 1.2},
    "moe": {"n_experts": 4, "n_routed_experts": 16, "top_k": 4,
            "expert_d_ff": 32},
    "param_dtype": "float32", "compute_dtype": "float32"}

CELL = {
    "kind": "train", "batch": 2, "seq": 32,
    "opt": tiny_catalog.CELLS["tiny.train"][2]["opt"],
    "limits": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
               "update_norm_gap": 0.5},
    "why": "tiny"}


def make(tmp: str) -> str:
    """``tiny_catalog.make`` plus the tiny MoE configuration and its
    training cell, listed under the metrics of the training cells."""
    root = tiny_catalog.make(tmp)
    bench = os.path.join(root, "bench")
    tiny_catalog._write(os.path.join(bench, "configs",
                                     "tiny-moe-window.json"),
                        {"name": "tiny-moe-window", "source": "test",
                         "reference": "moe_window",
                         "arch": dict(ARCH, n_layers=4)})
    tiny_catalog._write(os.path.join(bench, "workloads",
                                     "tiny.moe-train.json"), CELL)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-moe-window", "source": "test",
                            "file": "bench/configs/tiny-moe-window.json",
                            "reduced": [], "why": "tiny"})
    spec["workloads"].append({"name": "tiny.moe-train",
                              "config": "tiny-moe-window",
                              "traffic": "uniform-4k", "chips": 1,
                              "why": "tiny"})
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "tiny.train" in m.get("workloads", []):
                m["workloads"].append("tiny.moe-train")
    tiny_catalog._write(path, spec)
    return root


def _tokens(seed, b, s):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, ARCH["vocab_size"], (b, s)), jnp.int32)


def _program(flash):
    return dataclasses.replace(ArchConfig(**ARCH), attn_flash=flash)


def test_layout_matches_program():
    prog = flat_shapes(abstract_params(tf.model_template(ArchConfig(**ARCH))))
    assert moe_window.check_layout(ARCH, prog) is None
    wrong = dict(ARCH, moe=dict(ARCH["moe"], n_experts=8))
    assert moe_window.check_layout(wrong, prog) is not None


@pytest.mark.parametrize("flash", ["off", "on"])
def test_logits_loss_and_grads_match_program(flash):
    """Logits, loss (with the load-balancing term) and every first
    gradient; ``on`` runs the flash kernels with the window in interpret
    mode, ``off`` the blockwise jnp path."""
    params = moe_window.make_params(ARCH, 3)
    toks = _tokens(2, 2, 33)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    cfg = _program(flash)
    with jax.default_matmul_precision("highest"):
        (want, _), want_g = jax.value_and_grad(
            lambda p: tf.lm_loss(cfg, p, batch), has_aux=True)(params)
        want_lg, _, _ = tf.forward(cfg, params, batch["tokens"])
    got_lg = moe_window.logits(ARCH, params, moe_window.hidden(
        ARCH, params, batch["tokens"], q_block=8))
    np.testing.assert_allclose(np.asarray(got_lg), np.asarray(want_lg),
                               rtol=2e-5, atol=2e-5)
    got, got_g = moe_window.loss_and_grad(ARCH, params, batch["tokens"],
                                          batch["labels"])
    assert abs(float(got) - float(want)) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_window_and_yarn_change_the_model():
    """The band and the YaRN tables each move the logits: neither can be
    left out inside the comparison's tolerance."""
    params = moe_window.make_params(ARCH, 5)
    toks = _tokens(4, 1, 32)
    base = moe_window.logits(ARCH, params, moe_window.hidden(
        ARCH, params, toks, q_block=8))
    for other in (dict(ARCH, sliding_window=64), dict(ARCH, yarn=None)):
        lg = moe_window.logits(other, params, moe_window.hidden(
            other, params, toks, q_block=8))
        assert float(jnp.abs(lg - base).max()) > 1e-3


def test_int8_control_departs_from_fp32():
    params = moe_window.make_params(ARCH, 2)
    toks = _tokens(4, 1, 32)
    exact = moe_window.logits(ARCH, params, moe_window.hidden(
        ARCH, params, toks))
    ctl = moe_window.logits(ARCH, params, moe_window.hidden(
        ARCH, params, toks, quant=True), quant=True)
    rel = float(jnp.linalg.norm(ctl - exact) / jnp.linalg.norm(exact))
    assert 1e-3 < rel < 0.5


def test_counts_by_hand():
    """Mellum2's cut: ~1.18 GFLOP a trained token at 8192, 15 of 64
    blocks' worth of band pairs, and a grouped product over the 16,384
    pairs of balanced routing bound by the MXU."""
    with open(os.path.join(BENCH, "configs", "mellum2-12b-l4-ep8.json")) as f:
        arch = json.load(f)["arch"]
    band = (1024 * 1025 / 2 + 7168 * 1024) / 8192
    assert flops_moe.visible_keys("sliding", 8192, 1024) == band
    assert flops_moe.visible_keys("full", 8192, 1024) == 8193 / 2
    weights = 4 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64
                   + 3 * 2304 * 896) + 2304 * 12288
    attn = 4 * 32 * 128 * (3 * band + 8193 / 2)
    want = 6 * weights + 3 * attn
    assert flops_moe.train_flops_per_token(arch, 8192) == \
        pytest.approx(want, rel=1e-12)
    assert 1.17e9 < want < 1.19e9
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ops = 2 * 16384 * 2304 * 896
    assert flops_moe.expert_gmm_least_s(arch, 16384, peaks) == \
        pytest.approx(ops / 197e12, rel=1e-12)


@pytest.fixture
def obs():
    from repro import obs
    obs.reset()
    yield obs
    obs.reset()


def test_expert_reader_counts_the_traced_steps_pairs(obs):
    """A traced run as the expert reader sees it: the window opened at
    9 s, the profiler started at 10 s and the trace ran to 11 s. Only
    the steps dispatched inside it count (10.2 and 10.6 s, not 9.5 or
    11.2), and a call's P is their pairs over the 4 layers; a program
    that files no counts gives nothing."""
    from harness import Catalog
    read = Catalog(os.path.dirname(BENCH)).reader("expert_gmm_roofline")
    with open(os.path.join(BENCH, "configs", "mellum2-12b-l4-ep8.json")) as f:
        arch = json.load(f)["arch"]
    with open(os.path.join(BENCH, "workloads",
                           "mellum2-12b.train-8k.json")) as f:
        cell = dict(json.load(f), trace_from=0.5, trace_seconds=1.0)
    trace = {"ops": {"%gmm.3 = bf16[131072,896] custom-call(...)":
                     {"seconds": 0.05, "count": 48},
                     "%tgmm.1 = bf16[8,2304,896] custom-call(...)":
                     {"seconds": 0.05, "count": 48}}}
    run = types.SimpleNamespace(
        trace=trace, cell=cell, config={"arch": arch}, seconds=2.0,
        window_open=9.0, data={"profiler_on": 10.0},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert read(run) is None
    for t0, pairs in [(9.5, 1e6), (10.2, 40000.0), (10.6, 60000.0),
                      (11.2, 1e6)]:
        obs.record("train.step.dispatch", t0, t0 + 0.01,
                   moe_held_pairs=jnp.float32(pairs))
    pairs = (40000 + 60000) / (2 * 4)
    want = 100 * 96 * 2 * pairs * 2304 * 896 / 197e12 / 0.1
    assert read(run) == pytest.approx(want, rel=1e-9)


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    return make(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("fault,traced,correct,metrics", [
    (None, True, True, {"train_mfu", "moe_train_mfu"}),
    ("control", False, False, {"train_tokens_per_s", "setup_s"}),
])
def test_tiny_moe_train_cell_end_to_end(moe_root, fault, traced, correct,
                                        metrics):
    """A one-period cell runs through ``execute`` like any other. Traced
    on the CPU there is no device trace: the MFU reads, the two rooflines
    give nothing rather than raise. Its control, the int8 reference in
    the program's place, reads ``correct`` false."""
    res, run = tiny_catalog.execute(moe_root, "tiny.moe-train", SEED,
                                    traced=traced, fault=fault)
    assert run.correct is correct, run.checks
    assert set(res["metrics"]) == metrics
    assert res["notes"]["compiles_in_window"] == 0
