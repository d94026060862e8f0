"""Ahead-of-time compiles for one TPU v5e chip, described and not attached.

The TPU compiler ships with jax: it compiles for a chip described by
``jax.experimental.topologies`` and refuses what the chip would refuse —
Pallas block shapes the Mosaic lowering cannot tile, too much fast
memory, a program that does not fit the device. Nothing runs here, so
these tests say nothing about results or speed (the interpret-mode tests
check results). They guard the main path at real widths:

- the flash-attention kernel, forward and forward+backward, at the
  blocks it picks for itself (1024 at the train cell's 4096 x 128
  heads: the tiles must fit the default scoped VMEM), and with the
  Mellum2 cell's 1024 window at 8192;
- the bf16 and int8 Pallas matmuls;
- the full-width stablelm-1.6b serving decode step at the slot pool
  ``chip_smoke.py`` uses, and its training step at the depth cut it uses;
- MoE train and decode steps over a 2 x 2 mesh of four chips: the
  grouped products are Pallas calls XLA cannot partition, so the held
  expert layer must run as a shard_map there.

The topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the one that runs
this file loads the TPU library.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import REPO

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

HBM_BYTES = 16_909_336_064        # bytes_limit of one v5e chip's HBM
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def v5e_2x2():
    """The described four-chip v5e host (2 x 2)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (B, H, S, D) and dtype: chip_smoke.py's stablelm heads, the train
# cell's starcoder2-3b step (32 padded heads of 128 at batch 2 x 4096,
# 1024 blocks), and the widest fp32 head the block rule caps at 512
FLASH_OPERANDS = {
    "stablelm-2k": ((1, 32, 2048, 64), jnp.bfloat16),
    "train-4k": ((2, 32, 4096, 128), jnp.bfloat16),
    "d256-fp32": ((1, 8, 4096, 256), jnp.float32),
}


def _flash_operands(one_chip, case):
    shape, dtype = FLASH_OPERANDS[case]
    q = _sds(shape, dtype, one_chip)
    return q, _sds((shape[0], shape[2]), jnp.bool_, one_chip)


@pytest.mark.parametrize("case", FLASH_OPERANDS)
def test_flash_attention_forward_compiles(one_chip, case):
    from repro.kernels.attention import flash_attention
    q, kv_valid = _flash_operands(one_chip, case)

    def fwd(q, k, v, kv_valid):
        return flash_attention(q, k, v, kv_valid=kv_valid, causal=True,
                               interpret=False)
    compiled = jax.jit(fwd).lower(q, q, q, kv_valid).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("case", FLASH_OPERANDS)
def test_flash_attention_backward_compiles(one_chip, case):
    from repro.kernels.attention import flash_attention
    q, kv_valid = _flash_operands(one_chip, case)

    def loss(q, k, v, kv_valid):
        out = flash_attention(q, k, v, kv_valid=kv_valid, causal=True,
                              interpret=False)
        return out.astype(jnp.float32).sum()
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = grad.lower(q, q, q, kv_valid).compile().as_text()
    # forward (lse residual) + the dQ and dK/dV kernels
    assert text.count(KERNEL) >= 3


@pytest.mark.parametrize("pass_", ["fwd", "bwd"])
def test_flash_attention_window_compiles(one_chip, pass_):
    """The Mellum2 cell's sliding layers: q (2, 32, 8192, 128) bf16 with
    a 1024 window, forward alone and with both backward kernels."""
    from repro.kernels.attention import flash_attention
    q = _sds((2, 32, 8192, 128), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=1024,
                               interpret=False)
    fn = fwd if pass_ == "fwd" else jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, q, q).compile().as_text()
    assert text.count(KERNEL) >= (1 if pass_ == "fwd" else 3)


def test_flash_window_none_is_the_causal_program(one_chip):
    """``window=None`` lowers to the very program of a call that names no
    window; a window changes the kernels' bodies, not their outputs."""
    import re

    from repro.kernels.attention import flash_attention
    q = _sds((1, 8, 2048, 128), jnp.bfloat16, one_chip)

    def step(**kw):
        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=False,
                                   **kw).astype(jnp.float32).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)

    def outputs(lowered):
        return sorted(re.sub(r"\{[^}]*\}", "", ln.split(" = ", 1)[1]
                             .split(" custom-call(")[0])
                      for ln in lowered.compile().as_text().splitlines()
                      if KERNEL in ln and " custom-call(" in ln)

    plain, none, band = step(), step(window=None), step(window=512)
    assert none.as_text() == plain.as_text()
    assert band.as_text() != plain.as_text()
    assert len(outputs(plain)) == 3
    assert outputs(band) == outputs(plain)


def test_moe_window_step_kernels_keep_their_names(one_chip, monkeypatch):
    """A Mellum2-shaped train step (one period of 3 sliding + 1 full
    layer, 8 of 64 experts held, narrower widths) names its grouped
    products ``gmm``/``tgmm`` and its flash kernels as the cell's
    ``expert_kernels`` and ``flash_kernels`` patterns find them: per
    layer 3 forward products, 3 more under remat and 3 input gradients
    (``gmm``), 3 weight gradients (``tgmm``); the attention scopes name
    the layer kinds."""
    import json
    import re

    from repro.configs.base import ArchConfig
    from repro.kernels import ops
    from repro.models import attention
    from repro.models.sharding import MeshCtx
    from repro.optim.adamw import OptConfig
    from repro.train import step as step_lib
    monkeypatch.setattr(attention, "flash_route_enabled",
                        lambda mode="auto": True)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    with open(os.path.join(REPO, "bench", "configs",
                           "mellum2-12b-l4-ep8.json")) as f:
        arch = json.load(f)["arch"]
    with open(os.path.join(REPO, "bench", "workloads",
                           "mellum2-12b.train-8k.json")) as f:
        cell = json.load(f)
    cfg = dataclasses.replace(ArchConfig(**arch), d_model=512,
                              vocab_size=1024)
    bundle = step_lib.make_train_step(cfg, OptConfig(), MeshCtx(mesh=None))
    state = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), bundle.abstract_state)
    tok = _sds((1, 2048), jnp.int32, one_chip)
    lowered = jax.jit(bundle.step_fn).lower(state,
                                            {"tokens": tok, "labels": tok})
    scoped = lowered.as_text(debug_info=True)
    assert "attn.window/" in scoped and "attn.full/" in scoped
    assert "moe.experts/" in scoped
    lines = [ln.strip() for ln in lowered.compile().as_text().splitlines()]
    patterns = dict(cell["flash_kernels"], **cell["expert_kernels"])
    found = {k: sum(1 for ln in lines if re.search(p, ln))
             for k, p in patterns.items()}
    assert found == {"fwd": 8, "dq": 4, "dkv": 4, "gmm": 36, "tgmm": 12}, \
        found


def _moe_mesh_cfg(name):
    """A Mellum2-shaped config (held experts of a wider router, split
    over the lanes) at narrow widths, or reduced granite-moe (padded
    experts, the lanes split each expert's hidden width)."""
    import json

    from repro.configs import get_config, reduced
    from repro.configs.base import ArchConfig
    if name == "granite":
        return reduced(get_config("granite-moe-3b-a800m"))
    with open(os.path.join(REPO, "bench", "configs",
                           "mellum2-12b-l4-ep8.json")) as f:
        arch = json.load(f)["arch"]
    return dataclasses.replace(ArchConfig(**arch), d_model=512,
                               vocab_size=1024)


@pytest.mark.parametrize("step", ["train", "decode"])
@pytest.mark.parametrize("name", ["mellum2", "granite"])
def test_moe_steps_compile_on_a_four_chip_mesh(v5e_2x2, monkeypatch, name,
                                               step):
    """An MoE train step and decode step over a 2 x 2 mesh (data x model)
    of described v5e chips compile with the grouped products as Mosaic
    calls: XLA refuses to partition one outside a shard_map."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.kernels import ops
    from repro.models import attention
    from repro.models import transformer as tf
    from repro.models.layers import abstract_params
    from repro.models.sharding import MeshCtx
    from repro.optim.adamw import OptConfig
    from repro.train import step as step_lib
    monkeypatch.setattr(attention, "flash_route_enabled",
                        lambda mode="auto": True)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = _moe_mesh_cfg(name)
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(2, 2), ("data", "model"))
    ctx = MeshCtx(mesh=mesh)
    pspecs = step_lib.train_state_specs(cfg, ctx)["params"]
    if step == "train":
        bundle = step_lib.make_train_step(cfg, OptConfig(), ctx)
        tok = jax.ShapeDtypeStruct((2, 256), jnp.int32)
        batch = {"tokens": tok, "labels": tok}
        state_sh = step_lib.named_for(bundle.state_specs,
                                      bundle.abstract_state, mesh)
        with mesh:
            lowered = jax.jit(
                bundle.step_fn,
                in_shardings=(state_sh, step_lib.named_for(
                    bundle.batch_specs, batch, mesh)),
                out_shardings=(state_sh, None),
            ).lower(bundle.abstract_state, batch)
    else:
        aparams = abstract_params(tf.model_template(cfg),
                                  jnp.dtype(cfg.param_dtype))
        acache = tf.init_cache(cfg, 4, 256, abstract=True)
        batch = {"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32)}
        cspecs = step_lib.named_for(step_lib.cache_pspecs(cfg, ctx),
                                    acache, mesh)
        with mesh:
            lowered = jax.jit(
                step_lib.make_decode_step(cfg, ctx),
                in_shardings=(
                    step_lib.named_for(pspecs, aparams, mesh), cspecs,
                    step_lib.named_for(
                        step_lib.batch_pspecs(cfg, "decode", ctx), batch,
                        mesh)),
                out_shardings=(None, cspecs),
            ).lower(aparams, acache, batch)
    text = lowered.compile().as_text()
    # three grouped products a layer forward, and their gradients
    per_layer = 3 if step == "decode" else 9
    assert text.count(KERNEL) >= per_layer * cfg.n_layers, \
        text.count(KERNEL)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_matmul_compiles(one_chip, kind):
    from repro.kernels.matmul import matmul, matmul_int8
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.int8
    a = _sds((1024, 2048), dtype, one_chip)
    b = _sds((2048, 1024), dtype, one_chip)
    fn = matmul if kind == "bf16" else matmul_int8
    compiled = jax.jit(lambda a, b: fn(a, b, interpret=False)) \
        .lower(a, b).compile()
    assert KERNEL in compiled.as_text()


def test_serving_decode_step_fits_one_chip(one_chip):
    """stablelm-1.6b at full size, chip_smoke.py's slot pool: arguments
    (fp32 params + cache), the undonated output cache and temporaries
    fit one chip's HBM."""
    from repro.configs import get_config
    from repro.serving.engine import decode_lowering
    cfg = get_config(chip_smoke.ARCH)
    compiled = decode_lowering(cfg, chip_smoke.SLOTS, chip_smoke.MAX_SEQ,
                               sharding=one_chip).compile()
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes > 0 and m.temp_size_in_bytes > 0
    need = chip_smoke.footprint(compiled)
    assert need <= HBM_BYTES, need


def test_train_step_fits_one_chip_with_flash_kernel(one_chip, monkeypatch):
    """chip_smoke.py's training cut (stablelm-1.6b widths, its depth,
    batch and sequence) compiles with the flash kernel inside the step
    and fits one chip with fp32 params and Adam state. The kernel route
    and compiled (not interpreted) Pallas are what a TPU backend picks;
    here the backend is the CPU, so the test picks them."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import attention
    from repro.models.sharding import MeshCtx
    from repro.optim.adamw import OptConfig
    from repro.train import step as step_lib
    monkeypatch.setattr(attention, "flash_route_enabled",
                        lambda mode="auto": True)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config(chip_smoke.ARCH),
                              n_layers=chip_smoke.TRAIN_LAYERS)
    bundle = step_lib.make_train_step(cfg, OptConfig(), MeshCtx(mesh=None))
    state = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), bundle.abstract_state)
    tok = _sds((chip_smoke.TRAIN_BATCH, chip_smoke.TRAIN_SEQ), jnp.int32,
               one_chip)
    compiled = jax.jit(bundle.step_fn).lower(
        state, {"tokens": tok, "labels": tok}).compile()
    assert KERNEL in compiled.as_text()
    need = chip_smoke.footprint(compiled)
    assert need <= HBM_BYTES, need


def test_flash_kernels_keep_their_names_inside_named_scopes(one_chip,
                                                            monkeypatch):
    """The train step's ``attn``/``mlp``/``optimizer`` scopes leave the
    flash kernels' instruction names as the train cell's
    ``flash_kernels`` patterns find them in a device trace: the forward
    (twice, once more under remat), the dQ and the dK/dV kernel."""
    import json
    import re

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import attention
    from repro.models.sharding import MeshCtx
    from repro.optim.adamw import OptConfig
    from repro.train import step as step_lib
    monkeypatch.setattr(attention, "flash_route_enabled",
                        lambda mode="auto": True)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config(chip_smoke.ARCH), n_layers=1,
                              vocab_size=1024)
    bundle = step_lib.make_train_step(cfg, OptConfig(), MeshCtx(mesh=None))
    state = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), bundle.abstract_state)
    tok = _sds((1, 256), jnp.int32, one_chip)
    lowered = jax.jit(bundle.step_fn).lower(state,
                                            {"tokens": tok, "labels": tok})
    scoped = lowered.as_text(debug_info=True)
    assert "attn/" in scoped and "optimizer/" in scoped
    lines = [ln.strip() for ln in lowered.compile().as_text().splitlines()]
    with open(os.path.join(REPO, "bench", "workloads",
                           "starcoder2-3b.train-4k.json")) as f:
        patterns = json.load(f)["flash_kernels"]
    found = {k: sum(1 for ln in lines if re.search(p, ln))
             for k, p in patterns.items()}
    assert found == {"fwd": 2, "dq": 1, "dkv": 1}, found
