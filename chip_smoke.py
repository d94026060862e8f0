#!/usr/bin/env python3
"""Smoke run of the program's main paths on TPU, in one process.

    python chip_smoke.py             # one chip: serving, training, vector engine
    python chip_smoke.py --chips 4   # four chips: ClusterEngine and the 2x2
                                     # meshed train steps (dense and MoE),
                                     # each against one device

One chip runs three phases through the entry points a user calls:

1. serving: ``stablelm-1.6b`` at its published widths and depth (random
   weights from a seed) answers 8 requests through ``ServingEngine``; the
   cached forward's logits are checked against a no-cache fp32 forward;
2. training: three ``Trainer`` steps at ``stablelm-1.6b`` widths with the
   depth cut, at a sequence length that takes the flash-attention kernel;
3. vector engine: ``ReferenceEngine.run_many`` on the paper's n=256 DGEMM
   program and on a batch of the 32-bit differential cells, against the
   numpy oracle.

The script exits nonzero, and prints no result line, unless JAX finds a
TPU. Every phase checks its own outputs; the last line of stdout is
``{"ok": true, "device": {...}}`` only when all of them pass. Compiles go
to the persistent cache of ``repro.launch.jaxcache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.configs.ara import AraConfig  # noqa: E402
from repro.core import isa  # noqa: E402
from repro.core.cluster import ClusterEngine  # noqa: E402
from repro.core.vector_engine import ReferenceEngine  # noqa: E402
from repro.data.pipeline import DataConfig  # noqa: E402
from repro.launch import jaxcache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.layers import init_params  # noqa: E402
from repro.models.sharding import MeshCtx  # noqa: E402
from repro.optim.adamw import OptConfig  # noqa: E402
from repro.serving.engine import (Request, ServingEngine, State,  # noqa: E402
                                  decode_lowering)
from repro.testing import differential as diff  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

GIB, MIB = 1 << 30, 1 << 20
ARCH = "stablelm-1.6b"
SEED = 0

# serving: slots x max_seq from the decode step's memory_analysis (fp32
# params 6.1 GiB + two fp32 caches of 1.5 GiB, the step donates nothing,
# + 2.4 GiB temporaries = 11.5 GiB on a v5e; 8 slots would need 14.8)
SLOTS, MAX_SEQ = 4, 1024
PROMPT_LENS = (128, 256, 512)
N_REQUESTS, MAX_NEW = 8, 32
CHECK_PROMPT, CHECK_STEPS = 128, 3
# Relative L2 error of the cached path's last-position logits against the
# no-cache fp32 reference. The cached path computes in bf16 (the model's
# compute dtype) at d_model 2048 through 24 layers, so it cannot match fp32
# to fp32 ulps: at these widths the CPU gave 0.7e-2 at 1 layer and 1.6e-2
# at 8 layers (fp32 compute: 3e-6). A decode step whose cache row lands one
# position off gave 0.06-0.12, and the check asserts that it fails.
LOGITS_RTOL = 0.05

# training: stablelm-1.6b widths, depth cut to fit fp32 params + Adam state
# (memory_analysis on a v5e: 13.0 GiB at 2 layers, batch 2, seq 2048)
TRAIN_LAYERS, TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 2, 2048, 2, 3
# 2x2 mesh vs one device: same init, same batch; the bf16 contractions
# are split across lanes, so the summation order (not the math) differs
MESH_LOSS_RTOL = 1e-2
# the MoE train cell's config (one period of 4 layers, 8 of 64 experts
# held) on the 2x2 mesh: its grouped products run in moe_held_mesh's
# shard_map, the held experts split over the lanes
MOE_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "bench", "configs", "mellum2-12b-l4-ep8.json")

# vector engine: the paper's marquee DGEMM point (n=256 on 16 lanes). One
# program per call: the batched step evaluates every opcode branch per
# instruction row (ROADMAP S5), 2.5 ms per row on a v5e for a batch of 2 vs
# 83 us for one program, so a batch of 2 takes 9 minutes; the batched path
# runs on the differential cells instead.
DGEMM_N, DGEMM_BATCH, DGEMM_LANES = 256, 1, 16
DIFF_PER_CELL = 20


def log(msg: str):
    print(msg, flush=True)


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def footprint(compiled) -> int:
    """Device bytes one call of a compiled program needs at its peak."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def device_budget(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("bytes_limit", 16 * GIB))


# ---------------------------------------------------------------------------
# Phase 1: serving
# ---------------------------------------------------------------------------


def make_requests(vocab: int, n: int, prompt_lens, max_new: int, seed: int):
    rng = np.random.RandomState(seed)
    return [Request(uid=i,
                    prompt=rng.randint(0, vocab, size=prompt_lens[
                        i % len(prompt_lens)]).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def serve_once(cfg, params, requests, slots: int, max_seq: int):
    """Answer ``requests`` through a fresh engine; check every invariant."""
    engine = ServingEngine(cfg, params, slots=slots, max_seq=max_seq)
    for req in requests:
        reason = engine.submit(dataclasses.replace(req, out_tokens=[]))
        assert reason is None, f"request {req.uid} rejected: {reason}"
    done = engine.run_to_completion()
    assert len(done) == len(requests), (len(done), len(requests))
    assert not engine.events, engine.events
    for code in ("I_NAN_LOGITS", "I_KV_BOUNDS", "I_KV_CAPACITY",
                 "I_SLOT_LEAK", "I_SLOT_STALL"):
        assert engine.counters[code] == 0, (code, engine.counters[code])
    out = {}
    for req in done:
        assert req.state is State.DONE, (req.uid, req.state, req.finish_reason)
        toks = np.asarray(req.out_tokens)
        assert len(toks) == req.max_new_tokens, (req.uid, len(toks))
        assert ((toks >= 0) & (toks < cfg.vocab_size)).all(), req.uid
        out[req.uid] = toks.tolist()
    return out


def cache_logits_errors(cfg, params, prompt, max_seq: int, steps: int):
    """Prefill + ``steps`` greedy decode steps through ``tf.forward`` with
    the fp32 cache, called as the engine calls it, against one no-cache
    fp32 forward of the same tokens. Returns the per-position relative
    errors and, as the control, the error of a decode step whose cache
    row lands one position early."""
    ctx = MeshCtx(mesh=None)

    @jax.jit
    def cached(params, cache, tokens):
        logits, _, cache = tf.forward(cfg, params, tokens, ctx=ctx,
                                      cache=cache)
        return logits[0, -1].astype(jnp.float32), cache

    ref_cfg = dataclasses.replace(cfg, compute_dtype="float32",
                                  attn_flash="off")

    @jax.jit
    def reference(params, tokens):
        logits, _, _ = tf.forward(ref_cfg, params, tokens, ctx=ctx)
        return logits[0].astype(jnp.float32)

    plen = len(prompt)
    fresh = tf.init_cache(cfg, 1, max_seq, cache_dtype=jnp.float32)
    logits, cache = cached(params, fresh, jnp.asarray(prompt)[None])
    prefill_cache = cache
    got, toks = [logits], list(prompt)
    for _ in range(steps):
        toks.append(int(jnp.argmax(got[-1])))
        logits, cache = cached(params, cache,
                               jnp.asarray([[toks[-1]]], jnp.int32))
        got.append(logits)
    off = dict(prefill_cache, lengths=prefill_cache["lengths"] - 1)
    control, _ = cached(params, off, jnp.asarray([[toks[plen]]], jnp.int32))
    with jax.default_matmul_precision("highest"):
        ref = reference(params, jnp.asarray(toks, jnp.int32)[None])
    errs = [rel_l2(g, ref[plen - 1 + i]) for i, g in enumerate(got)]
    return errs, rel_l2(control, ref[plen])


def phase_serving(cfg, *, slots: int, max_seq: int, prompt_lens,
                  n_requests: int, max_new: int, check_prompt: int,
                  check_steps: int, rtol: float, budget: int):
    t0 = time.perf_counter()
    need = footprint(decode_lowering(cfg, slots, max_seq).compile())
    t_aot = time.perf_counter() - t0
    log(f"serve: {cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
        f"vocab {cfg.vocab_size}; {slots} slots x max_seq {max_seq}; decode "
        f"step needs {need / GIB:.2f} GiB of {budget / GIB:.2f} GiB "
        f"(compile {t_aot:.1f} s)")
    assert need <= budget, (need, budget)

    t0 = time.perf_counter()
    template = tf.model_template(cfg)
    params = jax.jit(lambda key: init_params(
        template, key, dtype=jnp.dtype(cfg.param_dtype)))(
            jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    log(f"serve: random init {time.perf_counter() - t0:.1f} s")

    requests = make_requests(cfg.vocab_size, n_requests, prompt_lens,
                             max_new, SEED)
    t0 = time.perf_counter()
    first = serve_once(cfg, params, requests, slots, max_seq)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = serve_once(cfg, params, requests, slots, max_seq)
    t_again = time.perf_counter() - t0
    assert again == first, "greedy outputs differ between two passes"
    n_tok = sum(len(t) for t in first.values())
    log(f"serve: {n_requests} requests DONE, {n_tok} tokens; pass 1 "
        f"(compile + run) {t_first:.1f} s, pass 2 (run) {t_again:.1f} s")

    t0 = time.perf_counter()
    errs, control = cache_logits_errors(
        cfg, params, requests[0].prompt[:check_prompt], max_seq, check_steps)
    log(f"serve: cached vs fp32 no-cache logits, rel L2 per position "
        f"{[f'{e:.2e}' for e in errs]} (bound {rtol}); cache row one "
        f"position off: {control:.2e} ({time.perf_counter() - t0:.1f} s)")
    assert all(math.isfinite(e) and e <= rtol for e in errs), errs
    assert control > rtol, f"a wrong cache row passes the check ({control})"


# ---------------------------------------------------------------------------
# Phase 2: training
# ---------------------------------------------------------------------------


def make_trainer(cfg, *, seq_len: int, batch: int, steps: int, mesh=None):
    data = DataConfig(seq_len=seq_len, global_batch=batch,
                      vocab_size=cfg.vocab_size, seed=SEED)
    opt = OptConfig(warmup_steps=1, decay_steps=steps)
    tcfg = TrainerConfig(steps=steps, log_every=1, seed=SEED)
    return Trainer(cfg, opt, data, tcfg, mesh=mesh)


def compile_step(trainer, seq_len: int, batch: int):
    tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
    return trainer.jit_step.lower(trainer.bundle.abstract_state,
                                  {"tokens": tok, "labels": tok}).compile()


def run_losses(trainer, label: str):
    t0 = time.perf_counter()
    _, state = trainer.run()
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    losses = [m["loss"] for m in trainer.metrics_log]
    log(f"{label}: {len(losses)} steps {dt:.1f} s, losses "
        f"{[f'{x:.4f}' for x in losses]}")
    assert losses and all(math.isfinite(x) for x in losses), losses
    return losses, state


def phase_training(cfg, *, seq_len: int, batch: int, steps: int,
                   budget: int) -> str:
    """Train ``steps`` steps; returns the compiled step's HLO text."""
    trainer = make_trainer(cfg, seq_len=seq_len, batch=batch, steps=steps)
    t0 = time.perf_counter()
    compiled = compile_step(trainer, seq_len, batch)
    need = footprint(compiled)
    log(f"train: step compile {time.perf_counter() - t0:.1f} s, needs "
        f"{need / GIB:.2f} GiB of {budget / GIB:.2f} GiB")
    assert need <= budget, (need, budget)
    run_losses(trainer, "train")
    return compiled.as_text()


# ---------------------------------------------------------------------------
# Phase 3: vector engine
# ---------------------------------------------------------------------------


def phase_engine(*, n: int, batch: int, lanes: int, per_cell: int):
    cfg = AraConfig(lanes=lanes)
    eng = ReferenceEngine(cfg, dtype=jnp.float32)
    prog = isa.matmul_program(n, 0, n * n, 2 * n * n, t=4,
                              vlmax=cfg.vlmax_dp)
    rng = np.random.RandomState(SEED)
    mems = [rng.randn(3 * n * n).astype(np.float32) for _ in range(batch)]
    t0 = time.perf_counter()
    outs, _ = eng.run_many([prog] * batch, mems)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    again, _ = eng.run_many([prog] * batch, mems)
    t_again = time.perf_counter() - t0
    for i, (mem, out) in enumerate(zip(mems, outs)):
        np.testing.assert_array_equal(again[i], out)
        want, _ = diff.numpy_oracle(prog, mem, cfg.vlmax_dp,
                                    storage=np.float32)
        np.testing.assert_allclose(out, want, rtol=diff.TOL[64],
                                   atol=diff.TOL[64])
    log(f"engine: DGEMM n={n} x{batch} on {lanes} lanes ({len(prog)} "
        f"instructions) matches the numpy oracle; first call (compile + "
        f"run) {t_first:.1f} s, second {t_again:.1f} s")

    ref = ReferenceEngine(AraConfig(lanes=2), vlmax=diff.VLMAX64,
                          dtype=jnp.float32)
    t0 = time.perf_counter()
    checked = diff.run_cells(diff.engine_batch(ref),
                             diff.oracle_batch(diff.VLMAX64),
                             diff.cells(per_cell, sews=(32,)),
                             label="engine-vs-oracle-sew32")
    log(f"engine: {checked} SEW=32 differential programs match the numpy "
        f"oracle ({time.perf_counter() - t0:.1f} s)")


# ---------------------------------------------------------------------------
# Four chips: ClusterEngine and the meshed train step
# ---------------------------------------------------------------------------


def phase_cluster(*, per_cell: int):
    cfg = AraConfig(lanes=2)
    ref = ReferenceEngine(cfg, vlmax=diff.VLMAX64, dtype=jnp.float32)
    clu = ClusterEngine(cfg, clusters=2, lanes_per_cluster=2,
                        vlmax=diff.VLMAX64, dtype=jnp.float32)
    t0 = time.perf_counter()
    checked = diff.run_cells(diff.engine_batch(ref), diff.engine_batch(clu),
                             diff.cells(per_cell),
                             label="cluster-2x2-vs-reference")
    log(f"cluster: ClusterEngine(2, 2) on {clu.mesh.devices.size} devices "
        f"matches ReferenceEngine on {checked} differential programs "
        f"({time.perf_counter() - t0:.1f} s)")


def phase_mesh_train(cfg, *, seq_len: int, batch: int, steps: int,
                     rtol: float) -> str:
    """The 2x2 meshed step against one device; returns the meshed step's
    HLO text."""
    mesh = make_mesh(2, 2)
    trainer = make_trainer(cfg, seq_len=seq_len, batch=batch, steps=steps,
                           mesh=mesh)
    t0 = time.perf_counter()
    hlo = compile_step(trainer, seq_len, batch).as_text()
    log(f"mesh: 2x2 step compile {time.perf_counter() - t0:.1f} s")
    meshed, state = run_losses(trainer, "mesh: 2x2")
    leaves = jax.tree_util.tree_leaves(state)
    per_dev = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per_dev[shard.device] = per_dev.get(shard.device, 0) \
                + shard.data.nbytes
    total = sum(leaf.nbytes for leaf in leaves)
    log("mesh: state bytes per device "
        + ", ".join(f"{d.id}: {b / MIB:.1f} MiB"
                    for d, b in sorted(per_dev.items(), key=lambda x: x[0].id))
        + f" (unsharded {total / MIB:.1f} MiB)")
    assert set(per_dev) == set(mesh.devices.flat), per_dev
    assert max(per_dev.values()) < total, "state is not sharded"
    del state, leaves, trainer

    single = make_trainer(cfg, seq_len=seq_len, batch=batch, steps=steps)
    one, state = run_losses(single, "mesh: one device")
    del state
    errs = [abs(a - b) / abs(b) for a, b in zip(meshed, one)]
    log(f"mesh: loss rel diff 2x2 vs one device "
        f"{[f'{e:.2e}' for e in errs]} (bound {rtol})")
    assert len(meshed) == len(one) == steps
    assert max(errs) <= rtol, errs
    return hlo


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the paths that span four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this run needs the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    jax.config.update("jax_enable_x64", False)
    log(f"compile cache: {jaxcache.enable()}")
    budget = device_budget(dev)

    full = get_config(ARCH)
    cut = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    log(f"train config: {ARCH} widths, depth cut {full.n_layers} -> "
        f"{cut.n_layers} layers, seq {TRAIN_SEQ}, batch {TRAIN_BATCH}")

    if args.chips == 4:
        phase_cluster(per_cell=5)
        hlo = phase_mesh_train(cut, seq_len=TRAIN_SEQ, batch=TRAIN_BATCH,
                               steps=2, rtol=MESH_LOSS_RTOL)
        assert "tpu_custom_call" in hlo, "meshed step runs no Pallas kernel"
        with open(MOE_CONFIG) as f:
            moe = ArchConfig(**json.load(f)["arch"])
        hlo = phase_mesh_train(moe, seq_len=TRAIN_SEQ, batch=TRAIN_BATCH,
                               steps=2, rtol=MESH_LOSS_RTOL)
        assert re.search(r"%gmm[.0-9]* = ", hlo) \
            and re.search(r"%tgmm[.0-9]* = ", hlo), \
            "meshed MoE step runs no grouped product"
    else:
        phase_serving(full, slots=SLOTS, max_seq=MAX_SEQ,
                      prompt_lens=PROMPT_LENS, n_requests=N_REQUESTS,
                      max_new=MAX_NEW, check_prompt=CHECK_PROMPT,
                      check_steps=CHECK_STEPS, rtol=LOGITS_RTOL,
                      budget=budget)
        hlo = phase_training(cut, seq_len=TRAIN_SEQ, batch=TRAIN_BATCH,
                             steps=TRAIN_STEPS, budget=budget)
        assert "tpu_custom_call" in hlo, "train step runs no Pallas kernel"
        log("train: flash-attention kernel compiled into the step "
            "(tpu_custom_call)")
        phase_engine(n=DGEMM_N, batch=DGEMM_BATCH, lanes=DGEMM_LANES,
                     per_cell=DIFF_PER_CELL)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
