"""Training loop: checkpoint/restart, straggler monitoring, multi-step
dispatch fusion, logging.

Restart contract: the loop always begins with restore-or-init; a SIGKILL at
any point loses at most ``ckpt_every`` steps (checkpoints are atomic). The
``fuse_steps``=k option scans k steps per dispatch — the paper's issue-rate
amortization (core/stripmine.fuse_steps).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint import ckpt
from repro.configs.base import ArchConfig
from repro.core.stripmine import fuse_steps as _fuse
from repro.data.pipeline import DataConfig, make_source
from repro.ft.elastic import StragglerMonitor
from repro.models.layers import init_params
from repro.models import transformer as tf
from repro.optim import adamw
from repro.train import step as step_lib


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    fuse_steps: int = 1
    grad_accum: int = 1
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ArchConfig, opt_cfg: adamw.OptConfig,
                 data_cfg: DataConfig, tcfg: TrainerConfig, mesh=None,
                 batch_axes=("data",)):
        from repro.models.sharding import MeshCtx
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.ctx = MeshCtx(mesh=mesh, batch_axes=batch_axes)
        self.bundle = step_lib.make_train_step(cfg, opt_cfg, self.ctx,
                                               grad_accum=tcfg.grad_accum)
        self.source = make_source(data_cfg)
        self.monitor = StragglerMonitor()
        self.metrics_log: list[dict] = []

        if mesh is not None:
            st_sh = step_lib.named_for(self.bundle.state_specs,
                                       self.bundle.abstract_state, mesh)
            self.state_sharding = st_sh
            self.jit_step = jax.jit(self.bundle.step_fn,
                                    in_shardings=(st_sh, None),
                                    out_shardings=(st_sh, None))
        else:
            self.state_sharding = None
            self.jit_step = jax.jit(self.bundle.step_fn)

    def step_fn(self, state, batch):
        """One step of ``jit_step``. A step of an MoE model files the pairs
        its expert layers computed (the ``moe_held_pairs`` metric, a
        device array, so no sync) on an ``obs`` span
        ``train.step.dispatch``, for readers that sum them over the steps
        of a window."""
        t0 = time.perf_counter()
        state, metrics = self.jit_step(state, batch)
        if "moe_held_pairs" in metrics:
            obs.record("train.step.dispatch", t0, time.perf_counter(),
                       moe_held_pairs=metrics["moe_held_pairs"])
        return state, metrics

    # -- state ------------------------------------------------------------

    def init_state(self):
        key = jax.random.PRNGKey(self.tcfg.seed)
        params = init_params(tf.model_template(self.cfg), key,
                             dtype=jax.numpy.dtype(self.cfg.param_dtype))
        state = {"params": params, "opt": adamw.init(self.opt_cfg, params)}
        if self.state_sharding is not None:
            state = jax.device_put(state, self.state_sharding)
        return state

    def restore_or_init(self):
        if self.tcfg.ckpt_dir:
            step, state = ckpt.restore(self.tcfg.ckpt_dir,
                                       shardings=self.state_sharding)
            if state is not None:
                return step, state
        return 0, self.init_state()

    # -- loop ---------------------------------------------------------------

    def run(self, on_step: Optional[Callable] = None):
        start, state = self.restore_or_init()
        t = self.tcfg
        fused = _fuse(self.jit_step, t.fuse_steps) if t.fuse_steps > 1 \
            else None
        step = start
        pending_save = None
        while step < t.steps:
            self.monitor.start_step()
            if fused is not None:
                k = min(t.fuse_steps, t.steps - step)
                if k < t.fuse_steps:
                    fused = _fuse(self.jit_step, k)
                batches = [self.source.batch(step + i) for i in range(k)]
                stacked = jax.tree_util.tree_map(
                    lambda *xs: np.stack(xs), *batches)
                state, metrics = fused(state, stacked)
                metrics = jax.tree_util.tree_map(lambda x: x[-1], metrics)
                step += k
            else:
                batch = self.source.batch(step)
                state, metrics = self.step_fn(state, batch)
                step += 1
            straggler = self.monitor.end_step()
            if step % t.log_every == 0 or step >= t.steps:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["straggler"] = straggler
                self.metrics_log.append(m)
                if on_step:
                    on_step(m)
            if t.ckpt_dir and (step % t.ckpt_every == 0 or step >= t.steps):
                if pending_save is not None:
                    pending_save.join()
                pending_save = ckpt.save(t.ckpt_dir, step, state,
                                         blocking=False)
        if pending_save is not None:
            pending_save.join()
        return step, state
