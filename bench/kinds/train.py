"""Training cells: ``Trainer.step_fn`` (``make_train_step`` with the flash
kernel on the TPU's route) over seeded token batches.

Set-up builds one trainer and one state from the seed (the weights from
the reference's initializer, the optimizer state from the program's) and
drives that same state through the first three steps with the window's
own call and feed; those steps compile the step and give the program's
readings for the check: each step's loss, every leaf's first gradient as
the optimizer holds it (its first moment over 1 - b1), and every leaf's
change after three steps. The window then continues from step 3, one
synced step after another; the next batch is made on the host while the
device runs the current step.

The check replays the three steps in the plain fp32 reference from the
same weights and batches, once the program's state is freed. Under the
fault ``control`` the int8 reference's three steps take the program's
readings' place.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import traffic_gen
from harness import BenchError, flat_shapes, log

CHECK_STEPS = 3
FAULTS = ("frozen", "half_batch", "control")     # see run.execute


def _program():
    import repro.models.layers as layers
    from repro.configs.base import ArchConfig
    from repro.data.pipeline import DataConfig
    from repro.models import transformer as tf
    from repro.optim import adamw
    from repro.train.trainer import Trainer, TrainerConfig
    return ArchConfig, DataConfig, tf, layers, adamw, Trainer, TrainerConfig


@jax.jit
def leaf_norms(tree, scale):
    """Frobenius norm of every leaf, times ``scale``."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)]) * scale


@jax.jit
def diff_norms(a, b):
    """Norm of the difference of every pair of leaves."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])


def leaf_names(tree):
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def batch(run, step: int) -> dict:
    cell = run.cell
    toks = traffic_gen.token_rows(run.traffic, run.seed_rng("batch", step),
                                  cell["batch"], cell["seq"] + 1,
                                  run.config["arch"]["vocab_size"])
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def setup(run, ref):
    ArchConfig, DataConfig, tf, layers, adamw, Trainer, TrainerConfig = \
        _program()
    cell, arch = run.cell, run.config["arch"]
    cfg = ArchConfig(**arch)
    bad = ref.check_layout(arch, flat_shapes(
        layers.abstract_params(tf.model_template(cfg))))
    if bad:
        raise BenchError(bad)
    opt = adamw.OptConfig(**cell["opt"])
    seed = run.jax_seed()
    trainer = Trainer(cfg, opt, DataConfig(
        seq_len=cell["seq"], global_batch=cell["batch"],
        vocab_size=cfg.vocab_size, seed=seed), TrainerConfig(seed=seed))
    params = ref.make_params(arch, seed)
    state = {"params": params, "opt": adamw.init(opt, params)}
    del params
    step_fn = _faulty(run, trainer.step_fn, cell["batch"])
    losses, grad_norms = [], None
    with run.span("first_steps"):
        for k in range(CHECK_STEPS):
            state, metrics = step_fn(state, batch(run, k))
            losses.append(float(metrics["loss"]))
            if k == 0:
                grad_norms = np.asarray(leaf_norms(
                    state["opt"]["m"], 1.0 / (1.0 - opt.b1)))
        p0 = ref.make_params(arch, seed)
        upd_norms = np.asarray(diff_norms(state["params"], p0))
        del p0
    names = leaf_names(state["params"])
    log(f"train: losses of the first {CHECK_STEPS} steps {losses}")
    run.data.update(step_fn=step_fn, state=state, trainer=trainer,
                    seed=seed, names=names,
                    program=dict(losses=losses, grad_norms=grad_norms,
                                 upd_norms=upd_norms))


def _faulty(run, step_fn, rows):
    """The step as the window calls it; a named fault replaces it only in
    the harness's own tests and calibration runs (the fault ``control``
    acts in the check)."""
    fault = run.data.get("fault")
    if fault == "frozen":
        def frozen(state, b):
            _, metrics = step_fn(state, b)
            return state, metrics
        return frozen
    if fault == "half_batch":
        def half(state, b):
            return step_fn(state, {k: v[:rows // 2] for k, v in b.items()})
        return half
    return step_fn


def window(run):
    cell = run.cell
    step_fn, state = run.data["step_fn"], run.data.pop("state")
    step = CHECK_STEPS
    nxt = batch(run, step)
    n = 0
    t0 = time.perf_counter()
    run.window_open = t0
    trace_at = t0 + cell.get("trace_from", 0.4) * run.seconds
    trace_to = trace_at + cell.get("trace_seconds", 3.0)
    while True:
        now = time.perf_counter()
        run.trace_window(now >= trace_at, now >= trace_to)
        with run.span("step"):
            state, metrics = step_fn(state, nxt)
        step += 1
        with run.span("next_batch"):
            nxt = batch(run, step)
        with run.span("sync"):
            loss = float(metrics["loss"])
        n += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    t1 = time.perf_counter()
    run.trace_window(True, True)
    tokens = n * cell["batch"] * cell["seq"]
    run.data["train"] = dict(steps=n, tokens=tokens, t0=t0, t1=t1,
                             last_loss=loss)
    run.attempted = n
    run.failed = 0 if np.isfinite(loss) else 1
    log(f"train: {n} steps, {tokens} tokens in {t1 - t0:.3f} s, last loss "
        f"{loss:.4f}")
    del state


def worst_leaf_gap(prog, ref_norms, grad_ref):
    """Largest |program norm - reference norm| over the reference's norm
    of that leaf or of the median leaf, whichever is larger, over the
    leaves whose reference gradient is at least a thousandth of the median
    leaf's (a gradient that is nought to rounding moves its leaf under
    Adam by round-off alone). Returns (gap, leaf index)."""
    prog, ref_norms = np.asarray(prog, np.float64), np.asarray(ref_norms,
                                                               np.float64)
    keep = np.asarray(grad_ref) >= 1e-3 * np.median(grad_ref)
    med = np.median(ref_norms[keep])
    gaps = np.abs(prog - ref_norms) / np.maximum(ref_norms, med)
    gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def reference_readings(run, ref, quant=False) -> dict:
    """The three steps in the reference: losses, first clipped gradient
    norms, and update norms per leaf."""
    cell, arch = run.cell, run.config["arch"]
    params = ref.make_params(arch, run.data["seed"])
    st = ref.adamw_init(params)
    losses, grad_norms = [], None
    for k in range(CHECK_STEPS):
        b = batch(run, k)
        loss, grads = ref.loss_and_grad(arch, params, b["tokens"],
                                        b["labels"], quant=quant)
        losses.append(float(loss))
        del b
        params, st, clipped = ref.adamw_step(cell["opt"], params, grads, st)
        del grads
        if k == 0:
            grad_norms = clipped
    del st
    upd = np.asarray(diff_norms(params, ref.make_params(
        arch, run.data["seed"])))
    return dict(losses=losses, grad_norms=grad_norms, upd_norms=upd)


def compare(prog: dict, refr: dict, names) -> dict:
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["losses"], refr["losses"]))
    g, gi = worst_leaf_gap(prog["grad_norms"], refr["grad_norms"],
                           refr["grad_norms"])
    u, ui = worst_leaf_gap(prog["upd_norms"], refr["upd_norms"],
                           refr["grad_norms"])
    return {"loss_gap": loss_gap, "grad_norm_gap": g, "update_norm_gap": u,
            "grad_leaf": names[gi], "update_leaf": names[ui]}


def finish(run, ref):
    run.data.pop("trainer", None)
    run.data.pop("step_fn", None)
    t = time.perf_counter()
    refr = reference_readings(run, ref)
    prog = run.data["program"]
    if run.data.get("fault") == "control":
        log(f"check: the program read {compare(prog, refr, run.data['names'])}"
            f"; the int8 control takes its place")
        prog = reference_readings(run, ref, quant=True)
    got = compare(prog, refr, run.data["names"])
    log(f"check: reference losses {refr['losses']}; worst grad leaf "
        f"{got['grad_leaf']}, worst update leaf {got['update_leaf']} "
        f"({time.perf_counter() - t:.1f} s)")
    limits = run.cell["limits"]
    for key in ("loss_gap", "grad_norm_gap", "update_norm_gap"):
        run.check(key, got[key], limits[key])
