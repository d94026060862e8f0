"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 50 --reduced --ckpt-dir /tmp/ckpt [--data 2 --model 2] \
      [--fuse-steps 4] [--grad-accum 2] [--seq-len 256 --batch 8]

--reduced runs the smoke-scale config (CPU-friendly); the full config needs
a real pod. With --data/--model a mesh is built from local devices (set
XLA_FLAGS=--xla_force_host_platform_device_count=N to fake them).
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fuse-steps", type=int, default=1)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--data", type=int, default=0)
    ap.add_argument("--model", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    args = ap.parse_args()

    import jax
    from repro.configs import get_config, reduced
    from repro.data.pipeline import DataConfig
    from repro.launch import jaxcache
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import OptConfig
    from repro.train.trainer import Trainer, TrainerConfig

    jaxcache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        over = {}
        if args.layers:
            over["n_layers"] = args.layers
        if args.d_model:
            over["d_model"] = args.d_model
            over["head_dim"] = max(args.d_model // 4, 8)
        cfg = reduced(cfg, **over)

    mesh = None
    if args.data and args.model:
        mesh = make_mesh(args.data, args.model)

    data_cfg = DataConfig(seq_len=args.seq_len, global_batch=args.batch,
                          vocab_size=cfg.vocab_size)
    opt_cfg = OptConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                        decay_steps=args.steps)
    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every, log_every=5,
                         fuse_steps=args.fuse_steps,
                         grad_accum=args.grad_accum)
    trainer = Trainer(cfg, opt_cfg, data_cfg, tcfg, mesh=mesh)

    def log(m):
        print(f"step {m['step']:5d} loss {m['loss']:.4f} "
              f"ce {m['ce']:.4f} lr {m['lr']:.2e} gnorm {m['grad_norm']:.2f}"
              + (" [STRAGGLER]" if m.get("straggler") else ""), flush=True)

    step, _ = trainer.run(on_step=log)
    print(f"done at step {step}; median step time "
          f"{trainer.monitor.median*1000:.1f} ms")


if __name__ == "__main__":
    main()
