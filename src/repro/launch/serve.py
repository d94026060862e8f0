"""Serving launcher: batched requests through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --requests 8 --slots 4 --max-new 16
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.launch import jaxcache
    from repro.models.layers import init_params
    from repro.models.transformer import model_template
    from repro.serving.engine import Request, ServingEngine

    jaxcache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = init_params(model_template(cfg), jax.random.PRNGKey(args.seed))
    engine = ServingEngine(cfg, params, slots=args.slots,
                           max_seq=args.max_seq)

    rng = np.random.RandomState(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        engine.submit(Request(
            uid=i,
            prompt=rng.randint(0, cfg.vocab_size,
                               size=args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new))
    done = engine.run_to_completion()
    dt = time.time() - t0
    total_new = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new/dt:.1f} tok/s)")
    for r in done[:3]:
        print(f"  req {r.uid}: {r.out_tokens[:8]}...")


if __name__ == "__main__":
    main()
