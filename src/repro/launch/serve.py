"""Serving launcher: batched requests through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --requests 8 --slots 4 --max-new 16
"""
from __future__ import annotations

import argparse


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
    for i in range(args.requests):
        engine.submit(Request(
            uid=i,
            prompt=rng.randint(0, cfg.vocab_size,
                               size=args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new))
    done = engine.run_to_completion()
    for line in summary(done, engine.counters):
        print(line)
    for r in done[:3]:
        print(f"  req {r.uid}: {r.out_tokens[:8]}...")


def summary(done, counters) -> list:
    """Lines read from the requests' host-clock stamps and the span
    recorder: TTFT, time between tokens (over every gap between two
    tokens of one request) and queue wait (p50/p90), the decode step's
    device wait and host time, and the longest step with the spans under
    it. Compiles are in these numbers: they are spans of
    their own under the step that paid them."""
    import numpy as np
    from repro import obs

    def p50_90(xs, scale=1e3):
        if not xs:
            return "-"
        a, b = np.percentile(np.asarray(xs) * scale, [50, 90])
        return f"p50 {a:.1f} / p90 {b:.1f}"

    served = [r for r in done if r.t_first is not None]
    tokens = sum(len(r.out_tokens) for r in served)
    ttft = [r.t_first - r.t_submit for r in served]
    tbt = [b - a for r in served for a, b in zip(r.t_tokens, r.t_tokens[1:])]
    wait = [r.t_admit - r.t_submit for r in served]
    lines = [f"served {len(served)} of {len(done)} requests, {tokens} "
             f"tokens; counters {dict(counters)}",
             f"TTFT ms {p50_90(ttft)}; TBT ms {p50_90(tbt)}; "
             f"queue wait ms {p50_90(wait)}"]
    steps = [(s, w) for s, w in obs.device_waits(obs.spans(), "serve.step")
             if s.attrs.get("admitted") == 0 and s.attrs.get("active")]
    if steps:
        n = len(steps)
        lines.append(
            f"decode step ({n}): wait {sum(w for _, w in steps) / n * 1e3:.2f}"
            f" ms, host {sum(s.seconds - w for s, w in steps) / n * 1e3:.2f}"
            f" ms")
    kept = obs.longest()
    if kept:
        root, kids = kept[0]
        lines.append(f"longest {root.name} {root.seconds * 1e3:.1f} ms "
                     f"{root.attrs}:")
        depth = {root.id: 0}
        pauses = [k for k in kids if k.name == "gc"]
        for k in sorted(kids, key=lambda k: k.t0):
            depth[k.id] = depth.get(k.parent, 0) + 1
            if k.name != "gc":
                lines.append(f"{'  ' * depth[k.id]}{k.name} "
                             f"{k.seconds * 1e3:.1f} ms")
        if pauses:
            lines.append(f"  gc: {len(pauses)} pauses, "
                         f"{sum(k.seconds for k in pauses) * 1e3:.1f} ms")
    return lines


if __name__ == "__main__":
    main()
