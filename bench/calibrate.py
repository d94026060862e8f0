#!/usr/bin/env python3
"""Calibration on the chip, many runs of one cell in one process: the
readings that set a cell's correctness limits, and the knee of a serving
cell.

    python bench/calibrate.py readings --workload W --seeds 1,2,3 --seconds 20
        [--fault frozen|half_batch|token|control]
    python bench/calibrate.py knee --workload W --seeds 1,2 --rates 1,1.5
        --seconds 60

``readings`` runs the cell once per seed and prints each compared number
beside its limit and ``correct``; with ``--fault`` the timed path is
broken as named, or with ``control`` the control takes the program's
place. ``knee`` runs the serving cell at each rate, once per seed with
the mix's schedule in the order the seed names (``order_seed``), and
prints what was completed, how the queue ended, the longest pause of
Python's garbage collector, and the checks. Every line is also appended
to ``chiprun_out/calibrate.jsonl``. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402
from harness import ROOT, Catalog, percentile  # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out", "calibrate.jsonl")


def emit(obj):
    line = json.dumps(obj)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def _seeds(args):
    return [int(s) for s in args.seeds.split(",")]


def readings(args):
    cat = Catalog(ROOT)
    for seed in _seeds(args):
        t = time.perf_counter()
        res, run = bench_run.execute(cat, args.workload, seed, args.seconds,
                                     False, fault=args.fault)
        emit({"mode": "readings", "workload": args.workload, "seed": seed,
              "fault": args.fault, "correct": run.correct,
              "checks": run.checks,
              "metrics": {k: v["value"] for k, v in res["metrics"].items()},
              "notes": res["notes"], "memory_peak_bytes":
                  res["device"]["memory_peak_bytes"],
              "checked_tokens": run.data.get("checked_tokens"),
              "seconds_total": time.perf_counter() - t})
        del res, run
        gc.collect()


class GcPauses:
    """The longest pause of Python's garbage collector since ``reset``."""

    def __init__(self):
        self.t = None
        self.longest = 0.0
        gc.callbacks.append(self)

    def __call__(self, phase, _info):
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            self.longest = max(self.longest, time.perf_counter() - self.t)

    def reset(self):
        self.longest = 0.0


def knee(args):
    cat = Catalog(ROOT)
    mix_of = cat.traffic
    pauses = GcPauses()
    for rate in [float(r) for r in args.rates.split(",")]:
        for seed in _seeds(args):
            cat.traffic = lambda name, r=rate, o=seed: dict(
                mix_of(name), rate_per_s=r, order_seed=o)
            pauses.reset()
            res, run = bench_run.execute(cat, args.workload, seed,
                                         args.seconds, False)
            tr = run.data["tracker"]
            t0, t1 = run.data["t0"], run.data["t_close"]
            due = [r for r in tr.reqs.values() if r["due"] is not None]
            admitted = sum(1 for r in due if r["admit_step"] is not None
                           and r["admit_step"] <= t1)
            tokens = sum(1 for r in tr.reqs.values() for x in r["times"]
                         if t0 <= x <= t1)
            half = t0 + (t1 - t0) / 2
            waits = [((r["admit_step"] or run.data["t_drained"]) - r["due"],
                      r["due"] < half) for r in due]
            emit({"mode": "knee", "workload": args.workload, "rate": rate,
                  "seed": seed, "due": len(due),
                  "backlog_at_close": len(due) - admitted,
                  "tokens_per_s": tokens / (t1 - t0),
                  "wait_p90_first_half_s": percentile(
                      [w for w, first in waits if first], 90),
                  "wait_p90_second_half_s": percentile(
                      [w for w, first in waits if not first], 90),
                  "gc_pause_max_ms": pauses.longest * 1e3,
                  "correct": run.correct, "checks": run.checks,
                  "metrics": {k: v["value"]
                              for k, v in res["metrics"].items()},
                  "notes": res["notes"]})
            del res, run, tr, due
            gc.collect()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    bench_run.enable_cache()
    {"readings": readings, "knee": knee}[args.mode](args)


if __name__ == "__main__":
    main()
