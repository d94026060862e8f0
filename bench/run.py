#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json`` and the files under ``bench/`` (see ``harness.py``).
Set-up makes the weights and inputs from ``--seed`` and warms every shape
the window uses; the window measures for ``--seconds``; then the outputs
of the window are checked against the plain reference. With ``--trace 0``
the line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from host spans and a profiler trace of part of
the window.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` when traced, and
``checks``: each number compared, beside its limit). The checks are also
the last lines of stderr. Without a TPU, or with fewer chips than the
cell asks for, the run exits nonzero and prints no result. JAX's compile
cache is ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import trace_reduce  # noqa: E402
from harness import (ROOT, BenchError, Catalog, Run, log,  # noqa: E402
                     result_line)

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(BenchError):
    """No accelerator, or fewer chips than the cell needs."""


class CompileCounter:
    """Counts XLA compiles and jaxpr traces in this process (JAX's
    monitoring events; a persistent-cache hit is no backend compile)."""
    _registered = False
    compiles = 0
    traces = 0

    @classmethod
    def register(cls):
        if cls._registered:
            return
        import jax.monitoring

        def on_event(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                cls.compiles += 1
            elif name == "/jax/core/compile/jaxpr_trace_duration":
                cls.traces += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
        cls._registered = True


def execute(cat: Catalog, name: str, seed: int, seconds: float, traced: bool,
            *, require_tpu: bool = True, fault: str | None = None):
    """One run of cell ``name``. Returns ``(result, run)`` where ``result``
    holds the result line's parts.

    ``require_tpu=False`` lets the tests run a tiny cell on the CPU; a
    named ``fault`` (one of the kind's ``FAULTS``) breaks the timed path
    or, as ``control``, puts the control in the program's place, for the
    tests and ``calibrate.py``."""
    wl = cat.workload(name)
    cell = cat.cell(name)
    config = cat.config(wl["config"])
    traffic = cat.traffic(wl["traffic"])
    wanted = cat.metrics_for(name, traced)
    readers = {m["name"]: cat.reader(m["name"]) for m in wanted}
    kind = cat.kind(cell["kind"])
    if fault is not None and fault not in kind.FAULTS:
        raise BenchError(f"fault {fault!r} is not one of {kind.FAULTS}")
    ref = cat.reference(config["reference"])

    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU found (platform {dev.platform}); this "
                     f"benchmark runs on the chip")
    if len(devices) < wl["chips"]:
        raise NoChip(f"cell {name} needs {wl['chips']} chips, found "
                     f"{len(devices)}")
    peaks = cat.peaks(dev.device_kind)
    src = os.path.join(cat.root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program under test is not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    CompileCounter.register()

    run = Run(workload=wl, cell=cell, config=config, traffic=traffic,
              seed=seed, seconds=seconds, traced=traced, t_start=T_START)
    run.peaks = peaks
    run.data["fault"] = fault
    if traced:
        run.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        kind.setup(run, ref)
        c0, tr0 = CompileCounter.compiles, CompileCounter.traces
        kind.window(run)
        in_window = (CompileCounter.compiles - c0,
                     CompileCounter.traces - tr0)
        used = devices[:wl["chips"]]
        mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in used)
        kind.finish(run, ref)
        run.data.pop("params", None)
        run.data.pop("engine", None)
        if traced:
            try:
                run.trace = trace_reduce.reduce(
                    trace_reduce.find_trace(run.trace_dir))
            except (FileNotFoundError, ValueError) as e:
                if require_tpu:
                    raise BenchError(f"trace unreadable: {e}") from e
                log(f"trace: {e}")
    finally:
        if run.trace_dir:
            shutil.rmtree(run.trace_dir, ignore_errors=True)

    metrics = {}
    for m in wanted:
        value = readers[m["name"]](run)
        if value is None:
            if not traced:
                raise BenchError(f"end-to-end metric {m['name']} has no "
                                 f"value in cell {name}")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": mem}
    breakdown = None
    if run.trace is not None:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        breakdown = run.trace["breakdown"]
    late = run.data.get("lateness") or [0.0]
    notes = {"compiles_in_window": in_window[0],
             "traces_in_window": in_window[1],
             "generator_late_max_ms": max(late) * 1e3,
             "seed": seed}
    log(f"window: {in_window[0]} compiles, {in_window[1]} traces; "
        f"memory peak {mem / 2 ** 30:.2f} GiB")
    return dict(metrics=metrics, device=device, breakdown=breakdown,
                notes=notes), run


def enable_cache():
    """JAX's persistent compile cache at the checkout's fixed path, for
    every program this process compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    enable_cache()
    try:
        res, run = execute(Catalog(ROOT), args.workload, args.seed,
                              args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    except BenchError as e:
        log(f"bench: {e}")
        return 4
    print(result_line(run, res["metrics"], res["device"], res["breakdown"],
                      res["notes"]), flush=True)
    for key, c in run.checks.items():
        log(f"check {key}: {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
