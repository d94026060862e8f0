"""Benchmark plumbing shared by every cell: finding files by name, host
spans, percentiles, and the result line.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``   sizes as run, source, assumptions
- ``bench/workloads/<cell>.json``   engine sizes, kind, correctness limits
- ``bench/traffic/<mix>.json``      parameters for ``traffic_gen``
- ``bench/metrics/<metric>.py``     ``read(run) -> float | None``
- ``bench/kinds/<kind>.py``         ``setup``, ``window`` and ``finish``
                                    (the check) for one kind of cell
- ``bench/refs/<reference>.py``     plain fp32 reference and weights

So a later cell, mix or metric is new files plus new entries, and no
edit of a file that is already here.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """A fault of the benchmark's own inputs or environment."""


# ---------------------------------------------------------------------------
# Files by name
# ---------------------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (metric and kind names hold dots)."""
    if not os.path.isfile(path):
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Catalog:
    """``BENCHMARK.json`` and the files it names, under one root."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))

    def _path(self, *parts) -> str:
        return os.path.join(self.bench_dir, *parts)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise BenchError(f"unknown config {name!r}")

    def cell(self, name: str) -> dict:
        return load_json(self._path("workloads", name + ".json"))

    def traffic(self, name: str) -> dict:
        return load_json(self._path("traffic", name + ".json"))

    def kind(self, name: str):
        return load_module(self._path("kinds", name + ".py"),
                           "bench_kind_" + name)

    def reference(self, name: str):
        return load_module(self._path("refs", name + ".py"),
                           "bench_ref_" + name)

    def metrics_for(self, workload: str, traced: bool) -> List[dict]:
        """The metrics a run of this cell reports: end-to-end ones when
        untraced, per-layer ones when traced."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str) -> Callable:
        mod = load_module(self._path("metrics", metric + ".py"),
                          "bench_metric_" + metric.replace(".", "_"))
        return mod.read

    def peaks(self, device_kind: str) -> dict:
        table = load_json(self._path("peaks.json"))
        if device_kind not in table["devices"]:
            raise BenchError(f"no peaks for device_kind {device_kind!r} in "
                             f"bench/peaks.json; known: "
                             f"{sorted(table['devices'])}")
        return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# What one run records
# ---------------------------------------------------------------------------


class Run:
    """State of one run: its inputs, host spans, and what the kind's
    window and check left for the metric readers.

    Spans are ``(name, t0, t1)`` on ``time.perf_counter``. With tracing
    on, each span is also a ``TraceAnnotation`` so that the profiler's
    trace carries it on the device's clock."""

    def __init__(self, *, workload: dict, cell: dict, config: dict,
                 traffic: dict, seed: int, seconds: float, traced: bool,
                 t_start: float):
        self.workload = workload
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.traced = traced
        self.t_start = t_start
        self.spans: List[tuple] = []
        self.data: Dict[str, Any] = {}        # filled by the kind
        self.checks: Dict[str, dict] = {}     # name -> {"value", "limit"}
        self.trace: Optional[dict] = None     # trace_reduce.reduce(...)
        self.peaks: dict = {}
        self.window_open: Optional[float] = None
        self.attempted = 0
        self.failed = 0
        self.trace_dir: Optional[str] = None
        self._trace_state: Optional[str] = None
        self._annotation = None

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append((name, t0, t1))

    def trace_window(self, begin: bool, end: bool):
        """Start the profiler the first time ``begin`` holds and stop it
        the first time ``end`` holds after that; the traced part of the
        window is the host span ``bench:window``. Starting and stopping
        stall the host for up to seconds; ``data["profiler_on"]`` is when
        the first stall began. No-op when untraced."""
        if not self.traced or self._trace_state == "done":
            return
        import jax
        if self._trace_state is None and begin:
            self.data["profiler_on"] = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # keep annotations, not every
            opts.host_tracer_level = 2       # Python call
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench:window")
            self._annotation.__enter__()
            self._trace_state = "on"
        if self._trace_state == "on" and end:
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._trace_state = "done"

    def check(self, name: str, value: float, limit: float):
        """A number compared with its limit; the run is correct only when
        every such number is at or under its limit."""
        self.checks[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.checks.values())

    def seed_rng(self, *stream):
        """numpy Generator for one named stream of this run's seed."""
        import numpy as np
        return np.random.default_rng([self.seed, *[_stream_id(s)
                                                   for s in stream]])

    def jax_seed(self) -> int:
        """A 31-bit key seed drawn from the run's seed (which may exceed
        32 bits)."""
        return int(self.seed_rng("jax").integers(0, 2 ** 31 - 1))


def _stream_id(s) -> int:
    if isinstance(s, int):
        return s
    import zlib
    return zlib.crc32(str(s).encode())


def flat_shapes(tree, prefix: str = "") -> dict:
    """``{"a/b/c": shape}`` for a nested dict of arrays or shape structs."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flat_shapes(v, path))
        else:
            out[path] = tuple(v.shape)
    return out


# ---------------------------------------------------------------------------
# Numbers
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile; None for an empty sample."""
    import numpy as np
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def result_line(run: Run, metrics: Dict[str, dict], device: dict,
                breakdown: Optional[dict] = None,
                notes: Optional[dict] = None) -> str:
    out: Dict[str, Any] = {
        "correct": run.correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    if notes:
        out["notes"] = notes
    out["checks"] = run.checks            # last key: numbers beside limits
    return json.dumps(out)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)
