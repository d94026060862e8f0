"""A benchmark catalog at tiny sizes, for running the harness on the CPU.

``make(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` to ``tmp`` and adds
tiny configurations, cells and mixes as new files and new entries only,
exactly as a later PR adds a cell, plus a ``cpu`` row in the peaks table.
"""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY_ARCH = {
    "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 128, "vocab_size": 256, "head_dim": 16,
    "rope_theta": 10000.0, "norm_eps": 1e-5, "param_dtype": "float32",
    "compute_dtype": "float32"}

CONFIGS = {
    "tiny-parallel": dict(TINY_ARCH, name="tiny-parallel", activation="silu",
                          parallel_block=True),
    "tiny-padded": dict(TINY_ARCH, name="tiny-padded", activation="gelu",
                        tie_embeddings=True, pad_heads_to=8),
}

# The serving cells check every finished request (about 250 tokens): on a
# few dozen tokens the int8 control's first choice can match the
# reference's everywhere at these widths, and the control reads 0.
CELLS = {
    "tiny.chat": ("tiny-parallel", "tiny-chat", {
        "kind": "serve", "slots": 2, "max_seq": 64,
        "prompt_grid": [8, 16, 24], "check_tokens": 1000, "check_max_out": 24,
        "limits": {"max_logit_gap": 1e-3}, "why": "tiny"}),
    "tiny.code": ("tiny-padded", "tiny-code", {
        "kind": "serve", "slots": 2, "max_seq": 64,
        "prompt_grid": [16, 32], "check_tokens": 1000, "check_max_out": 24,
        "limits": {"max_logit_gap": 1e-3}, "why": "tiny"}),
    "tiny.train": ("tiny-padded", "uniform-4k", {
        "kind": "train", "batch": 2, "seq": 32,
        "opt": {"peak_lr": 3e-4, "warmup_steps": 100, "decay_steps": 10000,
                "min_lr_ratio": 0.1, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                "weight_decay": 0.1, "clip_norm": 1.0,
                "moment_dtype": "float32"},
        "limits": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                   "update_norm_gap": 0.5},
        "why": "tiny"}),
}

MIXES = {
    "tiny-chat": {"rate_per_s": 20.0,
                  "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
                  "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
                  "fill_slots": False},
    "tiny-code": {"rate_per_s": 10.0,
                  "prompt": {"median": 20, "sigma": 0.3, "min": 16, "max": 32},
                  "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
                  "fill_slots": True},
}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(tmp: str) -> str:
    """Returns the root of a catalog holding the real cells and the tiny
    ones."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for name, arch in CONFIGS.items():
        _write(os.path.join(root, "bench", "configs", name + ".json"),
               {"name": name, "source": "test", "reference": "dense",
                "arch": arch})
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "tiny"})
    for name, mix in MIXES.items():
        _write(os.path.join(root, "bench", "traffic", name + ".json"), mix)
    for name, (config, mix, cell) in CELLS.items():
        _write(os.path.join(root, "bench", "workloads", name + ".json"), cell)
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": mix, "chips": 1, "why": "tiny"})
        kind = cell["kind"]
        for group in ("end_to_end", "per_layer"):
            for m in spec[group]:
                cells = m.get("workloads")
                if cells and any(
                        json.load(open(os.path.join(
                            root, "bench", "workloads", c + ".json")))["kind"]
                        == kind for c in cells if not c.startswith("tiny.")):
                    cells.append(name)
    peaks_path = os.path.join(root, "bench", "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = {"bf16_flops": 1e12, "int8_ops": 2e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    _write(peaks_path, peaks)
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    return root


DUMMY_METRIC = """def read(run):
    tr = run.data.get("tracker")
    return None if tr is None else float(sum(
        len(r["times"]) for r in tr.reqs.values()))
"""


def make_with_new_metric(tmp: str) -> str:
    """``make``, plus a later PR's new per-layer metric: one reader file
    and one ``BENCHMARK.json`` entry, no edit of a file already there."""
    root = make(tmp)
    with open(os.path.join(root, "bench", "metrics",
                           "tiny_tokens_served.py"), "w") as f:
        f.write(DUMMY_METRIC)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append({
        "name": "tiny_tokens_served", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "scheduler", "moves": "ttft_p90_s",
        "workloads": ["tiny.chat"]})
    _write(path, spec)
    return root


def execute(root, name, seed, *, traced=False, fault=None):
    """One run of a cell of the catalog at ``root`` on the CPU."""
    import run as bench_run
    from harness import Catalog
    return bench_run.execute(Catalog(root), name, seed, 1.5, traced,
                             require_tpu=False, fault=fault)
