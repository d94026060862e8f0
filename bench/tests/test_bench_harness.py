"""The harness on the CPU at tiny sizes: a cell, a mix, a configuration
and a metric are added as new files and new ``BENCHMARK.json`` entries
only; the command refuses to run without a chip or without the program;
and ``BENCHMARK.json`` keeps to the benchmark's contract."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

pytest.importorskip("jax")

import tiny_catalog  # noqa: E402
from harness import BenchError, Catalog  # noqa: E402

SEED = 2 ** 31 + 977          # seeds beyond 32 signed bits are valid


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_catalog.make_with_new_metric(
        str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("name,traced,expect", [
    ("tiny.chat", False, {"ttft_p90_s", "tbt_p95_ms", "setup_s"}),
    ("tiny.chat", True, {"queue_wait_p90_s", "prefill_step_ms",
                         "decode_step_ms", "decode_mfu",
                         "tiny_tokens_served"}),
    ("tiny.code", False, {"ttft_p90_s", "tbt_p95_ms", "setup_s"}),
    ("tiny.train", False, {"train_tokens_per_s", "setup_s"}),
    ("tiny.train", True, {"train_mfu"}),
])
def test_new_cells_and_metrics_from_files_alone(tiny_root, name, traced,
                                                expect):
    res, run = tiny_catalog.execute(tiny_root, name, SEED, traced=traced)
    assert run.correct, run.checks
    assert set(res["metrics"]) == expect
    assert res["notes"]["compiles_in_window"] == 0
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_unknown_device_kind_is_an_error(tiny_root):
    with pytest.raises(BenchError, match="no peaks"):
        Catalog(tiny_root).peaks("TPU v99")


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_command_refuses_without_a_chip():
    p = _cli(ROOT, "--workload", "stablelm-1.6b.chat", "--seed", str(SEED),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _cli(str(tmp_path), "--workload", "starcoder2-3b.train-4k", "--seed",
             "5", "--seconds", "1", "--trace", "1")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    layer = {m["name"]: m for m in spec["per_layer"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for group in (spec["configs"], spec["workloads"], spec["end_to_end"],
                  spec["per_layer"]):
        for entry in group:
            assert NAME.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                assert len(entry.get(key, "x")) <= 200
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(BENCH, "workloads",
                                           w["name"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in list(e2e.values()) + list(layer.values()):
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in layer.values():
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        mine = [m for m in e2e.values() if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in layer.values())
