"""Operation and byte counts behind ``decode_mfu``, ``train_mfu`` and
``flash_attn_roofline`` against hand counts, and the trace reduction on
hand-made events and on a trace recorded on a TPU v5e."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import trace_reduce  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _arch(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["arch"]


def test_matmul_params_by_hand():
    # stablelm-1.6b: q,k,v,o 4 x 2048 x 2048; gated MLP 3 x 2048 x 5632;
    # untied head 2048 x 100352
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert flops.matmul_params(_arch("stablelm-1.6b")) == \
        24 * per_layer + 2048 * 100352
    # starcoder2-3b, 4 layers: live q,o 2 x 3072 x 24 x 128; k,v with 2
    # kv heads; GELU MLP 2 x 3072 x 12288; tied head 3072 x 49152
    per_layer = 2 * 3072 * 3072 + 2 * 3072 * 256 + 2 * 3072 * 12288
    assert flops.matmul_params(_arch("starcoder2-3b-l4")) == \
        4 * per_layer + 3072 * 49152


def test_decode_least_time_by_hand():
    arch = _arch("stablelm-1.6b")
    lengths = [300, 500, 700, 900]
    weights = 24 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 2048 * 100352
    norms = (2 * 24 + 1) * 2048
    gathered = 4 * 2048                     # 4 embedding rows, untied
    kv = 24 * 2 * 32 * 64 * 2 * sum(lengths)
    bytes_ = 2 * (weights + norms + gathered) + kv
    ops = 2 * weights * 4 + 24 * 4 * 32 * 64 * sum(lengths)
    want = max(bytes_ / 819e9, ops / 197e12)
    assert flops.decode_step_least_s(arch, lengths, PEAKS) == \
        pytest.approx(want, rel=1e-12)
    assert bytes_ / 819e9 > ops / 197e12          # decode is bandwidth-bound
    assert flops.decode_step_least_s(arch, [], PEAKS) == 0.0


def test_train_flops_per_token_by_hand():
    arch = _arch("starcoder2-3b-l4")
    weights = 4 * (2 * 3072 * 3072 + 2 * 3072 * 256 + 2 * 3072 * 12288) \
        + 3072 * 49152
    attn_fwd = 4 * 4 * 24 * 128 * 4097 / 2     # layers x QK,PV x heads x d
    assert flops.train_flops_per_token(arch, 4096) == \
        pytest.approx(6 * weights + 3 * attn_fwd, rel=1e-12)


def test_flash_least_time_by_hand():
    g, s, d = 2 * 24, 4096, 128                 # batch x live heads
    pairs = s * (s + 1) / 2
    fwd = max(2 * 2 * d * pairs * g / 197e12,
              (4 * g * s * d * 2 + g * s * 4) / 819e9)
    bwd = max(5 * 2 * d * pairs * g / 197e12,
              (8 * g * s * d * 2 + 2 * g * s * 4) / 819e9)
    assert flops.flash_least_s("fwd", g, s, d, PEAKS) == pytest.approx(fwd)
    assert flops.flash_least_s("bwd", g, s, d, PEAKS) == pytest.approx(bwd)
    assert bwd == pytest.approx(2.5 * fwd)      # both compute-bound here
    with pytest.raises(ValueError):
        flops.flash_least_s("dq", g, s, d, PEAKS)


def _flash_reader():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "flash_attn_roofline",
        os.path.join(BENCH, "metrics", "flash_attn_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_flash_roofline_counts_live_heads_by_hand():
    """Batch 2 x 4 live heads of 8 stored (4 dead, padded in): the least
    time counts the 8 live rows of 32 x 16, not the 16 the kernel gets."""
    ms = 1_000_000
    cell = {"batch": 2, "seq": 32, "flash_kernels": {
        "fwd": "^fwd", "dq": "^dq", "dkv": "^dkv"}}
    devices = {"/device:TPU:0": [("fwd.1", 0, 2 * ms), ("fwd.2", 2 * ms,
                                                        3 * ms),
                                 ("dq.1", 3 * ms, 5 * ms),
                                 ("dkv.1", 5 * ms, 8 * ms)]}

    class Run:
        trace = trace_reduce.reduce_events([], devices)
        peaks = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e9}
        config = {"arch": {"n_heads": 4, "pad_heads_to": 8, "head_dim": 16}}
    Run.cell = cell
    g, s, d = 2 * 4, 32, 16
    pairs = s * (s + 1) / 2
    fwd = max(4 * d * pairs * g / 1e9, (4 * g * s * d * 2 + g * s * 4) / 1e9)
    bwd = max(10 * d * pairs * g / 1e9,
              (8 * g * s * d * 2 + 2 * g * s * 4) / 1e9)
    want = 100 * (2 * fwd + bwd) / 8e-3
    assert _flash_reader()(Run) == pytest.approx(want, rel=1e-12)
    Run.cell = dict(cell, flash_kernels={"fwd": "^none", "dq": "^none",
                                         "dkv": "^none"})
    assert _flash_reader()(Run) is None          # nothing to read


def test_reduce_events_by_hand():
    ms = 1_000_000
    spans = [("bench:window", 0, 100 * ms), ("bench:step", 0, 60 * ms),
             ("bench:admit", 5 * ms, 25 * ms), ("bench:step", 60 * ms,
                                                95 * ms)]
    devices = {"/device:TPU:0": [
        ("fusion.1", -10 * ms, 10 * ms),      # clipped to the window
        ("fusion.1", 30 * ms, 50 * ms),
        ("_fwd_kernel", 40 * ms, 50 * ms),    # nested: counted once
        ("while", 65 * ms, 85 * ms),          # holds the copy: self 10 ms
        ("copy", 70 * ms, 80 * ms),
        ("copy", 120 * ms, 130 * ms)]}        # outside the window
    t = trace_reduce.reduce_events(spans, devices)
    assert t["window_s"] == pytest.approx(0.1)
    assert t["busy_s"] == pytest.approx(0.05)       # 10 + 20 + 20 ms
    assert t["ops"]["fusion.1"] == {"seconds": pytest.approx(0.02),
                                    "count": 2}      # self time
    assert t["ops"]["copy"]["count"] == 1
    assert t["ops"]["while"]["seconds"] == pytest.approx(0.01)
    # gaps: 10-30 ms in admit (middle 20 ms), 50-65 in the first step,
    # 85-100 in the second step (its middle, 92.5 ms, is inside it)
    assert t["gaps"]["admit"] == pytest.approx(0.02)
    assert t["gaps"]["step"] == pytest.approx(0.03)
    assert trace_reduce.kernel_seconds(t, "_fwd") == (pytest.approx(0.01), 1)
    assert t["breakdown"]["device_ops"][0][0] == "fusion.1"


def test_reduce_events_needs_a_device():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events([("bench:window", 0, 1)], {})


def test_short_op_names():
    full = ("%fusion.187 = bf16[4,2048]{1,0:T(4,128)(2,1)S(1)} fusion("
            "bf16[24,5632,2048]{2,1,0} %get-tuple-element.776), kind=kOutput")
    assert trace_reduce.short_name(full) == "fusion.187 bf16[4,2048] fusion"
    tup = ("%copy-start.3 = (s32[4]{0:T(128)S(1)}, u32[]{:S(2)}) "
           "copy-start(s32[4]{0:T(128)} %x)")
    assert trace_reduce.short_name(tup) == \
        "copy-start.3 (s32[4], u32[]) copy-start"
    assert trace_reduce.short_name("jit_impl(123)") == "jit_impl(123)"


def test_reduce_recorded_train_trace():
    """A 1.4 s slice of a ``starcoder2-3b.train-4k`` traced run on a TPU
    v5e (two train steps): the four flash-attention calls per layer and
    step are found by the cell's patterns, and the reduction's numbers are
    those this file gave when it was recorded."""
    t = trace_reduce.reduce(os.path.join(HERE, "data", "train_v5e.xplane.pb"))
    assert t["window_s"] == pytest.approx(1.416714959)
    assert t["busy_s"] == pytest.approx(1.408056515)
    assert sum(v["seconds"] for v in t["ops"].values()) == \
        pytest.approx(t["busy_s"])           # self times tile the busy time
    with open(os.path.join(BENCH, "workloads",
                           "starcoder2-3b.train-4k.json")) as f:
        cell = json.load(f)
    calls = {k: trace_reduce.kernel_seconds(t, pat)[1]
             for k, pat in cell["flash_kernels"].items()}
    # 4 layers x 2 steps; the forward runs twice (once more under remat)
    assert calls == {"fwd": 16, "dq": 8, "dkv": 8}

    class Run:
        trace, peaks = t, PEAKS
        config = {"arch": _arch("starcoder2-3b-l4")}
    Run.cell = cell
    # 24 live heads of the 32 the kernel runs
    assert _flash_reader()(Run) == pytest.approx(4.347918291, rel=1e-6)
    assert t["breakdown"]["device_ops"][0][0].startswith("_flash_padded")
