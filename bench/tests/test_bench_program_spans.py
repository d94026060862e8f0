"""The chat cell's readers of the program's own spans and request stamps,
on the CPU at tiny sizes.

The tiny chat cell runs untraced (a CPU run has no device trace), then
takes a stand-in ``run.trace`` so that the readers of traced runs read.
Each gives a value; the decode step's wait and host time add up to the
mean decode ``serve.step``; with no trace, or against a program that
records no spans or stamps, each gives None (as a traced run of a program
without ``repro.obs`` must), and each reader of spans gives None once the
ring has lost the window's start. The empty-slot share is also counted by
hand on a few made-up steps."""
from __future__ import annotations

import collections
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

pytest.importorskip("jax")

import tiny_catalog  # noqa: E402
from harness import Catalog  # noqa: E402

SEED = 2 ** 31 + 4242
READERS = ("decode_wait_ms", "decode_host_ms", "prefill_wait_ms",
           "sched_wait_p90_s", "empty_slot_wait_share")
SPAN_READERS = ("decode_wait_ms", "decode_host_ms", "prefill_wait_ms",
                "empty_slot_wait_share")
STAND_IN = {"busy_s": 0.5, "window_s": 1.0, "ops": {}, "gaps": {},
            "breakdown": {"device_ops": [], "idle_gaps": []}}


@pytest.fixture(scope="module")
def chat(tmp_path_factory):
    root = tiny_catalog.make(str(tmp_path_factory.mktemp("bench")))
    _, run = tiny_catalog.execute(root, "tiny.chat", SEED)
    assert run.correct, run.checks
    run.trace = dict(STAND_IN)
    cat = Catalog(root)
    return run, {name: cat.reader(name) for name in READERS}


def test_each_reader_gives_a_value(chat):
    run, readers = chat
    got = {name: readers[name](run) for name in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert all(got[name] > 0 for name in READERS
               if name != "empty_slot_wait_share"), got
    assert got["empty_slot_wait_share"] < 100.0
    assert got["sched_wait_p90_s"] < run.seconds


def test_decode_wait_and_host_add_up_to_the_decode_step(chat):
    run, readers = chat
    from repro import obs
    steps = [s for s in obs.spans(run.data["t0"], run.data["t_close"],
                                  name="serve.step")
             if s.attrs["admitted"] == 0 and s.attrs["active"]]
    assert steps
    mean_ms = sum(s.t1 - s.t0 for s in steps) / len(steps) * 1e3
    total = readers["decode_wait_ms"](run) + readers["decode_host_ms"](run)
    assert total == pytest.approx(mean_ms, rel=0.01)
    # the same steps, timed by the harness from outside engine.step()
    assert len(steps) == sum(1 for _, _, firsts, lens in run.data["steps"]
                             if firsts == 0 and lens)


@pytest.mark.parametrize("missing", ["trace", "program"])
def test_readers_give_none_without_a_trace_or_the_program(chat, monkeypatch,
                                                          missing):
    run, readers = chat
    if missing == "trace":
        monkeypatch.setattr(run, "trace", None)
    else:
        monkeypatch.setitem(sys.modules, "repro.obs", None)
        tracker = run.data["tracker"]
        bare = {uid: dict(r, req=None if r["req"] is None else
                          types.SimpleNamespace(uid=r["req"].uid))
                for uid, r in tracker.reqs.items()}
        monkeypatch.setattr(tracker, "reqs", bare)
    assert {name: readers[name](run) for name in READERS} == dict.fromkeys(
        READERS)


def test_span_readers_give_none_once_the_ring_lost_the_window(chat,
                                                              monkeypatch):
    run, readers = chat
    from repro import obs
    assert obs.holds_since(run.data["t0"])
    monkeypatch.setattr(obs, "_lost", obs._lost)          # restored after
    monkeypatch.setattr(obs, "_ring", collections.deque(obs._ring,
                                                        maxlen=len(obs._ring)))
    with obs.span("late"):                # overwrites the oldest span
        pass
    assert obs.holds_since(run.data["t0"]) is (obs._lost <= run.data["t0"])
    monkeypatch.setattr(obs, "_lost", run.data["t0"] + 1e-6)
    assert not obs.holds_since(run.data["t0"])
    assert {name: readers[name](run) for name in SPAN_READERS} \
        == dict.fromkeys(SPAN_READERS)


def test_empty_slot_wait_share_by_hand(chat, monkeypatch):
    """Two slots, a window of 10 s before the profiler starts. Step A (1-2
    s) runs one slot while r1 waits from 1.5 s: 0.5 slot-seconds. Step B
    (2.1-3 s) fills the last slot with r1 at 2.2 s: 0.1 more; r2 arrives
    at 2.5 s with no slot free. Step C (4-5 s) hands r2 a slot a finished
    request left, at 4.1 s: 0.1 more. A step after the profiler started
    (10.4-11 s) and the gap between steps count nothing: 0.7 of 20
    slot-seconds is 3.5%."""
    _, readers = chat
    from repro import obs
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=obs.RING))
    monkeypatch.setattr(obs, "_lost", float("-inf"))
    for t0, t1, active in [(1.0, 2.0, 1), (2.1, 3.0, 2), (4.0, 5.0, 2),
                           (10.4, 11.0, 0)]:
        obs.record("serve.step", t0, t1, active=active)
    reqs = {uid: dict(req=types.SimpleNamespace(t_submit=a, t_admit=b))
            for uid, (a, b) in enumerate([(1.5, 2.2), (2.5, 4.1),
                                          (10.5, None)])}
    run = types.SimpleNamespace(
        trace=dict(STAND_IN), cell={"slots": 2},
        data={"t0": 0.0, "profiler_on": 10.0, "t_close": 12.0,
              "tracker": types.SimpleNamespace(reqs=reqs)})
    assert readers["empty_slot_wait_share"](run) == pytest.approx(3.5)
