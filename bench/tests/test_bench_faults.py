"""A run of each tiny cell on the CPU with its timed path broken
underneath, once per fault the cell can have, comes out not correct:
a decoded token altered where it is produced (serving), a step that
returns its state unchanged, and half of the batch left out with the
mean over the rest (training). So does a run in which the control, the
reference one precision step down, takes the program's place."""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

pytest.importorskip("jax")

import tiny_catalog  # noqa: E402

SEED = 2 ** 31 + 4099


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_catalog.make(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("name,fault,check", [
    ("tiny.chat", "token", "max_logit_gap"),
    ("tiny.code", "token", "max_logit_gap"),
    ("tiny.train", "frozen", "update_norm_gap"),
    ("tiny.train", "half_batch", "loss_gap"),
])
def test_broken_timed_path_is_not_correct(tiny_root, name, fault, check):
    _, run = tiny_catalog.execute(tiny_root, name, SEED, fault=fault)
    assert not run.correct
    c = run.checks[check]
    assert c["value"] > c["limit"], run.checks


@pytest.mark.parametrize("name", ["tiny.chat", "tiny.code", "tiny.train"])
def test_control_reads_above_the_program(tiny_root, name):
    """The control goes through the run's own checks and fails one."""
    _, sound = tiny_catalog.execute(tiny_root, name, SEED)
    _, ctl = tiny_catalog.execute(tiny_root, name, SEED, fault="control")
    assert sound.correct and not ctl.correct
    assert any(c["value"] > c["limit"] for c in ctl.checks.values())
    assert all(ctl.checks[k]["value"] > sound.checks[k]["value"]
               for k in ctl.checks if k != "invariant_events")


def test_unknown_fault_is_an_error(tiny_root):
    from harness import BenchError
    with pytest.raises(BenchError, match="not one of"):
        tiny_catalog.execute(tiny_root, "tiny.chat", SEED, fault="frozen")
