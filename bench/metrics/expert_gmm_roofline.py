"""Grouped expert products' share of their roofline, in percent: the least
time of every grouped-matmul call in the traced window, forward or
backward (``gmm`` or ``tgmm``, remat's extra calls counted as calls), over
the device time of those calls. A call does 2 x P x d x f operations for
P held (token, expert) pairs (``flops_moe.expert_gmm_least_s``). P is the
program's own count: each traced step files the pairs its layers held
(``moe_held_pairs``) on an ``obs`` span ``train.step.dispatch``, and every
layer of a step makes the same number of calls, so a call's mean P is
the traced steps' pairs over their layers. Routing decides P: in the
Mellum2 cell it swings from under half to nearly twice its balanced
value (T x top_k x held / router width) between steps, so a count from
shapes alone could read over 100%. The calls are found by the
regular expressions the cell lists under ``expert_kernels``. Nothing when
they match none, or where the program filed no counts for the traced
steps."""
import sys

import flops_moe
import trace_reduce


def read(run):
    t, names = run.trace, run.cell.get("expert_kernels")
    obs = sys.modules.get("repro.obs")
    if t is None or not names or obs is None \
            or "profiler_on" not in run.data:
        return None
    spent, calls = 0.0, 0
    for pattern in names.values():
        sec, n = trace_reduce.kernel_seconds(t, pattern)
        spent, calls = spent + sec, calls + n
    # the traced steps: dispatched from the profiler's start until the
    # window loop stops it (harness: kinds/train.py ``window``)
    t_on, cell = run.data["profiler_on"], run.cell
    t_off = run.window_open + cell.get("trace_from", 0.4) * run.seconds \
        + cell.get("trace_seconds", 3.0)
    steps = [s for s in obs.spans(t_on, name="train.step.dispatch")
             if s.t0 < t_off and "moe_held_pairs" in s.attrs]
    if not calls or not spent or not steps or not obs.holds_since(t_on):
        return None
    arch = run.config["arch"]
    pairs = sum(float(s.attrs["moe_held_pairs"]) for s in steps) \
        / (len(steps) * arch["n_layers"])
    least = calls * flops_moe.expert_gmm_least_s(arch, pairs, run.peaks)
    return 100.0 * least / spent
