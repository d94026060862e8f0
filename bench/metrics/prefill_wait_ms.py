"""Device time of a batch-1 prefill as the host sees it: the mean of the
program's ``serve.prefill.wait`` spans (one per admitted request: the sync
on its first token, after the prefill and the slot scatter are
dispatched) in the window. In ms. Traced runs only; None where the
program records no spans (``repro.obs``) or its ring no longer reaches
back to the window's start."""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    if run.trace is None or obs is None or "t_close" not in run.data \
            or not obs.holds_since(run.data["t0"]):
        return None
    waits = [s.t1 - s.t0 for s in obs.spans(
        run.data["t0"], run.data["t_close"], name="serve.prefill.wait")]
    return sum(waits) / len(waits) * 1e3 if waits else None
