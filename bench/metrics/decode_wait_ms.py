"""Device time of the decode step as the host sees it: the mean, over the
window's engine steps that admitted nothing (the program's ``serve.step``
spans with ``admitted`` 0 and slots ``active``), of the time in the
``.wait`` spans under each step (the audit's slot-length sync and the
decode step's token sync). In ms. Traced runs only; None where the
program records no spans (``repro.obs``) or its ring no longer reaches
back to the window's start."""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    if run.trace is None or obs is None or "t_close" not in run.data \
            or not obs.holds_since(run.data["t0"]):
        return None
    steps = obs.device_waits(obs.spans(run.data["t0"], run.data["t_close"]),
                             "serve.step")
    waits = [w for s, w in steps
             if s.attrs.get("admitted") == 0 and s.attrs.get("active")]
    return sum(waits) / len(waits) * 1e3 if waits else None
