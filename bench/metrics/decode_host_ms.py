"""Host time of the decode step, in which the device waits on the host:
the mean, over the window's engine steps that admitted nothing (the
program's ``serve.step`` spans with ``admitted`` 0 and slots ``active``),
of each step's time less its ``.wait`` spans. With ``decode_wait_ms`` it
adds up to the mean decode step. In ms. Traced runs only; None where the
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
    host = [s.t1 - s.t0 - w for s, w in steps
            if s.attrs.get("admitted") == 0 and s.attrs.get("active")]
    return sum(host) / len(host) * 1e3 if host else None
