"""Scheduler wait, 90th percentile: from a request's due time to the start
of the engine step that admits it, over the requests admitted that were
due in the window before the profiler started (starting and stopping it
stalls the host loop for seconds, a wait no untraced run has)."""
from harness import percentile


def read(run):
    tr = run.data.get("tracker")
    if tr is None:
        return None
    on = run.data.get("profiler_on", float("inf"))
    return percentile([r["admit_step"] - r["due"] for r in tr.reqs.values()
                       if r["due"] is not None and r["due"] < on
                       and r["admit_step"] is not None], 90)
