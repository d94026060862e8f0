"""Wait in the program's queue, 90th percentile: from the request's
``t_submit`` (accepted by the scheduler) to its ``t_admit`` (popped into a
slot), the program's own host-clock stamps, over the requests admitted
that were due in the window before the profiler started (as
``queue_wait_p90_s``). Traced runs only; None where requests carry no
stamps."""
from harness import percentile


def read(run):
    tr = run.data.get("tracker")
    if run.trace is None or tr is None:
        return None
    on = run.data.get("profiler_on", float("inf"))
    waits = []
    for r in tr.reqs.values():
        req = r["req"]
        if r["due"] is None or r["due"] >= on or req is None:
            continue
        t_submit = getattr(req, "t_submit", None)
        t_admit = getattr(req, "t_admit", None)
        if t_submit is not None and t_admit is not None:
            waits.append(t_admit - t_submit)
    return percentile(waits, 90)
