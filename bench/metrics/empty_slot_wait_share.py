"""Slot time the scheduler left empty while a request waited: over the
program's ``serve.step`` spans in the window before the profiler started
(starting it stalls the host for seconds), the time in which a slot
stood empty while a request stood in the queue (from its ``t_submit``,
accepted, to its ``t_admit``, popped into a slot), counted once for each
empty slot that a waiting request could have taken, over slots times
that stretch. In percent; 0 for a scheduler that fills a slot the moment
a request arrives. A slot that a step fills counts as empty until the
request's ``t_admit``; one freed inside a step counts from the next.
Traced runs only; None where the program records no spans or stamps
(``repro.obs``), or its ring no longer reaches back to the window's
start."""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    tr = run.data.get("tracker")
    if run.trace is None or obs is None or tr is None \
            or "t_close" not in run.data \
            or not obs.holds_since(run.data["t0"]):
        return None
    t0 = run.data["t0"]
    t1 = min(run.data.get("profiler_on", run.data["t_close"]),
             run.data["t_close"])
    waits = []
    for r in tr.reqs.values():
        t_submit = getattr(r["req"], "t_submit", None)
        if t_submit is not None:
            t_admit = getattr(r["req"], "t_admit", None)
            waits.append((t_submit, float("inf") if t_admit is None
                          else t_admit))
    steps = obs.spans(t0, t1, name="serve.step")
    if not waits or not steps:
        return None
    slots = run.cell["slots"]
    empty = 0.0
    for s in steps:
        here = [(a, b) for a, b in waits if a < s.t1 and b > s.t0]
        if not here:
            continue
        free = slots - s.attrs.get("active", slots)
        filled = [b for _, b in here if b <= s.t1]    # admitted in this step
        cuts = sorted({s.t0, s.t1, *(x for w in here for x in w
                                     if s.t0 < x < s.t1)})
        for a, b in zip(cuts, cuts[1:]):
            m = (a + b) / 2
            waiting = sum(1 for x, y in here if x <= m < y)
            open_ = free + sum(1 for y in filled if y > m)
            empty += min(waiting, open_) * (b - a)
    return 100.0 * empty / (slots * (t1 - t0))
