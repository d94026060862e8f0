"""Time to first token, 90th percentile over every request due in the
window: from its scheduled arrival until the host holds its first token.
A request never served by the end of the drain counts the whole wait up
to then (a lower bound), and is also counted as failed."""
from harness import percentile


def read(run):
    tr = run.data.get("tracker")
    if tr is None:
        return None
    end = run.data["t_drained"]
    waits = [(r["times"][0] if r["times"] else end) - r["due"]
             for r in tr.reqs.values() if r["due"] is not None]
    return percentile(waits, 90)
