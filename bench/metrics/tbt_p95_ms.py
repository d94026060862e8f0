"""Time between tokens, 95th percentile over every gap between two
consecutive output tokens of one request whose later token the host held
inside the window."""
from harness import percentile


def read(run):
    tr = run.data.get("tracker")
    if tr is None:
        return None
    t0, t1 = run.data["t0"], run.data["t_close"]
    gaps = [b - a for r in tr.reqs.values()
            for a, b in zip(r["times"], r["times"][1:]) if t0 <= b <= t1]
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3
