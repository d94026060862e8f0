"""Mean host-clock time of the window's engine steps that admitted at
least one request (batch-1 prefill, pool scatter, then the decode)."""


def read(run):
    steps = [te - ts for ts, te, firsts, _ in run.data.get("steps", [])
             if firsts > 0]
    return sum(steps) / len(steps) * 1e3 if steps else None
