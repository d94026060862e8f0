"""Mean host-clock time of the window's engine steps that admitted
nothing (one decode of every active slot, host bookkeeping included)."""


def read(run):
    steps = [te - ts for ts, te, firsts, lens in run.data.get("steps", [])
             if firsts == 0 and lens]
    return sum(steps) / len(steps) * 1e3 if steps else None
