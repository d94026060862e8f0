"""Tokens trained per second: every token of every step completed in the
window over the window's time (each step synced)."""


def read(run):
    t = run.data.get("train")
    if t is None:
        return None
    return t["tokens"] / (t["t1"] - t["t0"])
