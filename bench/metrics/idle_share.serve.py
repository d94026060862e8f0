"""Share of the traced window in which no operation ran on the device, in
percent (serving cells)."""


def read(run):
    t = run.trace
    if t is None or "tracker" not in run.data:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
