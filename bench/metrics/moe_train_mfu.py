"""Model FLOP/s utilization of a mixture-of-experts training cell:
operations per trained token (``flops_moe.train_flops_per_token``: 6 per
weight a token meets in a product, with the held experts' expected pairs,
plus the band or causal attention forward and backward; recomputation not
counted) times the window's tokens per second, over the bf16 peak. In
percent; nothing for a model without experts."""
import flops_moe


def read(run):
    t, arch = run.data.get("train"), run.config["arch"]
    if t is None or "moe" not in arch:
        return None
    rate = t["tokens"] / (t["t1"] - t["t0"])
    per_token = flops_moe.train_flops_per_token(arch, run.cell["seq"])
    return 100.0 * per_token * rate / run.peaks["bf16_flops"]
