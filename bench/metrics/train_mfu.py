"""Model FLOP/s utilization of training: operations per trained token
(6 per matrix-product weight plus the causal attention forward and
backward; recomputation not counted; ``flops.train_flops_per_token``)
times the window's tokens per second, over the bf16 peak. In percent."""
import flops


def read(run):
    t = run.data.get("train")
    if t is None:
        return None
    rate = t["tokens"] / (t["t1"] - t["t0"])
    per_token = flops.train_flops_per_token(run.config["arch"],
                                            run.cell["seq"])
    return 100.0 * per_token * rate / run.peaks["bf16_flops"]
