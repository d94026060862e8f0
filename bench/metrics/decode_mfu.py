"""Whole decode step's share of the chip's peak: the least time of each
decode-only step of the window (the larger of its operations over peak
FLOP/s and its bytes over HBM bandwidth, with the weights and each active
slot's K/V rows at bfloat16; ``flops.decode_step_least_s``) summed, over
the same steps' host-clock time. In percent."""
import flops


def read(run):
    least = spent = 0.0
    for ts, te, firsts, lens in run.data.get("steps", []):
        if firsts == 0 and lens:
            least += flops.decode_step_least_s(run.config["arch"], lens,
                                               run.peaks)
            spent += te - ts
    return 100.0 * least / spent if spent else None
