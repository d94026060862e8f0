"""Flash-attention kernels' share of their roofline in a cell whose layers
repeat a period of sliding-window and full kinds, in percent: the least
time of every forward and backward call in the traced window over the
device time of those calls. A call's least time averages the period's
kinds (``flops_moe.flash_least_s``: the band's pairs on a sliding layer,
the causal prefix on a full one), since every step calls each layer's
kernels equally often and the trace does not tell them apart. The calls
are found by the cell's ``flash_kernels`` regular expressions; a backward
call is one dQ and one dK/dV kernel. Nothing for a model without kinds."""
import flops_moe
import trace_reduce


def read(run):
    t, names = run.trace, run.cell.get("flash_kernels")
    arch, cell = run.config["arch"], run.cell
    if t is None or not names or not arch.get("layer_kinds"):
        return None
    shape = (cell["batch"] * arch["n_heads"], cell["seq"], arch["head_dim"],
             arch["sliding_window"], arch["layer_kinds"], run.peaks)
    fwd_s, n_fwd = trace_reduce.kernel_seconds(t, names["fwd"])
    dq_s, n_dq = trace_reduce.kernel_seconds(t, names["dq"])
    dkv_s, n_dkv = trace_reduce.kernel_seconds(t, names["dkv"])
    spent = fwd_s + dq_s + dkv_s
    if not spent or n_dq != n_dkv:
        return None
    least = n_fwd * flops_moe.flash_least_s("fwd", *shape) \
        + n_dq * flops_moe.flash_least_s("bwd", *shape)
    return 100.0 * least / spent
