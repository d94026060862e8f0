"""Flash-attention kernels' share of their roofline, in percent: the
least time of every forward and backward call in the traced window
(``flops.flash_least_s``, from the cell's shapes: batch x live heads,
sequence, head size) over the device time of those calls. Heads the
program pads in are no work the model needs, so they are not counted.
The kernels are found in the trace by the regular expressions the cell
lists under ``flash_kernels``: each is a ``tpu_custom_call`` named after
its jitted wrapper, told apart by its outputs. A backward call is one dQ
and one dK/dV kernel."""
import flops
import trace_reduce


def read(run):
    t, names = run.trace, run.cell.get("flash_kernels")
    if t is None or not names:
        return None
    arch, cell = run.config["arch"], run.cell
    groups = cell["batch"] * arch["n_heads"]
    shape = (groups, cell["seq"], arch["head_dim"], run.peaks)
    fwd_s, n_fwd = trace_reduce.kernel_seconds(t, names["fwd"])
    dq_s, n_dq = trace_reduce.kernel_seconds(t, names["dq"])
    dkv_s, n_dkv = trace_reduce.kernel_seconds(t, names["dkv"])
    spent = fwd_s + dq_s + dkv_s
    if not spent or n_dq != n_dkv:
        return None
    least = n_fwd * flops.flash_least_s("fwd", *shape) \
        + n_dq * flops.flash_least_s("bwd", *shape)
    return 100.0 * least / spent
