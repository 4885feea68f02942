"""Paged flash-decode attention: the least time its needed work takes on
the chip (the model's ``paged_attn_work``: valid tokens' K/V of each layer
that runs the kernel, query and output; useful FLOPs only), over the
kernel's device time in the trace, in %."""
import flops
import tracing

PATTERN = r"paged_decode_attention.* custom-call"


def read(run):
    if run.trace is None:
        return None
    ns, n = tracing.kernel_ns(run.trace["record"], PATTERN)
    if not n or ns <= 0:
        return None
    least = 0.0
    for t in run.trace["ticks"]:
        if not t["decoded"]:
            continue
        work = [run.model.paged_attn_work(run.sizes, p)
                for p in t["decoded"]]
        least += flops.roofline_seconds(
            sum(f for f, _ in work), sum(b for _, b in work), run.peaks)
    return 100.0 * least / (ns * 1e-9)
