"""KV append (the pool scatter kernel, decode and prefill alike): each
appended token row read once and written once, over the kernel's device
time in the trace, as a share of the chip's HBM bandwidth, in %."""
import flops
import tracing

# the pool scatter is the one Pallas kernel whose output aliases an
# operand; inside the decode step it carries the step's name, not its own
PATTERN = r"custom-call aliased"


def read(run):
    if run.trace is None:
        return None
    ns, n = tracing.kernel_ns(run.trace["record"], PATTERN)
    if not n or ns <= 0:
        return None
    rows = sum(len(t["decoded"]) + sum(p - s for p, s in t["admitted"])
               for t in run.trace["ticks"])
    least = flops.append_bytes(run.model.kv_row_bytes(run.sizes), rows) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ns * 1e-9)
