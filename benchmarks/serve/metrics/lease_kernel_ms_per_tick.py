"""Device time of the lease-table kernels (batched lease check, rowmax,
write advance) per tick in the trace, in ms."""
import tracing

PATTERN = r"^%(masked_lease_check|lease_check|write_advance)"


def read(run):
    if run.trace is None or not run.trace["ticks"]:
        return None
    ns, n = tracing.kernel_ns(run.trace["record"], PATTERN)
    if not n:
        return None
    return ns * 1e-6 / len(run.trace["ticks"])
