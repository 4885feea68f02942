"""Share of the time spent inside ticks in which no operation ran on the
device, in % (time waiting for arrivals is not counted)."""
import tracing


def read(run):
    if run.trace is None:
        return None
    inside, spans = tracing.busy_in_spans_ns(run.trace["record"])
    if spans <= 0 or inside <= 0:
        return None
    return 100.0 * (1.0 - inside / spans)
