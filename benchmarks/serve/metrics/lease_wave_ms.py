"""Host time of the prefix lease waves (span ``lease_wave``: classify,
renew, write, evict) per request admitted in the window, in ms."""
import program_spans


def read(run):
    return program_spans.ms_per(run, "span_lease_wave_ns",
                                "requests_admitted")
