"""Time a request waited in the program's queue before its admission began,
per request admitted in the window, in ms (program counters
``queue_wait_ns`` and ``requests_admitted``)."""
import program_spans


def read(run):
    return program_spans.ms_per(run, "queue_wait_ns", "requests_admitted")
