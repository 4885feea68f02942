"""Host time of the program's admission groups (lease wave, prefill,
suffix KV into the pool: span ``admit``) per request admitted in the
window, in ms."""
import program_spans


def read(run):
    return program_spans.ms_per(run, "span_admit_ns", "requests_admitted")
