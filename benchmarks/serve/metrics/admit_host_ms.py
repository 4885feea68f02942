"""Admission time in which the host was not waiting on the prefill (span
``admit`` less span ``prefill_wait``) per request admitted in the window,
in ms."""
import program_spans


def read(run):
    return program_spans.ms_per(run, "span_admit_ns", "requests_admitted",
                                less="span_prefill_wait_ns")
