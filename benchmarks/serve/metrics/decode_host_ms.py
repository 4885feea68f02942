"""Mean time per decode step in which the host was not waiting on the
step's result (span ``decode`` less span ``decode_wait``), over every
decode step of the window, in ms."""
import program_spans


def read(run):
    return program_spans.ms_per(run, "span_decode_ns", "span_decode_n",
                                less="span_decode_wait_ns")
