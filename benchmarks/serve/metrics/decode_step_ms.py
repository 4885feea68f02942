"""Mean host time of one decode step as the program times it (span
``decode``), over every decode step of the window, in ms."""
import program_spans


def read(run):
    return program_spans.ms_per(run, "span_decode_ns", "span_decode_n")
