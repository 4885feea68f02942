"""Median host wall time of the window's ticks that admitted nothing and
decoded: one decode step, ending in the tick's own read of its argmax."""
import statistics


def read(run):
    walls = [t["end"] - t["start"] for t in run.ticks
             if t["decoded"] and not t["admitted"]]
    if not walls:
        return None
    return 1e3 * statistics.median(walls)
