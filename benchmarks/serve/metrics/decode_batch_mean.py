"""Streams per decode step: tokens emitted by decode ticks over decode
ticks, in the window (harness counts of the program's live streams)."""


def read(run):
    sizes = [len(t["decoded"]) for t in run.ticks if t["decoded"]]
    if not sizes:
        return None
    return sum(sizes) / len(sizes)
