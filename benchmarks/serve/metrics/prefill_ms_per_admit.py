"""Prefill time per admitted request: the wall time of ticks that admitted,
less one median decode step for those that also decoded, over the
admissions (host clock)."""
from metrics import decode_tick_ms


def read(run):
    step = decode_tick_ms.read(run)
    n, wall = 0, 0.0
    for t in run.ticks:
        if t["admitted"]:
            n += len(t["admitted"])
            wall += 1e3 * (t["end"] - t["start"])
            if t["decoded"]:
                if step is None:
                    return None
                wall -= step
    if not n:
        return None
    return wall / n
