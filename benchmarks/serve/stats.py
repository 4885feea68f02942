"""The end-to-end metrics' arithmetic, kept apart from the clock.

- A percentile is numpy's linear interpolation between order statistics.
- Time to first token counts from the request's due time to the end of the
  tick that produced its first token.  A request due in the window that has
  no first token when the window closes counts with the time it has waited
  (censored at the close), so a stalled server cannot drop its slowest
  requests from the tail.
- A gap between tokens is the time between two consecutive tokens of one
  request, both delivered inside the window (two tokens from one tick give
  a gap of 0: the client gets them together).
- Tokens per second are the tokens delivered in the window over its length.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_samples(due: Sequence[float], first: Sequence[Optional[float]],
                 close: float) -> List[float]:
    """Seconds to first token of each request due before ``close``."""
    out = []
    for d, f in zip(due, first):
        if d >= close:
            continue
        out.append((f if f is not None and f <= close else close) - d)
    return out


def token_gaps(times: Dict[int, List[float]], close: float) -> List[float]:
    """Gaps between consecutive tokens of each request, in the window."""
    gaps = []
    for ts in times.values():
        ts = [t for t in ts if t <= close]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return gaps


def tokens_per_s(times: Dict[int, List[float]], open_: float,
                 close: float) -> float:
    n = sum(sum(1 for t in ts if open_ <= t <= close)
            for ts in times.values())
    return n / (close - open_)
