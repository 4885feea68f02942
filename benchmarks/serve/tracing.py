"""From a profiler trace to busy time, kernel time and idle gaps.

:func:`extract` reads the ``.xplane.pb`` the JAX profiler wrote into a small
JSON-able record: every device operation ``[name, start_ns, dur_ns]`` of
each TPU plane's op line (named by :func:`op_name`), and the harness's own
host spans (``tick``); device and host events share one clock.  Everything
else here works on that record, so the reduction is tested on a record
kept with the tests.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Sequence, Tuple

SPANS = ("tick",)
OP_LINE = "XLA Ops"

Interval = Tuple[float, float]


def op_name(hlo: str) -> str:
    """A device event's name as the reduction uses it: the HLO op name
    (``%paged_decode_attention.10``), then ``custom-call`` for a Pallas
    kernel and ``aliased`` where its output aliases an operand (the
    pool scatter updates the pool in place)."""
    name = hlo.split(" ", 1)[0]
    if "tpu_custom_call" in hlo:
        name += " custom-call"
        if "output_to_operand_aliasing" in hlo:
            name += " aliased"
    return name


def extract(logdir: str) -> Dict:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    if not paths:
        raise RuntimeError(f"no trace under {logdir}")
    data = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OP_LINE] or lines
            devices.append([[op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)]
                            for ln in ops for e in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in ln.events if e.name in SPANS)
    return {"devices": devices, "host": host}


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _intervals(events) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def overlap(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def busy_ns(record: Dict) -> float:
    """Union of device-busy intervals, averaged over the chips."""
    devs = record["devices"]
    return sum(length(merge(_intervals(d))) for d in devs) / len(devs)


def busy_in_spans_ns(record: Dict) -> Tuple[float, float]:
    """(device-busy time inside the harness's tick spans, total tick span
    time), averaged over the chips."""
    spans = merge(_intervals(record["host"]))
    devs = record["devices"]
    inside = sum(overlap(merge(_intervals(d)), spans) for d in devs)
    return inside / len(devs), length(spans)


def kernel_ns(record: Dict, pattern: str) -> Tuple[float, int]:
    """(summed device time, event count) of the ops whose name matches."""
    rx = re.compile(pattern)
    tot, n = 0.0, 0
    for d in record["devices"]:
        for name, _, dur in d:
            if rx.search(name):
                tot += dur
                n += 1
    return tot / len(record["devices"]), n


def top_ops(record: Dict, k: int = 10) -> List[List]:
    """The ``k`` operation names that took most device time, seconds."""
    by: Dict[str, float] = {}
    for d in record["devices"]:
        for name, _, dur in d:
            by[name] = by.get(name, 0.0) + dur
    n = len(record["devices"])
    return [[name, t / n * 1e-9]
            for name, t in sorted(by.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(record: Dict, k: int = 10) -> List[List]:
    """The ``k`` longest gaps between busy device intervals (first chip),
    each named by what the host was doing: inside a tick, or between
    ticks (waiting for arrivals)."""
    busy = merge(_intervals(record["devices"][0]))
    spans = merge(_intervals(record["host"]))
    gaps = []
    for (_, a), (b, _) in zip(busy, busy[1:]):
        mid = (a + b) / 2
        inside = any(s <= mid <= e for s, e in spans)
        gaps.append(["inside tick" if inside else "between ticks",
                     (b - a) * 1e-9])
    return sorted(gaps, key=lambda g: -g[1])[:k]
