#!/usr/bin/env python3
"""The program's own spans, as the per-layer metrics and the trace read them.

The serving loop times its phases itself (``span_<name>_ns`` and
``span_<name>_n`` in its counters, with ``requests_admitted`` and
``queue_wait_ns``) and, while a profiler trace is on, puts each phase on the
trace's host plane as ``serve.<name>``.  :func:`ms_per` turns the counters
into the per-layer metrics' readings; a program without them reads
``None``.  :func:`extract` keeps the ``serve.*`` events of a trace beside the
record :func:`tracing.extract` makes (``record["spans"]``, while
``record["host"]`` stays the harness's ticks), and :func:`idle_gaps` names
each idle gap of the device by the innermost program span around it.

Run as a script, it is ``bench.py`` with those two in place of the plain
reduction; on standard error it adds the idle time summed by span label,
each span's count and mean time inside the trace, the window's deltas of the span counters, and, for an untraced run, the
per-layer metrics that need no trace (``per-layer, untraced:``):

    python3 benchmarks/serve/program_spans.py --workload <name> \\
        --seed <n> --seconds <s> --trace <0|1>
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import tracing  # noqa: E402

PREFIX = "serve."
_extract_ticks = tracing.extract       # the plain reduction, kept apart
WINDOW_COUNTERS = ("requests_admitted", "queue_wait_ns", "decode_ticks")


def ms_per(run, num: str, den: str, less: Optional[str] = None
           ) -> Optional[float]:
    """``(delta(num) - delta(less)) / delta(den)`` over the window, with
    ``num`` and ``less`` in ns, read in ms; ``None`` where the program
    keeps no such counter or ``den`` did not move."""
    keys = [k for k in (num, den, less) if k]
    if any(k not in run.counters_open or k not in run.counters_close
           for k in keys):
        return None
    n = run.counter_delta(den)
    if n <= 0:
        return None
    ns = run.counter_delta(num) - (run.counter_delta(less) if less else 0)
    return 1e-6 * ns / n


def host_spans(logdir: str) -> List[List]:
    """Every ``serve.*`` host event of the trace: ``[name, start_ns,
    dur_ns]`` with the prefix taken off the name."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    if not paths:
        raise RuntimeError(f"no trace under {logdir}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out.extend([e.name[len(PREFIX):], float(e.start_ns),
                            float(e.duration_ns)]
                           for e in ln.events if e.name.startswith(PREFIX))
    return out


def extract(logdir: str) -> Dict:
    record = _extract_ticks(logdir)
    record["spans"] = host_spans(logdir)
    return record


def _gaps(record: Dict) -> List[List]:
    """Every gap between busy device intervals (first chip) as ``[label,
    seconds]``: the tick label :func:`tracing.idle_gaps` gives, and after
    it the innermost (shortest) program span holding the gap's midpoint."""
    busy = tracing.merge(tracing._intervals(record["devices"][0]))
    ticks = tracing.merge(tracing._intervals(record["host"]))
    spans = sorted(record.get("spans", []), key=lambda e: e[2])
    out = []
    for (_, a), (b, _) in zip(busy, busy[1:]):
        mid = (a + b) / 2
        label = ("inside tick" if any(s <= mid <= e for s, e in ticks)
                 else "between ticks")
        name = next((n for n, s, d in spans if s <= mid <= s + d), None)
        if name is not None:
            label = f"{label}: {name}"
        out.append([label, (b - a) * 1e-9])
    return out


def idle_gaps(record: Dict, k: int = 10) -> List[List]:
    """The ``k`` longest gaps, labelled as :func:`_gaps` labels them."""
    return sorted(_gaps(record), key=lambda g: -g[1])[:k]


def idle_by_label(record: Dict) -> Dict[str, float]:
    """Idle seconds of the first chip summed by gap label, most first."""
    by: Dict[str, float] = {}
    for label, s in _gaps(record):
        by[label] = by.get(label, 0.0) + s
    return dict(sorted(by.items(), key=lambda x: -x[1]))


def span_summary(record: Dict) -> Dict[str, List[float]]:
    """Per program span name in the trace: ``[count, mean ms]``."""
    by: Dict[str, List[float]] = {}
    for name, _, dur in record.get("spans", []):
        n, tot = by.get(name, [0, 0.0])
        by[name] = [n + 1, tot + dur]
    return {k: [n, tot * 1e-6 / n] for k, (n, tot) in by.items()}


def main(argv=None) -> int:
    import bench
    args = bench.parse_args(argv)
    runs, records = [], []

    class Recorded(bench.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

    def extract_kept(logdir):
        records.append(extract(logdir))
        return records[-1]

    bench.Run = Recorded
    tracing.extract, tracing.idle_gaps = extract_kept, idle_gaps
    rc = bench.main(argv)
    if records:
        bench.log("idle by span: " + json.dumps(idle_by_label(records[-1])))
        bench.log("spans in trace: " + json.dumps(span_summary(records[-1])))
    if runs:
        run = runs[-1]
        bench.log("program counters, window: " + json.dumps({
            k: run.counter_delta(k) for k in sorted(run.counters_close)
            if k.startswith("span_") or k in WINDOW_COUNTERS}))
    if runs and not args.trace:
        import importlib
        got = {}
        for m in bench.load_cell(args.workload).per_layer:
            mod = importlib.import_module(f"metrics.{m['name']}")
            v = mod.read(run)           # a trace reader reads None
            if v is not None:
                got[m["name"]] = v
        bench.log("per-layer, untraced: " + json.dumps(got))
    return rc


if __name__ == "__main__":
    sys.exit(main())
