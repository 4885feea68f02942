"""Named host spans of the serving loop, counted in its flat counter dict.

Entering ``spans("<name>")`` opens a ``jax.profiler.TraceAnnotation``
named ``serve.<name>`` (a no-op unless a profiler trace is on; when one is,
the span lands on the trace's host plane, on the device ops' clock) and on
exit adds the elapsed ``time.perf_counter_ns()`` to ``span_<name>_ns`` and
one to ``span_<name>_n`` of the counter dict it was given.  The counters
are always kept, so untraced runs report them too.
"""
from __future__ import annotations

import time
from typing import Dict, Iterable

import jax


class Spans:
    """The fixed set of span names of one counter dict; every name's pair
    of counters exists (at zero) from construction on."""

    def __init__(self, counters: Dict[str, int], names: Iterable[str]):
        self._counters = counters
        for name in names:
            counters[f"span_{name}_ns"] = 0
            counters[f"span_{name}_n"] = 0

    def __call__(self, name: str) -> "_Span":
        return _Span(self._counters, name)


class _Span:
    __slots__ = ("_counters", "_name", "_ann", "_t0")

    def __init__(self, counters: Dict[str, int], name: str):
        if f"span_{name}_n" not in counters:
            raise KeyError(f"undeclared span {name!r}")
        self._counters = counters
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(f"serve.{name}")

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter_ns() - self._t0
        c = self._counters
        c[f"span_{self._name}_ns"] += dt
        c[f"span_{self._name}_n"] += 1
        self._ann.__exit__(*exc)
        return False
