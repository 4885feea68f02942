"""The program's spans as the harness reads them: each new per-layer
metric on hand-made counters, the span-labelled idle gaps on a hand-made
record, the chip record reducing as before, and the counters read through
a whole reduced run on the CPU."""
import json
import os

import pytest

import bench
import cpu_cell
import program_spans
import tracing
from metrics import (admit_host_ms, admit_ms, decode_host_ms, decode_step_ms,
                     lease_wave_ms, queue_wait_ms)

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "trace_v5e.json")
NEW = (queue_wait_ms, admit_ms, admit_host_ms, lease_wave_ms,
       decode_step_ms, decode_host_ms)


def _run(open_, close):
    return bench.Run(ticks=[], counters_open=open_, counters_close=close,
                     model=None, sizes=None, peaks={}, trace=None)


OPEN = {"requests_admitted": 3, "queue_wait_ns": 5_000_000,
        "span_admit_ns": 10_000_000, "span_prefill_wait_ns": 4_000_000,
        "span_lease_wave_ns": 1_000_000, "span_decode_ns": 50_000_000,
        "span_decode_n": 2, "span_decode_wait_ns": 40_000_000}
# 4 more admissions and 10 more decode steps in the window
CLOSE = {"requests_admitted": 7, "queue_wait_ns": 805_000_000,
         "span_admit_ns": 1_490_000_000, "span_prefill_wait_ns": 604_000_000,
         "span_lease_wave_ns": 41_000_000, "span_decode_ns": 300_000_000,
         "span_decode_n": 12, "span_decode_wait_ns": 270_000_000}


@pytest.mark.parametrize("mod,want", [
    (queue_wait_ms, 800 / 4), (admit_ms, 1480 / 4),
    (admit_host_ms, (1480 - 600) / 4), (lease_wave_ms, 40 / 4),
    (decode_step_ms, 250 / 10), (decode_host_ms, (250 - 230) / 10)])
def test_readers_on_counters(mod, want):
    assert mod.read(_run(OPEN, CLOSE)) == pytest.approx(want)


@pytest.mark.parametrize("mod", NEW)
def test_readers_without_the_counters_read_none(mod):
    # a program that keeps no span counters, and a
    # window in which nothing was admitted or decoded
    assert mod.read(_run({"decode_ticks": 1}, {"decode_ticks": 9})) is None
    assert mod.read(_run(OPEN, OPEN)) is None


def _record():
    # ns; ticks [0, 100) and [200, 300); busy [10, 20) [50, 60) [90, 95)
    # [150, 160) [210, 280); nested program spans in the first tick
    return {
        "devices": [[["fusion.1", 10, 10], ["fusion.2", 50, 10],
                     ["fusion.3", 90, 5], ["fusion.4", 150, 10],
                     ["fusion.5", 210, 70]]],
        "host": [["tick", 0, 100], ["tick", 200, 100]],
        "spans": [["admit", 5, 80], ["kv_to_host", 25, 20],
                  ["decode", 86, 12]],
    }


def test_idle_gaps_take_the_innermost_span():
    # gaps: [20, 50) mid 35 in kv_to_host inside admit; [60, 90) mid 75 in
    # admit only; [95, 150) mid 122.5 between ticks; [160, 210) mid 185
    # between ticks
    gaps = program_spans.idle_gaps(_record())
    assert gaps == [["between ticks", pytest.approx(55e-9)],
                    ["between ticks", pytest.approx(50e-9)],
                    ["inside tick: kv_to_host", pytest.approx(30e-9)],
                    ["inside tick: admit", pytest.approx(30e-9)]]
    by = program_spans.idle_by_label(_record())
    assert by["between ticks"] == pytest.approx(105e-9)
    assert by["inside tick: admit"] == pytest.approx(30e-9)


def test_spans_leave_the_tick_reduction_alone():
    rec = _record()
    plain = {k: v for k, v in rec.items() if k != "spans"}
    assert tracing.busy_in_spans_ns(rec) == tracing.busy_in_spans_ns(plain)
    # without program spans the labels are the plain ones
    assert program_spans.idle_gaps(plain) == tracing.idle_gaps(plain)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no chip trace kept")
def test_recorded_chip_trace_reduces_as_before():
    with open(FIXTURE) as f:
        rec = json.load(f)["trace"]
    assert "spans" not in rec
    assert program_spans.idle_gaps(rec) == tracing.idle_gaps(rec)
    assert sum(program_spans.idle_by_label(rec).values()) == pytest.approx(
        sum(s for _, s in program_spans._gaps(rec)))


def test_counters_through_a_reduced_run(monkeypatch):
    """The shared cell at reduced widths on the CPU: all six readers read a
    value, the inner spans lie inside their outer ones, and the program
    times every decode tick of the window."""
    runs = []

    class Recorded(bench.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

    monkeypatch.setattr(bench, "Run", Recorded)
    cell = cpu_cell.reduced_cell("mistral-nemo-12b.agent_shared")
    with cpu_cell.plain_row_kernels():
        bench.run_cell(cell, 2 ** 33 + 7, 10.0, trace=False)
    run = runs[-1]
    got = {m.__name__.split(".")[-1]: m.read(run) for m in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["admit_host_ms"] <= got["admit_ms"]
    assert got["lease_wave_ms"] <= got["admit_host_ms"]
    assert got["decode_host_ms"] <= got["decode_step_ms"]
    assert run.counter_delta("span_decode_n") \
        == run.counter_delta("decode_ticks") \
        == sum(1 for t in run.ticks if t["decoded"])
    assert run.counter_delta("requests_admitted") \
        == sum(len(t["admitted"]) for t in run.ticks)


def test_span_summary_counts_and_means():
    rec = _record()
    rec["spans"].append(["admit", 300, 40])
    assert program_spans.span_summary(rec) == {
        "admit": [2, pytest.approx(60e-6)],
        "kv_to_host": [1, pytest.approx(20e-6)],
        "decode": [1, pytest.approx(12e-6)]}
