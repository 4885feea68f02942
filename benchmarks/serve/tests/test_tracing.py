"""The reduction from a trace record to busy time, kernel time and idle
share: on a hand-made record, and on a record taken on the chip."""
import json
import os
from types import SimpleNamespace

import pytest

import bench
import tracing
from metrics import device_idle_share, kv_append_roofline, paged_attn_roofline

WORKLOAD = "mistral-nemo-12b.agent_shared"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "trace_v5e.json")


def _record():
    # ns; two ticks [0, 100) and [200, 300); ops overlap inside the first
    return {
        "devices": [[["fusion.1", 10, 30], ["paged_decode_attention.3", 30, 40],
                     ["scatter_rows", 80, 10], ["fusion.2", 150, 20],
                     ["paged_decode_attention.7", 210, 50]]],
        "host": [["tick", 0, 100], ["tick", 200, 100]],
    }


def test_busy_is_the_union_of_op_intervals():
    # [10, 70) + [80, 90) + [150, 170) + [210, 260) = 60 + 10 + 20 + 50
    assert tracing.busy_ns(_record()) == 140


def test_idle_counts_only_inside_ticks():
    inside, spans = tracing.busy_in_spans_ns(_record())
    # the op at [150, 170) lies between the ticks and is not counted
    assert (inside, spans) == (120, 200)


def test_kernel_time_sums_matching_ops():
    assert tracing.kernel_ns(_record(), r"paged_decode") == (90, 2)
    assert tracing.kernel_ns(_record(), r"scatter_rows") == (10, 1)
    assert tracing.kernel_ns(_record(), r"nothing") == (0, 0)


def test_busy_averages_over_chips():
    rec = _record()
    rec["devices"].append([["fusion.9", 0, 40]])
    assert tracing.busy_ns(rec) == (140 + 40) / 2


def test_top_ops_and_gaps():
    top = tracing.top_ops(_record(), k=2)
    assert top[0][0] == "paged_decode_attention.7"
    assert top[0][1] == pytest.approx(50e-9)
    # busy [10, 70) [80, 90) [150, 170) [210, 260): gaps of 60 and 40
    # between the ticks, and 10 inside the first
    gaps = tracing.idle_gaps(_record(), k=3)
    assert gaps == [["between ticks", pytest.approx(60e-9)],
                    ["between ticks", pytest.approx(40e-9)],
                    ["inside tick", pytest.approx(10e-9)]]


def test_merge_and_overlap():
    assert tracing.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert tracing.overlap([(0, 10)], [(2, 3), (5, 20)]) == 6


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no chip trace kept")
def test_recorded_chip_trace():
    """A few ticks of the shared cell traced on one v5e: every device op of
    the window lies inside a tick span, the paged kernel ran once per layer
    per decode tick, and busy time is below the tick time."""
    with open(FIXTURE) as f:
        kept = json.load(f)
    rec, ticks = kept["trace"], kept["ticks"]
    inside, spans = tracing.busy_in_spans_ns(rec)
    busy = tracing.busy_ns(rec)
    assert 0 < inside <= spans
    assert inside <= busy
    decode_ticks = sum(1 for t in ticks if t["decoded"])
    _, n = tracing.kernel_ns(rec, paged_attn_roofline.PATTERN)
    assert n == kept["layers"] * decode_ticks
    # one pool scatter per decode step, one more per admission
    _, n = tracing.kernel_ns(rec, kv_append_roofline.PATTERN)
    assert n == decode_ticks + sum(len(t["admitted"]) for t in ticks)
    cell = bench.load_cell(WORKLOAD)
    run = SimpleNamespace(trace={"record": rec, "ticks": ticks},
                          model=cell.model, sizes=cell.sizes, peaks=PEAKS)
    for mod in (paged_attn_roofline, kv_append_roofline):
        assert 0 < mod.read(run) <= 100
    assert 0 <= device_idle_share.read(run) < 100
