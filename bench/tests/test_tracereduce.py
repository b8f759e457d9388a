"""The trace reduction's arithmetic, on hand-made events and on a small
trace recorded on a TPU v5e chip."""
import json
import os

import pytest

from bench import tracereduce as tr
from bench.metrics_util import kernel_seconds

HERE = os.path.dirname(os.path.abspath(__file__))

# [start_ns, duration_ns, name, kind]
EVENTS = [
    [100, 50, "fusion.1", ""],              # 100-150
    [120, 60, "fusion.2", ""],              # 120-180, overlaps, not nested
    [300, 100, "gram_factors.3", "kernel"],  # 300-400
    [500, 300, "while.4", ""],              # 500-800, a loop ...
    [550, 100, "fusion.5", ""],             # ... with its body inside
    [900, 200, "fusion.1", ""],             # 900-1100, crosses the end
]


def test_op_name_from_tpu_hlo_text():
    assert tr.op_name("%gram_factors.1 = (f32[72,72]) custom-call(f32[72,"
                      "65536] %pad.0), custom_call_target=\"tpu_custom_call\""
                      ) == ("gram_factors.1", "kernel")
    assert tr.op_name("%fusion.90 = f32[256] fusion(f32[32] %a), kind=kLoop"
                      ) == ("fusion.90", "")


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]


def test_busy_is_the_union_clipped_to_the_window():
    assert tr.busy_ns(EVENTS, 0, 1000) == 80 + 100 + 300 + 100


def test_gaps_and_busy_tile_the_window():
    gaps = tr.gaps(EVENTS, 0, 1000)
    assert gaps == [(0, 100), (180, 300), (400, 500), (800, 900)]
    assert sum(e - s for s, e in gaps) + tr.busy_ns(EVENTS, 0, 1000) == 1000


def test_op_seconds_count_own_time_once():
    ops = tr.op_seconds(EVENTS, 0, 1000)
    assert ops == pytest.approx({"fusion.1": 250e-9, "fusion.2": 60e-9,
                                 "gram_factors.3": 100e-9,
                                 "while.4": 200e-9, "fusion.5": 100e-9})


def test_kernel_seconds_finds_the_pallas_call():
    trace = {"devices": {"TPU:0": EVENTS}, "w0": 0, "w1": 1000}
    assert kernel_seconds(trace, "gram_factors") == pytest.approx(100e-9)
    assert kernel_seconds(trace, "fusion") == 0.0


def test_breakdown_names_gaps_by_the_innermost_span():
    spans = [[0, 1000, "sweep.call", ""],
             [350, 600, "engine.build_schedule", ""]]
    b = tr.breakdown({"TPU:0": EVENTS}, spans, 0, 1000)
    assert b["idle_gaps"][0] == ["sweep.call", pytest.approx(120e-9)]
    assert [g[0] for g in b["idle_gaps"][1:]].count(
        "engine.build_schedule") == 2
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_recorded_trace_is_consistent():
    with open(os.path.join(HERE, "data", "v5e_sweep_trace.json")) as fh:
        rec = json.load(fh)
    w0, w1 = rec["w0"], rec["w1"]
    for events in rec["devices"].values():
        busy = tr.busy_ns(events, w0, w1)
        idle = sum(e - s for s, e in tr.gaps(events, w0, w1))
        assert 0 < busy <= w1 - w0
        assert busy + idle == pytest.approx(w1 - w0)
    assert [tr.busy_ns(ev, w0, w1) for ev in rec["devices"].values()] == \
        pytest.approx(rec["busy_ns"])
    b = tr.breakdown(rec["devices"], rec["spans"], w0, w1)
    assert b["device_ops"] and b["idle_gaps"]
    # one engine call of the adaptive cell: the device waits on the host
    # control plane, and runs the gram precompute kernel once
    assert b["idle_gaps"][0][0] == "engine.build_schedule"
    assert kernel_seconds(rec, "gram_factors") > 0
