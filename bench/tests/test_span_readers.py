"""The benchmark's readers of the program's spans and the span audit, on
hand-made inputs."""
import types

import pytest

from bench import harness, spanaudit, tracereduce


def test_tracereduce_finds_each_program_span_once(tmp_path):
    import statistics

    import jax

    from repro.obs import trace

    trace.clear()
    with jax.profiler.trace(str(tmp_path)):
        for i in range(20):
            with trace.span("test.on_profiler_clock", part=i):
                sum(range(100))
    ring = trace.spans()
    host = tracereduce.load(str(tmp_path), {"test.on_profiler_clock"})["host"]
    assert len(host) == len(ring) == 20
    # the ring buffer's duration, to within a few microseconds
    assert statistics.median(
        abs(h[1] - r["dur_ns"]) for h, r in zip(host, ring)) < 20_000


def _ctx(spans, window_s=1.0, trace_=None):
    return types.SimpleNamespace(
        program_spans=[{"name": n, "ts_ns": ts, "dur_ns": dur}
                       for n, ts, dur in spans],
        window_s=window_s, trace=trace_)


SPANS = [("engine.make_problem", 0, 10_000_000),
         ("engine.stage_problem", 10_000_000, 30_000_000),
         ("engine.scan", 40_000_000, 400_000_000),
         ("pipeline.stage", 40_000_000, 5_000_000),
         ("pipeline.wait", 100_000_000, 200_000_000),
         ("pipeline.fetch", 300_000_000, 15_000_000),
         ("engine.results", 440_000_000, 20_000_000),
         ("numpy.data", 500_000_000, 70_000_000),
         ("numpy.data", 600_000_000, 30_000_000)]


@pytest.mark.parametrize("metric,pct", [
    ("problem_pct.sweep", 4.0), ("transfer_pct.sweep", 2.0),
    ("device_wait_pct.sweep", 20.0), ("results_pct.sweep", 2.0),
    ("oracle_data_pct.sweep", 10.0)])
def test_span_share_readers(metric, pct):
    read = harness.load_module("metrics", metric).read
    assert read(_ctx(SPANS)) == pytest.approx(pct)
    # a program that records none of these spans reads nothing, not 0
    assert read(_ctx([("engine.build_schedule", 0, 5)])) is None


def _untraced(t):
    return harness.load_module("metrics", "untraced_idle_pct.sweep").read(
        _ctx([], trace_=t))


def test_untraced_idle_counts_idle_time_under_no_program_span():
    # window 0-1000; the chip runs 100-200 and 600-700, so it idles
    # 0-100, 200-600 and 700-1000 (800 in all)
    t = {"w0": 0, "w1": 1000,
         "devices": {"TPU:0": [[100, 100, "fusion.1", ""],
                               [600, 100, "fusion.2", ""]]},
         "spans": [[0, 1000, "sweep.call", ""],         # does not count
                   [50, 300, "engine.build_schedule", ""],
                   [60, 100, "schedule.replay", ""],    # nested
                   [300, 200, "engine.scan", ""],       # overlaps
                   [900, 200, "engine.results", ""]]}   # past the end
    # spans cover 50-500 and 900-1000: idle under none of them is
    # 0-50, 500-600 and 700-900
    assert _untraced(t) == pytest.approx(35.0)
    # with a second chip the share is the chips' mean
    t["devices"]["TPU:1"] = [[0, 1000, "while.1", ""]]
    assert _untraced(t) == pytest.approx(17.5)


def test_untraced_idle_reads_nothing_without_trace_or_program_spans():
    assert _untraced(None) is None
    assert _untraced({"w0": 0, "w1": 10, "devices": {"TPU:0": []},
                      "spans": [[0, 10, "sweep.call", ""]]}) is None


def test_span_audit_counts_and_clock_skew():
    recorded = [[1_000, 50, "engine.scan", ""],
                [5_000, 60, "engine.scan", ""],
                [2_000, 10, "pipeline.wait", ""]]
    moved = [[4_997, 60, "engine.scan", ""], [1_002, 50, "engine.scan", ""],
             [2_000, 10, "pipeline.wait", ""],
             [3_000, 10, "pipeline.fetch", ""]]
    a = spanaudit.audit(moved, recorded)
    assert a["counts"] == {"engine.scan": [2, 2], "pipeline.fetch": [0, 1],
                           "pipeline.wait": [1, 1]}
    assert a["skew_us"] == pytest.approx(0.003)


def test_span_audit_host_time_outside_spans():
    calls = [[0, 100, "sweep.call", ""], [150, 100, "sweep.call", ""]]
    spans = [[10, 30, "engine.build_schedule", ""],
             [40, 50, "engine.scan", ""],            # the last ends at 90
             [160, 80, "engine.build_schedule", ""]]
    # heads 10 + 10, tails 10 + 10, one gap of 50 between the calls
    assert spanaudit.outside_spans(calls, spans, 0, 250) == pytest.approx(
        {"head": 8.0, "tail": 8.0, "between": 20.0})
