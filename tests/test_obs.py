"""Observability layer unit tests: metrics registry, span tracer,
warning dedup, and the efficiency report renderer.

These are pure-host tests (no engine runs except the report's tiny
batch) — the scan-level telemetry contract is covered end-to-end in
tests/test_telemetry.py and the differential suite.
"""
import json
import warnings

import numpy as np
import pytest

from repro.obs import metrics, oblog, trace
from repro.obs.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    snap = reg.snapshot()
    assert snap == {"c": {"kind": "counter", "value": 5},
                    "g": {"kind": "gauge", "value": 2.5}}


def test_registry_created_on_first_touch_and_kind_clash():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(TypeError, match="already registered as counter"):
        reg.gauge("x")


def test_snapshot_sorted_and_reset():
    reg = MetricsRegistry()
    reg.counter("b").inc()
    reg.counter("a").inc()
    assert list(reg.snapshot()) == ["a", "b"]
    reg.reset()
    assert reg.snapshot() == {}


def test_export_jsonl_appends_self_contained_lines(tmp_path):
    reg = MetricsRegistry()
    path = str(tmp_path / "sub" / "metrics.jsonl")
    reg.counter("events").inc(3)
    reg.export_jsonl(path)
    reg.counter("events").inc()
    reg.export_jsonl(path, extra={"phase": "end"})
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2
    assert lines[0]["metrics"]["events"]["value"] == 3
    assert lines[1]["metrics"]["events"]["value"] == 4
    assert lines[1]["phase"] == "end"
    assert all("ts" in ln for ln in lines)


def test_global_registry_helpers_share_namespace():
    metrics.counter("test_obs.shared").inc()
    assert metrics.REGISTRY.counter("test_obs.shared").value >= 1


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_records_name_duration_and_args():
    tr = trace.SpanTracer()
    with tr.span("outer", mode="test"):
        with tr.span("inner"):
            pass
    evs = tr.spans()
    assert [e["name"] for e in evs] == ["inner", "outer"]   # close order
    assert evs[1]["args"] == {"mode": "test"}
    assert all(e["dur_ns"] >= 0 for e in evs)


def test_span_yields_its_args_for_the_block():
    tr = trace.SpanTracer()
    with tr.span("fetch", lo=0) as args:
        args["bytes"] = 96
    with tr.span("bare"):
        pass
    fetch, bare = tr.spans()
    assert fetch["args"] == {"lo": 0, "bytes": 96}
    assert "args" not in bare


def test_span_recorded_even_when_body_raises():
    tr = trace.SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert [e["name"] for e in tr.spans()] == ["boom"]


def test_ring_buffer_bounded():
    tr = trace.SpanTracer(maxlen=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    evs = tr.spans()
    assert len(evs) == 4
    assert [e["name"] for e in evs] == ["s6", "s7", "s8", "s9"]


def test_ring_buffer_counts_evicted_spans_until_clear():
    """An evicted span silently lowers every share read from the buffer,
    so the tracer counts them; ``clear`` empties both."""
    tr = trace.SpanTracer(maxlen=4)
    for i in range(4):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped() == 0
    for i in range(3):
        with tr.span(f"t{i}"):
            pass
    assert tr.dropped() == 3
    tr.clear()
    assert tr.spans() == [] and tr.dropped() == 0
    with tr.span("u"):
        pass
    assert tr.dropped() == 0 and len(tr.spans()) == 1


def test_export_chrome_trace_json(tmp_path):
    tr = trace.SpanTracer()
    with tr.span("step", chunk=1):
        pass
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "step"
    assert ev["dur"] >= 0 and ev["args"] == {"chunk": 1}


# ---------------------------------------------------------------------------
# warning dedup
# ---------------------------------------------------------------------------


def test_warn_once_dedups_by_default_key():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert oblog.warn_once("msg one") is True
        assert oblog.warn_once("msg one") is False
        assert oblog.warn_once("msg two") is True
    assert [str(w.message) for w in caught] == ["msg one", "msg two"]


def test_warn_once_explicit_key_spans_message_variants():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        oblog.warn_once("detail A", key=("fallback", "reason1"))
        oblog.warn_once("detail B", key=("fallback", "reason1"))
        oblog.warn_once("detail C", key=("fallback", "reason2"))
    assert [str(w.message) for w in caught] == ["detail A", "detail C"]


def test_reset_warn_once_rearms():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        oblog.warn_once("again")
        oblog.reset_warn_once()
        oblog.warn_once("again")
    assert len(caught) == 2


def test_plan_fallback_warning_fires_once_per_reason():
    """The engine regression this layer fixes: a sweep calling run_batch
    repeatedly with a demoting config must warn ONCE per distinct
    fallback reason, not once per call."""
    from repro.core.engine import TrialSpec, run_batch
    from repro.core.engineplan.plan import PlanFallbackWarning

    # a filter baseline has no coefficient-only form, so an explicit
    # gram request demotes to the stream plane (with a warning)
    specs = [TrialSpec(byz=(2, 5), attack="sign_flip", steps=5, q=0.4,
                       seed=0, d=4, n_data=16, mode="filter:median")]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(3):
            run_batch(specs, backend="jax", data_plane="gram")
    fallback = [w for w in caught if issubclass(w.category,
                                                PlanFallbackWarning)]
    assert len(fallback) == 1


# ---------------------------------------------------------------------------
# efficiency report
# ---------------------------------------------------------------------------


def _tiny_batch():
    from repro.core.engine import TrialSpec, run_batch

    specs = [
        TrialSpec(byz=(2, 5), attack="sign_flip", steps=60, q=0.4, seed=0,
                  d=8, n_data=32),
        TrialSpec(byz=(2, 5), attack="sign_flip", steps=60, q=0.4, seed=1,
                  d=8, n_data=32),
        TrialSpec(byz=(1,), attack="drift", steps=60, q=0.2, seed=2,
                  d=8, n_data=32),
    ]
    return run_batch(specs, telemetry=True)


def test_efficiency_rows_group_and_bound():
    from repro.core import adaptive
    from repro.obs import report

    batch = _tiny_batch()
    rows = {r["scenario"]: r for r in report.efficiency_rows(batch)}
    assert set(rows) == {"sign_flip/f=2", "drift/f=1"}
    sf = rows["sign_flip/f=2"]
    assert sf["trials"] == 2 and sf["steps"] > 0
    # the expected column is the eq-2 closed form at the group's mean q
    assert sf["expected_overhead"] == pytest.approx(
        1.0 - adaptive.com_eff(sf["q_mean"], 2))
    # fixed q=0.4 trials: observed check rate concentrates near q
    assert 0.0 < sf["observed_overhead"] < 1.0


def test_render_report_table_and_missing_telemetry():
    from repro.core.engine import TrialSpec, run_batch
    from repro.obs import report

    text = report.render_report(_tiny_batch())
    lines = text.splitlines()
    assert lines[0].split()[0] == "scenario"
    assert len(lines) == 2 + 2                      # header, rule, 2 groups
    no_tel = run_batch([TrialSpec(byz=(), attack="none", steps=5, q=0.5,
                                  d=4, n_data=16)])
    with pytest.raises(ValueError, match="telemetry"):
        report.render_report(no_tel)


def test_obs_package_has_no_core_import_at_module_scope():
    """Layering contract: importing repro.obs alone must not pull in
    repro.core (the plan layer imports obs, not vice versa), nor jax,
    which the first span imports for its profiler annotation."""
    import subprocess
    import sys

    code = ("import sys; import repro.obs\n"
            "assert not any(m.startswith('repro.core') for m in sys.modules)\n"
            "assert 'jax' not in sys.modules\n"
            "with repro.obs.span('x'): pass\n"
            "assert 'jax' in sys.modules\n"
            "assert not any(m.startswith('repro.core') for m in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True)
    assert proc.returncode == 0, proc.stderr.decode()


def test_telemetry_container_derived_rates():
    from repro.obs.telemetry import TEL_KEYS, Telemetry, zero_counts

    counts = zero_counts(2)
    counts["steps"][:] = (10, 0)
    counts["checks"][:] = (4, 0)
    counts["redundant_steps"][:] = (5, 0)
    counts["detects"][:] = (2, 0)
    tel = Telemetry.from_counts(counts, q_traces=[[0.2, 0.6], []])
    assert len(tel) == 2
    assert tel.redundancy_overhead[0] == pytest.approx(0.5)
    assert tel.check_rate[0] == pytest.approx(0.4)
    assert tel.detection_rate[0] == pytest.approx(0.5)
    # zero-step trial: rates well-defined (0), q stats NaN
    assert tel.redundancy_overhead[1] == 0.0
    assert np.isnan(tel.q_mean[1]) and np.isnan(tel.q_final[1])
    assert tel.q_mean[0] == pytest.approx(0.4)
    assert tel.q_final[0] == pytest.approx(0.6)
    row = tel.per_trial(0)
    assert set(TEL_KEYS) <= set(row)
    assert tel.totals()["steps"] == 10
