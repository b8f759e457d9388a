"""The program's spans on the profiler's clock, and the span tree of one
``run_batch(specs, backend="jax")`` call, on the CPU."""
import glob
import os

from repro.core.engine import TrialSpec, run_batch
from repro.obs import trace


def _profiled_spans(trace_dir, name: str, n: int = 20):
    """Ring-buffer spans ``name`` made inside a JAX profiler trace, and
    the host events of that name in the trace, each [start_ns, dur_ns]."""
    import jax
    from jax.profiler import ProfileData

    trace.clear()
    with jax.profiler.trace(str(trace_dir)):
        for i in range(n):
            with trace.span(name, part=i):
                sum(range(100))
    ring = [e for e in trace.spans() if e["name"] == name]
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    host = sorted([ev.start_ns, ev.duration_ns]
                  for plane in ProfileData.from_file(path).planes
                  if not plane.name.startswith("/device:")
                  for line in plane.lines for ev in line.events
                  if ev.name == name)
    return ring, host


def test_spans_land_in_the_profiler_trace_once_each(tmp_path):
    import statistics

    ring, host = _profiled_spans(tmp_path, "test.on_profiler_clock")
    # each span once, under its bare name (the args stay in the buffer)
    assert len(host) == len(ring) == 20
    assert all(e["args"]["part"] == i for i, e in enumerate(ring))
    # the annotation opens just before the ring buffer's clock starts and
    # closes just after it stops
    assert statistics.median(
        h[1] - r["dur_ns"] for h, r in zip(host, ring)) < 20_000


# ---------------------------------------------------------------------------
# the span tree of one sweep call
# ---------------------------------------------------------------------------


def _call_spans(spec: dict, **kw):
    specs = [TrialSpec(byz=(1,), n=3, f=1, steps=6, seed=i, d=64,
                       n_data=12, **spec) for i in range(8)]
    trace.clear()
    run_batch(specs, backend="jax", **kw)
    return trace.spans()


def _inside(child, parent) -> bool:
    return (parent["ts_ns"] <= child["ts_ns"]
            and child["ts_ns"] + child["dur_ns"]
            <= parent["ts_ns"] + parent["dur_ns"])


def _only(spans, name):
    (s,) = [e for e in spans if e["name"] == name]
    return s


def _each_inside(spans, child, parent):
    kids = [e for e in spans if e["name"] == child]
    parents = [e for e in spans if e["name"] == parent]
    assert kids, child
    assert all(any(_inside(k, p) for p in parents) for k in kids), \
        (child, parent)


def test_span_tree_of_a_gram_sweep_with_vector_control():
    spans = _call_spans(dict(attack="drift", q=0.3), data_plane="gram")
    for child, parent in [("schedule.replay", "engine.build_schedule"),
                          ("replay.materialize", "schedule.replay"),
                          ("schedule.stack", "engine.build_schedule"),
                          ("pipeline.stage", "engine.scan"),
                          ("pipeline.drain", "engine.scan"),
                          ("pipeline.wait", "pipeline.drain"),
                          ("pipeline.fetch", "pipeline.drain")]:
        _each_inside(spans, child, parent)
    # the host phases between the control plane and the scan, and the
    # results after it, lie under no other span of the call
    order = [_only(spans, n) for n in (
        "engine.build_schedule", "engine.make_problem",
        "engine.stage_problem", "engine.scan", "engine.results")]
    for a, b in zip(order, order[1:]):
        assert a["ts_ns"] + a["dur_ns"] <= b["ts_ns"]
    assert not any(e["name"] == "schedule.oracle" for e in spans)
    assert _only(spans, "pipeline.stage")["args"]["bytes"] > 0
    assert _only(spans, "pipeline.fetch")["args"]["bytes"] > 0
    assert _only(spans, "pipeline.wait")["dur_ns"] >= 0


def test_span_tree_of_an_oracle_sweep():
    spans = _call_spans(dict(attack="sign_flip", q=None),
                        schedule="oracle")
    _each_inside(spans, "schedule.oracle", "engine.build_schedule")
    _each_inside(spans, "numpy.data", "schedule.oracle")
    # residuals, phase-1 gradients, aggregate and update: four a step,
    # each named by its product
    data = [e for e in spans if e["name"] == "numpy.data"]
    assert len(data) >= 4 * 6
    assert {e["args"]["product"] for e in data} >= {
        "residuals", "shard_gradients", "aggregate", "update"}
    assert not any(e["name"] == "schedule.replay" for e in spans)
