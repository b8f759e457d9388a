"""End-to-end telemetry contract for ``run_batch(..., telemetry=True)``.

Three guarantees, asserted across every execution path the engine has
(numpy oracle, jax host-control stream/gram, jax device-control):

1. *output-neutral* — turning telemetry on changes NOTHING about the
   primary outputs: final iterates bitwise identical, control decisions
   and detection flags equal;
2. *backend-exact* — the counters are control quantities, so the jax
   scan's on-device accumulation equals the numpy engine's host-side
   counts EXACTLY, per trial and per key;
3. *schedule-consistent* — on a recorded numpy pass, every counter
   equals the corresponding sum over the recorded per-step schedule
   arrays (the counters are a lossy projection of the schedule, not an
   independent bookkeeping that could drift).

The sharded variants (8-device mesh, chunked pipeline) live in the
sharded scenario harness (tests/test_sharded_engine.py).
"""
import numpy as np
import pytest

from repro.core.engine import (SCENARIOS, ScheduleRecorder, TrialSpec,
                               run_batch)
from repro.obs.telemetry import TEL_KEYS


def _assert_counters_equal(tn, tj, context=""):
    assert tn is not None and tj is not None
    for k in TEL_KEYS:
        assert np.array_equal(tn.counters[k], tj.counters[k]), \
            f"{context}:{k}"


def _assert_output_neutral(off, on):
    """telemetry=True must be invisible in every primary output."""
    for ro, rn in zip(off, on):
        assert np.array_equal(np.asarray(ro.w), np.asarray(rn.w))
        assert ro.identify_step == rn.identify_step
        assert ro.efficiency == rn.efficiency
        assert ro.q_trace == rn.q_trace
        assert np.array_equal(ro.state.active, rn.state.active)
    df_off = getattr(off, "detect_flags", None)
    if df_off is not None:
        assert np.array_equal(df_off, on.detect_flags)


# ---------------------------------------------------------------------------
# the SCENARIOS grid: every mode/attack/fault family, host control
# ---------------------------------------------------------------------------

_grid_cache: dict = {}


def _grid_runs(name):
    if name not in _grid_cache:
        mx = SCENARIOS[name]
        _grid_cache[name] = (mx.run(telemetry=True),
                             mx.run(backend="jax"),
                             mx.run(backend="jax", telemetry=True))
    return _grid_cache[name]


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_scenarios_grid_output_neutral(name):
    _, jx_off, jx_on = _grid_runs(name)
    _assert_output_neutral(jx_off, jx_on)


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_scenarios_grid_counters_match_numpy(name):
    np_on, _, jx_on = _grid_runs(name)
    _assert_counters_equal(np_on.telemetry, jx_on.telemetry, name)
    # labels/q summaries ride along for the report layer
    assert jx_on.telemetry.labels == tuple(s.label for s in jx_on.specs)
    assert np.allclose(np_on.telemetry.q_mean, jx_on.telemetry.q_mean,
                       equal_nan=True)


def test_telemetry_off_is_none():
    specs = [TrialSpec(byz=(2, 5), attack="drift", steps=10, q=0.4,
                       d=8, n_data=32)]
    assert run_batch(specs).telemetry is None
    assert run_batch(specs, backend="jax").telemetry is None


# ---------------------------------------------------------------------------
# the other execution paths: stream / gram / device control
# ---------------------------------------------------------------------------


def _plane_specs():
    # shared problem, affine attacks, host-schedulable AND
    # device-schedulable — eligible for every plane under test
    return [
        TrialSpec(byz=(2, 5), attack="drift", steps=40, q=0.3, seed=s,
                  d=8, n_data=32, label=f"s{s}")
        for s in range(3)
    ] + [
        TrialSpec(byz=(1,), attack="sign_flip", steps=40, q=0.6, seed=7,
                  d=8, n_data=32, label="hot"),
        TrialSpec(byz=(), attack="none", steps=0, q=0.5, seed=8,
                  d=8, n_data=32, label="zero_steps"),
    ]


@pytest.mark.parametrize("kw", [
    {"data_plane": "stream"},
    {"data_plane": "gram"},
], ids=["stream", "gram"])
def test_data_planes_output_neutral_and_exact(kw):
    specs = [s for s in _plane_specs() if s.steps > 0]   # keep planes engaged
    np_on = run_batch(specs, telemetry=True)
    off = run_batch(specs, backend="jax", **kw)
    on = run_batch(specs, backend="jax", telemetry=True, **kw)
    plane = kw["data_plane"]
    assert on.plan.data_plane == plane and off.plan.data_plane == plane
    _assert_output_neutral(off, on)
    _assert_counters_equal(np_on.telemetry, on.telemetry, str(kw))


def test_device_control_output_neutral_and_exact():
    specs = [s for s in _plane_specs() if s.steps > 0]
    np_on = run_batch(specs, rng="device", telemetry=True)
    off = run_batch(specs, backend="jax", schedule="device")
    on = run_batch(specs, backend="jax", schedule="device", telemetry=True)
    assert on.schedule.mode == "device"
    _assert_output_neutral(off, on)
    _assert_counters_equal(np_on.telemetry, on.telemetry, "device")


# ---------------------------------------------------------------------------
# schedule consistency: counters == sums over the recorded control trace
# ---------------------------------------------------------------------------


def test_counters_match_recorded_schedule():
    specs = [
        TrialSpec(byz=(2, 5), attack="sign_flip", steps=80, q=0.4, seed=0,
                  d=8, n_data=32),
        TrialSpec(byz=(3,), attack="scale", steps=80, mode="draco", q=None,
                  seed=1, d=8, n_data=32),          # vote1 coverage
        TrialSpec(byz=(1,), attack="drift", steps=80, mode="deterministic",
                  q=None, seed=2, d=8, n_data=32),
        TrialSpec(byz=(2, 5), attack="sign_flip", steps=80, q=0.3, seed=3,
                  onset=30, d=8, n_data=32),        # late onset
    ]
    rec = ScheduleRecorder()
    out = run_batch(specs, telemetry=True, _recorder=rec)
    tel = out.telemetry
    arr = {k: np.stack([stp[k] for stp in rec.steps])
           for k in rec.steps[0]}                   # (T, B, ...) stacks
    live = arr["live"]
    checks = arr["checks"]
    vote1 = arr["vote1"]
    identify = arr["identify"]
    assert np.array_equal(tel.counters["steps"], live.sum(0))
    assert np.array_equal(tel.counters["checks"], checks.sum(0))
    assert np.array_equal(tel.counters["redundant_steps"],
                          (checks | vote1).sum(0))
    assert np.array_equal(tel.counters["detects"], identify.sum(0))
    assert np.array_equal(tel.counters["identify_rounds"], identify.sum(0))
    assert np.array_equal(tel.counters["vote_rounds"],
                          (identify | vote1).sum(0))
    assert np.array_equal(tel.counters["tamper_events"],
                          arr["tam1"].sum(axis=(0, 2))
                          + arr["tam2"].sum(axis=(0, 2)))
    byz = np.zeros((len(specs), specs[0].n), bool)
    for b, s in enumerate(specs):
        byz[b, list(s.byz)] = True
    assert np.array_equal(
        tel.counters["byz_active_steps"],
        np.where(live, (byz[None] & arr["active"]).sum(2), 0).sum(0))
    # the draco trial pays redundancy every live step by construction
    assert (tel.counters["redundant_steps"][1]
            == tel.counters["steps"][1])


# ---------------------------------------------------------------------------
# degenerate batches
# ---------------------------------------------------------------------------


def test_zero_step_trials_have_zero_counters():
    specs = [TrialSpec(byz=(2, 5), attack="sign_flip", steps=0, q=0.4,
                       d=8, n_data=32)]
    for out in (run_batch(specs, telemetry=True),
                run_batch(specs, backend="jax", telemetry=True)):
        tel = out.telemetry
        assert all(int(tel.counters[k][0]) == 0 for k in TEL_KEYS)
        assert np.isnan(tel.q_mean[0])


def test_empty_batch_telemetry():
    out = run_batch([], telemetry=True)
    assert out.telemetry is not None
    assert len(out.telemetry) == 0
    assert out.telemetry.totals()["steps"] == 0


def test_mixed_zero_step_trial_inside_batch():
    """A steps=0 trial embedded in a live batch: its row is all-zero and
    its neighbours' counters are unaffected."""
    full = [s for s in _plane_specs() if s.steps > 0]
    out_full = run_batch(full, backend="jax", telemetry=True)
    mixed = _plane_specs()                           # + the steps=0 trial
    out = run_batch(mixed, backend="jax", telemetry=True)
    zi = [i for i, s in enumerate(mixed) if s.steps == 0]
    (zi,) = zi
    for k in TEL_KEYS:
        assert int(out.telemetry.counters[k][zi]) == 0, k
        assert np.array_equal(
            np.delete(out.telemetry.counters[k], zi),
            out_full.telemetry.counters[k]), k
