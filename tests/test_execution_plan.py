"""Unit grid over the ExecutionPlan layer (repro.core.engineplan.plan).

``resolve_plan`` is pure, so every path decision — schedule mode, data
plane, sharding, chunk sizing — is asserted here for the full
``SCENARIOS`` matrix without touching a device.  The one warning path
that needs the real engine (``PlanFallbackWarning`` on an explicit
``data_plane="gram"`` demotion) runs a tiny jax-backend batch.
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
import warnings

import pytest

from repro.core.engine import SCENARIOS, TrialSpec
from repro.core.engineplan.plan import (
    PlanFallbackWarning,
    device_schedulable,
    resolve_plan,
    resolve_schedule_mode,
    value_independent_control,
    warn_on_fallback,
)


def _spec(**kw) -> TrialSpec:
    base = dict(seed=0, steps=10, mode="randomized", q=0.2,
                attack="sign_flip", byz=(2, 5))
    base.update(kw)
    return TrialSpec(**base)


# ---------------------------------------------------------------------------
# resolve_plan over the full SCENARIOS matrix
# ---------------------------------------------------------------------------

# expected (schedule_mode, data_plane, sharded) per scenario under
# default knobs (schedule="auto", data_plane=None, single device).
# Every scenario holds at least one value-DEPENDENT trial (adaptive q*,
# or a detectability-scaling attack vs an active adversary), so "auto"
# resolves to the oracle replay batch-wide; every scenario runs at the
# default tiny d=8, below gram's size gate, so the stream plane runs.
_EXPECT = {
    "paper_core": ("oracle", "stream", False),   # filter baselines too
    "attack_sweep": ("oracle", "stream", False),
    "late_onset": ("oracle", "stream", False),
    "elastic_churn": ("oracle", "stream", False),
    "selective": ("oracle", "stream", False),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenarios_grid_default_plan(name):
    specs = SCENARIOS[name].expand()
    plan = resolve_plan(specs)
    assert (plan.schedule_mode, plan.data_plane,
            plan.sharded) == _EXPECT[name]
    assert plan.control == "host"
    assert plan.n_devices == 1
    assert plan.n_trials == len(specs)
    assert plan.steps == max(s.steps for s in specs)
    assert plan.shared_problem is True
    assert plan.data_plane_reason            # why not gram is recorded


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenarios_grid_forced_8_device_mesh(name):
    specs = SCENARIOS[name].expand()
    plan = resolve_plan(specs, n_devices=8)
    assert plan.sharded is True
    assert plan.n_devices == 8
    assert plan.chunk_trials % 8 == 0           # mesh-multiple rounding
    # sharding never changes the path selection itself
    assert (plan.schedule_mode, plan.data_plane) == _EXPECT[name][:2]


def test_value_independent_subset_takes_vector():
    # fixed-q randomized vs drift: detection outcomes are value-
    # independent, so "auto" picks the control-only vectorized replay
    specs = [s for s in SCENARIOS["attack_sweep"].expand()
             if s.attack == "drift" and s.q is not None]
    assert specs and all(value_independent_control(s) for s in specs)
    plan = resolve_plan(specs)
    assert (plan.schedule_mode, plan.data_plane) == ("vector", "stream")


def test_device_schedule_plan():
    specs = SCENARIOS["attack_sweep"].expand()
    assert all(device_schedulable(s) for s in specs)
    plan = resolve_plan(specs, schedule="device")
    assert (plan.schedule_mode, plan.control) == ("device", "device")
    assert plan.data_plane == "stream"
    assert "coin-flip sliver" in plan.data_plane_reason


# ---------------------------------------------------------------------------
# chunk sizing edge cases
# ---------------------------------------------------------------------------


def test_chunk_trials_zero_rejected():
    with pytest.raises(ValueError, match="chunk_trials must be >= 1"):
        resolve_plan([_spec()], chunk_trials=0)


def test_chunk_trials_one_rounds_to_mesh():
    plan = resolve_plan([_spec() for _ in range(20)], chunk_trials=1,
                        n_devices=8)
    assert plan.chunk_trials == 8


def test_chunk_auto_bounded_by_batch():
    plan = resolve_plan([_spec() for _ in range(3)])
    assert plan.chunk_trials == 3


def test_filter_trials_shrink_chunk():
    big = dict(n_data=256, d=4096, steps=1, n=8)
    plain = resolve_plan([_spec(**big) for _ in range(10_000)])
    filt = resolve_plan([_spec(mode="filter:median", **big)
                         for _ in range(10_000)])
    # the (chunk, n, d) gradient stack budget divides the chunk by ~n/4
    assert filt.chunk_trials < plain.chunk_trials


# ---------------------------------------------------------------------------
# schedule-mode errors: offending label + nearest accepting plan
# ---------------------------------------------------------------------------


def test_vector_error_names_label_and_nearest_plan():
    specs = [_spec(label="adaptive-run", q=None)]
    with pytest.raises(ValueError) as e:
        resolve_schedule_mode(specs, "vector")
    assert "adaptive-run" in str(e.value)
    assert 'nearest accepting plan: schedule="device"' in str(e.value)


def test_vector_error_nearest_plan_degrades_to_oracle():
    # selective checks exclude the device control plane, so the nearest
    # accepting plan falls back one more notch
    specs = [_spec(q=None, selective=True)]
    with pytest.raises(ValueError) as e:
        resolve_schedule_mode(specs, "proxy")
    assert 'nearest accepting plan: schedule="oracle"' in str(e.value)


def test_device_error_names_offending_spec():
    specs = [_spec(label="churny", events=SCENARIOS[
        "elastic_churn"].faults[0].events)]
    with pytest.raises(ValueError) as e:
        resolve_schedule_mode(specs, "device")
    assert "churny" in str(e.value)
    assert 'schedule="oracle"' in str(e.value)


# ---------------------------------------------------------------------------
# data-plane fallback: recorded reason, explain(), warning
# ---------------------------------------------------------------------------


def test_explain_on_and_off_paths():
    on = resolve_plan([_spec(n_data=64, d=4096)])
    assert "data     : gram — shared problem" in on.explain()
    off = resolve_plan([_spec(n_data=64, d=4096)], data_plane="stream")
    assert 'not gram: data_plane="stream" requested' in off.explain()


def test_auto_fallback_records_reason_without_warning():
    plan = resolve_plan([_spec(mode="filter:median")])   # auto plane
    assert plan.data_plane == "stream"
    assert "filter baseline" in plan.data_plane_reason
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        warn_on_fallback(plan)                           # no warning: auto


def test_zero_steps_never_warns():
    plan = resolve_plan([_spec(steps=0, mode="filter:median")],
                        data_plane="gram")
    assert plan.data_plane == "stream"
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        warn_on_fallback(plan)


def test_engine_result_carries_plan():
    from repro.core.engine import run_batch

    out = run_batch([_spec(steps=3)], backend="jax")
    assert out.plan is not None
    assert out.plan.data_plane == "stream"
    assert "ExecutionPlan[backend=jax" in out.plan.explain()


# ---------------------------------------------------------------------------
# gram data plane: auto gate, explicit request, demotion warning
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenarios_grid_stays_on_stream_plane(name):
    # every committed scenario runs at the default tiny d=8 < 4*I, so
    # the auto gate must leave the grid's paths exactly as they were
    # before the gram plane existed
    plan = resolve_plan(SCENARIOS[name].expand())
    assert plan.data_plane == "stream"
    assert plan.data_plane_requested is None
    assert plan.data_plane_reason            # the "why not" is recorded


def test_auto_gram_engages_at_large_d():
    plan = resolve_plan([_spec(n_data=64, d=4096)])
    assert plan.data_plane == "gram"
    text = plan.explain()
    assert "gram — shared problem" in text
    assert "I=66" in plan.data_plane_reason


def test_auto_gram_size_gate_keeps_stream():
    plan = resolve_plan([_spec(n_data=64, d=64)])
    assert plan.data_plane == "stream"
    assert "d=64 < 4*I=264" in plan.data_plane_reason


def test_auto_gram_keeps_stream_under_device_control():
    plan = resolve_plan([_spec(n_data=64, d=4096)], schedule="device")
    assert plan.data_plane == "stream"
    assert "coin-flip sliver" in plan.data_plane_reason


def test_explicit_gram_waives_auto_gates():
    # size gate (default d=8) and device control are auto-only gates
    plan = resolve_plan([_spec()], data_plane="gram")
    assert plan.data_plane == "gram"
    plan = resolve_plan([_spec()], data_plane="gram", schedule="device")
    assert (plan.data_plane, plan.control) == ("gram", "device")


def test_explicit_gram_demotion_warns():
    plan = resolve_plan([_spec(mode="filter:median")], data_plane="gram")
    assert plan.data_plane == "stream"
    assert "filter baseline" in plan.data_plane_reason
    with pytest.warns(PlanFallbackWarning, match="filter baseline"):
        warn_on_fallback(plan)
    text = plan.explain()
    assert "stream — not gram:" in text


def test_explicit_gram_zero_steps_never_warns():
    plan = resolve_plan([_spec(steps=0)], data_plane="gram")
    assert plan.data_plane == "stream"
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        warn_on_fallback(plan)


def test_unknown_data_plane_rejected():
    with pytest.raises(ValueError, match="unknown data_plane"):
        resolve_plan([_spec()], data_plane="coefficients")


def test_engine_emits_plan_fallback_warning_on_gram_demotion():
    from repro.core.engine import run_batch

    specs = [dataclasses.replace(_spec(), steps=3, mode="filter:median")]
    with pytest.warns(PlanFallbackWarning, match="filter baseline"):
        out = run_batch(specs, backend="jax", data_plane="gram")
    assert out.plan.data_plane == "stream"
    assert out.plan.data_plane_requested == "gram"


# ---------------------------------------------------------------------------
# layering: engineplan never imports the engines
# ---------------------------------------------------------------------------


def test_engineplan_import_ban():
    pkg = (pathlib.Path(__file__).resolve().parents[1]
           / "src" / "repro" / "core" / "engineplan")
    banned = ("repro.core.engine", "repro.core.engine_jax")
    for path in sorted(pkg.glob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            for m in mods:
                assert not any(m == b or m.startswith(b + ".")
                               for b in banned), \
                    f"{path.name} imports {m}: the plan layer sits " \
                    f"below the engines"
