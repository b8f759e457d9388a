"""Layered execution-plan package for the jax engine backend.

Layer diagram (see docs/architecture.md)::

    plan      resolve_plan(specs, ...) -> ExecutionPlan   (pure, no jax)
      |
    stepcore  step_core(...)  one parameterized lax.scan step
      |                       (control / gram statics pick the planes)
    shard     shard_wrap(plan, mesh, ...)  one shard_map builder
      |
    pipeline  run_chunks(...)  chunked async H2D/donation pipeline

``repro.core.engine_jax.run_batch_jax`` is the compose-and-dispatch
facade over these four layers; ``repro.core.engine`` re-exports the
schedulability predicates defined in :mod:`.plan`.

Import contract: nothing in this package imports ``repro.core.engine``
or ``repro.core.engine_jax`` (the engines sit ABOVE the plan layer) —
enforced by ruff's banned-import rule (pyproject.toml) and by
tests/test_execution_plan.py.
"""
from repro.core.engineplan.plan import (  # noqa: F401
    AFFINE_ATTACKS,
    CHUNK_ELEMS,
    FILTER_CODES,
    VALUE_INDEPENDENT_ATTACKS,
    ExecutionPlan,
    PlanFallbackWarning,
    device_schedulable,
    filter_name,
    is_adaptive,
    nearest_schedule,
    resolve_plan,
    resolve_schedule_mode,
    spec_display_names,
    validate_specs,
    value_independent_control,
    warn_on_fallback,
)
