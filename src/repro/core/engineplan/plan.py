"""ExecutionPlan: the engine's path selection as an inspectable value.

``run_batch(backend="jax")`` picks its execution path — host vs
on-device control plane, gram vs stream data plane, sharded vs
single-device, chunk size — once, up front, into a frozen
:class:`ExecutionPlan`:

* :func:`resolve_plan` is pure — specs plus keyword knobs in, plan out —
  so every path decision is unit-testable without touching a device
  (tests/test_execution_plan.py covers the full SCENARIOS grid);
* :meth:`ExecutionPlan.explain` names which path was picked and *why*,
  including the reason a requested gram run demoted
  (:attr:`ExecutionPlan.data_plane_reason`, surfaced as a
  :class:`PlanFallbackWarning` by the engine facade);
* the schedulability predicates (``value_independent_control``,
  ``device_schedulable``) and the affine-attack / filter tables live
  here as the single source of truth — ``repro.core.engine`` and
  ``repro.core.engine_jax`` re-export them.

Layering contract (enforced by ruff's banned-import rule and
tests/test_execution_plan.py): ``engineplan`` never imports
``repro.core.engine`` or ``repro.core.engine_jax`` — the plan layer is
below the engines, which import *it*.  The predicates are duck-typed
over any object with TrialSpec's fields, which is what keeps this
module import-free of the engine.
"""
from __future__ import annotations

import dataclasses

from repro.obs import oblog

# affine attack table: g' = alpha * g + beta * 1 + nu * noisevec, where
# noisevec is ATTACKS["noise"]'s fixed default_rng(0) draw.  Mirrors
# repro.core.simulation.ATTACKS exactly.
AFFINE_ATTACKS: dict[str, tuple[float, float, float]] = {
    "none": (1.0, 0.0, 0.0),
    "sign_flip": (-5.0, 0.0, 0.0),
    "scale": (10.0, 0.0, 0.0),
    "drift": (1.0, 1.0, 0.0),
    "zero": (0.0, 0.0, 0.0),
    "noise": (1.0, 0.0, 1.0),
}

# attacks whose detectability never depends on gradient magnitudes: they
# perturb by a fixed nonzero offset ("drift", "noise") or never perturb
# ("none"), so WHO gets caught is a pure function of the tamper/assignment
# coin flips.  "sign_flip"/"scale"/"zero" scale the gradient itself and
# become undetectable exactly at the convergence floor.
VALUE_INDEPENDENT_ATTACKS = frozenset({"none", "drift", "noise"})

FILTER_CODES = {"mean": 0, "median": 1, "krum": 2}

HOST_SCHEDULE_MODES = ("auto", "vector", "proxy", "oracle")

# element budget for sizing trials-per-device-chunk: the scan's largest
# live array is ~4 (B, d) buffers (W + update terms), or the (B, n, d)
# gradient stack when filter trials force it — either way the chunk is
# chosen to keep ~1 GiB of f32 in flight
CHUNK_ELEMS = 1 << 27


# auto-gate for the gram data plane: carrying (B, Ie) coefficients only
# pays off once the iterate is comfortably larger than the coefficient
# row — below this ratio the post-scan contraction plus the precompute
# pass cost as much as the stream scan they replace
GRAM_MIN_D_RATIO = 4


class PlanFallbackWarning(UserWarning):
    """A requested execution path was demoted by the plan's eligibility
    gates; the message (and the matching ``ExecutionPlan`` reason field)
    says why.  Filter with ``warnings.filterwarnings`` by this
    category."""


# ---------------------------------------------------------------------------
# Schedulability predicates (duck-typed over TrialSpec-shaped objects)
# ---------------------------------------------------------------------------


def filter_name(spec) -> str | None:
    """The gradient-filter baseline name, or None for protocol trials."""
    if not spec.mode.startswith("filter"):
        return None
    return spec.mode.split(":", 1)[1] if ":" in spec.mode else spec.filter_name


def is_adaptive(spec) -> bool:
    """Adaptive q*_t: randomized mode with no fixed check probability."""
    return spec.q is None and spec.mode == "randomized"


def value_independent_control(spec) -> bool:
    """True when the trial's control flow (check decisions, detection
    outcomes, identified sets) does not depend on gradient values, i.e.
    the schedule can be replayed without running the data plane at all
    (the proxy and vector schedules)."""
    if spec.q is None and spec.mode == "randomized":
        return False          # adaptive q*_t depends on the observed loss
    if not spec.byz:
        return True           # nothing ever tampers -> nothing to detect
    if spec.mode in ("none",) or spec.mode.startswith("filter"):
        return True           # no detection phase at all
    return isinstance(spec.attack, str) \
        and spec.attack in VALUE_INDEPENDENT_ATTACKS


def device_schedulable(spec) -> bool:
    """True when the trial's control plane can run INSIDE the jitted
    device scan (``schedule="device"``) under the ``rng="device"``
    stream contract: affine attacks, plain none/deterministic/randomized
    modes (adaptive q* included — that's the point), no selective
    checks, no crash/recover events, no filters, no draco.
    Value-DEPENDENT classes are fine; what's excluded is control flow
    the scan cannot express (per-worker selective coins, membership
    churn injected from outside)."""
    if not isinstance(spec.attack, str):
        return False
    return (spec.attack in AFFINE_ATTACKS
            and spec.mode in ("none", "deterministic", "randomized")
            and not spec.selective
            and not spec.events)


def spec_display_names(specs, flags) -> list[str]:
    """Human-readable names for the specs where ``flags`` is truthy —
    the label when one was given, otherwise a descriptive
    ``spec[i](mode/attack...)`` so error messages never degenerate to
    bare indices."""
    out = []
    for i, (s, bad) in enumerate(zip(specs, flags)):
        if not bad:
            continue
        if s.label:
            out.append(s.label)
        else:
            q = "adaptive" if s.q is None else f"q={s.q}"
            out.append(f"spec[{i}]({s.mode}/{s.attack}/{q})")
    return out


def nearest_schedule(specs) -> str:
    """The least-degraded schedule mode that accepts every spec in the
    batch: "device" keeps the control plane on device (valid when every
    trial is device-schedulable), else "oracle" — the host replay that
    accepts every engine trial class."""
    return "device" if all(device_schedulable(s) for s in specs) \
        else "oracle"


# ---------------------------------------------------------------------------
# Validation (shared by resolve_plan and the engine facade)
# ---------------------------------------------------------------------------


def validate_specs(specs) -> None:
    """Reject batches the jax data plane cannot represent, naming the
    offending specs and the nearest plan that would accept them."""
    dims = {(s.n_data, s.d) for s in specs}
    if len(dims) > 1:
        # same contract as the numpy backend (engine.run_batch): a batch
        # must share problem dimensions — catching it here replaces an
        # opaque broadcast error in the (B, n_data, d) copy loop
        counts = {dm: sum(1 for s in specs if (s.n_data, s.d) == dm)
                  for dm in dims}
        major = max(counts, key=counts.get)
        flags = [(s.n_data, s.d) != major for s in specs]
        raise ValueError(
            f"trials must share (n_data, d), got {sorted(dims)}; "
            f"offending: {spec_display_names(specs, flags)} — nearest "
            f"accepting plan: one run_batch call per (n_data, d) group")
    for i, s in enumerate(specs):
        if not isinstance(s.attack, str) or s.attack not in AFFINE_ATTACKS:
            raise NotImplementedError(
                f"jax backend supports the affine attack table "
                f"{sorted(AFFINE_ATTACKS)}, got {s.attack!r} "
                f"({spec_display_names(specs, [j == i for j in range(len(specs))])[0]}) "
                f'— nearest accepting plan: backend="numpy" (the '
                f"reference engine runs arbitrary attack callables)")
        name = filter_name(s)
        if name is not None and name not in FILTER_CODES:
            raise NotImplementedError(
                f"jax backend supports filters {sorted(FILTER_CODES)}, "
                f"got {name!r} "
                f"({spec_display_names(specs, [j == i for j in range(len(specs))])[0]}) "
                f'— nearest accepting plan: backend="numpy"')


def resolve_schedule_mode(specs, mode: str, *, host_only: bool = False) -> str:
    """Resolve/validate the schedule mode for a batch.

    Returns the concrete mode ("vector" | "proxy" | "oracle" |
    "device"); raises ValueError naming the offending specs AND the
    nearest plan that would accept them.  ``host_only=True`` is
    ``build_schedule``'s contract (mode "device" is not a host
    schedule — it is handled by the engine facade itself)."""
    if mode == "device" and not host_only:
        flags = [not device_schedulable(s) for s in specs]
        if any(flags):
            raise ValueError(
                'schedule="device" needs device-schedulable trials '
                "(affine string attacks, mode none/deterministic/"
                "randomized, no selective checks or membership events); "
                f"offending: {spec_display_names(specs, flags)}; nearest "
                'accepting plan: schedule="oracle" (the host replay '
                "accepts every engine trial class)")
        return "device"
    eligible = all(value_independent_control(s) for s in specs)
    if mode == "auto":
        return "vector" if eligible else "oracle"
    if mode in ("proxy", "vector"):
        if not eligible:
            flags = [not value_independent_control(s) for s in specs]
            offending = [s for s, bad in zip(specs, flags) if bad]
            raise ValueError(
                f"{mode} schedule invalid for value-dependent trials: "
                f"{spec_display_names(specs, flags)} — use "
                'schedule="device" (on-device control plane) or '
                '"oracle" for these; nearest accepting plan: '
                f'schedule="{nearest_schedule(offending)}"')
        return mode
    if mode == "oracle":
        return "oracle"
    raise ValueError(
        f"unknown schedule mode {mode!r} (build_schedule handles "
        f"host modes auto/vector/proxy/oracle; \"device\" lives in "
        f"run_batch_jax)")


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Every path decision of one jax-backend batch, resolved up front:
    ``result.plan`` carries the whole picture and
    ``result.plan.explain()`` says why."""

    backend: str                 # "jax"
    schedule_mode: str           # "vector" | "proxy" | "oracle" | "device"
    control: str                 # "host" | "device"
    shared_problem: bool         # one (problem_seed, n_data, d) for all
    has_filter: bool             # gradient-filter baselines in the batch
    has_bias: bool               # some attack has nonzero beta/nu terms
    sharded: bool                # shard_map over the ("trials",) mesh
    n_devices: int               # mesh size (1 when unsharded)
    chunk_trials: int            # trials per device pass (mesh-rounded)
    kernel_impl: str | None      # resolved batched-kernel dispatch
    n_trials: int                # batch size B
    steps: int                   # scan length T (max steps over specs)
    data_plane: str = "stream"   # "gram" | "stream" (the scan's domain)
    data_plane_requested: str | None = None  # explicit; None = auto
    data_plane_reason: str = ""  # why gram engaged / why it could not
    telemetry: bool = False      # thread protocol counters through scan

    def explain(self) -> str:
        """Human-readable account of which path was picked and why."""
        sched_why = {
            "vector": "all trials value-independent -> batched "
                      "control-only replay (no data plane)",
            "proxy": "tiny-problem full-engine replay (parity oracle "
                     "for \"vector\")",
            "oracle": "value-dependent trials present -> real-problem "
                      "host replay",
            "device": "control plane inside the jitted scan "
                      "(rng=\"device\" counter streams)",
        }[self.schedule_mode]
        if self.data_plane == "gram":
            data_line = f"gram — {self.data_plane_reason}"
        elif self.data_plane_reason:
            data_line = f"stream — not gram: {self.data_plane_reason}"
        else:
            data_line = "stream"
        if self.sharded:
            shard_line = (f"shard_map over a {self.n_devices}-device "
                          f"(\"trials\",) mesh")
        else:
            shard_line = "single device (plain jit)"
        return "\n".join([
            f"ExecutionPlan[backend={self.backend}, B={self.n_trials}, "
            f"T={self.steps}]",
            f"  schedule : {self.schedule_mode} ({self.control} control "
            f"plane) — {sched_why}",
            f"  data     : {data_line}",
            f"  sharding : {shard_line}, chunk={self.chunk_trials} "
            f"trials/pass",
            f"  kernels  : impl={self.kernel_impl}, "
            f"bias_terms={'yes' if self.has_bias else 'no'}, "
            f"filters={'yes' if self.has_filter else 'no'}",
        ])


def resolve_plan(specs, *, schedule: str = "auto",
                 n_devices: int | None = None,
                 chunk_trials: int | None = None,
                 kernel_impl: str | None = None,
                 n_max: int | None = None,
                 data_plane: str | None = None,
                 telemetry: bool = False) -> ExecutionPlan:
    """Resolve one batch's execution plan.  Pure: specs + knobs in,
    :class:`ExecutionPlan` out — no devices touched, so path selection
    is unit-testable for every spec class.

    ``n_devices``: mesh size, or None for the single-device jit path.
    ``n_max``: worker-axis width used for filter-chunk sizing; defaults
    to ``max(s.n)``.
    ``data_plane``: None = auto (gram whenever eligible AND d is large
    enough to pay for the precompute), "gram" = explicit request (size
    and control-plane auto-gates waived; hard eligibility still applies
    and demotion warns with :class:`PlanFallbackWarning`), "stream" =
    the classic (B, d)-carry scan.
    """
    specs = list(specs)
    if not specs:
        raise ValueError("resolve_plan needs at least one TrialSpec")
    if data_plane not in (None, "stream", "gram"):
        raise ValueError(
            f"unknown data_plane {data_plane!r}; allowed values: "
            f"'gram', 'stream' (or None for the auto choice)")
    validate_specs(specs)
    mode = resolve_schedule_mode(specs, schedule)
    control = "device" if mode == "device" else "host"

    B = len(specs)
    d = specs[0].d
    steps = max(s.steps for s in specs)
    if n_max is None:
        n_max = max(s.n for s in specs)
    shared = len({(s.problem_seed, s.n_data, s.d) for s in specs}) == 1
    # the device control plane never compiles the filter branch
    # (device_schedulable excludes filter modes)
    has_filter = control == "host" \
        and any(FILTER_CODES.get(filter_name(s), -1) >= 0 for s in specs)
    has_bias = any(AFFINE_ATTACKS[s.attack][1] != 0.0
                   or AFFINE_ATTACKS[s.attack][2] != 0.0 for s in specs)

    # gram data-plane gate: the scan can carry (B, Ie) residual
    # coefficients instead of the (B, d) iterate exactly when the whole
    # update is one shared contraction — shared problem, affine attacks
    # only, no gradient-filter baselines.  Auto additionally requires
    # host control (the device plane's q*/check coins read the loss,
    # and the gram-domain loss rounds differently in f32 — explicit
    # data_plane="gram" accepts that documented sliver) and d large
    # enough to amortize the precompute.
    Ie = specs[0].n_data + 2
    auto_plane = data_plane is None
    use_gram = False
    if data_plane == "stream":
        gram_reason = 'data_plane="stream" requested'
    elif steps == 0:
        gram_reason = "all trials have steps == 0: nothing to scan"
    elif not shared:
        n_prob = len({(s.problem_seed, s.n_data, s.d) for s in specs})
        gram_reason = (
            f"trials span {n_prob} distinct problems; the gram factors "
            f"G = R R^T are per-problem, so the coefficient recurrence "
            f"needs ONE shared extended matrix")
    elif has_filter:
        flags = [FILTER_CODES.get(filter_name(s), -1) >= 0 for s in specs]
        gram_reason = (
            f"filter baseline trials ({spec_display_names(specs, flags)}) "
            f"materialize the (B, n, d) gradient stack every step — "
            f"there is no coefficient-only form")
    elif auto_plane and control == "device":
        gram_reason = (
            'auto keeps the stream plane under schedule="device": the '
            "q*/check coins read the loss, and the gram-domain loss "
            'rounds differently in f32 — pass data_plane="gram" to '
            "accept the documented coin-flip sliver")
    elif auto_plane and d < GRAM_MIN_D_RATIO * Ie:
        gram_reason = (
            f"d={d} < {GRAM_MIN_D_RATIO}*I={GRAM_MIN_D_RATIO * Ie}: the "
            f"(B, I) coefficient carry would not beat the (B, d) "
            f"iterate, so the stream plane wins")
    else:
        use_gram = True
        gram_reason = (
            f"shared problem, affine attacks, no filter baselines, "
            f"{control} control — the scan carries (B, I={Ie}) "
            f"coefficients; d={d} is touched once before the scan "
            f"(gram precompute) and once after (W_T contraction)")

    # chunk sizing: bound scan memory; only filter trials ever
    # materialize a (chunk, n, d) gradient stack
    ndev = n_devices if n_devices is not None else 1
    if chunk_trials is None:
        per_trial = n_max * d if has_filter else 4 * d
        chunk = max(1, min(B, (2 * CHUNK_ELEMS * ndev)
                           // max(1, per_trial)))
    elif chunk_trials < 1:
        raise ValueError(f"chunk_trials must be >= 1, got {chunk_trials}")
    else:
        chunk = int(chunk_trials)
    if n_devices is not None:
        chunk = -(-chunk // ndev) * ndev

    return ExecutionPlan(
        backend="jax", schedule_mode=mode, control=control,
        shared_problem=shared, has_filter=has_filter, has_bias=has_bias,
        sharded=n_devices is not None, n_devices=ndev,
        chunk_trials=chunk, kernel_impl=kernel_impl, n_trials=B,
        steps=steps,
        data_plane="gram" if use_gram else "stream",
        data_plane_requested=data_plane, data_plane_reason=gram_reason,
        telemetry=telemetry,
    )


def warn_on_fallback(plan: ExecutionPlan, stacklevel: int = 3) -> None:
    """Emit a :class:`PlanFallbackWarning` when an explicitly requested
    gram data plane was demoted to the stream scan.  Zero-step batches
    never warn — there is no scan at all.

    Routed through :func:`repro.obs.oblog.warn_once`: one warning per
    distinct fallback reason per process (a sweep used to repeat it on
    every ``run_batch`` call); tests re-arm via
    ``oblog.reset_warn_once()``."""
    if plan.data_plane_requested == "gram" \
            and plan.data_plane != "gram" and plan.steps > 0:
        oblog.warn_once(
            f'data_plane="gram" requested but the plan fell back to the '
            f"stream scan: {plan.data_plane_reason} "
            f"(see BatchResult.plan.explain())",
            PlanFallbackWarning,
            key=("gram_fallback", plan.data_plane_reason),
            stacklevel=stacklevel)
