"""Jitted on-device engine backend: the whole protocol loop as ONE
``lax.scan`` over the batched per-iteration step.

``run_batch(specs, backend="jax")`` lands here.  The numpy engine
(repro.core.engine) stays the parity oracle; this backend splits the
protocol into

 * a **control plane** on the host producing dense per-step schedule
   arrays — check decisions, assignment layouts, tamper hits (both
   phases), identify events and their 2f+1 assignments, aggregation
   weights, live/active masks.  Control flow for the paper's fixed-q
   protocol classes is *value-independent* (detection outcomes depend
   only on WHO tampered, not on gradient magnitudes, for
   always-detectable attacks), so the schedule comes from the
   vectorized control-only replay (engine.replay_control_fast, mode
   "vector").  Value-dependent classes replay on the real problem
   instead ("oracle" schedule), or fuse the control plane into the scan
   itself (``schedule="device"``);

 * a **data plane** on device: one parameterized scan step
   (repro.core.engineplan.stepcore) recomputing every float quantity
   with NO host synchronization inside the scan.  Honest replicas are
   copies and every attack is affine, so the whole "shard gradients →
   tamper → aggregate/vote" pipeline folds algebraically into per-row
   residual coefficients; detection and vote agreement run on k-dim
   CountSketch symbols.  The trial batch shards over a 1-D
   ``("trials",)`` device mesh via shard_map and chunks stream through
   an async donated-buffer pipeline.

This module is the thin compose-and-dispatch **facade** over the
layered ``repro.core.engineplan`` package (see docs/architecture.md):

    plan      resolve_plan(specs, ...) -> ExecutionPlan  (pure)
    stepcore  step_core(...)       one parameterized lax.scan step
    shard     shard_wrap(plan, mesh, ...)   one shard_map builder
    pipeline  run_chunks(...)      chunked async H2D pipeline

``run_batch_jax`` resolves the plan once, prepares host arrays, picks
the jitted/sharded step core, streams the chunks, and assembles the
``BatchResult`` — whose ``plan`` attribute reports (and ``explain()``s)
every path decision, including why a requested gram run demoted
(``PlanFallbackWarning``).

Parity contract (tests/test_engine_parity.py, docs/performance.md):
control quantities — efficiency counters, check/identify schedules,
identified sets, q-traces — match the numpy engine EXACTLY; float
quantities (losses, iterates, final error) match to float32 tolerance
(the device plane computes in f32; the numpy engine in f64), asserted
at atol/rtol documented in the tests.

Engine-only extras supported: late onset, crash/recover events,
selective checks, filter baselines (mean / median / krum), draco.
Custom attack callables and non-affine attacks are not representable
on device and raise ``NotImplementedError``.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import rngstream
from repro.core.engine import (
    BatchResult,
    ScheduleRecorder,
    TrialSpec,
    replay_control_fast,
    replay_control_from_trace,
    run_batch,
)
from repro.core.engineplan import plan as planlib
from repro.core.engineplan.pipeline import run_chunks
from repro.core.engineplan.plan import (
    AFFINE_ATTACKS,            # noqa: F401  (public: tests import it here)
    ExecutionPlan,             # noqa: F401  (public re-export)
    PlanFallbackWarning,       # noqa: F401  (public re-export)
    device_schedulable,        # noqa: F401  (public re-export)
    resolve_plan,
)
from repro.core.engineplan.shard import shard_wrap
from repro.core.engineplan.stepcore import (
    TAU_DETECT,                # noqa: F401  (public re-export)
    TAU_VOTE,                  # noqa: F401  (public re-export)
    jitted_step_core,
)
from repro.core.simulation import make_problem
from repro.obs import metrics as obmetrics, trace as obtrace
from repro.obs.telemetry import Telemetry

_PROXY_N_DATA = 64
_PROXY_D = 4


# ---------------------------------------------------------------------------
# Control plane: record the numpy engine's per-step schedule
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Schedule:
    """Stacked (T, B, ...) control arrays + the control-plane results."""

    arrays: dict[str, np.ndarray]
    control: BatchResult
    used_proxy: bool
    mode: str = "oracle"


def build_schedule(specs: list[TrialSpec], mode: str = "auto") -> Schedule:
    """Replay the numpy engine's control machinery into dense arrays.

    mode: "vector" runs the batched control-only replay
    (engine.replay_control_fast) — no data plane at all, the fast path
    for fixed-q value-independent trial classes; "proxy" forces the
    tiny-problem full-engine replay (same schedule, kept as the parity
    oracle for "vector"); "oracle" forces the real-problem replay (a
    full numpy-engine pass — valid for every trial class, but the
    replay then costs the thing it schedules; on a large shared problem
    it runs the data products as GEMMs over the trials, since only its
    control is read); "auto" picks "vector"
    whenever valid.  Mode "device" is not a host schedule: it is
    handled by ``run_batch_jax`` itself (the decisions come back from
    the on-device control plane and this host machinery replays *from
    that trace* — see ``engine.replay_control_from_trace``).

    Mode resolution and eligibility errors route through the plan
    layer (``engineplan.resolve_schedule_mode``), so unschedulable
    specs are named alongside the nearest plan that would accept them.
    """
    mode = planlib.resolve_schedule_mode(specs, mode, host_only=True)

    rec = ScheduleRecorder()
    if mode == "vector":
        with obtrace.span("schedule.replay"):
            control = replay_control_fast(specs, rec)
    else:
        if mode == "proxy":
            n_data = max(_PROXY_N_DATA, 2 * max(s.n for s in specs))
            ctrl_specs = [dataclasses.replace(s, n_data=n_data, d=_PROXY_D)
                          for s in specs]
        else:
            ctrl_specs = specs
        # the device recomputes every value: only this pass's control is
        # read, so its shared-problem products may run as GEMMs
        with obtrace.span("schedule.oracle", mode=mode):
            control = run_batch(ctrl_specs, _recorder=rec,
                                _control_only=True)
    with obtrace.span("schedule.stack"):
        keys = rec.steps[0].keys() if rec.steps else ()
        arrays = {k: np.stack([st[k] for st in rec.steps]) for k in keys}
    return Schedule(arrays, control, mode != "oracle", mode)


# ---------------------------------------------------------------------------
# Public entry point: compose plan -> stepcore -> shard -> pipeline
# ---------------------------------------------------------------------------


def run_batch_jax(specs, *, schedule: str = "auto",
                  kernel_impl: str | None = None,
                  chunk_trials: int | None = None,
                  mesh="auto", data_plane: str | None = None,
                  telemetry: bool = False) -> BatchResult:
    """Run B protocol trials with the jitted on-device data plane.

    schedule: "auto" | "vector" | "proxy" | "oracle" (host control
        plane; see ``build_schedule``) | "device" (control plane run
        inside the scan — the only non-oracle option for value-dependent
        classes like adaptive q*_t; requires
        ``engine.device_schedulable`` trials and uses the
        ``rng="device"`` counter-RNG streams, so its parity oracle is
        ``run_batch(specs, rng="device")``, not the default host
        streams).
    kernel_impl: None (auto: Pallas on TPU, XLA elsewhere) | "pallas" |
        "xla" — forwarded to the batched kernel ops.
    data_plane: None | "gram" | "stream" — the scan's domain.  "gram"
        precomputes the Gram factors once (``ops.gram_factors``: G =
        R R^T, the per-step sketch tables) and scans (B, I) residual
        coefficients instead of the (B, d) iterate — NO d-sized work
        per step; d is touched once before the scan and once after
        (the W_T contraction).  ``None`` (default) auto-engages gram
        on eligible host-control shared-problem batches once d >=
        ``planlib.GRAM_MIN_D_RATIO`` * I; an explicit ``"gram"``
        waives the size/control auto-gates (demotion on hard
        ineligibility warns ``PlanFallbackWarning``).  Detection
        symbols use the same precomputed sketch tables with identical
        arithmetic, so detection verdicts match the stream plane
        bit-for-bit; iterates/losses match at the documented f32
        tolerances.
    telemetry: thread the protocol-counters pytree through the scan
        carry (see :mod:`repro.obs.telemetry`) and return it as
        ``BatchResult.telemetry``.  Opt-in and output-neutral: the
        primary outputs are bitwise identical with it on, sharded runs
        accumulate inside the per-trial shard (no new collectives), and
        the counters are integer-identical to the numpy oracle's.
    chunk_trials: trials per device pass (default: memory-sized; only
        filter trials materialize a (chunk, n, d) gradient stack).
        Rounded up to a multiple of the mesh size; the last chunk is
        padded with inert trials and the padding sliced off the results.
    mesh: "auto" shards the trial batch over all local devices
        (repro.sharding.trials_mesh 1-D "trials" mesh; single-device
        hosts fall back to plain jit); None forces single-device; or an
        explicit 1-D Mesh whose axis is named "trials".

    Chunks flow through an async pipeline: each chunk's schedule arrays
    are device_put (H2D) while the previous chunk's scan is still
    executing, and nothing synchronizes with the host until every chunk
    has been dispatched.

    The returned ``BatchResult`` additionally carries ``plan`` (the
    resolved :class:`~repro.core.engineplan.plan.ExecutionPlan`),
    ``schedule`` (the control plane) and ``detect_flags`` (T, B) — the
    scan's on-device sketch-detection verdicts per iteration, validated
    against the schedule's check outcomes in
    tests/test_engine_parity.py.  Under ``schedule="device"`` it also
    carries ``device_trace``, the raw per-step decision trace
    (q / check / detect / faulty2 arrays) the host control replay was
    reconstructed from; host modes set it to ``None``.
    """
    from repro.kernels import ops

    t_start = time.perf_counter()
    specs = [s if isinstance(s, TrialSpec) else TrialSpec(**s) for s in specs]
    if not specs:
        return BatchResult([], [], 0.0)
    # resolve once: the choice becomes a jit-cache key for the step
    # core, so a mid-process REPRO_KERNEL_IMPL change must not split
    # the run
    kernel_impl = ops.resolve_impl(kernel_impl)
    # early pure validation (problem dims, attack/filter tables,
    # schedule-mode eligibility) — resolve_plan re-checks these for free
    # once the mesh is known
    planlib.validate_specs(specs)
    mode = planlib.resolve_schedule_mode(specs, schedule)
    device_mode = mode == "device"
    B = len(specs)
    if device_mode:
        sched = None
        T = max(s.steps for s in specs)
        n_max = max(s.n for s in specs)
    else:
        with obtrace.span("engine.build_schedule", mode=mode, B=B):
            sched = build_schedule(specs, schedule)
        T = len(sched.arrays["live"]) if sched.arrays else 0
        n_max = sched.arrays["shard1"].shape[2] if sched.arrays else 0
    if T == 0:
        # every trial has steps == 0: nothing to scan, and a proxy
        # control pass would carry proxy-problem iterates — rerun the
        # numpy engine on the real specs (free at zero steps), keeping
        # the documented jax-backend extras attached (empty here)
        out = run_batch(specs, telemetry=telemetry)
        out.detect_flags = np.zeros((0, B), bool)
        out.plan = resolve_plan(
            specs, schedule=schedule, kernel_impl=kernel_impl,
            data_plane=data_plane, telemetry=telemetry)
        if device_mode:
            trace = dict(q=np.zeros((0, B), np.float32),
                         check=np.zeros((0, B), bool),
                         detect=np.zeros((0, B), bool),
                         faulty2=np.zeros((0, B, n_max), bool))
            control = replay_control_from_trace(specs, trace)
            out.device_trace = trace
            out.schedule = Schedule({}, control, True, "device")
        else:
            out.device_trace = None
            out.schedule = sched
        return out

    # -- trials mesh: shard the batch dimension across local devices ------
    if isinstance(mesh, str):
        if mesh != "auto":
            raise ValueError(f"unknown mesh option {mesh!r}")
        from repro.sharding import trials_mesh

        mesh = trials_mesh()
    if mesh is not None and tuple(mesh.axis_names) != ("trials",):
        raise ValueError(
            f"engine mesh must be 1-D ('trials',), got {mesh.axis_names}")
    if mesh is not None:
        from repro.sharding import mesh_num_devices

        ndev = mesh_num_devices(mesh)
    else:
        ndev = None

    # -- resolve the execution plan (pure) and surface gram demotion ------
    with obtrace.span("engine.resolve_plan", B=B):
        plan = resolve_plan(specs, schedule=schedule,
                            n_devices=ndev, chunk_trials=chunk_trials,
                            kernel_impl=kernel_impl, n_max=n_max,
                            data_plane=data_plane, telemetry=telemetry)
        planlib.warn_on_fallback(plan)
    obmetrics.counter("engine.batches").inc()
    obmetrics.counter("engine.trials").inc(B)
    obmetrics.counter(f"engine.plan.{plan.data_plane}"
                      f".{plan.control}").inc()
    shared = plan.shared_problem

    with obtrace.span("engine.make_problem"):
        problems, pkeys, pid_np, A_np, y_np, w_true = _make_problems(
            specs, shared)
    d = A_np.shape[-1]
    with obtrace.span("engine.stage_problem"):
        scan_fn, operands = _stage_problem(
            specs, sched, plan, mesh, problems, pkeys, A_np, y_np, T=T,
            n_max=n_max, kernel_impl=kernel_impl, telemetry=telemetry)

    # -- async chunk pipeline (depth 1; see engineplan.pipeline) ----------
    with obtrace.span("engine.scan", B=B, T=T,
                      data_plane=plan.data_plane, control=plan.control):
        W, losses, det, extras = run_chunks(
            scan_fn, plan, B=B, T=T, d=d, n_max=n_max, mesh=mesh,
            A_np=A_np, y_np=y_np, pid_np=pid_np, **operands)
    tel_counts = extras.pop("telemetry") if telemetry else None

    with obtrace.span("engine.results"):
        # -- materialize results: control plane + device values ---------------
        from repro.core.simulation import SimResult

        trace = None
        if device_mode:
            # reconstruct the full host control plane from the decision
            # trace (exact — the streams are counter-indexed, so schedule,
            # meters and eliminations are pure functions of the trace)
            trace = dict(q=extras["q"], check=extras["check"],
                         detect=det.copy(), faulty2=extras["faulty2"])
            rec = ScheduleRecorder()
            control = replay_control_from_trace(specs, trace, rec)
            keys = rec.steps[0].keys() if rec.steps else ()
            arrays = {k: np.stack([st[k] for st in rec.steps]) for k in keys}
            sched = Schedule(arrays, control, True, "device")

        results = []
        for b, (s, ctrl) in enumerate(zip(specs, sched.control.results)):
            results.append(SimResult(
                w=W[b],
                w_true=w_true[b],
                state=ctrl.state,
                losses=losses[:s.steps, b].tolist(),
                q_trace=ctrl.q_trace,
                identify_step=ctrl.identify_step,
            ))
        tel_obj = None
        if telemetry:
            tel_obj = Telemetry.from_counts(
                tel_counts, specs=specs,
                q_traces=[r.q_trace for r in results])
            obmetrics.counter("engine.telemetry.steps").inc(
                tel_obj.totals()["steps"])
        out = BatchResult(specs, results, time.perf_counter() - t_start,
                          plan=plan, telemetry=tel_obj)

    out.detect_flags = det
    out.schedule = sched
    out.device_trace = trace
    return out


def _make_problems(specs: list[TrialSpec], shared: bool):
    """The call's problems, one draw per distinct (problem_seed, n_data,
    d), and their f32 host arrays: the one shared problem's, or one per
    trial.  Returns ``(problems, pkeys, pid_np, A_np, y_np, w_true)``."""
    B = len(specs)
    # -- real problem arrays (f32 device copies) -------------------------
    problems: dict[tuple, tuple] = {}
    for s in specs:
        key = (s.problem_seed, s.n_data, s.d)
        if key not in problems:
            problems[key] = make_problem(n_data=s.n_data, d=s.d,
                                         seed=s.problem_seed)
    pkeys = list(problems)
    pid_np = np.array([pkeys.index((s.problem_seed, s.n_data, s.d))
                       for s in specs], np.int32)
    first = problems[pkeys[0]]
    n_data, d = first[0].shape
    if shared:
        A_np = np.asarray(first[0], np.float32)
        y_np = np.asarray(first[1], np.float32)
        w_true = [first[2]] * B
    else:
        A_np = np.empty((B, n_data, d), np.float32)
        y_np = np.empty((B, n_data), np.float32)
        w_true = []
        for b, s in enumerate(specs):
            Ab, yb, wt = problems[(s.problem_seed, s.n_data, s.d)]
            A_np[b], y_np[b] = Ab, yb
            w_true.append(wt)
    return problems, pkeys, pid_np, A_np, y_np, w_true


def _stage_problem(specs: list[TrialSpec], sched, plan, mesh, problems,
                   pkeys, A_np, y_np, *, T: int, n_max: int,
                   kernel_impl: str, telemetry: bool):
    """Host staging of one call up to the scan: the per-trial statics,
    the scan xs of a host schedule, the data rows and their device
    precompute, the step core, and the chunk-invariant operands placed
    on the device.  Returns the step core and the keyword operands of
    :func:`run_chunks` that this staging decides."""
    from repro.kernels import ops

    B = len(specs)
    n_data, d = A_np.shape[-2:]
    device_mode = plan.control == "device"
    use_gram = plan.data_plane == "gram"
    shared = plan.shared_problem
    has_filter = plan.has_filter
    has_bias = plan.has_bias

    # -- per-trial statics ------------------------------------------------
    abn = np.array([AFFINE_ATTACKS[s.attack] for s in specs], np.float32)
    noisevec = (np.random.default_rng(0).normal(size=d).astype(np.float32)
                if (abn[:, 2] != 0).any() else np.zeros(d, np.float32))
    base_stat = dict(
        lr=np.array([s.lr for s in specs], np.float32),
        alpha=abn[:, 0].copy(), beta=abn[:, 1].copy(), nu=abn[:, 2].copy(),
    )
    if device_mode:
        byz = np.zeros((B, n_max), bool)
        act0 = np.zeros((B, n_max), bool)
        skeys = {k: np.zeros(B, np.uint32)
                 for k in ("dk0", "dk1", "tk0", "tk1", "pk0", "pk1")}
        for b, s in enumerate(specs):
            act0[b, :s.n] = True
            if s.byz:
                byz[b, list(s.byz)] = True
            for pre, tag in (("d", rngstream.DECIDE),
                             ("t", rngstream.TAMPER),
                             ("p", rngstream.PERM)):
                k0, k1 = rngstream.key_for(s.seed, tag)
                skeys[pre + "k0"][b] = k0
                skeys[pre + "k1"][b] = k1
        stat_np = dict(
            base_stat,
            p=np.array([s.p_tamper for s in specs], np.float32),
            qfix=np.array([0.0 if s.q is None else float(s.q)
                           for s in specs], np.float32),
            qcode=np.array([3 if planlib.is_adaptive(s) else
                            {"none": 0, "deterministic": 1,
                             "randomized": 2}[s.mode] for s in specs],
                           np.int32),
            f0=np.array([s.f for s in specs], np.int32),
            onset=np.array([s.onset for s in specs], np.int32),
            steps=np.array([s.steps for s in specs], np.int32),
            byz=byz, act0=act0, **skeys,
        )
        xs_np = None
    else:
        codes = planlib.FILTER_CODES
        fcode = np.array([codes.get(planlib.filter_name(s), -1)
                          for s in specs], np.int32)
        stat_np = dict(
            base_stat, fcode=fcode,
            farr=np.array([max(1, s.f) for s in specs], np.int32),
        )
        if telemetry:
            # the byz_active_steps counter needs the Byzantine mask,
            # which only the device control plane stages otherwise
            byz = np.zeros((B, n_max), bool)
            for b, s in enumerate(specs):
                if s.byz:
                    byz[b, list(s.byz)] = True
            stat_np["byz"] = byz

        # -- stacked schedule -> scan xs ----------------------------------
        a = sched.arrays
        xs_np = dict(
            live=a["live"], checks=a["checks"], vote1=a["vote1"],
            identify=a["identify"],
            m1=a["m1"].astype(np.int32), shard1=a["shard1"].astype(np.int32),
            group1=a["group1"].astype(np.int32),
            aggw=a["aggw"].astype(np.float32), tam1=a["tam1"],
            m2=a["m2"].astype(np.int32), shard2=a["shard2"].astype(np.int32),
            group2=a["group2"].astype(np.int32), tam2=a["tam2"],
            active=a["active"],
        )

    # -- pre-sketched data rows for in-scan detection symbols -------------
    # sketches are linear, so a worker's symbol is its residual-coefficient
    # row times the (per-step-keyed) sketches of the data rows: one
    # O(I * d) sketch pass per step HOISTED OUT of the scan replaces an
    # O(B * n * d) per-step gradient sketch inside it.
    P = len(pkeys)
    rows_np = np.empty((P * n_data + 2, d), np.float32)
    for p, key in enumerate(pkeys):
        rows_np[p * n_data:(p + 1) * n_data] = problems[key][0]
    rows_np[-2] = 1.0
    rows_np[-1] = noisevec
    keys_t = np.uint32(0x9E3779B9) * (np.arange(T, dtype=np.uint32) + 1)
    if use_gram:
        # ONE streaming precompute pass replaces both the hoisted
        # per-step pre-sketch AND all in-scan d-traffic: G = R R^T plus
        # every step's sketch table (S0 = W0 R^T is identically zero —
        # chunks start from W0 = 0, so the pipeline stages the zero
        # carry directly).  Gram plans are shared-problem by
        # construction, so rows_np is the single (n_data + 2, d)
        # extended matrix.
        rows_dev = jnp.asarray(rows_np)
        _, _, sk_rows = ops.gram_factors(rows_dev, None, keys_t,
                                         impl=kernel_impl)
        # form G itself on the host with f64 chunk accumulation: each G
        # entry is a length-d dot whose plain f32 accumulation error in
        # the device dot grows ~sqrt(d)*eps (~1e-4 relative at d = 2^20)
        # — and G feeds EVERY step's residual, so that error alone would
        # blow the 1e-4 value contract.  f32 sgemm per 64K-column chunk
        # (numpy's blocked sgemm keeps within-chunk error ~1e-7) with the
        # cross-chunk sum carried in f64 costs ~0.1s once, amortized
        # across all T steps.
        G64 = np.zeros((rows_np.shape[0],) * 2, np.float64)
        for lo in range(0, d, 1 << 16):
            blk = rows_np[:, lo:lo + (1 << 16)]
            G64 += (blk @ blk.T).astype(np.float64)
        G_dev = jnp.asarray(G64.astype(np.float32))
        common = {
            "SA": sk_rows[:, :n_data],
            "sk_one": sk_rows[:, n_data],
            "sk_noise": sk_rows[:, n_data + 1],
        }
        if device_mode:
            common["tix"] = jnp.arange(T, dtype=jnp.int32)
    else:
        rows_dev = jnp.asarray(rows_np)
        sk_rows = jnp.stack([
            ops.batched_sketch(rows_dev, keys_t[t], impl=kernel_impl)
            for t in range(T)
        ])                                           # (T, P*I + 2, k)
        common = {
            "SA": sk_rows[:, :P * n_data].reshape(T, P, n_data, -1),
            "sk_one": sk_rows[:, -2],
            "sk_noise": sk_rows[:, -1],
        }
        if device_mode:
            # the device control plane scans the step index alongside the
            # pre-sketched rows (its only per-step host input)
            common["tix"] = jnp.arange(T, dtype=jnp.int32)

    # -- step core (single jit or shard_map-wrapped) + placement of the
    #    chunk-invariant operands ----------------------------------------
    if mesh is None:
        scan_fn = functools.partial(
            jitted_step_core, gram=use_gram,
            control=plan.control, shared=shared, has_filter=has_filter,
            has_bias=has_bias, impl=kernel_impl,
            telemetry=plan.telemetry)
        # non-shared problems upload per-chunk slices in the pipeline —
        # a full (B, n_data, d) upfront copy would defeat the chunk
        # memory bound
        if use_gram:
            A_dev = {"rows": rows_dev, "G": G_dev}
        else:
            A_dev = jnp.asarray(A_np) if shared else None
        y_dev = jnp.asarray(y_np) if shared else None
        com_dev = common
        noise_dev = None if use_gram else jnp.asarray(noisevec)
        in_specs = None
    else:
        stat_sig = tuple((k, v.ndim) for k, v in sorted(stat_np.items()))
        com_sig = tuple((k, int(v.ndim)) for k, v in sorted(common.items()))
        xs_sig = (None if xs_np is None else
                  tuple((k, v.ndim) for k, v in sorted(xs_np.items())))
        scan_fn, in_specs = shard_wrap(
            plan, mesh, stat_sig=stat_sig, xs_sig=xs_sig,
            com_sig=com_sig, a_ndim=A_np.ndim)
        from jax.sharding import NamedSharding

        ns = lambda spec: NamedSharding(mesh, spec)              # noqa: E731
        put = lambda tree, spec: jax.device_put(                 # noqa: E731
            tree, jax.tree.map(ns, spec))
        if use_gram:
            A_dev = put({"rows": rows_dev, "G": G_dev}, in_specs[0])
        else:
            A_dev = put(A_np, in_specs[0]) if shared else None
        y_dev = put(y_np, in_specs[1]) if shared else None
        com_dev = put(common, in_specs[6])
        noise_dev = None if use_gram else put(noisevec, in_specs[7])
    return scan_fn, dict(in_specs=in_specs, A_dev=A_dev,
                         y_dev=y_dev, com_dev=com_dev, noise_dev=noise_dev,
                         stat_np=stat_np, xs_np=xs_np)
