"""Protocol-level benchmarks reproducing the paper's analytical results.

One function per paper table/figure/equation, all driven by the batched
scenario engine (repro.core.engine) — each sweep is ONE run_batch call
instead of a serial run_protocol loop per cell:

  efficiency_vs_q        eq. (2): measured E[efficiency] vs the lower bound
                         1 - q*2f/(2f+1), over a q grid  [Fig. 3 scheme]
  scheme_comparison      §2/§3: randomized vs deterministic vs DRACO vs
                         gradient filters vs unprotected — exactness,
                         efficiency, identification  [the paper's core table]
  identification_time    §4.2: empirical time-to-identification vs the
                         (1 - q p)^t almost-sure bound
  adaptive_trace         §4.3: λ_t/q_t* trajectory; boundary conditions
  engine_speedup         the engine's own acceptance bar: a 256-trial
                         scenario sweep in one call, >= 10x faster than
                         the equivalent serial run_protocol loop, with
                         per-trial results bitwise identical; plus the
                         numpy-engine -> jitted-jax-backend column at
                         production gradient dimensions (d sweep up to
                         2^20, 256 trials — target >= 3x at d >= 1M)
  fused_sweep            the fused data plane's acceptance bar: the
                         single-pass protocol-step megakernel
                         (fused=True) vs the three-pass scan body
                         (fused=False) at production d — >= 1.5x on
                         TPU / >= 1.2x off-TPU, parity enforced
  gram_sweep             the gram data plane's acceptance bar: the
                         coefficient-space scan (data_plane="gram")
                         vs the fused megakernel at production d,
                         long T — >= 5x warm at d = 2^20, control
                         bit-exact, values <= 1e-4 sup-norm
  schedule_build         control-plane column: vectorized control-only
                         replay vs full-engine proxy replay (>= 3x,
                         arrays identical)
  engine_devices         multi-device smoke: the sharded trials-mesh
                         path on the devices this process holds
  fig2_code              Fig. 2: linear detection code — detection works,
                         communication = 1/2 of replication's

Environment knobs for the backend sweep: REPRO_BENCH_TRIALS (default
256), REPRO_BENCH_DEXP (comma-separated log2 dimensions, default
"16,20"), REPRO_BENCH_STEPS (default 3 — the numpy engine needs
~3.5 min per step at d=2^20, B=256; shrink the knobs for quick runs).
REPRO_PROFILE=<dir> additionally wraps the warm timed runs in
``jax.profiler.trace(<dir>/<label>)`` for kernel/HBM inspection.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import adaptive
from repro.core.engine import ModeSpec, ScenarioMatrix, TrialSpec, run_batch
from repro.core.simulation import run_protocol
from repro.obs.trace import profile_trace

F, N = 2, 8


def _timeit(fn, reps=3):
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def efficiency_vs_q() -> list[tuple]:
    qs = (0.05, 0.1, 0.2, 0.3, 0.5, 0.8, 1.0)
    seeds = range(5)
    batch = run_batch([
        TrialSpec(byz=(2, 5), attack="sign_flip", steps=150, q=q, seed=s,
                  label=f"q{q}/s{s}")
        for q in qs for s in seeds
    ])
    by_q: dict[float, list] = {}
    for spec, r in zip(batch.specs, batch.results):
        by_q.setdefault(spec.q, []).append(r.efficiency)
    rows, detail = [], []
    for q in qs:
        measured = float(np.mean(by_q[q]))
        bound = adaptive.com_eff(q, F)
        detail.append({"q": q, "measured": measured, "bound_eq2": bound})
        # measured efficiency must sit ON/ABOVE the eq-2 lower bound
        # (elimination pushes it above once both byz workers are caught)
        rows.append((f"efficiency_vs_q[q={q}]", 0.0,
                     f"meas={measured:.4f};bound={bound:.4f}"))
    gaps = [d["measured"] - d["bound_eq2"] for d in detail]
    rows.append(("efficiency_vs_q[min_gap_above_bound]", 0.0,
                 f"{min(gaps):+.4f}"))
    _dump("efficiency_vs_q", detail)
    return rows


def scheme_comparison() -> list[tuple]:
    matrix = ScenarioMatrix(
        name="scheme_comparison",
        modes=(
            ModeSpec("none", "none"),
            ModeSpec("filter_median", "filter:median"),
            ModeSpec("filter_krum", "filter:krum"),
            ModeSpec("draco", "draco"),
            ModeSpec("deterministic", "deterministic"),
            ModeSpec("randomized_q0.2", "randomized", q=0.2),
            ModeSpec("adaptive", "randomized", q=None),
        ),
        seeds=(0, 1, 2),
        steps=300,
    )
    res = matrix.run()
    detail = [
        {**row, "scheme": row["scenario"].split("/", 1)[0]}
        for row in res.summarize()
    ]
    rows = []
    for d in detail:
        # per-scheme wall time is not separable out of one shared batch;
        # the batch-level rate is reported once below
        rows.append((
            f"scheme[{d['scheme']}]", 0.0,
            f"err={d['final_error']:.2e};eff={d['efficiency']:.3f};"
            f"kappa={d['identified']:.1f}",
        ))
    rows.append(("scheme[batch_us_per_trial_step]",
                 res.elapsed_s * 1e6 / (len(res) * matrix.steps),
                 f"{len(res)}trials x {matrix.steps}steps"))
    # headline claims
    eff = {d["scheme"]: d["efficiency"] for d in detail}
    rows.append(("scheme[det_vs_draco_eff_ratio]", 0.0,
                 f"{eff['deterministic'] / eff['draco']:.2f}"))
    rows.append(("scheme[rand_vs_draco_eff_ratio]", 0.0,
                 f"{eff['randomized_q0.2'] / eff['draco']:.2f}"))
    _dump("scheme_comparison", detail)
    return rows


def identification_time() -> list[tuple]:
    q, p = 0.3, 0.8
    batch = run_batch([
        TrialSpec(byz=(4,), attack="drift", steps=200, q=q, p_tamper=p,
                  seed=s) for s in range(20)
    ])
    times = np.asarray([r.identify_step.get(4, 200) for r in batch])
    # bound: P(unidentified after t) <= (1-qp)^t; median bound:
    t_med_bound = np.log(0.5) / np.log(1 - q * p)
    detail = {
        "times": times.tolist(),
        "median": float(np.median(times)),
        "p95": float(np.percentile(times, 95)),
        "median_bound": float(t_med_bound),
        "all_identified": bool((times < 200).all()),
    }
    _dump("identification_time", detail)
    return [
        ("ident_time[median]", 0.0,
         f"{detail['median']:.1f};bound={t_med_bound:.1f}"),
        ("ident_time[p95]", 0.0, f"{detail['p95']:.1f}"),
        ("ident_time[all_identified]", 0.0, str(detail["all_identified"])),
    ]


def adaptive_trace() -> list[tuple]:
    r = run_batch([TrialSpec(byz=(2, 5), attack="sign_flip", steps=300,
                             q=None, p_tamper=0.8)])[0]
    qt = np.asarray(r.q_trace)
    detail = {
        "q_first10": qt[:10].tolist(),
        "q_last10": qt[-10:].tolist(),
        "kappa": r.state.kappa,
        "final_error": r.final_error,
    }
    _dump("adaptive_trace", detail)
    return [
        ("adaptive[q_initial]", 0.0, f"{qt[0]:.3f}"),
        ("adaptive[q_final]", 0.0, f"{qt[-1]:.3f}"),  # 0 after κ=f (§4.3)
        ("adaptive[exact]", 0.0, str(r.final_error < 1e-3)),
    ]


def engine_speedup() -> list[tuple]:
    """The batched engine vs the equivalent serial run_protocol loop on a
    256-cell scenario sweep (attacks x q grid x seeds), bitwise-identical
    results required.  The acceptance bar is >= 10x."""
    steps = 200
    specs = [
        TrialSpec(byz=(2, 5), attack=a, q=q, steps=steps, seed=s,
                  label=f"{a}/q{q}/s{s}")
        for a in ("sign_flip", "scale", "drift", "zero")
        for q in (0.2, 0.3, 0.4, 0.5)
        for s in range(16)
    ]
    run_batch(specs[:8])                       # warm caches
    # best-of-3 for the engine: the ~0.5s measurement is sensitive to
    # scheduler noise that the multi-second serial loop self-averages
    t_engine = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        batch = run_batch(specs)
        t_engine = min(t_engine, time.perf_counter() - t0)

    t0 = time.perf_counter()
    serial = [run_protocol(**s.protocol_kwargs()) for s in specs]
    t_serial = time.perf_counter() - t0

    mismatches = sum(
        not (a.final_error == b.final_error and a.efficiency == b.efficiency
             and a.identify_step == b.identify_step)
        for a, b in zip(serial, batch)
    )
    speedup = t_serial / t_engine
    backend_rows, backend_detail = _backend_speedup()
    detail = {
        "trials": len(specs),
        "steps": steps,
        "engine_s": t_engine,
        "serial_s": t_serial,
        "speedup": speedup,
        "bitwise_mismatches": mismatches,
        "backend_sweep": backend_detail,
    }
    _dump("engine_speedup", detail)
    return [
        ("engine[trials_per_call]", 0.0, str(len(specs))),
        ("engine[batch_time]", t_engine * 1e6, f"{t_engine*1e3:.0f}ms"),
        ("engine[serial_time]", t_serial * 1e6, f"{t_serial*1e3:.0f}ms"),
        ("engine[speedup_vs_serial]", 0.0, f"{speedup:.1f}x"),
        ("engine[target_10x_met]", 0.0, str(speedup >= 10.0)),
        ("engine[bitwise_parity]", 0.0, str(mismatches == 0)),
    ] + backend_rows


def _backend_speedup() -> tuple[list[tuple], list[dict]]:
    """numpy engine vs the jitted jax backend (backend="jax") at
    production gradient dimensions — the paper's computation-efficiency
    claims measured where they matter.  Both backends run the identical
    256-trial fixed-q drift sweep; the jax time includes its host
    control-plane replay (proxy: O(B*T*n), d-independent) and is taken
    warm (second call) so compile time is reported separately."""
    B = int(os.environ.get("REPRO_BENCH_TRIALS", "256"))
    steps = int(os.environ.get("REPRO_BENCH_STEPS", "3"))
    d_exps = [int(x) for x in
              os.environ.get("REPRO_BENCH_DEXP", "16,20").split(",")]
    rows, detail = [], []
    for dexp in d_exps:
        d = 1 << dexp
        specs = [
            TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=steps,
                      seed=s, n_data=64, d=d, label=f"d2^{dexp}/s{s}")
            for s in range(B)
        ]
        t0 = time.perf_counter()
        jx = run_batch(specs, backend="jax")
        t_cold = time.perf_counter() - t0
        with profile_trace(f"jax_d2^{dexp}"):
            t0 = time.perf_counter()
            jx = run_batch(specs, backend="jax")
            t_jax = time.perf_counter() - t0
        t0 = time.perf_counter()
        npb = run_batch(specs)
        t_np = time.perf_counter() - t0
        ctrl_ok = all(
            a.identify_step == b.identify_step
            and a.efficiency == b.efficiency
            for a, b in zip(npb, jx)
        )
        # value parity: f32 contraction rounding scales with the iterate
        # magnitude (sqrt(d)-length dot products), so the criterion is
        # sup-norm deviation <= 1e-4 * (1 + ||w||_inf) — ~5e-7 relative
        # in practice at d = 2^20
        val_ok = all(
            float(np.abs(b.w - np.asarray(a.w)).max())
            <= 1e-4 * (1.0 + float(np.abs(np.asarray(a.w)).max()))
            for a, b in zip(npb, jx)
        )
        speedup = t_np / t_jax
        detail.append({
            "d": d, "trials": B, "steps": steps,
            "numpy_s": t_np, "jax_warm_s": t_jax, "jax_cold_s": t_cold,
            "speedup": speedup,
            "control_parity": ctrl_ok, "value_parity": val_ok,
        })
        rows.append((f"engine[numpy_vs_jax_d=2^{dexp}]", 0.0,
                     f"{speedup:.2f}x;np={t_np:.1f}s;jax={t_jax:.1f}s"))
        rows.append((f"engine[jax_parity_d=2^{dexp}]", 0.0,
                     str(ctrl_ok and val_ok)))
    return rows, detail


def fused_sweep() -> list[tuple]:
    """The fused data plane's acceptance bar: backend="jax" with the
    fused protocol-step megakernel (fused=True, the default) vs the
    unfused three-pass scan body (fused=False, the parity oracle) on
    the production-d drift sweep.  Warm wall-clock, compile reported
    separately.  Target: >= 1.5x on TPU (three HBM passes -> one), or
    >= 1.2x with the single jitted XLA fallback off-TPU.  Control
    quantities must match bit-exactly and values at the documented
    1e-4 contract; set REPRO_PROFILE=<dir> to capture profiler traces
    of both variants."""
    import jax

    B = int(os.environ.get("REPRO_BENCH_TRIALS", "256"))
    steps = int(os.environ.get("REPRO_BENCH_STEPS", "3"))
    d_exps = [int(x) for x in
              os.environ.get("REPRO_BENCH_DEXP", "16,20").split(",")]
    on_tpu = jax.default_backend() == "tpu"
    target = 1.5 if on_tpu else 1.2
    rows, sweep = [], []
    for dexp in d_exps:
        d = 1 << dexp
        specs = [
            TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=steps,
                      seed=s, n_data=64, d=d, label=f"d2^{dexp}/s{s}")
            for s in range(B)
        ]
        timing = {}
        res = {}
        # fused=True must be explicit: at these shapes the auto data
        # plane would otherwise pick gram (see gram_sweep below) and
        # this sweep would stop measuring the megakernel at all
        for label, kw in (("unfused", {"fused": False}),
                          ("fused", {"fused": True})):
            run_batch(specs, backend="jax", **kw)          # compile
            with profile_trace(f"{label}_d2^{dexp}"):
                best = float("inf")
                for _ in range(2):          # min-of-2: tame host jitter
                    t0 = time.perf_counter()
                    res[label] = run_batch(specs, backend="jax", **kw)
                    best = min(best, time.perf_counter() - t0)
                timing[label] = best
        fu, un = res["fused"], res["unfused"]
        assert fu.fused_used and not un.fused_used
        ctrl_ok = all(
            a.identify_step == b.identify_step
            and a.efficiency == b.efficiency
            and a.q_trace == b.q_trace
            for a, b in zip(un, fu)
        ) and bool(np.array_equal(un.detect_flags, fu.detect_flags))
        val_ok = all(
            float(np.abs(b.w - a.w).max())
            <= 1e-4 * (1.0 + float(np.abs(a.w).max()))
            for a, b in zip(un, fu)
        )
        speedup = timing["unfused"] / timing["fused"]
        sweep.append({
            "d": d, "unfused_s": timing["unfused"],
            "fused_s": timing["fused"], "speedup": speedup,
            "control_parity": ctrl_ok, "value_parity": val_ok,
            "target_met": bool(speedup >= target and ctrl_ok and val_ok),
        })
        rows.append((f"fused[d=2^{dexp}]", 0.0,
                     f"{speedup:.2f}x;unfused={timing['unfused']:.1f}s;"
                     f"fused={timing['fused']:.1f}s"))
        rows.append((f"fused[parity_d=2^{dexp}]", 0.0,
                     str(ctrl_ok and val_ok)))
    detail = {"trials": B, "steps": steps, "backend":
              jax.default_backend(), "target": target, "sweep": sweep}
    _dump("fused_sweep", detail)
    rows.append((f"fused[target_{target}x_met]", 0.0,
                 str(all(r["target_met"] for r in sweep))))
    return rows


def gram_sweep() -> list[tuple]:
    """The gram data plane's acceptance bar: data_plane="gram" (the
    coefficient-space scan, auto-selected at these shapes) vs the fused
    stream megakernel (fused=True, the previous fast path) on a long-T
    production-d drift sweep.  The gram scan carries (B, I) coefficients
    — per-step traffic O(B*I^2) instead of O(B*d) — so the speedup
    GROWS with d; the bar is >= 5x warm at d = 2^20, T >= 100.  Control
    quantities (schedules, q-traces, detection verdicts) must match the
    fused run bit-exactly and values at the documented 1e-4 sup-norm
    contract.  The learning rate is scaled as lr = n_data/d so gradient
    descent stays contractive at every d (the least-squares Lipschitz
    constant grows ~d/n_data; the TrialSpec default lr=0.05 diverges to
    NaN within a few steps at production d, which would make the value
    comparison vacuous).  Knobs: REPRO_BENCH_GRAM_TRIALS (default 32),
    REPRO_BENCH_GRAM_STEPS (default 120, keep >= 100 for the headline
    row), REPRO_BENCH_GRAM_DEXP (default "16,20")."""
    B = int(os.environ.get("REPRO_BENCH_GRAM_TRIALS", "32"))
    steps = int(os.environ.get("REPRO_BENCH_GRAM_STEPS", "120"))
    d_exps = [int(x) for x in
              os.environ.get("REPRO_BENCH_GRAM_DEXP", "16,20").split(",")]
    rows, sweep = [], []
    for dexp in d_exps:
        d = 1 << dexp
        specs = [
            TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=steps,
                      seed=s, n_data=64, d=d, lr=64.0 / d,
                      label=f"d2^{dexp}/s{s}")
            for s in range(B)
        ]
        timing = {}
        res = {}
        for label, kw in (("fused", {"fused": True}),
                          ("gram", {"data_plane": "gram"})):
            run_batch(specs, backend="jax", **kw)          # compile
            with profile_trace(f"gram_{label}_d2^{dexp}"):
                best = float("inf")
                for _ in range(2):          # min-of-2: tame host jitter
                    t0 = time.perf_counter()
                    res[label] = run_batch(specs, backend="jax", **kw)
                    best = min(best, time.perf_counter() - t0)
                timing[label] = best
        gr, fu = res["gram"], res["fused"]
        assert gr.plan.data_plane == "gram" and fu.fused_used
        ctrl_ok = all(
            a.identify_step == b.identify_step
            and a.efficiency == b.efficiency
            and a.q_trace == b.q_trace
            for a, b in zip(fu, gr)
        ) and bool(np.array_equal(fu.detect_flags, gr.detect_flags)) and all(
            np.array_equal(v, gr.schedule.arrays[k])
            for k, v in fu.schedule.arrays.items()
        )
        val_ok = all(
            float(np.abs(b.w - a.w).max())
            <= 1e-4 * (1.0 + float(np.abs(a.w).max()))
            for a, b in zip(fu, gr)
        )
        speedup = timing["fused"] / timing["gram"]
        target_met = bool((speedup >= 5.0 or d < 1 << 20)
                          and ctrl_ok and val_ok)
        sweep.append({
            "d": d, "fused_s": timing["fused"], "gram_s": timing["gram"],
            "speedup": speedup, "control_parity": ctrl_ok,
            "value_parity": val_ok, "target_met": target_met,
        })
        rows.append((f"gram[d=2^{dexp}]", 0.0,
                     f"{speedup:.2f}x;fused={timing['fused']:.1f}s;"
                     f"gram={timing['gram']:.1f}s"))
        rows.append((f"gram[parity_d=2^{dexp}]", 0.0,
                     str(ctrl_ok and val_ok)))
    detail = {"trials": B, "steps": steps, "target": 5.0, "sweep": sweep}
    _dump("gram_sweep", detail)
    rows.append(("gram[target_5x_at_1M_met]", 0.0,
                 str(all(r["target_met"] for r in sweep))))
    return rows


def telemetry_overhead() -> list[tuple]:
    """Observability acceptance bar: threading the protocol counters
    through the scan carry (run_batch(..., telemetry=True)) must cost
    < 5% warm wall-time on the fused d=2^16 sweep config, with the
    primary outputs bitwise identical to the telemetry-off run."""
    B = int(os.environ.get("REPRO_BENCH_TRIALS", "256"))
    steps = int(os.environ.get("REPRO_BENCH_STEPS", "3"))
    d = 1 << 16
    specs = [
        TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=steps,
                  seed=s, n_data=64, d=d, label=f"tel/s{s}")
        for s in range(B)
    ]
    timing = {}
    res = {}
    for label, tel in (("off", False), ("on", True)):
        run_batch(specs, backend="jax", fused=True, telemetry=tel)  # compile
        with profile_trace(f"telemetry_{label}"):
            best = float("inf")
            for _ in range(3):          # min-of-3: tame host jitter
                t0 = time.perf_counter()
                res[label] = run_batch(specs, backend="jax", fused=True,
                                       telemetry=tel)
                best = min(best, time.perf_counter() - t0)
            timing[label] = best
    off, on = res["off"], res["on"]
    assert on.telemetry is not None and off.telemetry is None
    # counters must be populated and self-consistent with the schedule
    tot = on.telemetry.totals()
    assert tot["steps"] == sum(s.steps for s in specs)
    bitwise_ok = all(
        bool(np.array_equal(np.asarray(a.w), np.asarray(b.w)))
        for a, b in zip(off, on)
    )
    overhead_frac = timing["on"] / timing["off"] - 1.0
    detail = {
        "d": d, "trials": B, "steps": steps,
        "off_s": timing["off"], "on_s": timing["on"],
        "overhead_frac": overhead_frac, "bitwise_identical": bitwise_ok,
        "target": 0.05, "target_met": bool(bitwise_ok
                                           and overhead_frac < 0.05),
        "totals": {k: int(v) for k, v in tot.items()},
    }
    _dump("telemetry_overhead", detail)
    return [
        ("telemetry[overhead_frac]", 0.0, f"{overhead_frac:+.4f}"),
        ("telemetry[bitwise_identical]", 0.0, str(bitwise_ok)),
        ("telemetry[target_lt_5pct_met]", 0.0, str(detail["target_met"])),
    ]


def schedule_build() -> list[tuple]:
    """Control-plane throughput: the vectorized control-only replay
    (build_schedule mode "vector") vs the full-engine proxy replay on a
    256-trial fixed-q long-T sweep — the host-side bottleneck the jax
    backend pays per run.  Acceptance bar: >= 3x, arrays identical."""
    import numpy as np

    from repro.core.engine_jax import build_schedule

    B = int(os.environ.get("REPRO_BENCH_TRIALS", "256"))
    T = 400
    specs = [
        TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=T, seed=s,
                  n_data=64, d=1024, label=f"s{s}")
        for s in range(B)
    ]
    vec = build_schedule(specs, "vector")      # warm numpy caches
    prx = build_schedule(specs, "proxy")
    parity = all(np.array_equal(vec.arrays[k], prx.arrays[k])
                 for k in prx.arrays)
    t_vec = t_prx = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        build_schedule(specs, "proxy")
        t_prx = min(t_prx, time.perf_counter() - t0)
        t0 = time.perf_counter()
        build_schedule(specs, "vector")
        t_vec = min(t_vec, time.perf_counter() - t0)
    speedup = t_prx / t_vec
    detail = {
        "trials": B, "steps": T,
        "proxy_s": t_prx, "vector_s": t_vec, "speedup": speedup,
        "arrays_identical": parity,
    }
    _dump("schedule_build", detail)
    return [
        ("schedule[proxy_replay]", t_prx * 1e6, f"{t_prx*1e3:.0f}ms"),
        ("schedule[vector_replay]", t_vec * 1e6, f"{t_vec*1e3:.0f}ms"),
        ("schedule[speedup]", 0.0, f"{speedup:.1f}x"),
        ("schedule[target_3x_met]", 0.0, str(speedup >= 3.0)),
        ("schedule[arrays_identical]", 0.0, str(parity)),
    ]


def engine_devices() -> list[tuple]:
    """Device scaling of the sharded engine, in this process, on the
    devices it holds: the same 64-trial drift sweep (d = 2^16) on one
    device and sharded over the ("trials",) mesh of all of them.  A
    one-device process records only the first column.  Parity of the
    sharded path on an emulated 8-device CPU host is pinned by
    tests/test_sharded_engine.py."""
    import jax

    from repro.sharding import trials_mesh

    B, d, steps = 64, 1 << 16, 3
    specs = [TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=steps,
                       seed=s, n_data=64, d=d) for s in range(B)]
    devs = jax.devices()
    mesh = trials_mesh()
    detail = {"devices": len(devs), "platform": devs[0].platform,
              "device_kind": devs[0].device_kind,
              "mesh": None if mesh is None else int(mesh.devices.size)}
    for label, m in (("unsharded", None), ("sharded", mesh)):
        if label == "sharded" and mesh is None:
            continue
        run_batch(specs, backend="jax", mesh=m)            # compile
        t0 = time.perf_counter()
        run_batch(specs, backend="jax", mesh=m)
        detail[label + "_s"] = time.perf_counter() - t0
        detail[label + "_trials_per_s"] = B / detail[label + "_s"]
    if "sharded_s" in detail:
        detail["sharded_vs_unsharded"] = (detail["unsharded_s"]
                                          / detail["sharded_s"])
    _dump("engine_devices", detail)
    rows = [("devices[count]", 0.0,
             f"{detail['devices']}x{detail['device_kind']}")]
    for label in ("unsharded", "sharded"):
        if label + "_s" in detail:
            rows.append((f"devices[{label}]", detail[label + "_s"] * 1e6,
                         f"{detail[label + '_trials_per_s']:.1f}trials/s"))
    return rows


def adaptive_sweep() -> list[tuple]:
    """The on-device control plane's acceptance bar: a 256-trial
    ADAPTIVE (q*_t) sweep with schedule="device" — value-dependent
    check decisions computed inside the device scan, no host oracle
    replay — vs schedule="oracle" (full numpy-engine control replay,
    previously the only option for adaptive trials).  Control parity is
    asserted against the numpy engine under the same counter-RNG
    streams (rng="device").  Acceptance: >= 5x warm wall-clock."""
    B = int(os.environ.get("REPRO_BENCH_TRIALS", "256"))
    steps = int(os.environ.get("REPRO_BENCH_ADAPTIVE_STEPS", "24"))
    d = 1 << int(os.environ.get("REPRO_BENCH_ADAPTIVE_DEXP", "13"))
    specs = [
        TrialSpec(byz=(2, 5), attack="sign_flip", q=None, steps=steps,
                  seed=s, n_data=64, d=d, label=f"s{s}")
        for s in range(B)
    ]
    t0 = time.perf_counter()
    dev = run_batch(specs, backend="jax", schedule="device")
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev = run_batch(specs, backend="jax", schedule="device")
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_batch(specs, backend="jax", schedule="oracle")
    t_oracle = time.perf_counter() - t0
    npb = run_batch(specs, rng="device")       # parity oracle
    ctrl_ok = all(
        a.identify_step == b.identify_step
        and a.state.kappa == b.state.kappa
        and a.efficiency == b.efficiency
        for a, b in zip(npb, dev)
    )
    # q*_t traces: the device loss is an f32 d-length dot product vs
    # the host's f64, so q* carries the float contract (1e-4)
    q_ok = all(
        np.allclose(np.asarray(b.q_trace), np.asarray(a.q_trace),
                    rtol=1e-4, atol=1e-4)
        for a, b in zip(npb, dev)
    )
    speedup = t_oracle / t_dev
    detail = {
        "trials": B, "steps": steps, "d": d,
        "oracle_s": t_oracle, "device_warm_s": t_dev,
        "device_cold_s": t_cold, "speedup": speedup,
        "control_parity": ctrl_ok, "q_parity": q_ok,
    }
    _dump("adaptive_sweep", detail)
    return [
        ("adaptive_sweep[oracle]", t_oracle * 1e6, f"{t_oracle:.2f}s"),
        ("adaptive_sweep[device_warm]", t_dev * 1e6, f"{t_dev:.2f}s"),
        ("adaptive_sweep[speedup]", 0.0, f"{speedup:.1f}x"),
        ("adaptive_sweep[target_5x_met]", 0.0, str(speedup >= 5.0)),
        ("adaptive_sweep[control_parity]", 0.0, str(ctrl_ok and q_ok)),
    ]


def fig2_code() -> list[tuple]:
    import jax
    import jax.numpy as jnp

    from repro.core.codes import Fig2Code, ReplicationCode

    d = 4096
    g1, g2, g3 = jax.random.normal(jax.random.PRNGKey(0), (3, d))
    c = [
        Fig2Code.encode(0, g1, g2),
        Fig2Code.encode(1, g2, g3),
        Fig2Code.encode(2, g3, g1),
    ]
    clean = bool(Fig2Code.check(*c))
    c_bad = [c[0], c[1] + 0.1, c[2]]
    detected = not bool(Fig2Code.check(*c_bad))
    ok = bool(
        jnp.allclose(Fig2Code.decode(*c), g1 + g2 + g3, rtol=1e-5, atol=1e-5)
    )
    # communication: each worker sends ONE d-vector vs f+1=2 gradient
    # replicas it computed (replication symbol = its gradient tuple)
    comm_ratio = 1 / 2
    us = _timeit(lambda: Fig2Code.check(*c).block_until_ready())
    return [
        ("fig2[detects_single_fault]", us, str(clean and detected and ok)),
        ("fig2[comm_vs_replication]", 0.0, f"{comm_ratio:.2f}"),
    ]


def _dump(name: str, obj) -> None:
    import os

    os.makedirs("results/bench", exist_ok=True)
    with open(f"results/bench/{name}.json", "w") as fh:
        json.dump(obj, fh, indent=1)


ALL = [efficiency_vs_q, scheme_comparison, identification_time,
       adaptive_trace, engine_speedup, fused_sweep, gram_sweep,
       telemetry_overhead, schedule_build, engine_devices,
       adaptive_sweep, fig2_code]
