"""Drive the engine and the BFT trainer once on TPU chips and check them.

    python3 chip_smoke.py              # one chip: engine_stream, engine_gram,
                                       # engine_device_control, trainer
    python3 chip_smoke.py --chips 4    # four chips: engine_sharded, trainer_4

Every phase calls a normal entry point (``run_batch(..., backend="jax")``
or ``Trainer``) at full width, compares what comes back with a reference
that does not run the path under test, and prints one JSON line: the
phase, the resolved plan, the compile time, the warm wall time and the
comparison.  The last line is ``{"ok": true, "device": {...}}``.  The run
stops with a non-zero exit, before that line, on the first phase that
fails and in any process whose JAX finds no TPU.  Problems, tokens and
weights are made from ``--seed``; nothing is read from outside the repo.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.engine import TrialSpec, run_batch  # noqa: E402
from repro.core.engine_jax import build_schedule  # noqa: E402
from repro.core.randomized import BFTConfig  # noqa: E402
from repro.data import global_batch_for_step  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim import OptConfig  # noqa: E402
from repro.sharding import make_mesh, set_mesh  # noqa: E402
from repro.train import AttackConfig, StepConfig, Trainer, TrainerConfig  # noqa: E402

ARCH = "llama3.2-1b"
# Depth kept for the trainer phases; width is never cut.  With AdamW at
# seq_len 512, compiled for described v5e chips: on one chip the 16-layer
# check step needs 15.81 GiB of the 15.75 there are, 15 layers fit and 14
# leave headroom; on four chips (n=4) the 14-layer check step needs
# 20.48 GiB, and 12 layers fit every step kind.
TRAINER_LAYERS = 14
TRAINER_4_LAYERS = 12
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def compile_clock():
    """Seconds JAX spends tracing, lowering and compiling inside the block
    (``clock["s"]``), from JAX's own compile-duration events."""
    clock = {"s": 0.0}

    def listen(event, duration, **_):
        if event in _COMPILE_EVENTS:
            clock["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield clock
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _kernels() -> str:
    """How ``repro.kernels.ops`` runs the Pallas kernels in this process:
    compiled by Mosaic on a TPU, in the interpreter anywhere else."""
    return "interpret" if ops._interpret(None) else "mosaic"


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _plan_fields(plan) -> dict:
    return {k: getattr(plan, k) for k in (
        "data_plane", "control", "schedule_mode", "kernel_impl",
        "sharded", "n_devices", "chunk_trials", "n_trials", "steps")}


def _run_engine(specs, **kw):
    """Cold then warm ``run_batch(backend="jax")``; returns the warm
    result, the compile seconds of the cold run and the warm wall time."""
    with compile_clock() as clock:
        run_batch(specs, backend="jax", **kw)
    t0 = time.perf_counter()
    res = run_batch(specs, backend="jax", **kw)
    return res, clock["s"], time.perf_counter() - t0


def _sup_dev(a, b) -> float:
    """max|a - b| / (1 + max|b|): the engine's production-d value
    contract (docs/performance.md, Parity guarantee) reads <= 1e-4."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    return float(np.abs(a - b).max() / (1.0 + np.abs(b).max()))


def _control_equal(a, b) -> bool:
    return (a.identify_step == b.identify_step
            and a.efficiency == b.efficiency
            and np.array_equal(a.state.identified, b.state.identified)
            and np.array_equal(a.state.active, b.state.active))


def _against_numpy(res, specs_ref) -> dict:
    """Host-control jax results vs the float64 numpy engine on the first
    ``len(specs_ref)`` trials: control and detection exact, values at
    the 1e-4 contract."""
    k = len(specs_ref)
    ref = build_schedule(specs_ref, "oracle")          # numpy engine pass
    got = res.results[:k]
    arrays_equal = all(np.array_equal(res.schedule.arrays[name][:, :k], v)
                       for name, v in ref.arrays.items())
    want_det = ref.arrays["checks"] & ref.arrays["identify"]
    w_dev = max(_sup_dev(g.w, r.w) for g, r in zip(got, ref.control))
    loss_dev = max(_sup_dev(g.losses, r.losses)
                   for g, r in zip(got, ref.control))
    checks = {
        "ref_trials": k,
        "control_exact": all(_control_equal(g, r) and g.q_trace == r.q_trace
                             for g, r in zip(got, ref.control)),
        "schedule_exact": arrays_equal,
        "detect_flags_exact": bool(np.array_equal(
            res.detect_flags[:, :k], want_det)),
        "detections": int(want_det.sum()),
        "w_dev": w_dev,
        "loss_dev": loss_dev,
    }
    checks["ok"] = bool(checks["control_exact"] and checks["schedule_exact"]
                        and checks["detect_flags_exact"]
                        and w_dev <= 1e-4 and loss_dev <= 1e-4)
    return checks


def _line(phase, plan, compile_s, warm_s, checks, ok, **extra) -> dict:
    return {"phase": phase, "plan": plan, "compile_s": compile_s,
            "warm_s": warm_s, **extra, "checks": checks, "ok": bool(ok)}


def _engine_line(phase, res, compile_s, warm_s, checks, **expect) -> dict:
    """The phase line of an engine run; ``expect`` names the plan fields
    the phase is about.  Every engine phase must run the Pallas kernels,
    compiled rather than interpreted."""
    plan = res.plan
    expect["kernel_impl"] = "pallas"
    kernels = _kernels()
    ok = (checks["ok"] and kernels == "mosaic"
          and all(getattr(plan, k) == v for k, v in expect.items()))
    return _line(phase, _plan_fields(plan), compile_s, warm_s, checks, ok,
                 kernels=kernels)


# ---------------------------------------------------------------------------
# engine phases
# ---------------------------------------------------------------------------


def drift_specs(trials=256, d=1 << 20, steps=3, seed=0):
    """A fixed-q drift sweep on one shared problem of 64 rows at
    production d."""
    return [TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=steps,
                      seed=seed + s, problem_seed=seed, n_data=64, d=d)
            for s in range(trials)]


def engine_stream(*, trials=256, d=1 << 20, steps=3, n_ref=8, seed=0):
    """The stream plane (the (B, d) iterate carried through the scan) on
    the drift sweep vs the numpy engine."""
    specs = drift_specs(trials, d, steps, seed)
    res, compile_s, warm_s = _run_engine(specs, data_plane="stream")
    checks = _against_numpy(res, specs[:n_ref])
    return _engine_line("engine_stream", res, compile_s, warm_s, checks,
                        data_plane="stream")


def engine_gram(*, trials=32, d=1 << 20, steps=120, n_ref=2, seed=0):
    """The auto plan on a long-T drift sweep at production d (it must
    resolve to the gram plane) vs the numpy engine; lr = n_data/d keeps
    gradient descent contractive at this d."""
    specs = [TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=steps,
                       seed=seed + s, problem_seed=seed, n_data=64, d=d,
                       lr=64.0 / d)
             for s in range(trials)]
    res, compile_s, warm_s = _run_engine(specs)
    checks = _against_numpy(res, specs[:n_ref])
    return _engine_line("engine_gram", res, compile_s, warm_s, checks,
                        data_plane="gram")


def engine_device_control(*, trials=256, d=1 << 16, steps=24, n_ref=16,
                          seed=0):
    """An adaptive-q sign_flip sweep under ``schedule="device"`` vs
    the numpy engine on the same counter-RNG streams (``rng="device"``):
    control exact, q*_t at float tolerance."""
    specs = [TrialSpec(byz=(2, 5), attack="sign_flip", q=None, steps=steps,
                       seed=seed + s, problem_seed=seed, n_data=64, d=d)
             for s in range(trials)]
    res, compile_s, warm_s = _run_engine(specs, schedule="device")
    ref = run_batch(specs[:n_ref], rng="device")
    got = res.results[:n_ref]
    q_dev = max(float(np.abs(np.subtract(g.q_trace, r.q_trace)).max(
        initial=0.0)) for g, r in zip(got, ref))
    checks = {
        "ref_trials": n_ref,
        "control_exact": all(_control_equal(g, r)
                             for g, r in zip(got, ref)),
        "identified": int(sum(r.state.kappa for r in ref)),
        "q_close": all(np.allclose(g.q_trace, r.q_trace, rtol=1e-4,
                                   atol=1e-4) for g, r in zip(got, ref)),
        "q_dev": q_dev,
    }
    checks["ok"] = bool(checks["control_exact"] and checks["q_close"])
    return _engine_line("engine_device_control", res, compile_s, warm_s,
                        checks, control="device")


def engine_sharded(*, trials=256, d=1 << 20, steps=3, seed=0):
    """Phase 1's batch under the auto plan, sharded over every chip
    (``mesh="auto"``), vs the same batch on one chip in this process:
    control exact, values at the f32 cross-configuration tolerance."""
    specs = drift_specs(trials, d, steps, seed)
    one = run_batch(specs, backend="jax", mesh=None)
    res, compile_s, warm_s = _run_engine(specs, mesh="auto")
    checks = {
        "devices": res.plan.n_devices,
        "control_exact": all(_control_equal(a, b) and a.q_trace == b.q_trace
                             for a, b in zip(res, one)),
        "detect_flags_exact": bool(np.array_equal(res.detect_flags,
                                                  one.detect_flags)),
        "values_close": all(
            np.allclose(a.w, b.w, rtol=1e-5, atol=1e-6)
            and np.allclose(a.losses, b.losses, rtol=1e-5, atol=1e-6)
            for a, b in zip(res, one)),
        "w_dev": max(_sup_dev(a.w, b.w) for a, b in zip(res, one)),
    }
    checks["ok"] = bool(checks["control_exact"]
                        and checks["detect_flags_exact"]
                        and checks["values_close"])
    return _engine_line("engine_sharded", res, compile_s, warm_s, checks,
                        sharded=True, n_devices=len(jax.devices()))


# ---------------------------------------------------------------------------
# trainer phases
# ---------------------------------------------------------------------------


def model_config(layers=None, reduced=False):
    """``llama3.2-1b`` at its published widths; ``layers`` cuts depth
    only, ``reduced`` swaps in the tiny same-family config for CPU."""
    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduced()
    if layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    return cfg


def _adamw():
    return OptConfig(kind="adamw", peak_lr=3e-4, warmup_steps=20,
                     total_steps=100)


def _trainer_plan(cfg, mesh, tc, bft) -> dict:
    return {"arch": cfg.name, "layers": cfg.num_layers,
            "d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "mesh": dict(mesh.shape),
            "seq_len": tc.seq_len, "global_batch": tc.global_batch,
            "optimizer": "adamw", "n": bft.n, "f": bft.f,
            "mode": bft.mode}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def trainer(cfg, *, seq_len=512, global_batch=2, fast_steps=3,
            check_steps=2, seed=0):
    """``Trainer`` on a 1x1 (data, model) mesh: one warm-up step, timed
    fast steps, then a warm-up check step and timed check steps.

    One worker has no second replica, so neither ``mode="none"`` nor
    ``mode="deterministic"`` (f_t = 0) ever schedules a check; the phase
    dispatches the compiled check step (sketch, all-gather, detection,
    guarded update) itself, with a one-worker group whose verdict must
    be clean.  Reference: the first step's loss equals
    ``models.model.train_loss`` on the same params and batch."""
    from repro.core.assignment import build_assignment

    mesh = make_mesh((1, 1), ("data", "model"))
    bft = BFTConfig(n=1, f=0, mode="none", seed=seed)
    tc = TrainerConfig(seq_len=seq_len, global_batch=global_batch,
                       seed=seed, log_every=0)
    with compile_clock() as clock:
        tr = Trainer(cfg, _adamw(), bft, mesh, tc,
                     attack=AttackConfig(kind="none"),
                     sc=StepConfig(worker_axes=("data",)))
        batch = global_batch_for_step(cfg, global_batch=global_batch,
                                      seq_len=seq_len, step=0, seed=seed)
        with set_mesh(mesh):           # before the step donates the params
            ref_loss = float(jax.jit(lambda p, b: M.train_loss(p, b, cfg)[0])(
                tr.params, {k: jnp.asarray(v) for k, v in batch.items()}))
        first, warm_up_s = _timed(tr.train_step)
    steps = [{"kind": "warm_up", "s": warm_up_s, "peak_bytes": _peak_bytes()}]
    for _ in range(fast_steps):
        _, s = _timed(tr.train_step)
        steps.append({"kind": "fast", "s": s, "peak_bytes": _peak_bytes()})

    group = build_assignment(tr.state.active, 1)

    def check_step():
        batch = global_batch_for_step(cfg, global_batch=global_batch,
                                      seq_len=seq_len, step=tr.state.step,
                                      seed=seed)
        with set_mesh(mesh):
            m = jax.block_until_ready(tr._dispatch("check", group, batch))
        tr.state.step += 1
        return bool(m["any_fault"])

    faults = []
    with compile_clock() as check_clock:
        fault, s = _timed(check_step)
    faults.append(fault)
    steps.append({"kind": "check_warm_up", "s": s,
                  "peak_bytes": _peak_bytes()})
    for _ in range(check_steps):
        fault, s = _timed(check_step)
        faults.append(fault)
        steps.append({"kind": "check", "s": s, "peak_bytes": _peak_bytes()})
    rel = abs(first["loss"] - ref_loss) / abs(ref_loss)
    checks = {"loss_first_step": first["loss"], "loss_reference": ref_loss,
              "rel_diff": rel, "check_any_fault": any(faults),
              "finite": bool(np.isfinite([h["loss"] for h in tr.history]).all())}
    ok = rel <= 1e-2 and not any(faults) and checks["finite"]
    fast = [s["s"] for s in steps if s["kind"] == "fast"]
    return _line("trainer", _trainer_plan(cfg, mesh, tc, bft),
                 clock["s"] + check_clock["s"],
                 float(np.mean(fast)) if fast else None, checks, ok,
                 steps=steps)


def trainer_4(cfg, *, n=4, seq_len=512, max_steps=20, q=0.5, seed=0):
    """``Trainer`` on a (data=n, model=1) mesh, n workers of which the
    last is Byzantine (``sign_flip``, tampering every step), randomized
    checks at fixed q, run until the protocol identifies a worker: the
    identified set must be exactly {n - 1}.  Each step kind runs for the
    first time inside this loop, so the line gives each step's time and
    no warm time."""
    mesh = make_mesh((n, 1), ("data", "model"), devices=jax.devices()[:n])
    bft = BFTConfig(n=n, f=1, mode="randomized", q=q, p_assumed=1.0,
                    seed=seed)
    tc = TrainerConfig(seq_len=seq_len, global_batch=n, seed=seed,
                       log_every=0)
    steps = []
    with compile_clock() as clock:
        tr = Trainer(cfg, _adamw(), bft, mesh, tc,
                     attack=AttackConfig(kind="sign_flip", p_tamper=1.0),
                     sc=StepConfig(worker_axes=("data",)),
                     true_byzantine=np.arange(n) == n - 1)
        while tr.state.kappa == 0 and len(steps) < max_steps:
            rec, s = _timed(tr.train_step)
            steps.append({"step": rec["step"], "s": s, "loss": rec["loss"],
                          "identified": rec.get("identified"),
                          "peak_bytes": _peak_bytes()})
    identified = np.flatnonzero(tr.state.identified).tolist()
    checks = {"identified": identified, "expected": [n - 1],
              "steps_to_identify": len(steps),
              "check_iterations": tr.state.meter.check_iterations,
              "efficiency": tr.state.meter.overall,
              "finite": bool(np.isfinite([h["loss"] for h in tr.history]).all())}
    ok = identified == [n - 1] and checks["finite"]
    return _line("trainer_4", _trainer_plan(cfg, mesh, tc, bft), clock["s"],
                 None, checks, ok, steps=steps)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _phases(chips: int, seed: int):
    if chips == 1:
        cfg = model_config(TRAINER_LAYERS)
        return [lambda: engine_stream(seed=seed),
                lambda: engine_gram(seed=seed),
                lambda: engine_device_control(seed=seed),
                lambda: trainer(cfg, seed=seed)]
    cfg = model_config(TRAINER_4_LAYERS)
    return [lambda: engine_sharded(seed=seed),
            lambda: trainer_4(cfg, n=chips, seed=seed)]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the phases that "
                    "span four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"chips, JAX found {len(devices)}")
    enable_compile_cache()
    for phase in _phases(args.chips, args.seed):
        line = phase()
        print(json.dumps(line), flush=True)
        if not line["ok"]:
            sys.exit(f"chip_smoke: phase {line['phase']} failed")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
