"""Batched scenario engine == serial reference, bitwise.

The engine's exactness contract (repro.core.engine): for any TrialSpec
whose fields match run_protocol's keyword arguments, run_batch must
reproduce run_protocol's final_error, efficiency and identify_step
EXACTLY — not approximately — for the same seeds, with the trial run
inside an arbitrary mixed batch.  This is what makes wide sweeps
trustworthy: a scenario cell can be debugged by re-running its single
trial serially and getting the identical trajectory.

Both paths share the matmul primitives in repro.core.engine, and every
batched contraction keeps the per-item operand shapes of the serial
path, so the floating-point streams agree bit-for-bit.  These tests run
ALL configs below in ONE batch (also proving cross-trial isolation)
and compare against fresh serial runs.
"""
import numpy as np
import pytest

from repro.core.engine import TrialSpec, run_batch
from repro.core.simulation import run_protocol

# one config per protocol mode / decision class, plus n/f and problem
# variations — all batched together
PARITY_CONFIGS = [
    dict(byz=(2, 5), attack="sign_flip", steps=120, q=0.4,
         mode="randomized", seed=1),
    dict(byz=(2, 5), attack="sign_flip", steps=120, q=None,
         mode="randomized", seed=3),                      # adaptive q* (§4.3)
    dict(byz=(1,), attack="drift", steps=100, mode="deterministic",
         q=None, seed=2),
    # draco runs long enough to reach the converged noise floor, where
    # replica order inside the vote matters (regression: engine must
    # feed replicas in sorted-id order, like the serial path)
    dict(byz=(3,), attack="scale", steps=300, mode="draco", q=None, seed=0),
    dict(byz=(2, 5), attack="sign_flip", steps=100, mode="filter:median",
         q=0.4, seed=5),
    dict(byz=(6,), attack="scale", steps=120, q=0.3, selective=True,
         seed=7),                                         # §5 selective
    dict(byz=(), attack="none", steps=100, q=0.4, seed=4),
    dict(byz=(2,), attack="zero", steps=100, q=0.2, seed=9, n=6, f=1),
    dict(byz=(4,), attack="noise", steps=90, q=0.3, seed=12),
    dict(byz=(2, 5), attack="drift", steps=100, q=0.5, seed=13,
         problem_seed=3),
    dict(byz=(2, 5), attack="sign_flip", steps=400, q=0.4, seed=1),
]

_batch = None


def _get_batch():
    global _batch
    if _batch is None:
        _batch = run_batch([TrialSpec(**c) for c in PARITY_CONFIGS])
    return _batch


@pytest.mark.parametrize("idx", range(len(PARITY_CONFIGS)),
                         ids=[f"{c.get('mode', 'randomized')}-s{c['seed']}"
                              for c in PARITY_CONFIGS])
def test_batched_engine_reproduces_run_protocol_exactly(idx):
    cfg = PARITY_CONFIGS[idx]
    batched = _get_batch()[idx]
    serial = run_protocol(**cfg)

    # the headline contract: exact equality, not tolerance
    assert serial.final_error == batched.final_error
    assert serial.efficiency == batched.efficiency
    assert serial.identify_step == batched.identify_step
    # and the full trajectories behind them
    assert serial.losses == batched.losses
    assert serial.q_trace == batched.q_trace
    assert np.array_equal(serial.w, batched.w)
    assert np.array_equal(serial.state.active, batched.state.active)
    assert np.array_equal(serial.state.identified, batched.state.identified)


def test_meter_counters_match_exactly():
    for cfg, batched in zip(PARITY_CONFIGS, _get_batch()):
        serial = run_protocol(**cfg)
        sm, bm = serial.state.meter, batched.state.meter
        assert (sm.used, sm.computed, sm.iterations, sm.check_iterations,
                sm.identify_iterations) == (
            bm.used, bm.computed, bm.iterations, bm.check_iterations,
            bm.identify_iterations)
        assert sm.history == bm.history


def test_batch_order_does_not_change_results():
    """Trials are independent: reversing the batch permutes nothing."""
    specs = [TrialSpec(**c) for c in PARITY_CONFIGS[:4]]
    fwd = run_batch(specs)
    rev = run_batch(specs[::-1])
    for i, r in enumerate(fwd):
        r2 = rev[len(specs) - 1 - i]
        assert r.final_error == r2.final_error
        assert r.losses == r2.losses


def test_single_trial_batch_matches_serial():
    cfg = dict(byz=(2, 5), attack="sign_flip", steps=150, q=0.3, seed=21)
    b = run_batch([TrialSpec(**cfg)])[0]
    s = run_protocol(**cfg)
    assert s.final_error == b.final_error
    assert s.losses == b.losses


# ===========================================================================
# Jitted on-device backend: run_batch(..., backend="jax")
#
# Parity contract (documented in docs/performance.md): CONTROL quantities
# — efficiency counters, check/identify schedules, identified sets,
# q-traces — equal the numpy engine EXACTLY (they come from the same
# host state machine).  FLOAT quantities are recomputed on device in
# float32 (the numpy engine runs float64), so they match to the
# tolerances below: converged trials agree to ~1e-6 absolute; the
# deliberately-diverging unprotected trials agree to f32 relative
# accuracy (~1e-6 of a ~1e9 iterate), which rtol covers.
# ===========================================================================

JAX_W_RTOL, JAX_W_ATOL = 1e-4, 1e-4
JAX_LOSS_RTOL, JAX_LOSS_ATOL = 1e-3, 1e-4

_jax_cache: dict = {}


def _both_backends(name):
    from repro.core.engine import SCENARIOS

    if name not in _jax_cache:
        mx = SCENARIOS[name]
        _jax_cache[name] = (mx.run(), mx.run(backend="jax"))
    return _jax_cache[name]


def _scenario_names():
    from repro.core.engine import SCENARIOS

    return list(SCENARIOS)


@pytest.mark.parametrize("name", _scenario_names())
def test_jax_backend_control_parity(name):
    """Control plane: exact equality with the numpy engine across the
    whole SCENARIOS grid (identify steps, efficiency, q-trace, meters)."""
    npb, jxb = _both_backends(name)
    for rn, rj in zip(npb, jxb):
        assert rn.identify_step == rj.identify_step
        assert rn.efficiency == rj.efficiency
        assert rn.q_trace == rj.q_trace
        assert np.array_equal(rn.state.identified, rj.state.identified)
        assert np.array_equal(rn.state.active, rj.state.active)
        sm, jm = rn.state.meter, rj.state.meter
        assert (sm.used, sm.computed, sm.check_iterations,
                sm.identify_iterations) == (
            jm.used, jm.computed, jm.check_iterations,
            jm.identify_iterations)


@pytest.mark.parametrize("name", _scenario_names())
def test_jax_backend_value_parity(name):
    """Data plane: float32 device values vs float64 host values."""
    npb, jxb = _both_backends(name)
    for spec, rn, rj in zip(npb.specs, npb, jxb):
        np.testing.assert_allclose(rj.w, np.asarray(rn.w),
                                   rtol=JAX_W_RTOL, atol=JAX_W_ATOL,
                                   err_msg=spec.label)
        np.testing.assert_allclose(np.asarray(rj.losses),
                                   np.asarray(rn.losses),
                                   rtol=JAX_LOSS_RTOL, atol=JAX_LOSS_ATOL,
                                   err_msg=spec.label)
        # exact fault-tolerance verdicts agree
        assert (rn.final_error < 1e-3) == (rj.final_error < 1e-3), spec.label


@pytest.mark.parametrize("name", _scenario_names())
def test_jax_backend_sketch_detection_matches_engine(name):
    """The scan's on-device sketch detection (DESIGN §7 symbols built
    from pre-sketched data rows) reaches the numpy engine's
    full-gradient verdict on every check iteration of the grid."""
    _, jxb = _both_backends(name)
    sched = jxb.schedule.arrays
    mism = (jxb.detect_flags != sched["identify"]) & sched["checks"]
    assert not mism.any()


def test_jax_backend_proxy_schedule_equals_oracle():
    """For value-independent trial classes the tiny-proxy control replay
    must produce the identical schedule (and results) as a full
    real-problem replay."""
    specs = [
        TrialSpec(byz=(2, 5), attack="drift", steps=80, q=0.4, seed=1),
        TrialSpec(byz=(3,), attack="drift", steps=80, mode="draco",
                  q=None, seed=0),
        TrialSpec(byz=(4,), attack="noise", steps=80, q=0.3, seed=2),
        TrialSpec(byz=(), attack="none", steps=80, q=0.4, seed=3),
    ]
    px = run_batch(specs, backend="jax", schedule="proxy")
    ox = run_batch(specs, backend="jax", schedule="oracle")
    assert px.schedule.used_proxy and not ox.schedule.used_proxy
    for k, v in px.schedule.arrays.items():
        assert np.array_equal(v, ox.schedule.arrays[k]), k
    for rp, ro in zip(px, ox):
        assert rp.identify_step == ro.identify_step
        np.testing.assert_array_equal(rp.w, ro.w)


def test_jax_backend_auto_schedule_selection():
    eligible = [TrialSpec(byz=(2,), attack="drift", steps=20, q=0.5)]
    dependent = [TrialSpec(byz=(2,), attack="sign_flip", steps=20, q=0.5)]
    assert run_batch(eligible, backend="jax").schedule.used_proxy
    assert not run_batch(dependent, backend="jax").schedule.used_proxy
    with pytest.raises(ValueError):
        run_batch(dependent, backend="jax", schedule="proxy")


def test_jax_backend_interpret_kernels_smoke():
    """The Pallas kernel path (interpret mode on CPU) stays alive inside
    the jitted scan — the CI smoke configuration."""
    specs = [
        TrialSpec(byz=(2, 5), attack="drift", steps=25, q=0.6, seed=1),
        TrialSpec(byz=(1,), attack="noise", steps=25, q=0.6, seed=2),
    ]
    npb = run_batch(specs)
    jxb = run_batch(specs, backend="jax", kernel_impl="pallas")
    for rn, rj in zip(npb, jxb):
        assert rn.identify_step == rj.identify_step
        np.testing.assert_allclose(rj.w, np.asarray(rn.w),
                                   rtol=JAX_W_RTOL, atol=JAX_W_ATOL)


def test_jax_backend_rejects_non_affine_attacks():
    with pytest.raises(NotImplementedError):
        run_batch([TrialSpec(attack=lambda g: g ** 2, steps=5)],
                  backend="jax")


def test_jax_backend_zero_steps_returns_real_problem():
    """steps == 0 must hand back the REAL problem's (zero) iterate, not
    the proxy control problem's (regression: the proxy early-return)."""
    spec = TrialSpec(byz=(2,), attack="drift", steps=0, q=0.5)
    rn = run_batch([spec])[0]
    rj = run_batch([spec], backend="jax")[0]
    assert rj.w.shape == rn.w.shape
    assert rj.final_error == rn.final_error
    assert rj.losses == rn.losses == []


# ===========================================================================
# Gram data plane: the coefficient-space scan (resid = S0 - C_t G) must
# reproduce the stream plane's control quantities bit-for-bit and its
# values to the f32 tolerance — it is the same protocol in a different
# basis.  SCENARIOS run at the default tiny d=8, below the auto size
# gate, so the plane is requested explicitly here.
# ===========================================================================


@pytest.mark.parametrize("name", _scenario_names())
def test_jax_backend_gram_vs_stream(name):
    import warnings

    from repro.core.engine import SCENARIOS
    from repro.core.engineplan.plan import PlanFallbackWarning

    _, jst = _both_backends(name)               # default: stream at d=8
    assert jst.plan.data_plane == "stream"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PlanFallbackWarning)
        jgr = SCENARIOS[name].run(backend="jax", data_plane="gram")
    if name == "paper_core":
        # filter baselines hard-gate the gram plane even when explicit
        assert jgr.plan.data_plane == "stream"
        return
    assert jgr.plan.data_plane == "gram"
    for rg, rs in zip(jgr, jst):
        # control plane: exact agreement
        assert rg.identify_step == rs.identify_step
        assert rg.efficiency == rs.efficiency
        assert rg.q_trace == rs.q_trace
        # value plane: f32-vs-f32 tolerance
        np.testing.assert_allclose(rg.w, rs.w, rtol=JAX_W_RTOL,
                                   atol=JAX_W_ATOL)
        np.testing.assert_allclose(np.asarray(rg.losses),
                                   np.asarray(rs.losses),
                                   rtol=JAX_LOSS_RTOL, atol=JAX_LOSS_ATOL)
    # sketch-detection verdicts: bitwise (same precomputed tables, same
    # einsum arithmetic as the stream plane's pre-sketched rows)
    assert np.array_equal(jgr.detect_flags, jst.detect_flags)
    for k, v in jgr.schedule.arrays.items():
        assert np.array_equal(v, jst.schedule.arrays[k]), k


def test_jax_backend_gram_auto_engages_at_production_d():
    """Above the size gate the auto plane picks gram with no knobs, and
    the result still matches the numpy oracle."""
    # lr is scaled to the least-squares Lipschitz constant (~d/n_data):
    # the TrialSpec default lr=0.05 makes GD divergent at this d, and
    # exponentially growing iterates amplify basis-order rounding past
    # any meaningful value tolerance
    specs = [TrialSpec(byz=(2, 5), attack="sign_flip", steps=40, q=0.4,
                       seed=1, n_data=64, d=4096, lr=64.0 / 4096),
             TrialSpec(byz=(3,), attack="drift", steps=40, q=0.5,
                       seed=2, n_data=64, d=4096, lr=64.0 / 4096)]
    jxb = run_batch(specs, backend="jax")
    assert jxb.plan.data_plane == "gram"
    npb = run_batch(specs)
    for rn, rj in zip(npb, jxb):
        assert rn.identify_step == rj.identify_step
        assert rn.q_trace == rj.q_trace
        # the attack drives iterates to ~1e8 before identification, so
        # EVERY f32 plane agrees with the f64 numpy oracle only to
        # ~1e-3 at this shape (the jax stream planes show the same
        # deviation — this is not gram-specific); the control plane
        # above and the fault verdict below are the exact contract
        np.testing.assert_allclose(rj.w, np.asarray(rn.w),
                                   rtol=1e-2, atol=JAX_W_ATOL)
        assert (rn.final_error < 1e-3) == (rj.final_error < 1e-3)


def test_jax_backend_gram_corners():
    """B=1, adaptive q*=None, steps=0, and a draco-mode vote through the
    gram plane."""
    one = [TrialSpec(byz=(2, 5), attack="sign_flip", steps=60, q=None,
                     seed=3)]                                # adaptive, B=1
    jg = run_batch(one, backend="jax", data_plane="gram")
    assert jg.plan.data_plane == "gram"
    rn = run_batch(one)[0]
    assert rn.identify_step == jg[0].identify_step
    assert rn.q_trace == jg[0].q_trace
    np.testing.assert_allclose(jg[0].w, np.asarray(rn.w),
                               rtol=JAX_W_RTOL, atol=JAX_W_ATOL)

    zero = [TrialSpec(byz=(2,), attack="drift", steps=0, q=0.5)]
    jz = run_batch(zero, backend="jax", data_plane="gram")   # silent demote
    assert jz.plan.data_plane == "stream"
    assert jz[0].final_error == run_batch(zero)[0].final_error

    draco = [TrialSpec(byz=(3,), attack="scale", steps=80, mode="draco",
                       q=None, seed=0)]
    jd = run_batch(draco, backend="jax", data_plane="gram")
    assert jd.plan.data_plane == "gram"
    rd = run_batch(draco)[0]
    assert rd.identify_step == jd[0].identify_step
    np.testing.assert_allclose(jd[0].w, np.asarray(rd.w),
                               rtol=JAX_W_RTOL, atol=JAX_W_ATOL)


def test_jax_backend_gram_device_control():
    """Explicit gram under the on-device control plane: the q*/check
    coins read the loss, and the gram-domain loss rounds differently in
    f32 — the documented reason auto keeps the stream plane here.  For
    these seeds no coin lands inside the last-ulp sliver, so decisions
    agree exactly and the adaptive q* trace agrees to f32 accuracy."""
    specs = [TrialSpec(byz=(2, 5), attack="sign_flip", steps=50, q=0.4,
                       seed=1),
             TrialSpec(byz=(2,), attack="drift", steps=50, q=None, seed=2)]
    jst = run_batch(specs, backend="jax", schedule="device")
    jgr = run_batch(specs, backend="jax", schedule="device",
                    data_plane="gram")
    assert (jst.plan.data_plane, jgr.plan.data_plane) == ("stream", "gram")
    for rs, rg in zip(jst, jgr):
        assert rs.identify_step == rg.identify_step
        np.testing.assert_allclose(rg.q_trace, rs.q_trace,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(rg.w, rs.w, rtol=1e-4, atol=1e-4)


def test_jax_backend_mixed_batch():
    """Non-shared problems (per-trial A, per-problem sketch tables),
    mixed n/f, and non-uniform step counts through the device path."""
    specs = [
        TrialSpec(byz=(2, 5), attack="drift", steps=90, q=0.4, seed=1),
        TrialSpec(byz=(2,), attack="noise", steps=60, q=0.3, seed=9,
                  n=6, f=1, problem_seed=3),
        TrialSpec(byz=(), attack="none", steps=75, q=0.5, seed=4,
                  problem_seed=7),
    ]
    npb = run_batch(specs)
    jxb = run_batch(specs, backend="jax")
    for rn, rj in zip(npb, jxb):
        assert rn.identify_step == rj.identify_step
        assert rn.efficiency == rj.efficiency
        assert len(rn.losses) == len(rj.losses)
        np.testing.assert_allclose(rj.w, np.asarray(rn.w),
                                   rtol=JAX_W_RTOL, atol=JAX_W_ATOL)
        np.testing.assert_allclose(np.asarray(rj.losses),
                                   np.asarray(rn.losses),
                                   rtol=JAX_LOSS_RTOL, atol=JAX_LOSS_ATOL)
