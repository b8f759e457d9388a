"""The control oracle's products over a shared problem, as GEMMs over the
trials.

``build_schedule(specs, "oracle")`` reads only the control of its numpy
pass, so on a shared problem whose trials' data I·d is at least
``engine.POOL_MIN_ITEM`` it runs the residuals and the phase-1 worker
gradients as GEMMs over the trials (``engine.residuals_gemm``,
``engine.worker_gradients_gemm``).  The schedule it records must equal
the per-trial pass's in every control array and field, with q*_t within
float rounding; the products must equal the per-trial ones to float64
rounding, replicas bit for bit; and the counter
``engine.numpy.batched_products`` must rise there and nowhere else.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import engine
from repro.core.engine import SCENARIOS, ScheduleRecorder, TrialSpec, \
    run_batch
from repro.core.engine_jax import build_schedule
from repro.obs import metrics

B, N_DATA, D, STEPS = 64, 71, 2048, 24


def _batched() -> int:
    return metrics.counter("engine.numpy.batched_products").value


def _specs(seed: int, attack: str = "sign_flip", q=None,
           per_trial_problem: bool = False) -> list[TrialSpec]:
    """The sign_flip cell's protocol (n = 3, f = 1, worker 1 Byzantine)
    at d = 2048, past the size gate."""
    return [TrialSpec(n=3, f=1, byz=(1,), attack=attack, p_tamper=0.5, q=q,
                      mode="randomized", steps=STEPS, lr=16 / D,
                      seed=seed + b, n_data=N_DATA, d=D,
                      problem_seed=seed + (b % 2 if per_trial_problem
                                           else 0))
            for b in range(B)]


def _per_trial_schedule(specs):
    """The oracle's pass with every product per trial: the numpy
    backend's own path, recorded as ``build_schedule`` records it."""
    rec = ScheduleRecorder()
    control = run_batch(specs, _recorder=rec)
    arrays = {k: np.stack([st[k] for st in rec.steps]) for k in rec.steps[0]}
    return arrays, control


CONTROL_CASES = {
    "sign_flip-adaptive-s0": lambda: _specs(2**31 + 17),
    "sign_flip-adaptive-s1": lambda: _specs(90_001),
    "sign_flip-adaptive-s2": lambda: _specs(5),
    # every step a check, and majority votes over replica groups
    "sign_flip-deterministic": lambda: [
        dataclasses.replace(s, mode="deterministic") for s in _specs(41)],
    "sign_flip-draco": lambda: [
        dataclasses.replace(s, mode="draco") for s in _specs(43)],
    # fixed q, and unequal lengths: finished trials' rows in the GEMMs
    "drift-fixedq": lambda: [dataclasses.replace(s, steps=STEPS - b % 5)
                             for b, s in enumerate(
                                 _specs(77, attack="drift", q=0.2))],
}


@pytest.mark.parametrize("case", sorted(CONTROL_CASES))
def test_batched_oracle_control_equals_per_trial_oracle(case):
    specs = CONTROL_CASES[case]()
    before = _batched()
    got = build_schedule(specs, "oracle")
    assert _batched() > before
    before = _batched()
    want_arrays, want = _per_trial_schedule(specs)
    assert _batched() == before
    assert got.arrays.keys() == want_arrays.keys()
    for k in want_arrays:
        assert np.array_equal(got.arrays[k], want_arrays[k]), k
    assert sum(len(r.identify_step) for r in want) > 0   # not vacuous
    for g, w in zip(got.control, want):
        assert g.identify_step == w.identify_step
        assert np.array_equal(g.state.active, w.state.active)
        assert np.array_equal(g.state.identified, w.state.identified)
        gm, wm = g.state.meter, w.state.meter
        assert (gm.used, gm.computed, gm.iterations, gm.check_iterations,
                gm.identify_iterations, gm.history) == (
            wm.used, wm.computed, wm.iterations, wm.check_iterations,
            wm.identify_iterations, wm.history)
        np.testing.assert_allclose(g.q_trace, w.q_trace, rtol=1e-12, atol=0)


def _rel(a, b) -> float:
    return float(np.abs(a - b).max() / np.abs(b).max())


def test_batched_residuals_equal_per_trial_ones():
    rng = np.random.default_rng(11)
    A0 = rng.normal(size=(N_DATA, D))
    y0 = rng.normal(size=N_DATA)
    W = rng.normal(size=(B, D))
    want = engine.residuals(np.broadcast_to(A0, (B, N_DATA, D)),
                            np.broadcast_to(y0, (B, N_DATA)), W)
    got = engine.residuals_gemm(A0, y0, W, np.empty((B, N_DATA, 1)))
    assert _rel(got, want) < 1e-13


def _mixed_assignment(n: int = 3):
    """A batch in the three states the sign_flip cell's steps hold: fast
    (m = 3), a check (one group of two replicas, one worker idle) and
    after an identification (m = 2, the identified worker idle)."""
    shard = np.zeros((B, n), np.int32)
    group = np.zeros((B, n), np.int32)
    m = np.zeros(B, np.int64)
    perm = np.random.default_rng(5).permutation
    for b in range(B):
        kind = b % 3
        if kind == 0:
            shard[b] = group[b] = np.arange(n)
            m[b] = n
        elif kind == 1:
            idle = perm(n)[0]
            group[b] = 0
            group[b, idle] = -1
            m[b] = 1
        else:
            gone = perm(n)[0]
            rank = np.cumsum(np.arange(n) != gone) - 1
            shard[b] = np.where(np.arange(n) == gone, 0, rank)
            group[b] = np.where(np.arange(n) == gone, -1, rank)
            m[b] = n - 1
    return shard, group, m


def test_batched_worker_gradients_equal_per_trial_ones():
    rng = np.random.default_rng(12)
    A0 = rng.normal(size=(N_DATA, D))
    resid = rng.normal(size=(B, N_DATA))
    shard, group, m = _mixed_assignment()
    got = engine.worker_gradients_gemm(A0, resid, shard, group, m,
                                       out=np.empty((B, 3, D)))
    want = np.empty_like(got)
    for mm in np.unique(m):
        sub = np.flatnonzero(m == mm)
        rows = N_DATA // mm
        sg = engine.shard_gradients(
            A0[: mm * rows].reshape(1, mm, rows, D),
            resid[sub, : mm * rows].reshape(len(sub), mm, 1, rows), rows)
        want[sub] = engine.worker_gradients(sg, shard[sub], group[sub])
    assert _rel(got, want) < 1e-13
    assert not got[group < 0].any()                   # idle: zeros
    for b in np.flatnonzero(m == 1):                  # replicas: bitwise
        first, second = np.flatnonzero(group[b] == 0)
        assert np.array_equal(got[b, first], got[b, second])


def test_batched_products_engage_only_in_the_shared_problem_oracle():
    specs = _specs(3)
    before = _batched()
    build_schedule(specs, "oracle")
    # one residual GEMM and one worker-gradient call a step
    assert _batched() - before == 2 * STEPS
    cases = {
        "numpy backend": lambda: run_batch(specs),
        "per-trial-problem oracle": lambda: build_schedule(
            _specs(3, per_trial_problem=True), "oracle"),
        "d = 8 attack_sweep grid": lambda: SCENARIOS["attack_sweep"].run(),
        "d = 8 attack_sweep oracle": lambda: build_schedule(
            SCENARIOS["attack_sweep"].expand(), "oracle"),
        "proxy schedule": lambda: build_schedule(
            _specs(3, attack="drift", q=0.2), "proxy"),
    }
    for name, run in cases.items():
        before = _batched()
        run()
        assert _batched() == before, name


def test_size_gate_keeps_small_problems_per_trial():
    """Just under the gate, the oracle pass is the numpy backend's, bit
    for bit."""
    d = engine.POOL_MIN_ITEM // N_DATA
    assert N_DATA * d < engine.POOL_MIN_ITEM <= N_DATA * (d + 1)
    specs = [dataclasses.replace(s, d=d, lr=16 / d) for s in _specs(9)[:8]]
    before = _batched()
    got = build_schedule(specs, "oracle")
    assert _batched() == before
    want = run_batch(specs)
    for g, w in zip(got.control, want):
        assert np.array_equal(g.w, w.w)
        assert g.q_trace == w.q_trace
