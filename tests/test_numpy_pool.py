"""The numpy engine's trial-chunked products on the host thread pool.

Large batches run residuals, shard gradients, aggregation and the
update in trial chunks on a pool (``engine.trial_chunks``); each chunk
issues the per-item BLAS calls of the unsplit batch, so every trial
still equals the serial ``run_protocol`` bit for bit, and each
primitive equals the plain numpy expression of its math.  The pool engages
only when each thread gets at least two trials and a trial's data (I·d)
is at least ``engine.POOL_MIN_ITEM``: the d = 8 scenario grids and the
proxy schedule stay serial.
"""
import sys

import numpy as np
import pytest

from repro.core import engine
from repro.core.engine import SCENARIOS, TrialSpec, run_batch
from repro.core.engine_jax import build_schedule
from repro.core.simulation import run_protocol
from repro.obs import metrics

B, N_DATA, D = 32, 64, 1024
SAMPLED = (0, 9, 22, 31)


def _pooled() -> int:
    return metrics.counter("engine.numpy.pooled_products").value


@pytest.fixture
def own_pool(monkeypatch):
    """A pool of the test's own, made at the thread count it patches
    into ``engine.pool_threads``; the module's pool comes back after."""
    monkeypatch.setattr(engine, "_pool", None)
    yield monkeypatch
    if engine._pool is not None:
        engine._pool.shutdown()


def _specs(attack: str, q, per_trial) -> list[TrialSpec]:
    return [TrialSpec(n=3, f=1, byz=(1,), attack=attack, p_tamper=0.5,
                      q=q, mode="randomized", lr=16 / D, seed=100 + b,
                      n_data=N_DATA, d=D, **per_trial(b))
            for b in range(B)]


CASES = {
    # the sign_flip cell's protocol: adaptive q*, one shared problem
    "sign_flip-adaptive": lambda: _specs(
        "sign_flip", None, lambda b: dict(steps=10)),
    # fixed q, two problems and unequal lengths: the per-trial data rows
    # and the frozen iterates of finished trials
    "drift-fixedq": lambda: _specs(
        "drift", 0.3, lambda b: dict(steps=8 + b % 3, problem_seed=b % 2)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pooled_batch_equals_serial_run_protocol(case, own_pool):
    own_pool.setattr(engine, "pool_threads", lambda: 4)
    specs = CASES[case]()
    before = _pooled()
    out = run_batch(specs)
    assert _pooled() > before
    assert metrics.gauge("engine.numpy.pool_threads").value == 4
    for b in SAMPLED:
        ref = run_protocol(**specs[b].protocol_kwargs())
        got = out[b]
        assert np.array_equal(got.w, ref.w), b
        assert got.losses == ref.losses, b
        assert got.q_trace == ref.q_trace, b
        assert got.identify_step == ref.identify_step, b


def test_small_d_scenario_grid_stays_serial(monkeypatch):
    monkeypatch.setattr(engine, "pool_threads", lambda: 4)
    matrix = SCENARIOS["attack_sweep"]
    assert len(matrix.expand()) >= 2 * 4          # the trial count alone
    assert matrix.n_data * matrix.d < engine.POOL_MIN_ITEM   # would pool
    before = _pooled()
    matrix.run()
    assert _pooled() == before


def test_oracle_schedule_same_with_and_without_pool(own_pool):
    specs = CASES["sign_flip-adaptive"]()
    own_pool.setattr(engine, "pool_threads", lambda: 4)
    before = _pooled()
    pooled = build_schedule(specs, "oracle")
    assert _pooled() > before
    own_pool.setattr(engine, "pool_threads", lambda: 1)
    before = _pooled()
    serial = build_schedule(specs, "oracle")
    assert _pooled() == before
    assert pooled.arrays.keys() == serial.arrays.keys()
    for k in pooled.arrays:
        assert np.array_equal(pooled.arrays[k], serial.arrays[k]), k
    for a, b in zip(pooled.control, serial.control):
        assert np.array_equal(a.w, b.w)
        assert a.losses == b.losses and a.q_trace == b.q_trace
        assert a.identify_step == b.identify_step


def _operands(S: int, I: int, d: int, seed: int):
    rng = np.random.default_rng(seed)
    A = np.broadcast_to(rng.normal(size=(I, d)), (S, I, d))
    return dict(
        A=A, y=rng.normal(size=(S, I)), W=rng.normal(size=(S, d)),
        rr=rng.normal(size=(S, 2, 1, I // 2)),
        Ar=A[0].reshape(1, 2, I // 2, d),
        weight=rng.random((S, 3)).astype(np.float32),
        grads=rng.normal(size=(S, 3, d)), lr=rng.random(S),
        live=rng.random(S) < 0.8)


@pytest.mark.parametrize("pooled", [False, True])
def test_primitives_equal_their_plain_numpy_expressions(pooled, own_pool):
    """Each primitive, serial or in slices on the pool, equals the plain
    batched expression of its math bit for bit: one per-item matmul a
    trial, then the elementwise steps in the same order."""
    own_pool.setattr(engine, "pool_threads", lambda: 4)
    S, I, d = 16, 16, 1024
    chunks = engine.trial_chunks(S, I * d) if pooled else None
    assert (chunks is not None) == pooled
    o = _operands(S, I, d, seed=3)
    A, y, W, rr, Ar = o["A"], o["y"], o["W"], o["rr"], o["Ar"]
    weight, grads, lr, live = o["weight"], o["grads"], o["lr"], o["live"]
    assert np.array_equal(
        engine.residuals(A, y, W, chunks=chunks),
        np.matmul(A, W[:, :, None])[:, :, 0] - y)
    assert np.array_equal(
        engine.shard_gradients(Ar, rr, I // 2, chunks),
        2.0 * np.matmul(rr, Ar)[:, :, 0, :] / (I // 2))
    upd = engine.aggregate(weight, grads, chunks)
    assert np.array_equal(upd, np.matmul(weight[:, None, :], grads)[:, 0, :])
    assert np.array_equal(engine._step(W, lr, upd, live, chunks),
                          np.where(live[:, None], W - lr[:, None] * upd, W))


def test_slices_hold_under_thread_switch_stress(own_pool):
    """Twice as many threads as CPUs, switching every microsecond: the
    slices write disjoint rows of one output, so each pooled primitive
    still equals its serial call bit for bit."""
    threads = 2 * engine.pool_threads()
    own_pool.setattr(engine, "pool_threads", lambda: threads)
    S, I, d = 4 * threads, 16, 1024
    assert I * d >= engine.POOL_MIN_ITEM
    chunks = engine.trial_chunks(S, I * d)
    assert chunks is not None and len(chunks) == threads
    o = _operands(S, I, d, seed=7)
    A, y, W, rr, Ar = o["A"], o["y"], o["W"], o["rr"], o["Ar"]
    weight, grads, lr, live = o["weight"], o["grads"], o["lr"], o["live"]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(3):
            assert np.array_equal(
                engine.residuals(A, y, W, out=np.empty((S, I, 1)),
                                 chunks=chunks),
                engine.residuals(A, y, W))
            assert np.array_equal(
                engine.shard_gradients(Ar, rr, I // 2, chunks),
                engine.shard_gradients(Ar, rr, I // 2))
            upd = engine.aggregate(weight, grads, chunks)
            assert np.array_equal(upd, engine.aggregate(weight, grads))
            assert np.array_equal(engine._step(W, lr, upd, live, chunks),
                                  engine._step(W, lr, upd, live, None))
    finally:
        sys.setswitchinterval(old)
