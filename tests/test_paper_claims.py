"""The paper's analytical claims, checked on the numpy engine.

* eq. (2): the measured computation efficiency of the randomized scheme
  is at or above ``1 - q * 2f / (2f + 1)`` (``adaptive.com_eff``) at
  every check probability q; eliminations push it above once the
  Byzantine workers are caught.
* §4.2: under the randomized schedule a worker that tampers with
  probability p is identified almost surely; each seeded trial below
  identifies it well inside its horizon.
"""
import functools

import numpy as np
import pytest

from repro.core import adaptive
from repro.core.engine import TrialSpec, run_batch

F = 2
EFF_QS = (0.05, 0.1, 0.2, 0.3, 0.5, 0.8, 1.0)


@functools.lru_cache(maxsize=1)
def _efficiency_by_q() -> dict:
    batch = run_batch([
        TrialSpec(byz=(2, 5), attack="sign_flip", steps=150, q=q, seed=s,
                  label=f"q{q}/s{s}")
        for q in EFF_QS for s in range(5)
    ])
    by_q: dict = {}
    for spec, r in zip(batch.specs, batch.results):
        by_q.setdefault(spec.q, []).append(r.efficiency)
    return {q: float(np.mean(v)) for q, v in by_q.items()}


@pytest.mark.parametrize("q", EFF_QS)
def test_efficiency_at_or_above_eq2_bound(q):
    assert _efficiency_by_q()[q] >= adaptive.com_eff(q, F)


def test_drift_attacker_identified_within_horizon():
    """n = 8, f = 2, one drift attacker tampering with p = 0.8, checks
    at q = 0.3: every one of 20 seeded trials identifies it before step
    200.  (Their median, 5 steps, is above the 2.5 of
    log 0.5 / log(1 - qp); see PERF.md section 7.)"""
    steps = 200
    batch = run_batch([
        TrialSpec(byz=(4,), attack="drift", steps=steps, q=0.3,
                  p_tamper=0.8, seed=s) for s in range(20)
    ])
    times = np.asarray([r.identify_step.get(4, steps) for r in batch])
    assert (times < steps).all(), times.tolist()
