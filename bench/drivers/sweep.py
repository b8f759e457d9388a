"""Engine sweeps: each call is one ``run_batch(specs, backend="jax")`` on
the default plan, the way a researcher sweeps the protocol.

Traffic parameters (the ``traffic`` block of a workload file):

- ``trials_per_call``: trials in one ``run_batch`` call;
- ``steps``: protocol steps of every trial;
- ``attack``: the Byzantine workers' tampering;
- ``q``: the fixed check probability, or null for the adaptive q*_t;
- ``lr_times_d``: the step size times d (the step is ``lr_times_d / d``);
- ``kept_per_call``: trials of each call kept for the check, half of
  them from each half of the call's trials;
- ``min_verified``: the fewest kept trials a sound window yields.

The configuration gives the problem (``n_data``, ``d``) and the protocol
(``n``, ``f``, ``byz``, ``p_tamper``, ``mode``).  Call ``i`` (0 is the
warm-up) runs trials ``seed + i * trials_per_call + b`` on the problem
``seed + i * trials_per_call``: every seed gives the same shapes, and
the same seed the same inputs.

Of each call ``kept_per_call`` trials, drawn from the seed, are kept as
the timed call returned them.  After the window every kept trial is run
through the float64 reference under the random streams of the control
plane the call reports (``plan.control``), and in float32, and compared:
the control record (checks, detections, identified workers and when,
gradients used and computed, q per step) exactly; the final iterate by
its largest deviation relative to the reference's largest entry, over
the same deviation of the reference's float32 run, which measures how
far the trial itself amplifies float32 rounding (a Byzantine worker's
sign flip turns steps into ascent until it is identified, so this
varies a hundredfold between trials); where the cell names it, the loss
before every step by its largest deviation relative to the reference's
first loss.
"""
from __future__ import annotations

import numpy as np

from bench.reference import lsq_protocol


# the float32 run's deviation is never this small at these sizes; the
# floor keeps a ratio over it finite
TINY = 1e-30


def sup_dev(a, b) -> float:
    """max |a - b| / (1 + max |b|); the largest float where ``a`` has
    another shape or a value that is not finite."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    if a.shape != b.shape or not np.isfinite(a).all():
        return float(np.finfo(np.float64).max)
    return float(np.abs(a - b).max() / (1.0 + np.abs(b).max()))


def control_equal(got: dict, ref: dict) -> bool:
    return (got["checks"] == ref["checks"]
            and got["detected"] == ref["detected"]
            and got["identified_at"] == ref["identified_at"]
            and got["used"] == ref["used"]
            and got["computed"] == ref["computed"]
            and len(got["q"]) == len(ref["q"])
            and np.allclose(got["q"], ref["q"], rtol=1e-6, atol=0.0))


def loss_dev(got, ref) -> float:
    """max_t |L_t - L_ref,t| / L_ref,0; the largest float where the
    losses differ in number or are not finite."""
    a, b = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if a.shape != b.shape or not np.isfinite(a).all():
        return float(np.finfo(np.float64).max)
    return float(np.abs(a - b).max() / b[0]) if b.size else 0.0


def compare(kept: list[dict], refs: list[dict], refs32: list[dict],
            limits: dict) -> list[dict]:
    """The checks of a run, those its cell's ``limits`` name: control
    records that differ (``control_mismatch``, limit 0); the final
    iterates' largest deviation over the deviation of the reference's own
    float32 run of the same trial (``w_excess``); the losses' largest
    deviation (``loss_dev``)."""
    pairs = list(zip(kept, refs, refs32))
    numbers = {
        "control_mismatch": sum(not control_equal(g, r)
                                for g, r, _ in pairs),
        "w_excess": max((sup_dev(g["w"], r["w"])
                         / max(sup_dev(r32["w"], r["w"]), TINY)
                         for g, r, r32 in pairs), default=0.0),
        "loss_dev": max((loss_dev(g["losses"], r["losses"])
                         for g, r, _ in pairs), default=0.0),
    }
    checks = [("control_mismatch", numbers["control_mismatch"], 0)] + [
        (name, numbers[name], lim) for name, lim in limits.items()]
    return [{"name": n, "value": v, "limit": lim, "ok": bool(v <= lim)}
            for n, v, lim in checks]


def trial_specs(config: dict, traffic: dict, seed: int, i: int) -> list[dict]:
    """The trials of call ``i`` as plain dicts."""
    prob, proto = config["problem"], config["protocol"]
    B = traffic["trials_per_call"]
    base = seed + i * B
    common = dict(n=proto["n"], f=proto["f"], byz=tuple(proto["byz"]),
                  p_tamper=proto["p_tamper"], mode=proto["mode"],
                  attack=traffic["attack"], q=traffic["q"],
                  steps=traffic["steps"],
                  lr=traffic["lr_times_d"] / prob["d"],
                  n_data=prob["n_data"], d=prob["d"], problem_seed=base)
    return [dict(common, seed=base + b) for b in range(B)]


def kept_trials(pick, traffic: dict) -> list[int]:
    """The trials of one call kept for the check, drawn from ``pick``:
    half from the first half of the call's trials, half from the
    second."""
    B, k = traffic["trials_per_call"], traffic["kept_per_call"]
    lo = pick.choice(B // 2, size=k // 2, replace=False)
    hi = B // 2 + pick.choice(B - B // 2, size=k - k // 2, replace=False)
    return sorted(int(j) for j in np.concatenate([lo, hi]))


def references(kept: list[dict], **kw) -> list[dict]:
    """The reference's run of every kept trial; trials of one problem
    share its draw."""
    problems = {}
    refs = []
    for k in kept:
        s = k["spec"]
        key = (s["problem_seed"], s["n_data"], s["d"])
        if key not in problems:
            problems[key] = lsq_protocol.make_problem(*key[1:], key[0])
        refs.append(lsq_protocol.run_trial(s, streams=k["streams"],
                                           problem=problems[key], **kw))
    return refs


class Driver:
    span_name = "sweep.call"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.core.engine import TrialSpec, run_batch

        self.run_batch = run_batch
        self.TrialSpec = TrialSpec
        self.config, self.traffic, self.seed = config, traffic, seed
        self.items_per_call = traffic["trials_per_call"]
        self.steps = traffic["steps"]
        self.pick = np.random.default_rng([seed, 0x5EED])
        self.kept: list[dict] = []

    def specs(self, i: int) -> list[dict]:
        return trial_specs(self.config, self.traffic, self.seed, i)

    def _call(self, i: int):
        specs = [self.TrialSpec(**s) for s in self.specs(i)]
        return self.run_batch(specs, backend="jax")

    def warm_up(self) -> None:
        self._call(0)

    def call(self, i: int):
        res = self._call(i)
        return self.items_per_call * self.steps, res

    def keep(self, i: int, res) -> None:
        """Trials of the call, drawn from the seed, as the timed call
        produced them."""
        sched = res.schedule.arrays
        specs = self.specs(i)
        for j in kept_trials(self.pick, self.traffic):
            self.kept.append(self._record(specs[j], j, res, sched))

    @staticmethod
    def _record(spec: dict, j: int, res, sched) -> dict:
        r = res.results[j]
        T = len(r.losses)
        return {
            "spec": spec,
            "streams": res.plan.control,
            "w": np.array(r.w, np.float64),
            "losses": [float(x) for x in r.losses],
            "q": [float(x) for x in r.q_trace],
            "checks": [bool(x) for x in sched["checks"][:T, j]],
            "detected": [bool(x) for x in res.detect_flags[:T, j]],
            "identified_at": {int(k): int(v)
                              for k, v in r.identify_step.items()},
            "used": int(r.state.meter.used),
            "computed": int(r.state.meter.computed),
        }

    def release(self) -> None:
        """Results come back to the host whole; nothing stays on device."""

    def verify(self, limits: dict) -> list[dict]:
        """Compares every kept trial; fewer than ``min_verified`` (a
        window whose calls failed) is itself a failed check."""
        want = self.traffic["min_verified"]
        return compare(self.kept, references(self.kept),
                       references(self.kept, dtype=np.float32), limits) + [
            {"name": "verified_trials", "value": len(self.kept),
             "limit": want, "ok": len(self.kept) >= want}]
