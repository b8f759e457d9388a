"""A whole run on the CPU at a small size, past the harness's look for a
chip: sound, it reads correct; with the timed path broken underneath in
each way an engine sweep can break, it reads not correct."""
import copy
import dataclasses
import time

import numpy as np
import pytest

from bench import control, harness

CELLS = ["riboflavin-n3f1.drift-fixedq", "riboflavin-n3f1.signflip-adaptive"]


# The limits at the tests' size (8 trials a call, at most 24 steps), from
# their readings on the CPU: the sound run of ``run`` reads w_excess 1.20
# and 1.65 (drift, sign_flip) and loss_dev 1.4e-07 (drift); the control
# on its seed reads w_excess 20.2 and 18.8 and loss_dev 1.8e-06.
SMALL_LIMITS = {"w_excess": 4.0, "loss_dev": 3e-7}


def small(cell):
    wl = copy.deepcopy(harness.read_json(
        harness.named_file("workloads", cell, ".json")))
    cfg = harness.read_json(harness.named_file("configs", wl["config"],
                                               ".json"))
    wl["traffic"]["trials_per_call"] = 8
    wl["traffic"]["steps"] = min(wl["traffic"]["steps"], 24)
    wl["traffic"]["kept_per_call"] = 2
    wl["traffic"]["min_verified"] = 2
    wl["limits"] = {k: SMALL_LIMITS[k] for k in wl["limits"]}
    return wl, cfg


@pytest.fixture
def run(monkeypatch):
    """One run of a cell at the small size on the CPU; ``fault(specs,
    call)`` stands between the driver and the program's ``run_batch``."""
    import jax

    def go(cell, fault=None, seconds=0.5):
        wl, cfg = small(cell)
        files = {harness.named_file("workloads", cell, ".json"): wl,
                 harness.named_file("configs", wl["config"], ".json"): cfg}
        read = harness.read_json
        monkeypatch.setattr(harness, "read_json",
                            lambda path: files.get(path) or read(path))
        monkeypatch.setattr(harness, "accelerators", lambda chips: (
            jax.devices()[:chips], "cpu", "cpu", None))
        if fault is not None:
            load = harness.load_module

            def load_module(kind, name):
                mod = load(kind, name)
                if kind == "drivers":
                    init = mod.Driver.__init__

                    def __init__(self, *args):
                        init(self, *args)
                        rb = self.run_batch
                        self.run_batch = lambda specs, **kw: fault(
                            specs, lambda s: rb(s, **kw))
                    mod.Driver.__init__ = __init__
                return mod
            monkeypatch.setattr(harness, "load_module", load_module)
        return harness.run(cell, 2**31 + 77, seconds, False,
                           t_start=time.perf_counter())
    return go


def unchanged_state(specs, call):
    res = call(specs)
    for r in res.results:
        r.w = np.zeros_like(r.w)
    return res


def half_the_batch(specs, call):
    """Every gradient taken over half of the problem's rows: the first
    half of the data matrix (a problem of n_data / 2 rows draws the same
    first rows)."""
    return call([dataclasses.replace(s, n_data=s.n_data // 2)
                 for s in specs])


def altered_answer(specs, call):
    res = call(specs)
    for r in res.results:
        r.w = r.w * (1 + 1e-3)
    return res


def second_half_altered(specs, call):
    """An answer altered where it is produced, in half of the trials."""
    res = call(specs)
    for r in res.results[len(res.results) // 2:]:
        r.w = r.w * (1 + 1e-3)
    return res


def flipped_detection(specs, call):
    res = call(specs)
    res.detect_flags[0] = ~res.detect_flags[0]
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(run, cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "trial_steps_per_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [unchanged_state, half_the_batch,
                                   altered_answer, second_half_altered,
                                   flipped_detection])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(run, cell, fault):
    out = run(cell, fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_failing_calls_are_counted_and_not_correct(run, cell):
    calls = []

    def fail(specs, call):
        calls.append(1)
        if len(calls) > 1:                 # past the warm-up
            raise RuntimeError("broken")
        return call(specs)

    out = run(cell, fail)
    assert not out["correct"]
    assert out["failed"] == out["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_is_not_correct(cell):
    wl, cfg = small(cell)
    checks = control.control_run(wl, cfg, 2**31 + 5, 8)
    assert not all(c["ok"] for c in checks), checks
