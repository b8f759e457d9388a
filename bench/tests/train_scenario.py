"""Whole runs of the trainer cell on the CPU at a tiny Qwen3, past the
harness's look for a chip (run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4: one worker a host
device).

    python3 bench/tests/train_scenario.py <case> [<case> ...]

Cases: ``sound``; three faults planted under the timed path
(``update_skipped``: the parameters and moments of the first kept step
restored after it, as if its update were skipped; ``shard_dropped``:
worker 0's gradient left out of every aggregate; ``coin_ignored``: every
step made fast); ``control``: the sound run's kept steps recomputed by
the reference with fp8 matrix products in place of the program's, judged
by the driver's ``verify`` under the workload's limits.
Prints one ``RESULT <json>`` line: each case's checks and whether they
all held.
"""
import copy
import dataclasses
import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench import harness  # noqa: E402

CELL = "qwen3-4b-bft4.honest-q25"
SEED = 2**31 + 4321
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
        "num_hidden_layers": 2}
# Limits at this size, from the readings on the CPU (seeds 2**31 + 77,
# 3000000123 and SEED): sound runs read loss_dev up to 8.6e-05, grad_dev
# up to 0.0104 and update_dev up to 0.048; the fp8 control reads grad_dev
# 0.13-0.16 and update_dev 0.18-0.20, and its loss_dev (6.8e-05 to
# 1.2e-03) overlaps the sound runs' at this size, so loss_dev's limit
# here only bounds a sound run.
TINY_LIMITS = {"loss_dev": 1e-3, "grad_dev": 0.04, "update_dev": 0.1}


def small():
    wl = copy.deepcopy(harness.read_json(
        harness.named_file("workloads", CELL, ".json")))
    cfg = copy.deepcopy(harness.read_json(
        harness.named_file("configs", wl["config"], ".json")))
    cfg["model"].update(TINY)
    wl["traffic"]["seq_len"] = 32
    wl["limits"] = dict(TINY_LIMITS)
    return wl, cfg


def update_skipped(driver):
    call = driver.call

    def skip(i):
        tr = driver.trainer
        if i != 1:
            return call(i)
        saved = jax.tree.map(jax.numpy.copy, (tr.params, tr.opt_state))
        out = call(i)
        tr.params, tr.opt_state = saved
        return out
    driver.call = skip


def shard_dropped(driver):
    tr = driver.trainer
    dispatch = tr._dispatch

    def drop(mode, a, batch):
        w = a.weight.copy()
        w[0] = 0.0
        return dispatch(mode, dataclasses.replace(a, weight=w), batch)
    tr._dispatch = drop


def coin_ignored(driver):
    st = driver.trainer.state

    def fast(loss=None):
        st.decide_rng.random()
        st.last_q = st.check_probability(loss)
        return False
    st.decide_check = fast


FAULTS = {"update_skipped": update_skipped, "shard_dropped": shard_dropped,
          "coin_ignored": coin_ignored}


def run(fault=None):
    """One run of the small cell; returns (result line, driver)."""
    wl, cfg = small()
    files = {harness.named_file("workloads", CELL, ".json"): wl,
             harness.named_file("configs", wl["config"], ".json"): cfg}
    read, load = harness.read_json, harness.load_module
    made = []

    def load_module(kind, name):
        mod = load(kind, name)
        if kind == "drivers":
            init = mod.Driver.__init__

            def __init__(self, *args):
                init(self, *args)
                made.append(self)
                if fault is not None:
                    fault(self)
            mod.Driver.__init__ = __init__
        return mod

    harness.read_json = lambda path: files.get(path) or read(path)
    harness.accelerators = lambda chips: (jax.devices()[:chips], "cpu",
                                          "cpu", None)
    harness.load_module = load_module
    try:
        out = harness.run(CELL, SEED, 1.0, False,
                          t_start=time.perf_counter())
    finally:
        harness.read_json, harness.load_module = read, load
    return out, made[0]


def main(cases) -> None:
    out = {}
    sound = None
    for case in cases:
        if case == "control":
            driver = sound or run()[1]
            checks = driver.verify(small()[0]["limits"], control=True)
            out[case] = {"correct": all(c["ok"] for c in checks),
                         "checks": {c["name"]: {"value": c["value"],
                                                "limit": c["limit"]}
                                    for c in checks}}
            continue
        line, driver = run(FAULTS.get(case))
        if case == "sound":
            sound = driver
        out[case] = {"correct": line["correct"], "checks": line["checks"],
                     "metrics": sorted(line["metrics"])}
    print("RESULT " + json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
