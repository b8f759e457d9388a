"""The control of an engine cell's check: the plain reference put in the
program's place and computed one precision below what the configuration
states (float32 at the highest matrix-product precision): float32 with
every product formed like the TPU's three-pass bf16 ("high") precision,
which splits each float32 operand into two bf16 parts and drops the
product of the low parts.  A sound limit reads it as not correct.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--trials 64]

For each seed it draws calls and trials the way a run of the cell
draws them (from its first calls, as many as ``--trials``),
runs the control and the float64 reference on them, and prints the
check's numbers as a run would, one JSON line per seed.  It needs no
accelerator.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import harness  # noqa: E402
from bench.drivers import sweep  # noqa: E402


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bf16 (ties to even), kept in float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def split(a: np.ndarray):
    """float32 ``a`` as a sum of two bf16 parts, high and low."""
    a = np.asarray(a, np.float32)
    hi = bf16(a)
    return hi, bf16(a - hi)


def high_dot(a, b):
    """float32 matmul in three bf16 passes: hi*hi + hi*lo + lo*hi,
    accumulated in float32."""
    ah, al = split(a)
    bh, bl = split(b)
    return ah @ bh + ah @ bl + al @ bh


def control_run(workload: dict, config: dict, seed: int,
                n_trials: int) -> list[dict]:
    """The checks a run of the cell would print with the control in the
    program's place, over the first ``n_trials`` trials a run keeps."""
    traffic = workload["traffic"]
    pick = np.random.default_rng([seed, 0x5EED])
    kept, i = [], 0
    while len(kept) < n_trials:
        i += 1
        call = sweep.trial_specs(config, traffic, seed, i)
        kept += [{"spec": call[j], "streams": "host"}
                 for j in sweep.kept_trials(pick, traffic)]
    kept = kept[:n_trials]
    return sweep.compare(sweep.references(kept, dot=high_dot,
                                          dtype=np.float32),
                         sweep.references(kept),
                         sweep.references(kept, dtype=np.float32),
                         workload["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trials", type=int, default=64,
                    help="kept trials compared, as many as a run keeps")
    args = ap.parse_args(argv)
    workload = harness.read_json(harness.named_file("workloads",
                                                    args.workload, ".json"))
    config = harness.read_json(harness.named_file("configs",
                                                  workload["config"], ".json"))
    for seed in args.seeds:
        checks = control_run(workload, config, seed, args.trials)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": all(c["ok"] for c in checks),
                          "checks": {c["name"]: c["value"] for c in checks}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
