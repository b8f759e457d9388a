"""Qwen3 under 4-worker BFT training against the plain reference, and
the trainer's spans and counters (run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4).

In one process:
  1. a fast and a check step of ``Trainer`` (randomized, n=4, f=1,
     AdamW) on ``qwen3-4b`` reduced, in float32, each against the
     reference's full-batch step from the same parameters and moments;
  2. the spans and counters of those two steps;
  3. a check step that finds a Byzantine worker and the identify step
     after it;
  4. the three steps of 1-2 (and one more) again, from the same seed,
     under a JAX profiler trace.

Prints one ``RESULT <json>`` line; the pytest wrapper asserts on it.
"""
import dataclasses
import json
import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(HERE, "..", "..", "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import qwen3_reference as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.randomized import BFTConfig, decide_generator  # noqa: E402
from repro.data import global_batch_for_step  # noqa: E402
from repro.obs import metrics, trace  # noqa: E402
from repro.optim import OptConfig  # noqa: E402
from repro.sharding import make_mesh  # noqa: E402
from repro.train import AttackConfig, Trainer, TrainerConfig  # noqa: E402

N = 4
CFG = dataclasses.replace(get_config("qwen3-4b").reduced(), dtype="float32")
OPT = OptConfig(kind="adamw", peak_lr=1e-3, warmup_steps=5, total_steps=50)
TC = dict(seq_len=16, global_batch=4, log_every=0)
Q = 0.5


def first_seed_with(kinds: list[bool]) -> int:
    """The first protocol seed whose coin at Q gives ``kinds``."""
    return next(s for s in range(1000)
                if list(decide_generator(s).random(len(kinds)) < Q) == kinds)


def trainer(seed: int, byz: int | None = None, q: float = Q) -> Trainer:
    mask = np.zeros(N, bool)
    if byz is not None:
        mask[byz] = True
    return Trainer(
        CFG, OPT, BFTConfig(n=N, f=1, mode="randomized", q=q, seed=seed),
        make_mesh((N, 1), ("data", "model")),
        TrainerConfig(seed=seed, **TC),
        attack=AttackConfig(kind="none" if byz is None else "sign_flip"),
        true_byzantine=mask)


def host(tree) -> dict:
    return {k: np.asarray(v) for k, v in ref.from_program(
        jax.device_get(tree)).items()}


def rel(a: dict, b: dict) -> float:
    num = sum(float(np.sum((a[k] - np.asarray(b[k])) ** 2)) for k in a)
    den = sum(float(np.sum(np.asarray(b[k]) ** 2)) for k in a)
    return float(np.sqrt(num / den))


def against_reference(tr: Trainer) -> dict:
    """One step of ``tr`` and the reference's step from the same state:
    relative deviations of the loss, the first moment and the update."""
    step = tr.state.step
    theta, mu, nu = (host(tr.params), host(tr.opt_state["mu"]),
                     host(tr.opt_state["nu"]))
    rec = tr.train_step()
    batch = global_batch_for_step(CFG, global_batch=TC["global_batch"],
                                  seq_len=TC["seq_len"], step=step,
                                  seed=tr.tc.seed)
    m = ref.model_block(CFG)
    loss, g = ref.loss_and_grad(theta, batch["tokens"], batch["labels"], m)
    theta_r, mu_r, _ = ref.adamw_step(OPT, step, theta, mu, nu, g)
    theta_a = host(tr.params)
    return {"kind": "check" if rec["efficiency"] < 1 else "fast",
            "loss": abs(rec["loss"] - float(loss)) / abs(float(loss)),
            "mu": rel(host(tr.opt_state["mu"]), mu_r),
            "update": rel({k: theta_a[k] - theta[k] for k in theta},
                          {k: np.asarray(theta_r[k]) - theta[k]
                           for k in theta})}


def counts() -> dict:
    """The trainer's counters (its gauges are checked in
    tests/test_gradtap.py)."""
    return {k: v["value"] for k, v in metrics.snapshot().items()
            if k.startswith("train.") and v["kind"] == "counter"}


def span_counts() -> dict:
    out: dict = {}
    for s in trace.spans():
        out[s["name"]] = out.get(s["name"], 0) + 1
    return out


def main() -> None:
    out = {}
    seed = first_seed_with([False, True])
    # 1-2: a fast then a check step against the reference, with spans
    metrics.reset()
    trace.clear()
    tr = trainer(seed)
    out["steps"] = [against_reference(tr), against_reference(tr)]
    out["spans"], out["counters"] = span_counts(), counts()
    out["tokens_per_step"] = TC["global_batch"] * TC["seq_len"]
    tr.train_step()
    plain = ([h["loss"] for h in tr.history], jax.device_get(tr.params))

    # 3: worker 3 flips its gradient's sign; q = 1 checks the first step
    metrics.reset()
    trace.clear()
    rec = trainer(0, byz=3, q=1.0).train_step()
    out["identify"] = {"identified": rec.get("identified"),
                       "spans": span_counts(), "counters": counts()}

    # 4: the same three steps as in 1-2 under the profiler
    tr = trainer(seed)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(3):
            tr.train_step()
        jax.profiler.stop_trace()
    out["profiled_losses_equal"] = plain[0] == [h["loss"] for h in tr.history]
    out["profiled_params_equal"] = all(
        np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(plain[1]),
            jax.tree.leaves(jax.device_get(tr.params))))
    print("RESULT " + json.dumps(out))


if __name__ == "__main__":
    main()
