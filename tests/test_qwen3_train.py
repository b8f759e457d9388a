"""Qwen3 as a BFT training job against the plain float32 reference
(``tests/qwen3_reference.py``), and the trainer's spans and counters.

The loss and its gradient are compared in this process at the reduced
``qwen3-4b`` in float32; the steps of ``Trainer`` over 4 workers run in
a subprocess with 4 host devices (tests/scenarios/qwen3_train_scenario.py).
Tolerances are float32 rounding: the program and the reference order
their sums differently (blockwise attention, fused norms), which moves
a loss by about 1e-7 relative and an AdamW update, whose first step is
about the sign of each gradient element, by about 1e-4 relative.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import qwen3_reference as ref
from repro.configs import get_config
from repro.data import global_batch_for_step
from repro.models import model as M

SCENARIO = os.path.join(os.path.dirname(__file__), "scenarios",
                        "qwen3_train_scenario.py")
SPANS = ("train.step", "train.batch", "train.put", "train.compile",
         "train.sync")


def test_train_loss_and_gradient_equal_the_reference():
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(3))
    batch = global_batch_for_step(cfg, global_batch=3, seq_len=24, step=5,
                                  seed=11)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(M.train_loss, has_aux=True)(
            params, batch, cfg)
    loss_r, grads_r = ref.loss_and_grad(ref.from_program(params),
                                        batch["tokens"], batch["labels"],
                                        ref.model_block(cfg))
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    got = ref.from_program(grads)
    assert set(got) == set(grads_r)
    assert len(jax.tree.leaves(grads)) == len(got)
    for k in got:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(grads_r[k]),
                                   rtol=1e-3, atol=1e-6, err_msg=k)


@pytest.fixture(scope="module")
def results():
    proc = subprocess.run([sys.executable, SCENARIO], capture_output=True,
                          text=True, timeout=900,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, proc.stdout[-4000:]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("i,kind", [(0, "fast"), (1, "check")])
def test_trainer_step_equals_the_reference_step(results, i, kind):
    step = results["steps"][i]
    assert step["kind"] == kind
    assert step["loss"] <= 1e-5
    assert step["mu"] <= 1e-5
    assert step["update"] <= 1e-3


def test_each_span_once_per_compiled_step(results):
    spans = results["spans"]
    # two steps: a fast step, then a check step; each dispatched once
    assert spans == {**{s: 2 for s in SPANS}, "train.fast": 1,
                     "train.check": 1}


def test_counters(results):
    assert results["counters"] == {
        "train.steps.fast": 1, "train.steps.check": 1,
        "train.step_cache_misses": 2,
        "train.tokens": 2 * results["tokens_per_step"]}


def test_identify_step_spans_and_fault_counter(results):
    ident = results["identify"]
    assert ident["identified"] == [3]
    # one step, one global batch, two compiled steps on it
    assert ident["spans"] == {**{s: 2 for s in SPANS}, "train.step": 1,
                              "train.batch": 1, "train.check": 1,
                              "train.identify": 1}
    assert ident["counters"] == {
        "train.steps.check": 1, "train.steps.identify": 1,
        "train.faults_detected": 1, "train.step_cache_misses": 2,
        "train.tokens": results["tokens_per_step"]}


def test_outputs_bitwise_equal_under_the_profiler(results):
    assert results["profiled_losses_equal"] is True
    assert results["profiled_params_equal"] is True

