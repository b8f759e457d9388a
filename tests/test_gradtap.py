"""The gradient reduced in the backward pass (``repro.train.gradtap``)
against the whole-tree reduction after it, in the fast and check steps
over 4 workers (subprocess ``tests/scenarios/gradtap_scenario.py`` on 4
host devices, one a model, run side by side).

Agreement is f32 rounding: each piece is the same bf16-or-f32 worker
gradient summed over the workers in f32, and only the two uses of a tied
embedding are summed in another order.  In bf16 that order shows: the
tied table's looked-up rows differ, every other leaf is equal bit for
bit, and the taps' sum is the nearer to the f32 model's.  The noise attack draws its
normals per tap, so its values differ from the whole-tree draws; its
draws are checked for their spread and mean instead.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.core import byzantine

SCENARIO = os.path.join(os.path.dirname(__file__), "scenarios",
                        "gradtap_scenario.py")
# the tied model (a scanned group, the sparse lookup) and the untied one
# (unrolled repeats, the dense lookup) share the attack kinds between them;
# an untied model with a larger vocabulary takes the sparse lookup, or the
# dense one under an attack whose bias reaches every row
CASES = {"tied": ["honest", "sign_flip", "zero", "constant_drift", "noise"],
         "untied": ["honest", "scale", "inf", "noise"],
         "untied512": ["sign_flip", "constant_drift"]}
SCALE = 10.0


# the models compared leaf by leaf in bfloat16, each a subprocess of its own
BF16 = ["tied", "untied"]


@pytest.fixture(scope="module")
def results():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    runs = {**{m: [m, *cases] for m, cases in CASES.items()},
            **{"bf16:" + m: [m, "bf16"] for m in BF16}}
    procs = {m: subprocess.Popen([sys.executable, SCENARIO, *args],
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE, text=True, env=env)
             for m, args in runs.items()}
    out = {}
    for m, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=900)
        assert proc.returncode == 0, stderr[-4000:]
        line = [x for x in stdout.splitlines() if x.startswith("RESULT ")]
        assert line, stdout[-4000:]
        out[m] = json.loads(line[-1][len("RESULT "):])
    return out


def test_every_attack_kind_is_a_case():
    assert sorted({c for cs in CASES.values() for c in cs} - {"honest"}) \
        == sorted(set(byzantine.ATTACKS) - {"none"})


@pytest.mark.parametrize("model,case", [(m, c) for m, cs in CASES.items()
                                        for c in cs])
def test_tapped_steps_equal_the_whole_tree_reduction(results, model, case):
    r = results[model]["cases"][case]
    for step in ("fast", "check"):
        s = r[step]
        assert s["loss"] == 0.0, step
        if step == "check":
            assert s["any_fault"][0] == s["any_fault"][1]
            assert s["group_fault"][0] == s["group_fault"][1]
            # only worker 3's group is faulty, and a faulty step keeps
            # the parameters
            assert s["group_fault"][0] == (
                [False, False] if case == "honest" else [False, True])
            assert s["updated"] == (case == "honest")
        else:
            assert s["updated"]
        if case == "noise" and step == "fast":
            continue            # other draws: see test_noise_...
        assert s["params"] <= 1e-6, step
        assert s["mu"] <= 1e-6, step
        assert s["nu"] <= 1e-6, step
        assert s["grad_norm"] <= 1e-6, step


@pytest.mark.parametrize("model,case,sparse", [
    ("tied", "constant_drift", True), ("untied", "honest", False),
    ("untied512", "sign_flip", True), ("untied512", "constant_drift", False)])
def test_lookup_route_from_shapes(results, model, case, sparse):
    # the sparse route all-gathers the lookup's rows; the whole-tree
    # reduction never gathers
    r = results[model]["cases"][case]
    assert r["sparse"] is sparse
    assert r["old_gathers"] is False


@pytest.mark.parametrize("model", BF16)
def test_bf16_pieces_equal_the_whole_tree_but_the_tied_lookup(results,
                                                               model):
    # in bf16 every piece is the worker's bf16 gradient summed in f32 as
    # the whole-tree reduction sums it, bit for bit; only the rows of a
    # tied table that the batch looks up differ: there the taps sum the
    # head's and the lookup's parts in f32 apiece, where the whole tree
    # first adds them (and the lookup's repeated rows) in bf16 inside
    # each worker, so the taps come no further from the f32 model's sum
    leaves = results["bf16:" + model]["bf16"]
    tied = "['embed']['tokens'].looked_up"
    for leaf, r in leaves.items():
        if model == "tied" and leaf in (tied, "['embed']['tokens']"):
            assert r["tap~whole"] > 0, leaf
            assert r["tap~f32"] <= r["whole~f32"], leaf
        else:
            assert r["tap~whole"] == 0.0, leaf
    assert tied in leaves


def test_groups_scanned_and_unrolled(results):
    # nine repeats keep their scan, two are unrolled; each has a tail
    assert results["tied"]["groups"] == [9, 1]
    assert results["untied"]["groups"] == results["untied512"]["groups"] \
        == [2, 1]


def test_noise_once_per_coordinate_at_the_attack_scale(results):
    n = results["tied"]["noise"]
    assert n["deterministic"] is True
    # one draw a coordinate: sd SCALE (two draws would read SCALE * 1.41),
    # mean within 4 standard errors of 0 on every leaf
    assert n["std"]
    for leaf, sd in n["std"].items():
        assert abs(sd - SCALE) <= 0.05 * SCALE, leaf
    for leaf, z in n["mean_over_se"].items():
        assert abs(z) <= 4.0, leaf


def test_reduce_gauges_split_the_tree(results):
    g = results["tied"]["gauges"]
    inside = g["gauges"]["train.grad_reduce_in_backward_bytes.fast"]
    after = g["gauges"]["train.grad_reduce_after_backward_bytes.fast"]
    assert inside + after == g["total"]
    # after the backward: the leaves outside the layers and the embedding
    assert after == g["tree_level"] > 0
