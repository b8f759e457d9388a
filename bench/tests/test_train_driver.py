"""The trainer cell on the CPU: a sound run of the train driver at a tiny
Qwen3 reads correct, each planted fault and the fp8 control read not
correct (bench/tests/train_scenario.py, in a subprocess with 4 host
devices); the reference against its tier-1 copy and the program's
token stream; the cell's readers on hand-made inputs."""
import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import counts, harness
from bench.drivers import train as driver
from bench.reference import qwen3_train as ref

HERE = os.path.dirname(os.path.abspath(__file__))
CASES = ["sound", "update_skipped", "shard_dropped", "coin_ignored",
         "control"]


@pytest.fixture(scope="module")
def runs():
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "train_scenario.py"), *CASES],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, proc.stdout[-4000:]
    return json.loads(line[-1][len("RESULT "):])


def test_sound_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert out["checks"]["protocol_mismatch"]["value"] == 0
    assert out["checks"]["kept_steps"]["value"] == 2
    assert out["metrics"] == ["setup_s", "trial_steps_per_s"]


@pytest.mark.parametrize("case", CASES[1:])
def test_planted_fault_is_not_correct(runs, case):
    assert not runs[case]["correct"], runs[case]["checks"]


def _tests_copy():
    spec = importlib.util.spec_from_file_location(
        "qwen3_reference", os.path.join(harness.ROOT, "tests",
                                        "qwen3_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reference_copies_agree():
    import jax

    m = {"hidden_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 48,
         "vocab_size": 64, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
         "rope_theta": 1e6}
    rng = np.random.default_rng(5)
    shapes = {"embed": (64, 32), "final_norm": (32,), "attn_norm": (2, 32),
              "mlp_norm": (2, 32), "wq": (2, 32, 32), "wk": (2, 32, 16),
              "wv": (2, 32, 16), "wo": (2, 32, 32), "q_norm": (2, 8),
              "k_norm": (2, 8), "gate": (2, 32, 48), "up": (2, 32, 48),
              "down": (2, 48, 32)}
    p = {k: (rng.standard_normal(s) * 0.2 + (1.0 if "norm" in k else 0.0))
         .astype(np.float32) for k, s in shapes.items()}
    tokens, labels = ref.token_batch(9, 3, 2, 12, 64)
    loss, g = ref.loss_and_grad(p, tokens, labels, m)
    loss_t, g_t = _tests_copy().loss_and_grad(p, tokens, labels, m)
    # float32 rounding apart: one sums the sequences' gradients, the
    # other differentiates their mean, and recomputes no activation
    assert loss == pytest.approx(float(loss_t), rel=1e-6)
    for k in shapes:
        np.testing.assert_allclose(np.asarray(jax.device_get(g[k])),
                                   np.asarray(g_t[k]), rtol=1e-4, atol=1e-6)


def test_token_batch_is_the_programs_stream():
    from repro.configs import get_config
    from repro.data import global_batch_for_step

    cfg = get_config("qwen3-4b")
    want = global_batch_for_step(cfg, global_batch=3, seq_len=40, step=17,
                                 seed=2**31 + 9)
    tokens, labels = ref.token_batch(2**31 + 9, 17, 3, 40, cfg.vocab_size)
    assert np.array_equal(tokens, want["tokens"])
    assert np.array_equal(labels, want["labels"])


def test_check_coin_and_counts_are_the_protocols():
    from repro.core.randomized import BFTConfig, ProtocolState

    st = ProtocolState.create(BFTConfig(n=4, f=1, q=0.25, seed=2**31 + 3))
    coin = ref.check_coin(2**31 + 3, 0.25, 64)
    for c in coin:
        if st.decide_check(1.0):
            a = st.assignment_check()
            st.meter.record(a.num_shards, a.gradients_computed(), checked=True)
        else:
            a = st.assignment_fast()
            st.meter.record(a.num_shards, a.gradients_computed())
    assert [e < 1 for e in st.meter.history] == list(coin)
    assert ref.efficiency_counts(coin, 4, 1) == (st.meter.used,
                                                 st.meter.computed)
    # a fast step uses and computes 4; a check step computes 2 shards twice
    assert ref.efficiency_counts([False, True], 4, 1) == (6, 8)


def test_warm_up_steps():
    F, C = False, True
    coin = np.array([C, F, F, F, F, F, F, F, F, F, C, F, C, C, F])
    # at least 4 steps, a check after the first step (step 10), then a
    # fast and a check step (11, 12)
    assert driver.warm_up_steps(coin, 4) == 11
    # from 12: steps 12 and 13 are both checks, 13 and 14 differ
    assert driver.warm_up_steps(coin, 12) == 13


# two calls, 0-200 ms and 300-600 ms, of a window of 1 s: the time
# between them (the driver's host copies) is in no reader's denominator
CALLS = [(0, 200_000_000), (300_000_000, 600_000_000)]


def _ctx(spans, window_s=1.0, trace=None, calls=CALLS):
    cfg = harness.read_json(harness.named_file("configs", "qwen3-4b-bft4",
                                               ".json"))
    wl = harness.read_json(harness.named_file(
        "workloads", "qwen3-4b-bft4.honest-q25", ".json"))
    return types.SimpleNamespace(
        program_spans=[{"name": n, "ts_ns": ts, "dur_ns": dur}
                       for n, ts, dur in spans],
        records=[{"t0": a, "t1": b} for a, b in calls],
        window_s=window_s, trace=trace, config=cfg, traffic=wl["traffic"],
        peaks={"bf16_flops_per_s": 197e12})


SPANS = [("train.step", 0, 200_000_000),
         ("train.fast", 1_000_000, 190_000_000),
         ("train.batch", 1_000_000, 2_000_000),
         ("train.put", 3_000_000, 8_000_000),
         ("train.sync", 20_000_000, 150_000_000),
         ("train.step", 300_000_000, 300_000_000),
         ("train.check", 301_000_000, 290_000_000),
         ("train.batch", 301_000_000, 1_000_000),
         ("train.put", 302_000_000, 9_000_000),
         ("train.sync", 330_000_000, 250_000_000)]


@pytest.mark.parametrize("metric,pct", [
    ("fast_pct.train", 38.0), ("check_pct.train", 58.0),
    ("host_input_pct.train", 4.0), ("sync_pct.train", 80.0)])
def test_span_readers(metric, pct):
    read = harness.load_module("metrics", metric).read
    assert read(_ctx(SPANS)) == pytest.approx(pct)
    # a program without the trainer's spans reads nothing, not 0
    assert read(_ctx([("engine.scan", 0, 5)])) is None


def test_mfu_by_hand():
    read = harness.load_module("metrics", "mfu.train").read
    ctx = _ctx(SPANS)
    flops = 2 * 4096 * counts.dense_flops_per_token(ctx.config["model"],
                                                    1024)
    assert read(ctx) == pytest.approx(100 * flops / (0.5 * 4 * 197e12))
    assert read(_ctx([("engine.scan", 0, 5)])) is None


def test_collective_and_idle_readers():
    # two chips; calls 0-450 and 550-1000 on the host's clock, which is
    # the trace's clock plus 1000.  Chip 0 runs a fusion 0-400, an
    # all-reduce 300-500 (its start and done halves) and an all-gather
    # 600-700; chip 1 a fusion 0-800 and a psum 800-900.  Inside the
    # calls (900 in all) chip 0 is busy 550, 250 of it in collectives;
    # chip 1 busy 800, 100 of it in its psum.
    t = {"w0": 0, "w1": 1000, "devices": {
        "TPU:0": [[0, 400, "fusion.1", ""], [300, 50, "all-reduce-start.1", ""],
                  [350, 150, "all-reduce-done.1", ""],
                  [600, 100, "all-gather.2", ""]],
        "TPU:1": [[0, 800, "fusion.1", ""], [800, 100, "psum.95", ""]]},
        "spans": []}
    calls = [(1000, 1450), (1550, 2000)]
    coll = harness.load_module("metrics", "collective_pct.train").read
    idle = harness.load_module("metrics", "device_idle_pct.train").read
    assert coll(_ctx([], trace=t, calls=calls)) == pytest.approx(
        (250 / 900 + 100 / 900) / 2 * 100)
    assert idle(_ctx([], trace=t, calls=calls)) == pytest.approx(
        (350 / 900 + 100 / 900) / 2 * 100)
    assert coll(_ctx([])) is None and idle(_ctx([])) is None
    t["devices"] = {"TPU:0": [[0, 400, "fusion.1", ""]]}
    assert coll(_ctx([], trace=t, calls=calls)) is None


def test_overlap_of_interval_lists():
    from bench import calls

    assert calls.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert calls.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert calls.overlap_ns([(0, 100)], [(1, 2), (3, 5), (50, 150)]) == 53
    assert calls.overlap_ns([], [(0, 1)]) == 0
