"""``chip_smoke.py``'s phases at tiny sizes on the CPU.

The Pallas kernels run in interpret mode (``REPRO_KERNEL_IMPL=pallas``
off a TPU), so each phase's plan assertions and reference comparisons
are the ones the chip run makes.  The four-chip phases run in a child
process on four forced host devices.  ``main()`` itself must refuse a
machine without a TPU.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

TINY_ENGINE = {
    "engine_stream": dict(trials=8, d=1 << 10, steps=3, n_ref=4),
    "engine_gram": dict(trials=4, d=1 << 10, steps=12, n_ref=2),
    "engine_device_control": dict(trials=16, d=1 << 8, steps=8, n_ref=8),
}


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")


@pytest.mark.parametrize("phase", sorted(TINY_ENGINE))
def test_engine_phase(pallas, phase):
    """Every comparison passes; the phase itself fails, because off a
    TPU its kernels ran in the interpreter and not compiled."""
    line = getattr(cs, phase)(**TINY_ENGINE[phase])
    assert line["phase"] == phase
    assert line["checks"]["ok"], line
    assert line["plan"]["kernel_impl"] == "pallas"
    assert line["kernels"] == "interpret" and not line["ok"]
    assert line["compile_s"] > 0 and line["warm_s"] > 0
    json.dumps(line)


def test_engine_phase_fails_on_a_value_mismatch(pallas, monkeypatch):
    """A reference that disagrees in values fails the comparison."""
    monkeypatch.setattr(cs, "_sup_dev", lambda a, b: 1.0)
    line = cs.engine_stream(**TINY_ENGINE["engine_stream"])
    assert not line["checks"]["ok"]


def test_trainer_phase():
    line = cs.trainer(cs.model_config(reduced=True), seq_len=32,
                      global_batch=2, fast_steps=1, check_steps=1)
    assert line["ok"], line
    assert [s["kind"] for s in line["steps"]] == [
        "warm_up", "fast", "check_warm_up", "check"]
    assert line["checks"]["rel_diff"] <= 1e-2
    assert not line["checks"]["check_any_fault"]
    json.dumps(line)


_FOUR_DEVICES = """
import json, sys
sys.path.insert(0, {root!r})
import chip_smoke as cs
print("LINE " + json.dumps(cs.engine_sharded(trials=8, d=1 << 10, steps=3)))
print("LINE " + json.dumps(cs.trainer_4(cs.model_config(reduced=True),
                                        seq_len=32, max_steps=12)))
"""


def test_four_device_phases():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "REPRO_KERNEL_IMPL": "pallas",
           "PYTHONPATH": os.pathsep.join(
               [os.path.join(ROOT, "src"), os.environ.get("PYTHONPATH", "")])}
    proc = subprocess.run([sys.executable, "-c",
                           _FOUR_DEVICES.format(root=ROOT)],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = {ln["phase"]: ln for ln in (
        json.loads(s[len("LINE "):]) for s in proc.stdout.splitlines()
        if s.startswith("LINE "))}
    sharded, train = lines["engine_sharded"], lines["trainer_4"]
    assert sharded["checks"]["ok"], sharded
    assert sharded["kernels"] == "interpret" and not sharded["ok"]
    assert sharded["plan"]["n_devices"] == 4 and sharded["plan"]["sharded"]
    assert train["ok"], train
    assert train["checks"]["identified"] == [3]


def test_main_refuses_a_machine_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/jax-cache"],
                         ids=["repo_default", "from_env"])
def test_compile_cache_dir(env_dir):
    code = ("import jax\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = env_dir or os.path.join(ROOT, ".jax_cache")
    assert proc.stdout.split() == [want, want]


def test_importing_repro_initializes_no_backend():
    code = ("import repro, repro.core.engine, repro.kernels.ops, repro.train\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
