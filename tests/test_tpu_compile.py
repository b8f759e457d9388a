"""Compile the engine's Pallas kernels, and the scan that calls them,
for a described TPU v5e chip.

No chip is needed: the TPU compiler installed with JAX compiles for a
``v5e:2x2`` topology that is described, not attached.  Each test lowers
one kernel of the engine's main path at the sizes the engine runs,
with ``interpret=False``, or one engine call's scan at a benchmark
cell's shapes, and asserts that the compiled program holds the Mosaic
kernel (``tpu_custom_call``) and fits one chip's 16 GiB.  Nothing runs,
so these say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engineplan.stepcore import jitted_step_core
from repro.kernels import coded_encode, gram, majority_vote, ops, sketch

CHIP_BYTES = 16 * 2**30
D = 1 << 20          # the engine's production gradient dimension
B = 256              # a production sweep's trial batch
IE = 66              # n_data = 64 data rows + the ones- and noise-rows


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_chip_program(fn, *args, **statics):
    compiled = fn.lower(*args, **statics).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < CHIP_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB"


@pytest.mark.parametrize("with_w0", [False, True], ids=["no_w0", "w0"])
def test_gram_factors_compiles(one_chip, with_w0):
    # the key chunk ops.gram_factors hands one kernel call
    ie_p = -(-IE // 8) * 8
    t = ops._GRAM_SK_VMEM // (ie_p * gram.DEFAULT_K * 4)
    rows = _arg((IE, D), jnp.float32, one_chip)
    keys = _arg((t,), jnp.uint32, one_chip)
    if with_w0:
        fn = jax.jit(lambda r, w, k: gram.gram_factors(
            r, w, k, interpret=False))
        _assert_chip_program(fn, rows, _arg((32, D), jnp.float32, one_chip),
                             keys)
    else:
        fn = jax.jit(lambda r, k: gram.gram_factors(
            r, None, k, interpret=False))
        _assert_chip_program(fn, rows, keys)


def test_sketch_batched_compiles(one_chip):
    fn = jax.jit(lambda g, k: sketch.sketch_batched(g, k, interpret=False))
    _assert_chip_program(fn, _arg((B, 1 << 16), jnp.float32, one_chip),
                         _arg((), jnp.uint32, one_chip))


def test_coded_encode_batched_compiles(one_chip):
    # the stream plane's per-trial contraction: one symbol over the rows
    fn = jax.jit(lambda c, g: coded_encode.coded_encode_batched(
        c, g, interpret=False))
    _assert_chip_program(fn, _arg((64, 1, IE), jnp.float32, one_chip),
                         _arg((64, IE, 1 << 16), jnp.float32, one_chip))


@pytest.mark.parametrize("replicas", [3, 5])
def test_pairwise_relmax_batched_compiles(one_chip, replicas):
    fn = jax.jit(lambda r: majority_vote.pairwise_relmax_batched(
        r, interpret=False))
    _assert_chip_program(fn, _arg((B, replicas, 1 << 16), jnp.float32,
                                  one_chip))


# one call of the riboflavin-n3f1 cells: n = 3 workers over a shared
# 71 x 4,088 problem, 1,024 trials a call, 120 steps, host control
RIBO_N, RIBO_I, RIBO_D, RIBO_B, RIBO_T = 3, 71, 4088, 1024, 120


def _host_schedule(sh):
    """The scan xs a host schedule stages (engine_jax._stage_problem)."""
    tb, tbn = (RIBO_T, RIBO_B), (RIBO_T, RIBO_B, RIBO_N)
    arrays = dict(live=(tb, jnp.bool_), checks=(tb, jnp.bool_),
                  vote1=(tb, jnp.bool_), identify=(tb, jnp.bool_),
                  m1=(tb, jnp.int32), shard1=(tbn, jnp.int32),
                  group1=(tbn, jnp.int32), aggw=(tbn, jnp.float32),
                  tam1=(tbn, jnp.bool_), m2=(tb, jnp.int32),
                  shard2=(tbn, jnp.int32), group2=(tbn, jnp.int32),
                  tam2=(tbn, jnp.bool_), active=(tbn, jnp.bool_))
    return {k: _arg(shape, dt, sh) for k, (shape, dt) in arrays.items()}


@pytest.mark.parametrize("plane", ["stream", "gram"])
def test_step_core_compiles(one_chip, plane, monkeypatch):
    """One engine call's scan on either data plane, with the batched
    kernels dispatched to Pallas (the vote's pairwise kernel runs inside
    the scan), lowered as a process on a TPU lowers it: the kernels
    compiled by Mosaic, not interpreted."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    f32, k = jnp.float32, sketch.DEFAULT_K
    ie = RIBO_I + 2
    stat = {k: _arg((RIBO_B,), f32, one_chip)
            for k in ("lr", "alpha", "beta", "nu")}
    stat.update(fcode=_arg((RIBO_B,), jnp.int32, one_chip),
                farr=_arg((RIBO_B,), jnp.int32, one_chip))
    if plane == "gram":
        a = {"rows": _arg((ie, RIBO_D), f32, one_chip),
             "G": _arg((ie, ie), f32, one_chip)}
        cw0 = _arg((RIBO_B, ie), f32, one_chip)
        sa = _arg((RIBO_T, RIBO_I, k), f32, one_chip)
        noise = pid = None
    else:
        a = _arg((RIBO_I, RIBO_D), f32, one_chip)
        cw0 = None
        sa = _arg((RIBO_T, 1, RIBO_I, k), f32, one_chip)
        noise = _arg((RIBO_D,), f32, one_chip)
        pid = _arg((RIBO_B,), jnp.int32, one_chip)
    com = {"SA": sa, "sk_one": _arg((RIBO_T, k), f32, one_chip),
           "sk_noise": _arg((RIBO_T, k), f32, one_chip)}
    _assert_chip_program(
        jitted_step_core, a, _arg((RIBO_I,), f32, one_chip),
        _arg((RIBO_B, RIBO_D), f32, one_chip), cw0, stat,
        _host_schedule(one_chip), com, noise, pid,
        control="host", shared=True, has_filter=False, has_bias=True,
        impl="pallas", gram=plane == "gram")
