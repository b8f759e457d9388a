"""Compile the engine's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler installed with JAX compiles for a
``v5e:2x2`` topology that is described, not attached.  Each test lowers
one kernel of the engine's main path at the sizes the engine runs,
with ``interpret=False``, and asserts that the compiled program holds
the Mosaic kernel (``tpu_custom_call``) and fits one chip's 16 GiB.
Nothing runs, so these say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (coded_encode, fused_step, gram, majority_vote, ops,
                           sketch)

CHIP_BYTES = 16 * 2**30
D = 1 << 20          # the engine's production gradient dimension
B = 256              # the fused sweep's trial batch
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


def _assert_chip_program(fn, *args):
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < CHIP_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB"


@pytest.mark.parametrize("rows_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_step_compiles(one_chip, rows_dtype):
    ie_p = -(-IE // 8) * 8
    fn = jax.jit(lambda r, w, c, k: fused_step.fused_step(
        r, w, c, k, interpret=False))
    _assert_chip_program(fn, _arg((ie_p, D), rows_dtype, one_chip),
                         _arg((B, D), jnp.float32, one_chip),
                         _arg((B, ie_p), jnp.float32, one_chip),
                         _arg((), jnp.uint32, one_chip))


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
