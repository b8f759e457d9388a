"""Pallas TPU kernel: linear detection-code encode (paper §4.1
generalization — 'any suitable fault detection code may be used').

symbols = C @ G where C (n_sym, m) are the code coefficients (e.g. the
Figure-2 code rows) and G (m, d) are the worker's shard gradients.  A
skinny matmul: m, n_sym are tiny (m = shards/worker <= ~8), d is huge — so
the kernel is a single HBM-bound pass streaming G in (m, BLOCK_D) tiles
through the MXU with the coefficient matrix resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 2048


def _encode_kernel(c_ref, g_ref, o_ref):
    c = c_ref[...].astype(jnp.float32)                    # (n_sym, m)
    g = g_ref[...].astype(jnp.float32)                    # (m, BD)
    # f32 precision stated: Mosaic's default multiplies f32 in bf16 passes
    o_ref[...] = jnp.dot(c, g, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def coded_encode(coeffs: jnp.ndarray, grads: jnp.ndarray,
                 block_d: int = BLOCK_D, interpret: bool = False):
    """coeffs (n_sym, m) @ grads (m, d) -> (n_sym, d) f32."""
    n_sym, m = coeffs.shape
    m2, d = grads.shape
    assert m == m2
    pad = (-d) % block_d
    g = jnp.pad(grads, ((0, 0), (0, pad)))
    nsteps = g.shape[1] // block_d
    out = pl.pallas_call(
        _encode_kernel,
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((n_sym, m), lambda i: (0, 0)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n_sym, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_sym, g.shape[1]), jnp.float32),
        interpret=interpret,
    )(coeffs, g)
    return out[:, :d]


def _encode_kernel_batched(c_ref, g_ref, o_ref):
    c = c_ref[0].astype(jnp.float32)                      # (n_sym, m)
    g = g_ref[0].astype(jnp.float32)                      # (m, BD)
    o_ref[0] = jnp.dot(c, g, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def coded_encode_batched(coeffs: jnp.ndarray, grads: jnp.ndarray,
                         block_d: int = BLOCK_D, interpret: bool = False):
    """Per-trial encode: (B, n_sym, m) @ (B, m, d) -> (B, n_sym, d) f32.

    ``coded_encode`` with a leading batch dimension — grid (B, d-blocks),
    each trial's coefficient matrix resident in VMEM while its gradient
    matrix streams through.  The jitted engine (repro.core.engine_jax)
    expresses weighted aggregation and vote means as 1-symbol encodes
    over the (n,)-worker axis, so this is its per-iteration workhorse."""
    B, n_sym, m = coeffs.shape
    B2, m2, d = grads.shape
    assert B == B2 and m == m2
    pad = (-d) % block_d
    g = jnp.pad(grads, ((0, 0), (0, 0), (0, pad)))
    nsteps = g.shape[2] // block_d
    out = pl.pallas_call(
        _encode_kernel_batched,
        grid=(B, nsteps),
        in_specs=[
            pl.BlockSpec((1, n_sym, m), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, m, block_d), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, n_sym, block_d), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, n_sym, g.shape[2]), jnp.float32),
        interpret=interpret,
    )(coeffs, g)
    return out[:, :, :d]
