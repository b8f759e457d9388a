"""Pallas TPU kernel: CountSketch detection symbol (DESIGN.md §7.1).

Computes s[c] = sum_{r} sign(idx(r,c), key) * g[r, c] over a flat gradient
reshaped to (T, k) — the detection symbol compared across replica groups.

TPU mapping: the gradient streams HBM -> VMEM in (ROWS_PER_STEP, k) tiles;
the +-1 signs are rematerialized in-register from a hash of the global
coordinate (no sign tensor ever exists in memory); the k-vector accumulator
lives in the output VMEM block, revisited every grid step (output block
index_map is constant).  Arithmetic intensity is 1 FMA/byte — the kernel is
HBM-bound by construction, hence one single pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_K = 256
ROWS_PER_STEP = 512
SUBLANES = 8         # f32 rows of one TPU vector register


def _sketch_kernel(g_ref, key_ref, o_ref, *, k: int, rows: int):
    i = pl.program_id(0)
    g = g_ref[...].astype(jnp.float32)                     # (rows, k)
    row0 = (i * rows).astype(jnp.uint32)
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, k), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, k), 1)
    idx = (row0 + r) * jnp.uint32(k) + c
    h = idx * jnp.uint32(2654435761) + key_ref[0, 0]
    h ^= h >> 16
    h *= jnp.uint32(2246822519)
    h ^= h >> 13
    sign = jnp.where((h & 1) == 1, 1.0, -1.0).astype(jnp.float32)
    partial = (g * sign).sum(axis=0, keepdims=True)        # (1, k)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("k", "rows_per_step", "interpret"))
def sketch(flat_g: jnp.ndarray, key_scalar, k: int = DEFAULT_K,
           rows_per_step: int = ROWS_PER_STEP, interpret: bool = False):
    """CountSketch of a flat vector: (d,) -> (k,) f32.

    Numerically equals repro.kernels.ref.sketch_ref up to f32 summation
    order (per-tile partial sums added in grid order).
    """
    d = flat_g.shape[0]
    pad = (-d) % k
    g = jnp.pad(flat_g, (0, pad)).reshape(-1, k)
    t = g.shape[0]
    pad_t = (-t) % rows_per_step
    g = jnp.pad(g, ((0, pad_t), (0, 0)))
    nsteps = g.shape[0] // rows_per_step
    key_arr = jnp.full((1, 1), key_scalar, jnp.uint32)

    out = pl.pallas_call(
        functools.partial(_sketch_kernel, k=k, rows=rows_per_step),
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((rows_per_step, k), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        interpret=interpret,
    )(g, key_arr)
    return out[0]


def _sketch_kernel_batched(g_ref, key_ref, o_ref, *, k: int, rows: int):
    i = pl.program_id(1)
    g = g_ref[0].astype(jnp.float32)                       # (rows, k)
    row0 = (i * rows).astype(jnp.uint32)
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, k), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, k), 1)
    idx = (row0 + r) * jnp.uint32(k) + c
    h = idx * jnp.uint32(2654435761) + key_ref[0, 0]
    h ^= h >> 16
    h *= jnp.uint32(2246822519)
    h ^= h >> 13
    sign = jnp.where((h & 1) == 1, 1.0, -1.0).astype(jnp.float32)
    partial = (g * sign).sum(axis=0, keepdims=True)        # (1, k)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += partial


@functools.partial(jax.jit, static_argnames=("k", "rows_per_step", "interpret"))
def sketch_batched(flat_g: jnp.ndarray, key_scalar, k: int = DEFAULT_K,
                   rows_per_step: int = ROWS_PER_STEP,
                   interpret: bool = False):
    """CountSketch of B flat vectors under one shared key: (B, d) -> (B, k).

    Grid (B, row-blocks); every batch row hashes its own coordinate
    index from 0, so row b equals ``sketch(flat_g[b], key_scalar)``
    exactly.  The jitted engine sketches all (trial, worker) gradients
    of a check iteration in one call for on-device sketch detection."""
    B, d = flat_g.shape
    pad = (-d) % k
    g = jnp.pad(flat_g, ((0, 0), (0, pad))).reshape(B, -1, k)
    t = g.shape[1]
    if t <= SUBLANES:
        # a row of at most 8 tiles (d <= 8k) fits one 8-sublane block:
        # the same sums as a block padded to rows_per_step, which would
        # read rows_per_step / 8 times the bytes, nearly all zeros
        rows_per_step = min(rows_per_step, SUBLANES)
    pad_t = (-t) % rows_per_step
    g = jnp.pad(g, ((0, 0), (0, pad_t), (0, 0)))
    nsteps = g.shape[1] // rows_per_step
    key_arr = jnp.full((1, 1), key_scalar, jnp.uint32)
    out = pl.pallas_call(
        functools.partial(_sketch_kernel_batched, k=k, rows=rows_per_step),
        grid=(B, nsteps),
        in_specs=[
            pl.BlockSpec((1, rows_per_step, k), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, k), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
        interpret=interpret,
    )(g, key_arr)
    return out[:, 0]
