"""Pallas TPU megakernel: one fused protocol-step pass over the data plane.

One ``pl.pallas_call`` streams the data rows and the ``(B, d)`` gradient
state HBM -> VMEM in ``d``-blocks and, per block, does everything the
jitted engine's scan body previously paid three separate full-``d``
passes for:

  (a) applies the pending residual-coefficient contraction — the
      aggregation/attack/vote update folded into per-row coefficients
      ``cw`` by the engine — as ``W' = W - cw @ rows`` (the coded-encode
      contraction), written back through ``input_output_aliases`` so the
      iterate is updated in place;
  (b) accumulates the new residual symbols ``resid = W' @ rows^T`` into
      an fp32 VMEM accumulator (the (B, Ie) block is revisited every
      grid step, constant ``index_map`` + ``pl.when`` zero-init — the
      same accumulator idiom as ``sketch.py``);
  (c) accumulates the per-step CountSketch of the data rows
      (``sk[i, c] = sum_p sign(p, key) * rows[i, p]`` bucketed by
      ``p % k``) — the detection-symbol table the engine previously
      pre-sketched in a separate hoisted pass per step.

``rows`` is the engine's extended data matrix ``(Ie, d)``: the problem
rows ``A`` plus a ones-row and the noise-row, so affine-attack bias
terms ride along as two extra coefficient columns and the whole update
is ONE contraction.  Pallas's automatic block pipelining double-buffers
the HBM reads; ``rows`` may be stored bf16 (optional streaming mode) —
all arithmetic and all accumulators stay fp32 in VMEM.

Arithmetic intensity is ~2 FMA/byte on the W stream, so the step is
HBM-bound by construction: one read+write of W and one read of rows per
protocol step, where the unfused scan body paid three full passes
(update contraction, residual contraction, pre-sketch).  The jnp oracle
is ``ref.fused_step_ref`` (composed from the coded-encode and sketch
refs); dispatch lives in ``ops.fused_step``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_K = 256
# d-block per grid step; must be a multiple of the sketch width k so the
# in-block bucket layout matches ref.sketch_ref's global reshape(-1, k)
BLOCK_D = 512


def _fused_step_kernel(rows_ref, w_ref, cw_ref, key_ref,
                       w_out_ref, resid_ref, sk_ref, *,
                       k: int, block_d: int):
    j = pl.program_id(0)
    rows = rows_ref[...].astype(jnp.float32)               # (Ie, bd)
    w = w_ref[...]                                         # (B, bd)
    cw = cw_ref[...]                                       # (B, Ie)

    # (a) pending update: W' = W - cw @ rows, written back in place.
    # Both dots state f32 precision: Mosaic's default multiplies f32 in
    # bf16 passes, which misses the engine's parity contract
    upd = jnp.dot(cw, rows, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    w_new = w - upd
    w_out_ref[...] = w_new

    # (b) residual symbols of the NEW iterate: resid += W' @ rows^T
    pres = jax.lax.dot_general(w_new, rows, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)

    # (c) CountSketch of the data rows: signs rematerialized in-register
    # from the global column position (ref.hash_signs_ref's hash), then
    # bucketed by position % k — block_d % k == 0 keeps buckets aligned
    pos = (j * block_d).astype(jnp.uint32) \
        + jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1)
    h = pos * jnp.uint32(2654435761) + key_ref[0, 0]
    h ^= h >> 16
    h *= jnp.uint32(2246822519)
    h ^= h >> 13
    sign = jnp.where((h & 1) == 1, 1.0, -1.0).astype(jnp.float32)
    signed = rows * sign                                   # (Ie, bd)
    psk = signed[:, :k]
    for c in range(1, block_d // k):
        psk = psk + signed[:, c * k:(c + 1) * k]

    @pl.when(j == 0)
    def _init():
        resid_ref[...] = jnp.zeros_like(resid_ref)
        sk_ref[...] = jnp.zeros_like(sk_ref)

    resid_ref[...] += pres
    sk_ref[...] += psk


@functools.partial(jax.jit,
                   static_argnames=("k", "block_d", "interpret"))
def fused_step(rows: jnp.ndarray, W: jnp.ndarray, cw: jnp.ndarray,
               key_scalar, k: int = DEFAULT_K, block_d: int = BLOCK_D,
               interpret: bool = False):
    """Fused protocol step: (rows (Ie, d) f32/bf16, W (B, d) f32,
    cw (B, Ie) f32, key) -> (W' (B, d), resid (B, Ie), sk (Ie, k)).

    W' = W - cw @ rows;  resid = W' @ rows^T;  sk = CountSketch_k(rows)
    under ``key_scalar`` (== ref.sketch_ref per row, up to f32 summation
    order).  One grid pass over d-blocks; W is aliased into W' when d is
    already a block multiple (the engine pre-pads so this always holds
    on its hot path).
    """
    if block_d % k:
        raise ValueError(f"block_d {block_d} must be a multiple of k {k}")
    Ie, d = rows.shape
    B = W.shape[0]
    if W.shape[1] != d or cw.shape != (B, Ie):
        raise ValueError(
            f"shape mismatch: rows {rows.shape}, W {W.shape}, "
            f"cw {cw.shape} (want W (B, {d}), cw ({B}, {Ie}))")
    pad_d = (-d) % block_d
    pad_i = (-Ie) % 8                 # f32 sublane tile
    rows_p = jnp.pad(rows, ((0, pad_i), (0, pad_d)))
    W_p = jnp.pad(W.astype(jnp.float32), ((0, 0), (0, pad_d)))
    cw_p = jnp.pad(cw.astype(jnp.float32), ((0, 0), (0, pad_i)))
    Ie_p, d_p = Ie + pad_i, d + pad_d
    nsteps = d_p // block_d
    key_arr = jnp.full((1, 1), key_scalar, jnp.uint32)

    alias = {}
    if pad_d == 0:
        # every (B, block_d) W block is read and written exactly once by
        # its own grid step, so in-place aliasing is safe; with padding
        # the shapes differ and the copy is unavoidable anyway
        alias = {"input_output_aliases": {1: 0}}
    W_out, resid, sk = pl.pallas_call(
        functools.partial(_fused_step_kernel, k=k, block_d=block_d),
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((Ie_p, block_d), lambda j: (0, j)),
            pl.BlockSpec((B, block_d), lambda j: (0, j)),
            pl.BlockSpec((B, Ie_p), lambda j: (0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, block_d), lambda j: (0, j)),
            pl.BlockSpec((B, Ie_p), lambda j: (0, 0)),
            pl.BlockSpec((Ie_p, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, d_p), jnp.float32),
            jax.ShapeDtypeStruct((B, Ie_p), jnp.float32),
            jax.ShapeDtypeStruct((Ie_p, k), jnp.float32),
        ],
        interpret=interpret,
        **alias,
    )(rows_p, W_p, cw_p, key_arr)
    if pad_d:
        W_out = W_out[:, :d]
    if pad_i:
        resid = resid[:, :Ie]
        sk = sk[:Ie]
    return W_out, resid, sk
