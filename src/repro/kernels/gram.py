"""Pallas TPU precompute kernel for the gram-domain data plane.

One ``pl.pallas_call`` streams the extended data rows ``R`` (and
optionally ``W_0``) HBM -> VMEM in ``d``-blocks and accumulates, in one
pass, every d-sized quantity the gram-domain scan will ever need:

  (a) the Gram matrix ``G = R @ R^T`` (Ie, Ie) — after this, residual
      symbols of ANY iterate ``W_t = W_0 - C_t @ R`` follow from
      ``W_t @ R^T = S_0 - C_t @ G`` without touching ``d`` again;
  (b) ``S_0 = W_0 @ R^T`` (B, Ie), the starting symbols (skipped when
      the caller starts from ``W_0 = 0``, where ``S_0`` is identically
      zero — the engine's chunked pipeline stages the zero carry
      directly);
  (c) the per-step CountSketch tables ``SK[t] = CountSketch_k(R)``
      under ``keys[t]`` for every protocol step t — the tables the
      stream plane pre-sketches in T separate passes.

All three are constant-``index_map`` VMEM accumulators revisited every
grid step (``pl.when(j == 0)`` zero-init).  The sketch signs are
rematerialized in-register from the global column position with
``ref.hash_signs_ref``'s hash, so (c) is bitwise the same bucket layout
as the stream plane's tables.

The (T, Ie, k) sketch accumulator must fit VMEM alongside the rows
block: ~``T * Ie_p * k * 4`` bytes (≈7.4 MB at T=100, Ie_p=72, k=256).
``ops.gram_factors`` keeps each call under that budget by chunking the
key axis (re-streaming ``rows`` once per chunk); this module is the
single-chunk primitive.  The jnp oracle is ``ref.gram_factors_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_K = 256
# d-block per grid step; a multiple of the sketch width k so the
# in-block bucket layout matches ref.sketch_ref's global reshape(-1, k)
BLOCK_D = 512


def _gram_factors_kernel(*refs, t_count: int, k: int, block_d: int,
                         has_w0: bool):
    if has_w0:
        rows_ref, w0_ref, keys_ref, g_ref, s0_ref, sk_ref = refs
    else:
        rows_ref, keys_ref, g_ref, sk_ref = refs
        w0_ref = s0_ref = None
    j = pl.program_id(0)
    rows = rows_ref[...].astype(jnp.float32)               # (Ie_p, bd)

    @pl.when(j == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        if has_w0:
            s0_ref[...] = jnp.zeros_like(s0_ref)
        sk_ref[...] = jnp.zeros_like(sk_ref)

    # (a) Gram block: G += rows @ rows^T over this d-slab.  Both dots
    # state f32 precision: Mosaic's default multiplies f32 in bf16
    # passes, and every residual of the gram scan is read off G
    g_ref[...] += jax.lax.dot_general(
        rows, rows, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    # (b) starting symbols: S0 += W0 @ rows^T
    if has_w0:
        s0_ref[...] += jax.lax.dot_general(
            w0_ref[...], rows, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    # (c) per-step CountSketch tables: signs rematerialized in-register
    # from the global column position (ref.hash_signs_ref's hash), then
    # bucketed by position % k — block_d % k == 0 keeps buckets aligned
    pos = (j * block_d).astype(jnp.uint32) \
        + jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1)
    for t in range(t_count):
        h = pos * jnp.uint32(2654435761) + keys_ref[0, t]
        h ^= h >> 16
        h *= jnp.uint32(2246822519)
        h ^= h >> 13
        sign = jnp.where((h & 1) == 1, 1.0, -1.0).astype(jnp.float32)
        signed = rows * sign                               # (Ie_p, bd)
        psk = signed[:, :k]
        for c in range(1, block_d // k):
            psk = psk + signed[:, c * k:(c + 1) * k]
        sk_ref[t] += psk


@functools.partial(jax.jit,
                   static_argnames=("k", "block_d", "interpret"))
def gram_factors(rows: jnp.ndarray, W0: jnp.ndarray | None,
                 keys: jnp.ndarray, k: int = DEFAULT_K,
                 block_d: int = BLOCK_D, interpret: bool = False):
    """Gram-plane precompute: (rows (Ie, d) f32/bf16, W0 (B, d) f32 or
    None, keys (T,) u32) -> (G (Ie, Ie), S0 (B, Ie) or None,
    SK (T, Ie, k)).

    G = rows @ rows^T;  S0 = W0 @ rows^T;  SK[t] = CountSketch_k(rows)
    under ``keys[t]`` (== ref.sketch_ref per row, up to f32 summation
    order).  One grid pass over d-blocks; the whole key axis is
    accumulated in VMEM, so callers bound T per call (ops.gram_factors
    chunks for them).
    """
    if block_d % k:
        raise ValueError(f"block_d {block_d} must be a multiple of k {k}")
    Ie, d = rows.shape
    keys = jnp.asarray(keys, jnp.uint32)
    (T,) = keys.shape
    if T < 1:
        raise ValueError("gram_factors needs at least one sketch key")
    has_w0 = W0 is not None
    if has_w0 and W0.shape[1] != d:
        raise ValueError(
            f"shape mismatch: rows {rows.shape}, W0 {W0.shape} "
            f"(want W0 (B, {d}))")
    pad_d = (-d) % block_d
    pad_i = (-Ie) % 8                 # f32 sublane tile
    pad_t = (-T) % 128                # lane tile for the key vector
    rows_p = jnp.pad(rows, ((0, pad_i), (0, pad_d)))
    keys_p = jnp.pad(keys, (0, pad_t))[None, :]            # (1, T_p)
    Ie_p, d_p, T_p = Ie + pad_i, d + pad_d, T + pad_t
    nsteps = d_p // block_d

    in_specs = [pl.BlockSpec((Ie_p, block_d), lambda j: (0, j))]
    operands = [rows_p]
    out_specs = [pl.BlockSpec((Ie_p, Ie_p), lambda j: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((Ie_p, Ie_p), jnp.float32)]
    if has_w0:
        B = W0.shape[0]
        in_specs.append(pl.BlockSpec((B, block_d), lambda j: (0, j)))
        operands.append(jnp.pad(W0.astype(jnp.float32),
                                ((0, 0), (0, pad_d))))
        out_specs.append(pl.BlockSpec((B, Ie_p), lambda j: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, Ie_p), jnp.float32))
    in_specs.append(pl.BlockSpec((1, T_p), lambda j: (0, 0)))
    operands.append(keys_p)
    out_specs.append(pl.BlockSpec((T, Ie_p, k), lambda j: (0, 0, 0)))
    out_shape.append(jax.ShapeDtypeStruct((T, Ie_p, k), jnp.float32))

    out = pl.pallas_call(
        functools.partial(_gram_factors_kernel, t_count=T, k=k,
                          block_d=block_d, has_w0=has_w0),
        grid=(nsteps,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    if has_w0:
        G, S0, SK = out
    else:
        (G, SK), S0 = out, None
    if pad_i:
        G = G[:Ie, :Ie]
        SK = SK[:, :Ie]
        if has_w0:
            S0 = S0[:, :Ie]
    return G, S0, SK
