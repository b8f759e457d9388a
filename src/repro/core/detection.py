"""Fault detection (paper §4.1 detection phase, TPU-adapted).

Paper-faithful baseline: replicas of a shard's gradient are compared
directly (replication is an f-fault-detection code).  On a pod that costs an
all-gather of full gradients inside each replica group — O(d * r) bytes.

Optimized detection (beyond paper, DESIGN.md §7): each worker compresses its
gradient into a k-dim *CountSketch* s = sum_i sigma_i(key) * g_i per bucket,
with per-iteration signs derived from a hash of the coordinate index and the
master's private per-step key.  The sketch is linear, so replicas of equal
gradients have equal sketches; a Byzantine worker that wants to defeat the
sketch must hit the (secret, per-iteration) null space — probability ~0.
Detection traffic drops from O(d) to O(k) per worker.

Both paths are exposed; ``detect_groups`` consumes either full gradients or
sketches.  The Pallas kernel (repro.kernels.sketch) implements the same hash
— ``hash_sign_sketch_ref`` here is its oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_K = 256
DEFAULT_TAU = 1e-5


def _hash_signs(idx: jnp.ndarray, key_scalar: jnp.ndarray) -> jnp.ndarray:
    """Deterministic ±1 from coordinate index and a scalar key (uint32).

    xorshift-style mixing; elementwise over ``idx`` so XLA fuses it with the
    multiply-accumulate — no materialized sign vector.
    """
    h = idx.astype(jnp.uint32) * jnp.uint32(2654435761) + key_scalar
    h ^= h >> 16
    h *= jnp.uint32(2246822519)
    h ^= h >> 13
    return jnp.where((h & 1) == 1, 1.0, -1.0).astype(jnp.float32)


def hash_sign_sketch(flat_g: jnp.ndarray, key_scalar, k: int = DEFAULT_K):
    """CountSketch of a flat vector: (d,) -> (k,) float32."""
    d = flat_g.shape[0]
    pad = (-d) % k
    g = jnp.pad(flat_g.astype(jnp.float32), (0, pad))
    idx = jax.lax.iota(jnp.uint32, d + pad)
    signed = g * _hash_signs(idx, jnp.uint32(key_scalar))
    return signed.reshape(-1, k).sum(axis=0)


def leaf_sketch(leaf: jnp.ndarray, key_scalar, k: int = DEFAULT_K):
    """``hash_sign_sketch(leaf.reshape(-1))``, up to the order of the f32
    sums: where the last axis is a multiple of ``k``, coordinate
    ``(row, col)`` has flat index ``row * n + col`` and bucket ``col % k``,
    so the leaf is summed over its rows as it is laid out, with no f32
    copy of the flattened leaf."""
    n = leaf.shape[-1] if leaf.ndim else 1
    if leaf.ndim < 2 or n % k:
        return hash_sign_sketch(leaf.reshape(-1), key_scalar, k)
    g = leaf.reshape(-1, n)
    row = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 1)
    signs = _hash_signs(row * jnp.uint32(n) + col, jnp.uint32(key_scalar))
    return (g.astype(jnp.float32) * signs).sum(axis=0).reshape(-1, k).sum(
        axis=0)


def sketch_tree(grad_tree, key_scalar, k: int = DEFAULT_K):
    """Sketch a whole gradient pytree into one (k,) vector.

    Leaves are sketched independently (with an offset so identical values in
    different leaves don't cancel) and summed — linearity keeps the equal-
    gradients => equal-sketch property.
    """
    leaves = jax.tree.leaves(grad_tree)
    total = jnp.zeros((k,), jnp.float32)
    offset = jnp.uint32(key_scalar)
    for i, leaf in enumerate(leaves):
        total = total + leaf_sketch(
            leaf, offset + jnp.uint32(0x9E3779B9) * jnp.uint32(i + 1), k
        )
    return total


def key_scalar_for_step(key) -> jnp.ndarray:
    """Fold a jax PRNG key to the uint32 scalar the hash consumes."""
    data = jax.random.key_data(key).reshape(-1).astype(jnp.uint32)
    return data[0] ^ data[-1]


# ---------------------------------------------------------------------------
# group comparison
# ---------------------------------------------------------------------------

def detect_groups(symbols: jnp.ndarray, group_of_worker: jnp.ndarray,
                  num_groups: int, tau: float = DEFAULT_TAU):
    """Per-group fault flags from per-worker symbols.

    symbols: (n, k) — sketches (or any fixed-size symbol) per worker.
    group_of_worker: (n,) int32, -1 for idle workers.
    Returns (group_fault (num_groups,) bool, worker_mismatch (n,) bool).

    A group is faulty iff its members' symbols are not unanimous (within
    relative tolerance tau), tested as deviation from the group mean.
    worker_mismatch is a *suspicion* signal only — with r = f+1 replicas a
    deviation does not prove which member lied; identification requires the
    reactive 2f+1 round, exactly as the paper argues.
    """
    n, k = symbols.shape
    valid = group_of_worker >= 0
    gid = jnp.where(valid, group_of_worker, 0)
    onehot = jax.nn.one_hot(gid, num_groups, dtype=symbols.dtype) * valid[:, None]
    count = onehot.sum(axis=0)                                   # (G,)
    # f32 precision stated: at a TPU's default (bf16 passes) the group
    # mean of identical replicas misses them by more than tau
    gsum = jnp.einsum("nk,ng->gk", symbols, onehot,
                      precision=jax.lax.Precision.HIGHEST)
    gmean = gsum / jnp.maximum(count, 1.0)[:, None]
    ref = gmean[gid]                                             # (n, k)
    scale = 1.0 + jnp.abs(ref)
    mismatch = (jnp.abs(symbols - ref) > tau * scale).any(axis=-1) & valid
    group_fault = (
        jax.ops.segment_sum(mismatch.astype(jnp.int32), gid, num_groups) > 0
    )
    return group_fault, mismatch


def detect_groups_batched(symbols: jnp.ndarray, group_of_worker: jnp.ndarray,
                          tau: float = 1e-9):
    """Replica compare over B trials at once, against each group's FIRST
    member (ascending worker id) with an ABSOLUTE tolerance — mirroring
    the scenario engines' check-iteration compare (``|g - g_first| >
    tau``) in symbol space.  Because sketches are linear and honest
    replicas are bitwise copies, a group's symbols are equal exactly
    when its gradients are; for d <= k the sketch IS a signed
    permutation of the gradient and the verdict is identical.

    symbols: (B, n, k); group_of_worker: (B, n) int32, -1 idle.
    Returns (trial_fault (B,) bool, worker_mismatch (B, n) bool).  The
    jitted engine (repro.core.engine_jax) calls this every check
    iteration inside its scan.
    """
    B, n, _ = symbols.shape
    valid = group_of_worker >= 0
    same = (group_of_worker[:, :, None] == group_of_worker[:, None, :]) \
        & valid[:, None, :] & valid[:, :, None]
    idx = jnp.arange(n)
    first = jnp.min(jnp.where(same, idx[None, None, :], n), axis=2)
    ref = symbols[jnp.arange(B)[:, None], jnp.minimum(first, n - 1)]
    dev = jnp.abs(symbols - ref).max(axis=2)
    mismatch = valid & (first < n) & (dev > tau)
    return mismatch.any(axis=1), mismatch


def detect_full(replica_grads: jnp.ndarray, tau: float = DEFAULT_TAU):
    """Paper-faithful replica comparison on full gradients.

    replica_grads: (r, d).  Returns scalar bool fault (replicas not
    unanimous within tau).
    """
    ref = replica_grads[0]
    scale = 1.0 + jnp.abs(ref)
    return (jnp.abs(replica_grads - ref[None]) > tau * scale[None]).any()
