"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are *definitions*, deliberately naive: correctness references, not
fast paths.  Each kernel's test sweeps shapes/dtypes against these.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# CountSketch (detection symbol) — see repro.core.detection
# ---------------------------------------------------------------------------

def hash_signs_ref(idx: jnp.ndarray, key_scalar) -> jnp.ndarray:
    h = idx.astype(jnp.uint32) * jnp.uint32(2654435761) + jnp.uint32(key_scalar)
    h ^= h >> 16
    h *= jnp.uint32(2246822519)
    h ^= h >> 13
    return jnp.where((h & 1) == 1, 1.0, -1.0).astype(jnp.float32)


def sketch_ref(flat_g: jnp.ndarray, key_scalar, k: int) -> jnp.ndarray:
    d = flat_g.shape[0]
    pad = (-d) % k
    g = jnp.pad(flat_g.astype(jnp.float32), (0, pad))
    idx = jax.lax.iota(jnp.uint32, d + pad)
    return (g * hash_signs_ref(idx, key_scalar)).reshape(-1, k).sum(axis=0)


# ---------------------------------------------------------------------------
# Majority vote over replicas — see repro.core.identification
# ---------------------------------------------------------------------------

def pairwise_maxdiff_ref(replicas: jnp.ndarray):
    """replicas (R, d) -> (maxdiff (R,R), maxscale (R,R)) f32.

    maxdiff[i,j]  = max_t |r_i[t] - r_j[t]|
    maxscale[i,j] = max over t achieving... we need the agreement decision
    max_t (|r_i - r_j| - tau*(1+min(|r_i|,|r_j|))) <= 0; so the reference
    returns the elementwise-max of (diff - tau*scale) per pair for tau=0 and
    the paired scale; instead we return the max of (diff / (1+min|.|)) which
    the kernel reproduces: agreement iff relmax <= tau.
    """
    a = replicas[:, None].astype(jnp.float32)
    b = replicas[None, :].astype(jnp.float32)
    rel = jnp.abs(a - b) / (1.0 + jnp.minimum(jnp.abs(a), jnp.abs(b)))
    return rel.max(axis=-1)


def majority_vote_ref(replicas: jnp.ndarray, tau: float):
    """(value (d,), faulty (R,) bool, has_majority ()) — same semantics as
    repro.core.identification.majority_vote."""
    R = replicas.shape[0]
    agree = pairwise_maxdiff_ref(replicas) <= tau
    counts = agree.sum(axis=1)
    is_major = counts > (R // 2)
    has_majority = is_major.any()
    winner = jnp.argmax(is_major)
    value = replicas[winner]
    faulty = ~agree[winner] & has_majority
    return value, faulty, has_majority


# ---------------------------------------------------------------------------
# Linear detection-code encode (generalized Fig-2 codes)
# ---------------------------------------------------------------------------

def coded_encode_ref(coeffs: jnp.ndarray, grads: jnp.ndarray) -> jnp.ndarray:
    """coeffs (n_sym, m) @ grads (m, d) -> symbols (n_sym, d), f32 accum."""
    return jnp.einsum(
        "sm,md->sd", coeffs.astype(jnp.float32), grads.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


# ---------------------------------------------------------------------------
# Batched variants (leading trial dimension) — naive vmaps of the above.
# These double as the off-TPU XLA implementations behind the batched ops
# in repro.kernels.ops (the jitted engine's inner loop); the blocked
# relmax there exists only to bound peak memory, its values equal this.
# ---------------------------------------------------------------------------

def batched_sketch_ref(flat_g: jnp.ndarray, key_scalar, k: int) -> jnp.ndarray:
    """(B, d) -> (B, k): per-row ``sketch_ref`` under one shared key."""
    return jax.vmap(lambda g: sketch_ref(g, key_scalar, k))(flat_g)


def batched_pairwise_maxdiff_ref(replicas: jnp.ndarray) -> jnp.ndarray:
    """(B, R, d) -> (B, R, R): per-row ``pairwise_maxdiff_ref``."""
    return jax.vmap(pairwise_maxdiff_ref)(replicas)


def batched_regroup_ref(keys, active, repl):
    """numpy oracle for ``ops.batched_regroup``: per trial, order the
    active worker ids by a stable argsort on their keys (the host
    engine's ``CounterPermuter`` permutation contract) and group the
    first m*r of them, ``engine._grouped_rows`` style."""
    import numpy as np

    keys = np.asarray(keys)
    active = np.asarray(active)
    repl = np.asarray(repl)
    B, n = active.shape
    shard = np.zeros((B, n), np.int32)
    group = np.full((B, n), -1, np.int32)
    m_out = np.zeros(B, np.int32)
    for b in range(B):
        act_idx = np.flatnonzero(active[b])
        perm = act_idx[np.argsort(keys[b, act_idx], kind="stable")]
        r = max(1, int(repl[b]))
        m = len(perm) // r
        m_out[b] = m
        mem = perm[: m * r]
        gid = np.repeat(np.arange(m, dtype=np.int32), r)
        shard[b, mem] = gid
        group[b, mem] = gid
    return shard, group, m_out


def batched_coded_encode_ref(coeffs: jnp.ndarray,
                             grads: jnp.ndarray) -> jnp.ndarray:
    """(B, n_sym, m) @ (B, m, d) -> (B, n_sym, d), f32 accum."""
    return jnp.einsum(
        "bsm,bmd->bsd", coeffs.astype(jnp.float32), grads.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


# ---------------------------------------------------------------------------
# Gram-plane precompute — see repro.kernels.gram
# ---------------------------------------------------------------------------

def gram_factors_ref(rows: jnp.ndarray, W0: jnp.ndarray | None,
                     keys, k: int = 256):
    """Composed oracle for the gram precompute kernel: the three
    quantities it accumulates, each expressed through the existing
    single-op refs.

    G = rows @ rows^T;  S0 = W0 @ rows^T;  SK[t] = per-row CountSketch
    of the rows under keys[t].
    """
    rows32 = rows.astype(jnp.float32)
    G = coded_encode_ref(rows32, rows32.T)
    S0 = None if W0 is None else coded_encode_ref(W0, rows32.T)
    keys = jnp.asarray(keys, jnp.uint32)
    Ie = rows32.shape[0]
    if keys.shape[0] == 0:
        SK = jnp.zeros((0, Ie, k), jnp.float32)
    else:
        SK = jnp.stack([batched_sketch_ref(rows32, keys[t], k)
                        for t in range(keys.shape[0])])
    return G, S0, SK


# ---------------------------------------------------------------------------
# Flash attention (causal / windowed), GQA — see repro.models.attention
# ---------------------------------------------------------------------------

def mha_ref(q, k, v, *, causal: bool = True, window: int | None = None,
            scale: float | None = None):
    """Naive full-matrix attention.  q (B,Sq,H,hd); k/v (B,Sk,K,hd)."""
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qg = q.reshape(B, Sq, K, G, hd).astype(jnp.float32)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    keep = jnp.ones((Sq, Sk), bool)
    if causal:
        keep &= kpos <= qpos + (Sk - Sq)
    if window is not None:
        keep &= kpos > qpos + (Sk - Sq) - window
    logits = jnp.where(keep[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(jnp.float32))
    return o.reshape(B, K * G, Sq, hd).transpose(0, 2, 1, 3).astype(q.dtype)
