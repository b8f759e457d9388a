"""Byzantine attack models (simulation).

A Byzantine worker may send an arbitrary symbol.  For experiments we model
the standard attack families from the BFT-SGD literature; each attack is a
pure function applied to the honest gradient *inside* the worker's shard_map
body, gated by the worker's Byzantine mask and its per-iteration tampering
coin (the paper's ``p_i``: worker i tampers independently w.p. >= p_i).

Attacks operate on pytrees (the worker's gradient tree).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Every attack is affine in the gradient: it sends ``a·g + b`` for the
# honest gradient ``g``.  ``a`` is a function of the attack's scale; ``b``
# is None (no bias) or a function of (key, g, scale).  Because the map is
# affine, a gradient that is a sum of pieces can be tampered piece by
# piece: ``a`` on every piece and ``b`` on exactly one.  The trainer's
# gradient taps (``repro.train.gradtap``) do that, and the tied embedding's
# head use then takes ``a·h + b`` and its lookup use ``a·l``.
AFFINE = {
    "none": (lambda s: 1.0, None),
    "sign_flip": (lambda s: -s, None),
    "scale": (lambda s: s, None),
    "noise": (lambda s: 1.0, lambda k, g, s: s * jax.random.normal(
        k, g.shape, jnp.float32).astype(g.dtype)),
    "zero": (lambda s: 0.0, None),
    "inf": (lambda s: 0.0, lambda k, g, s: jnp.full_like(g, 1e30)),
    # a stealthy attack: small constant bias pushing w away from w*
    "constant_drift": (lambda s: 1.0, lambda k, g, s: 0.1 * jnp.ones_like(g)),
}
ATTACKS = tuple(AFFINE)


def has_bias(attack: str) -> bool:
    """Whether ``attack`` adds a term ``b`` that does not scale with g."""
    return AFFINE[attack][1] is not None


def affine(g, attack: str, key, scale: float = 10.0, bias: bool = True):
    """``a·g + b`` of ``attack`` on one array; ``a·g`` alone when not
    ``bias``.  ``key`` draws the noise of ``b``."""
    a, b = AFFINE[attack]
    a = a(scale)
    out = g if a == 1 else (jnp.zeros_like(g) if a == 0 else a * g)
    if not bias or b is None:
        return out
    return b(key, g, scale) if a == 0 else out + b(key, g, scale)


def apply_attack(grad_tree, attack: str, key, scale: float = 10.0):
    """Return the tampered gradient tree for a given attack kind (static):
    each leaf ``a·g + b``, the noise of leaf i drawn from the i-th key of
    ``split(key, leaves)``."""
    if attack not in AFFINE:
        raise ValueError(f"unknown attack {attack!r}")
    leaves, treedef = jax.tree.flatten(grad_tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([affine(g, attack, k, scale)
                              for g, k in zip(leaves, keys)])


def tamper_coin(key, is_byz, p_tamper: float):
    """The tampering decision of this worker in this iteration, and the
    key its attack draws from: ``is_byz`` AND a ``p_tamper`` coin."""
    kc, ka = jax.random.split(key)
    coin = jax.random.bernoulli(kc, p_tamper)
    return jnp.logical_and(is_byz, coin), ka


def maybe_tamper(grad_tree, *, is_byz, key, attack: str, p_tamper: float,
                 scale: float = 10.0):
    """Tamper iff this worker is Byzantine AND its iteration coin fires.

    ``is_byz`` is a traced scalar bool; the tampering coin uses ``key``.
    The paper's analysis assumes worker i tampers independently each
    iteration with probability at least p_i.
    """
    do, ka = tamper_coin(key, is_byz, p_tamper)
    tampered = apply_attack(grad_tree, attack, ka, scale)
    return jax.tree.map(
        lambda t, g: jnp.where(do, t, g), tampered, grad_tree
    ), do
