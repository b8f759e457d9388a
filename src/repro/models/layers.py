"""Core layers: norms, rotary embeddings, embeddings, SwiGLU MLP.

All layers are pure functions over explicit param pytrees.  Param *structure*
is described once by ``abstract_*`` functions returning pytrees of
:class:`repro.sharding.Annotated` (shape + logical axes + dtype + init);
:func:`materialize` instantiates them with a PRNG key.  This keeps sharding
annotation, dry-run ShapeDtypeStructs and real initialization in one place.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.sharding import Annotated


def _dt(cfg) -> Any:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# materialization
# ---------------------------------------------------------------------------

def materialize(abstract_tree, key):
    """Instantiate an Annotated tree (trunc-normal matrices, ones/zeros etc.)."""
    leaves, treedef = jax.tree.flatten(
        abstract_tree, is_leaf=lambda x: isinstance(x, Annotated)
    )
    keys = jax.random.split(key, max(1, len(leaves)))

    def init_one(a: Annotated, k):
        if a.init == "ones":
            return jnp.ones(a.shape, a.dtype)
        if a.init == "zeros":
            return jnp.zeros(a.shape, a.dtype)
        if a.init == "ssm_a":  # -log A in (log 1 .. log 16), mamba2 default
            u = jax.random.uniform(k, a.shape, jnp.float32, 1.0, 16.0)
            return jnp.log(u).astype(a.dtype)
        if a.init == "ssm_dt":  # softplus^-1 of dt in (1e-3, 1e-1)
            u = jax.random.uniform(k, a.shape, jnp.float32, 1e-3, 1e-1)
            return (u + jnp.log(-jnp.expm1(-u))).astype(a.dtype)
        fan_in = a.shape[-2] if len(a.shape) >= 2 else a.shape[-1]
        std = 1.0 / math.sqrt(max(1, fan_in))
        w = jax.random.truncated_normal(k, -2.0, 2.0, a.shape, jnp.float32) * std
        return w.astype(a.dtype)

    return treedef.unflatten([init_one(a, k) for a, k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def abstract_rmsnorm(dim: int, cfg):
    return {"scale": Annotated((dim,), ("norm",), _dt(cfg), init="ones")}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def l2norm(x, eps: float = 1e-6):
    """Scale-free RMS normalization (qk-norm without learned scale)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)  # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def abstract_embedding(cfg):
    p = {
        "tokens": Annotated(
            (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), _dt(cfg)
        )
    }
    if not cfg.tie_embeddings:
        p["head"] = Annotated(
            (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), _dt(cfg)
        )
    return p


def embed(params, tokens, cfg, hook=None):
    """``hook`` (the training step's gradient taps on ``params``) taps the
    lookup; a tied table's head use takes the attack's bias term."""
    # gather rows; scale as in gemma-style models is omitted (standard llama)
    if hook is None:
        return params["tokens"].astype(_dt(cfg))[tokens]
    rows = hook.at("tokens").lookup(params["tokens"], tokens,
                                    bias=not cfg.tie_embeddings)
    return rows.astype(_dt(cfg))


def unembed(params, x, cfg, hook=None):
    name = "tokens" if cfg.tie_embeddings else "head"
    w = params[name] if hook is None else hook.at(name)(params[name])
    # logits in f32 for a numerically stable loss; a tied table (vocab,
    # embed) is contracted as it is stored, so its gradient comes out in
    # the table's own layout
    spec = "...d,vd->...v" if cfg.tie_embeddings else "...d,dv->...v"
    return jnp.einsum(spec, x.astype(jnp.float32), w.astype(jnp.float32))


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def abstract_mlp(cfg, d_ff: int | None = None):
    d_ff = cfg.d_ff if d_ff is None else d_ff
    dt = _dt(cfg)
    return {
        "gate": Annotated((cfg.d_model, d_ff), ("embed", "ffn"), dt),
        "up": Annotated((cfg.d_model, d_ff), ("embed", "ffn"), dt),
        "down": Annotated((d_ff, cfg.d_model), ("ffn", "embed"), dt),
    }


def mlp(params, x):
    g = jnp.einsum("...d,df->...f", x, params["gate"])
    u = jnp.einsum("...d,df->...f", x, params["up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, params["down"])
