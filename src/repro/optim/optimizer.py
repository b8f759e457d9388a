"""Optimizers: SGD / momentum / AdamW with warmup+cosine schedule.

Optimizer state mirrors the parameter tree's sharding (ZeRO-1: the state
lives wherever the param shard lives; with FSDP rules the state is fully
sharded).  The moments are f32 whatever the param dtype; there is no
f32 master copy of the params: each update is computed in f32 from the
param as stored and rounded back to its dtype (bf16 params keep bf16
rounding of every step).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Literal

import jax
import jax.numpy as jnp

from repro.sharding import Annotated


@dataclasses.dataclass(frozen=True)
class OptConfig:
    kind: Literal["sgd", "momentum", "adamw"] = "adamw"
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    momentum: float = 0.9
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0


def lr_at(opt: OptConfig, step) -> jnp.ndarray:
    step = jnp.asarray(step, jnp.float32)
    warm = opt.peak_lr * (step + 1) / max(1, opt.warmup_steps)
    prog = jnp.clip(
        (step - opt.warmup_steps)
        / max(1, opt.total_steps - opt.warmup_steps),
        0.0,
        1.0,
    )
    cos = opt.peak_lr * (
        opt.min_lr_ratio + (1 - opt.min_lr_ratio) * 0.5 * (1 + jnp.cos(math.pi * prog))
    )
    return jnp.where(step < opt.warmup_steps, warm, cos)


def _f32(a: Annotated) -> Annotated:
    return Annotated(a.shape, a.logical, jnp.float32, init="zeros")


def abstract_opt_state(opt: OptConfig, abstract_params):
    is_leaf = lambda x: isinstance(x, Annotated)  # noqa: E731
    if opt.kind == "sgd":
        return {}
    if opt.kind == "momentum":
        return {"mu": jax.tree.map(_f32, abstract_params, is_leaf=is_leaf)}
    return {
        "mu": jax.tree.map(_f32, abstract_params, is_leaf=is_leaf),
        "nu": jax.tree.map(_f32, abstract_params, is_leaf=is_leaf),
    }


def init_opt_state(opt: OptConfig, params):
    if opt.kind == "sgd":
        return {}
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    if opt.kind == "momentum":
        return {"mu": jax.tree.map(zeros, params)}
    return {"mu": jax.tree.map(zeros, params), "nu": jax.tree.map(zeros, params)}


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def opt_update(opt: OptConfig, grads, state, params, step):
    """Returns (new_params, new_state, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, opt.grad_clip / jnp.maximum(gnorm, 1e-12)) if opt.grad_clip else 1.0
    lr = lr_at(opt, step)

    if opt.kind == "sgd":
        new_params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32) - lr * scale * g.astype(jnp.float32)).astype(p.dtype),
            params, grads,
        )
        return new_params, state, {"grad_norm": gnorm, "lr": lr}

    if opt.kind == "momentum":
        new_mu = jax.tree.map(
            lambda m, g: opt.momentum * m + g.astype(jnp.float32) * scale,
            state["mu"], grads,
        )
        new_params = jax.tree.map(
            lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
            params, new_mu,
        )
        return new_params, {"mu": new_mu}, {"grad_norm": gnorm, "lr": lr}

    # adamw
    t = jnp.asarray(step, jnp.float32) + 1.0
    b1, b2 = opt.beta1, opt.beta2

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * jnp.square(g)
        m_hat = m_new / (1 - b1**t)
        v_hat = v_new / (1 - b2**t)
        p32 = p.astype(jnp.float32)
        upd_ = m_hat / (jnp.sqrt(v_hat) + opt.eps) + opt.weight_decay * p32
        return (p32 - lr * upd_).astype(p.dtype), m_new, v_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["mu"])
    flat_v = treedef.flatten_up_to(state["nu"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_mu = treedef.unflatten([o[1] for o in out])
    new_nu = treedef.unflatten([o[2] for o in out])
    return new_params, {"mu": new_mu, "nu": new_nu}, {"grad_norm": gnorm, "lr": lr}
