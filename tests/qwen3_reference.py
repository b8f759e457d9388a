"""A plain float32 Qwen3 dense decoder and AdamW step, for the tests:
the copy of the benchmark's reference (``bench/reference/qwen3_train.py``)
that the tests may import.  Nothing of the program is imported.

The model (huggingface.co/Qwen/Qwen3-4B, ``Qwen3ForCausalLM``): token
embedding; per layer RMSNorm, grouped-query attention with a per-head
RMSNorm of q and k before RoPE (rotate-half, base ``rope_theta``), a
causal softmax, the output projection and the residual; RMSNorm, a
SwiGLU MLP and the residual; a final RMSNorm and the LM head tied to the
embedding.  The loss is the mean next-token cross-entropy over every
token of the batch.

Parameters are a flat dict, the layers stacked on a leading axis (see
``from_program``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def from_program(tree) -> dict:
    """The flat names of a parameter-shaped tree of the program's dense
    decoder (one scanned layer group)."""
    (layer,), = tree["decoder"]
    mix, ffn = layer["mixer"], layer["ffn"]
    return {"embed": tree["embed"]["tokens"],
            "final_norm": tree["final_norm"]["scale"],
            "attn_norm": layer["ln1"]["scale"],
            "mlp_norm": layer["ln2"]["scale"],
            "wq": mix["wq"], "wk": mix["wk"], "wv": mix["wv"],
            "wo": mix["wo"], "q_norm": mix["q_norm"],
            "k_norm": mix["k_norm"], "gate": ffn["gate"], "up": ffn["up"],
            "down": ffn["down"]}


def model_block(cfg) -> dict:
    """A program ``ModelConfig`` in the key names of a ``config.json``."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "intermediate_size": cfg.d_ff,
            "vocab_size": cfg.vocab_size,
            "num_hidden_layers": cfg.num_layers, "rms_norm_eps": 1e-6,
            "rope_theta": cfg.rope_theta}


def rmsnorm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, theta: float):
    """x: (S, heads, hd), positions 0..S-1."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w: dict, m: dict):
    S = h.shape[0]
    H, K, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    q = (h @ w["wq"]).reshape(S, H, hd)
    k = (h @ w["wk"]).reshape(S, K, hd)
    v = (h @ w["wv"]).reshape(S, K, hd)
    q = rope(rmsnorm(q, w["q_norm"], eps), theta)
    k = rope(rmsnorm(k, w["k_norm"], eps), theta)
    k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, H * hd)
    return o @ w["wo"]


LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
         "mlp_norm", "gate", "up", "down")


def sequence_loss(p: dict, tokens, labels, m: dict):
    eps = m["rms_norm_eps"]

    def layer(x, w):
        x = x + attention(rmsnorm(x, w["attn_norm"], eps), w, m)
        h = rmsnorm(x, w["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"], None

    x, _ = jax.lax.scan(layer, p["embed"][tokens], {k: p[k] for k in LAYER})
    logits = rmsnorm(x, p["final_norm"], eps) @ p["embed"].T
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])
    return nll.mean()


def batch_loss(p: dict, tokens, labels, m: dict):
    """Mean over the batch (B, S) of sequences of equal length."""
    return jnp.mean(jnp.stack([sequence_loss(p, t, y, m)
                               for t, y in zip(tokens, labels)]))


def loss_and_grad(params: dict, tokens, labels, m: dict):
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        return jax.value_and_grad(batch_loss)(p, jnp.asarray(tokens),
                                              jnp.asarray(labels), m)


def adamw_step(opt, step: int, params: dict, mu: dict, nu: dict, g: dict):
    """One AdamW step of every array from the gradient ``g``: clip by the
    global norm, bias-corrected moments, decoupled weight decay, a linear
    warm-up then cosine schedule.  ``opt`` has the fields of the
    program's ``OptConfig``.  Returns (params, mu, nu)."""
    norm = math.sqrt(sum(float(jnp.sum(v * v)) for v in g.values()))
    scale = min(1.0, opt.grad_clip / max(norm, 1e-12))
    if step < opt.warmup_steps:
        lr = opt.peak_lr * (step + 1) / max(1, opt.warmup_steps)
    else:
        prog = min(1.0, (step - opt.warmup_steps)
                   / max(1, opt.total_steps - opt.warmup_steps))
        r = opt.min_lr_ratio
        lr = opt.peak_lr * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))
    b1, b2, t = opt.beta1, opt.beta2, step + 1
    out = ({}, {}, {})
    for k in params:
        gk = g[k] * scale
        m = b1 * mu[k] + (1 - b1) * gk
        v = b2 * nu[k] + (1 - b2) * gk * gk
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt.eps)
        theta = params[k] - lr * (upd + opt.weight_decay * params[k])
        out[0][k], out[1][k], out[2][k] = theta, m, v
    return out
