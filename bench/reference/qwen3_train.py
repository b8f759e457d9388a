"""A plain float32 training step of a Qwen3 dense decoder
(huggingface.co/Qwen/Qwen3-4B, ``Qwen3ForCausalLM``) and what the
randomized protocol does around it, for the trainer cell's check.

Imports nothing of the program under test.  Everything is
straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``:

- the model: token embedding; per layer RMSNorm, grouped-query attention
  with a per-head RMSNorm of q and k before RoPE (rotate-half, base
  ``rope_theta``), a causal softmax over every key, the output
  projection and the residual; RMSNorm and a SwiGLU MLP and the
  residual; a final RMSNorm and the LM head tied to the embedding;
- the loss: mean next-token cross-entropy over every token of the
  global batch, one sequence at a time with the gradient accumulated,
  so that the step fits one chip;
- AdamW in float32: clip by the global norm, bias-corrected moments,
  decoupled weight decay, a linear warm-up then cosine schedule;
- the protocol for a fixed check probability q: the check coin of every
  step, replayed from the seed, and the computation efficiency's counts
  (arXiv:1912.09528, Definition 2).

``control=True`` rounds every operand of every matrix product, forward
and backward (the cotangent too), to fp8 e4m3 with one scale a tensor
(the largest magnitude maps to 448), then multiplies those values at
the highest precision: a precision below the bf16 the configuration
states, for the limits of the check.

Parameters are a flat dict, the layers stacked on a leading axis:
``embed`` (V, D), ``final_norm`` (D,), and (L, ...) ``attn_norm``,
``wq`` (D, H*hd), ``wk``, ``wv`` (D, K*hd), ``wo`` (H*hd, D), ``q_norm``,
``k_norm`` (hd,), ``mlp_norm``, ``gate``, ``up`` (D, F), ``down`` (F, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0           # largest finite float8_e4m3fn


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec: str, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b))


def _mm_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return jnp.einsum(spec, qa, qb), (qa, qb)


def _mm_fp8_bwd(spec, res, ct):
    """The backward products take fp8 operands too: the rounded inputs
    and the rounded cotangent."""
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y), *res)
    return vjp(_fp8(ct))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(spec: str, a, b, control: bool):
    return _mm_fp8(spec, a, b) if control else jnp.einsum(spec, a, b)


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


def attention(h, w: dict, m: dict, control: bool):
    """One layer's attention over one sequence h (S, D); ``w`` holds
    that layer's arrays."""
    S = h.shape[0]
    H, K, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    q = _mm("sd,dh->sh", h, w["wq"], control).reshape(S, H, hd)
    k = _mm("sd,dh->sh", h, w["wk"], control).reshape(S, K, hd)
    v = _mm("sd,dh->sh", h, w["wv"], control).reshape(S, K, hd)
    q = rope(rmsnorm(q, w["q_norm"], eps), theta)
    k = rope(rmsnorm(k, w["k_norm"], eps), theta)
    # query head j reads key/value head j // (H / K)
    k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
    scores = _mm("qhd,khd->hqk", q, k, control) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, control).reshape(S, H * hd)
    return _mm("sh,hd->sd", o, w["wo"], control)


LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
         "mlp_norm", "gate", "up", "down")


def sequence_loss(p: dict, tokens, labels, m: dict, control: bool = False):
    """Mean next-token cross-entropy of one sequence (S,) of ids; the
    layers one after another (a scan over their stacked arrays)."""
    eps = m["rms_norm_eps"]

    def layer(x, w):
        x = x + attention(rmsnorm(x, w["attn_norm"], eps), w, m, control)
        h = rmsnorm(x, w["mlp_norm"], eps)
        g = _mm("sd,df->sf", h, w["gate"], control)
        u = _mm("sd,df->sf", h, w["up"], control)
        return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["down"],
                       control), None

    # each layer's activations are recomputed for the backward pass, so
    # that one sequence's fit one chip beside the parameters and gradient
    x, _ = jax.lax.scan(jax.checkpoint(layer), p["embed"][tokens],
                        {k: p[k] for k in LAYER})
    x = rmsnorm(x, p["final_norm"], eps)
    logits = _mm("sd,vd->sv", x, p["embed"], control)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])
    return nll.mean()


@functools.lru_cache(maxsize=None)
def _accumulate(model: tuple, control: bool):
    """jit(acc, loss, p, tokens, labels) -> (acc + grad, loss + l) of one
    sequence, ``acc`` donated."""
    m = dict(model)

    def add(acc, loss, p, tokens, labels):
        l, g = jax.value_and_grad(sequence_loss)(p, tokens, labels, m,
                                                 control)
        return jax.tree.map(jnp.add, acc, g), loss + l

    return jax.jit(add, donate_argnums=(0,))


def loss_and_grad(params: dict, tokens: np.ndarray, labels: np.ndarray,
                  m: dict, control: bool = False, device=None):
    """Loss and gradient of the mean over a batch (B, S) of sequences of
    equal length, each sequence's gradient added to the last on
    ``device``.  ``params`` are cast to float32 there.  Returns
    (loss, {name: float32 gradient on ``device``})."""
    add = _accumulate(tuple(sorted(m.items())), control)
    with jax.default_matmul_precision("highest"):
        p = {k: jax.device_put(np.asarray(v, np.float32), device)
             for k, v in params.items()}
        acc = jax.tree.map(jnp.zeros_like, p)
        loss = jax.device_put(np.float32(0), device)
        for t, y in zip(tokens, labels):
            acc, loss = add(acc, loss, p, jax.device_put(t, device),
                            jax.device_put(y, device))
        B = tokens.shape[0]
        return float(loss) / B, {k: a / B for k, a in acc.items()}


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``peak_lr`` over ``warmup_steps``, then a cosine
    down to ``min_lr_ratio`` of it at ``total_steps``; ``step`` from 0."""
    peak, warm = opt["peak_lr"], opt["warmup_steps"]
    if step < warm:
        return peak * (step + 1) / max(1, warm)
    prog = min(1.0, max(0.0, (step - warm) / max(1, opt["total_steps"]
                                                 - warm)))
    r = opt["min_lr_ratio"]
    return peak * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))


def clip_scale(opt: dict, grad_norm: float) -> float:
    """The factor of every gradient element when clipping to the global
    norm ``grad_clip``."""
    return min(1.0, opt["grad_clip"] / max(grad_norm, 1e-12))


def adamw(opt: dict, step: int, theta, mu, nu, g):
    """One AdamW update of one array in float32 from the clipped gradient
    ``g``.  Returns (theta, mu, nu) after the step."""
    b1, b2, t = opt["beta1"], opt["beta2"], step + 1
    theta = theta.astype(jnp.float32)
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    m_hat = mu / (1 - b1 ** t)
    v_hat = nu / (1 - b2 ** t)
    upd = m_hat / (jnp.sqrt(v_hat) + opt["eps"]) + opt["weight_decay"] * theta
    return theta - lr_at(opt, step) * upd, mu, nu


# ---------------------------------------------------------------------------
# the protocol at a fixed check probability
# ---------------------------------------------------------------------------

def check_coin(seed: int, q: float, steps: int) -> np.ndarray:
    """(steps,) bool: which steps check.  One uniform draw a step from
    the protocol's decide stream of ``seed``, a check where it is below
    q (all workers honest, so the fault budget never shrinks)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed),
                                                        0x0DEC1DE]))
    return rng.random(steps) < q


def efficiency_counts(checks, n: int, f: int) -> tuple[int, int]:
    """(gradients used, gradients computed) over the steps, Definition 2:
    a fast step uses and computes n; a check step computes the n // (f+1)
    shards f + 1 times each and uses each once."""
    checks = np.asarray(checks, bool)
    shards = n // (f + 1)
    used = int(np.where(checks, shards, n).sum())
    computed = int(np.where(checks, shards * (f + 1), n).sum())
    return used, computed


# ---------------------------------------------------------------------------
# the synthetic token stream
# ---------------------------------------------------------------------------

def token_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """The global batch of ``step``: ids drawn by a Zipf law of exponent
    1.2 over the first min(vocab, 4096) ids, an even id followed by the
    next id with probability 1/2; tokens and their next-token labels,
    each (batch, seq_len) int32."""
    rng = np.random.default_rng((seed * 1_000_003 + step) & 0x7FFFFFFF)
    V = min(vocab, 4096)
    probs = np.arange(1, V + 1, dtype=np.float64) ** -1.2
    probs /= probs.sum()
    ids = rng.choice(V, size=(batch, seq_len + 1), p=probs).astype(np.int32)
    follow = np.minimum(ids[:, :-1] + 1, V - 1)
    pair = ((ids[:, :-1] % 2) == 0) & (rng.random((batch, seq_len)) < 0.5)
    ids[:, 1:] = np.where(pair, follow, ids[:, 1:])
    return ids[:, :-1].copy(), ids[:, 1:].copy()
