"""Model facade: abstract params, init, train loss, prefill, decode.

All functions are pure and jit-friendly; distribution is applied by the
caller through in/out shardings derived from the same ``Annotated`` trees
(see repro.sharding / repro.launch.dryrun).

Batch dict keys:
  tokens  (B, S) int32          input token ids
  labels  (B, S) int32          next-token targets (-100 = ignore)
  ctx     (B, Tctx, D) dtype    stub modality embeddings (vlm / audio only)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, layer_groups, layer_kinds
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import (
    abstract_embedding,
    abstract_rmsnorm,
    embed,
    materialize,
    mlp,
    rmsnorm,
    unembed,
)
from repro.sharding import Annotated, constrain_here

MOE_AUX_COEF = 0.01


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    groups = layer_groups(cfg)
    p: dict[str, Any] = {
        "embed": abstract_embedding(cfg),
        "decoder": tfm.abstract_stack(groups, cfg, enc_dec_cross=cfg.is_encoder_decoder),
        "final_norm": abstract_rmsnorm(cfg.d_model, cfg),
    }
    if cfg.is_encoder_decoder:
        from repro.configs.base import LayerGroup, LayerKind

        enc_groups = [
            LayerGroup((LayerKind("attn", "mlp"),), cfg.encoder_layers)
        ]
        p["encoder"] = tfm.abstract_stack(enc_groups, cfg)
        p["encoder_norm"] = abstract_rmsnorm(cfg.d_model, cfg)
    return p


def init(cfg: ModelConfig, key):
    return materialize(abstract_params(cfg), key)


def _at(hook, name):
    return None if hook is None else hook.at(name)


def _encode(params, ctx, cfg, hook=None):
    """Whisper-style encoder over stub frame embeddings (B, T, D)."""
    from repro.configs.base import LayerGroup, LayerKind

    enc_groups = [LayerGroup((LayerKind("attn", "mlp"),), cfg.encoder_layers)]
    positions = jnp.arange(ctx.shape[1])[None]
    x, _, _ = tfm.run_stack(
        params["encoder"], enc_groups, ctx.astype(jnp.dtype(cfg.dtype)), cfg,
        positions=positions, causal=False, hook=_at(hook, "encoder"),
    )
    return rmsnorm(params["encoder_norm"], x, cfg.norm_eps)


def _context(params, batch, cfg, hook=None):
    ctx = batch.get("ctx")
    if ctx is None:
        return None
    ctx = ctx.astype(jnp.dtype(cfg.dtype))
    if cfg.is_encoder_decoder:
        return _encode(params, ctx, cfg, hook)
    return ctx  # vlm: precomputed patch embeddings used directly


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def forward(params, batch, cfg: ModelConfig, collect_kv: bool = False,
            hook=None):
    """``hook``: a training step's gradient taps (``repro.train.gradtap``),
    placed on each layer's parameters, the embedding's uses and the head;
    None leaves the model as it is."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    groups = layer_groups(cfg)
    ctx = _context(params, batch, cfg, hook)
    x = embed(params["embed"], tokens, cfg, _at(hook, "embed"))
    x = constrain_here(x, ("batch", "seq", "embed"))
    positions = jnp.arange(S)[None]
    x, kv_all, aux = tfm.run_stack(
        params["decoder"], groups, x, cfg,
        positions=positions, ctx=ctx, causal=True, collect_kv=collect_kv,
        hook=_at(hook, "decoder"),
    )
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg, _at(hook, "embed"))
    logits = constrain_here(logits, ("batch", "seq", "vocab"))
    return logits, kv_all, aux


def train_loss(params, batch, cfg: ModelConfig, hook=None):
    """Mean next-token cross-entropy (+ MoE aux).  Returns (loss, metrics).

    The CE is computed as logsumexp - <one_hot, logits> (never a gather
    along the vocab dim), so the (B, S, V) logits stay sharded over both
    the batch (`data`) and vocab (`model`) axes end-to-end — a gather-based
    CE forces an all-gather of the logits, which at 128k vocab is the
    difference between 2 GB and >100 GB of per-chip temps.
    """
    logits, _, aux = forward(params, batch, cfg, hook=hook)
    labels = batch["labels"]
    valid = labels >= 0
    labels_safe = jnp.maximum(labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels_safe, cfg.vocab_size, dtype=logits.dtype)
    label_logit = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = lse - label_logit
    denom = jnp.maximum(valid.sum(), 1)
    ce = jnp.where(valid, nll, 0.0).sum() / denom
    loss = ce + MOE_AUX_COEF * aux
    return loss, {"ce": ce, "moe_aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def abstract_cache(cfg: ModelConfig, batch: int, seq_len: int,
                   long_context: bool = False):
    """Decode-time cache tree (self-attn KV + mamba + cross KV)."""
    dt = jnp.dtype(cfg.dtype)
    cache: dict[str, Any] = {}
    n_attn = len(tfm.attn_layer_indices(cfg))
    if n_attn:
        KH = cfg.num_kv_heads * cfg.head_dim
        seq_axis = "decode_seq" if long_context else None
        cache["k"] = Annotated(
            (n_attn, batch, seq_len, KH), ("layers", "batch", seq_axis, "kv"), dt
        )
        cache["v"] = Annotated(
            (n_attn, batch, seq_len, KH), ("layers", "batch", seq_axis, "kv"), dt
        )
    n_mamba = len(tfm.mamba_layer_indices(cfg))
    if n_mamba:
        cache["mamba"] = ssm_mod.abstract_mamba_cache(cfg, batch, n_mamba)
    n_cross = sum(
        1 for k in layer_kinds(cfg) if k.mixer == "cross_attn"
    ) + (len(layer_kinds(cfg)) if cfg.is_encoder_decoder else 0)
    if n_cross:
        KH = cfg.num_kv_heads * cfg.head_dim
        Tctx = (
            cfg.num_encoder_positions
            if cfg.is_encoder_decoder
            else cfg.num_vision_tokens
        )
        cache["cross_k"] = Annotated(
            (n_cross, batch, Tctx, KH), ("layers", "batch", None, "kv"), dt
        )
        cache["cross_v"] = Annotated(
            (n_cross, batch, Tctx, KH), ("layers", "batch", None, "kv"), dt
        )
    return cache


def _layer_param(params_stack, groups, layer_idx: int):
    """Slice the stacked group params for a single layer index."""
    off = 0
    for g_idx, g in enumerate(groups):
        if layer_idx < off + g.num_layers:
            local = layer_idx - off
            r, pos = divmod(local, len(g.pattern))
            return jax.tree.map(lambda a: a[r], params_stack[g_idx][pos])
        off += g.num_layers
    raise IndexError(layer_idx)


def decode_step(params, token, pos, cache, cfg: ModelConfig):
    """One decode step.  token: (B,) int32; pos: scalar int32 (the position
    the new token occupies; cache holds pos valid entries before the call).

    Returns (logits (B, V), new_cache).  Layers are unrolled in python
    (small per-layer graphs; trivial cache slicing).
    """
    groups = layer_groups(cfg)
    kinds = layer_kinds(cfg)
    x = embed(params["embed"], token[:, None], cfg)  # (B,1,D)
    positions = pos[None, None] if jnp.ndim(pos) == 0 else pos[:, None]

    new_cache = dict(cache)
    if "k" in cache:
        new_cache["k"], new_cache["v"] = cache["k"], cache["v"]
    if "mamba" in cache:
        new_cache["mamba"] = dict(cache["mamba"])

    attn_i = 0
    mamba_i = 0
    cross_i = 0
    K, hd = cfg.num_kv_heads, cfg.head_dim
    for li, kind in enumerate(kinds):
        p = _layer_param(params["decoder"], groups, li)
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        if kind.mixer == "mamba":
            mcache = {
                k: new_cache["mamba"][k][mamba_i] for k in new_cache["mamba"]
            }
            out, mnew = ssm_mod.mamba_decode_step(p["mixer"], h[:, 0], mcache, cfg)
            for k in mnew:
                new_cache["mamba"][k] = (
                    new_cache["mamba"][k].at[mamba_i].set(mnew[k])
                )
            x = x + out[:, None]
            mamba_i += 1
        elif kind.mixer == "cross_attn":
            q = attn.project_q(p["mixer"], h, cfg, None, rope=False)
            ck = new_cache["cross_k"][cross_i]
            cv = new_cache["cross_v"][cross_i]
            B, T = ck.shape[0], ck.shape[1]
            o = attn.decode_attention(
                q, ck.reshape(B, T, K, hd), cv.reshape(B, T, K, hd),
                valid_len=T,
            )
            mix = attn.output_proj(p["mixer"], o)
            mix = mix * jnp.tanh(p["mixer"]["gate_attn"].astype(mix.dtype))
            x = x + mix
            cross_i += 1
        else:
            window = cfg.sliding_window if kind.mixer == "attn_local" else None
            q = attn.project_q(p["mixer"], h, cfg, positions)
            k_new, v_new = attn.project_kv(p["mixer"], h, cfg, positions)
            B = q.shape[0]
            # single in-place update on the stacked cache (donation-friendly:
            # no slice-out/set-back round trip, no full-cache copy)
            new_cache["k"] = jax.lax.dynamic_update_slice(
                new_cache["k"], k_new.reshape(1, B, 1, K * hd),
                (attn_i, 0, pos, 0),
            )
            new_cache["v"] = jax.lax.dynamic_update_slice(
                new_cache["v"], v_new.reshape(1, B, 1, K * hd),
                (attn_i, 0, pos, 0),
            )
            ck = new_cache["k"][attn_i]
            cv = new_cache["v"][attn_i]
            S = ck.shape[1]
            o = attn.decode_attention(
                q, ck.reshape(B, S, K, hd), cv.reshape(B, S, K, hd),
                valid_len=pos + 1, window=window,
            )
            x = x + attn.output_proj(p["mixer"], o)
            attn_i += 1
        if "cross" in p:  # whisper decoder cross-attn sub-block
            h = rmsnorm(p["ln_cross"], x, cfg.norm_eps)
            q = attn.project_q(p["cross"], h, cfg, None, rope=False)
            ck = new_cache["cross_k"][cross_i]
            cv = new_cache["cross_v"][cross_i]
            B, T = ck.shape[0], ck.shape[1]
            o = attn.decode_attention(
                q, ck.reshape(B, T, K, hd), cv.reshape(B, T, K, hd), valid_len=T
            )
            x = x + attn.output_proj(p["cross"], o)
            cross_i += 1
        if kind.ffn != "none":
            h = rmsnorm(p["ln2"], x, cfg.norm_eps)
            if kind.ffn == "mlp":
                x = x + mlp(p["ffn"], h)
            else:
                f, _ = moe_mod.moe(p["ffn"], h, cfg)
                x = x + f
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x[:, 0], cfg)
    return logits, new_cache


def prefill(params, batch, cfg: ModelConfig, cache_len: int | None = None):
    """Run the full prompt, returning (last-token logits, populated cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_len = S if cache_len is None else cache_len
    logits, kv_all, _ = forward(params, batch, cfg, collect_kv=True)
    cache: dict[str, Any] = {}
    if kv_all:
        ks = jnp.concatenate([kv[0] for kv in kv_all], axis=1)  # (B, L, S, KH)
        vs = jnp.concatenate([kv[1] for kv in kv_all], axis=1)
        ks = ks.transpose(1, 0, 2, 3)
        vs = vs.transpose(1, 0, 2, 3)
        if cache_len > S:
            pad = cache_len - S
            ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cache["k"], cache["v"] = ks, vs
    # mamba / cross caches are produced for decode entry points; prefill of
    # those is exercised through serve-time APIs in repro.serving.
    return logits[:, -1], cache
