"""Layer stacks: grouped-scan decoder (and encoder), heterogeneous layer
kinds (attention / local attention / cross-attention / mamba; mlp / moe).

Compile-size strategy: layers are grouped into maximal periodic patterns
(configs.layer_groups); each group is a single ``lax.scan`` over its repeats
with the (short) pattern unrolled inside the body.  A 100-layer model
compiles O(pattern) HLO, not O(100).  The decode path unrolls layers in
python instead (each layer's decode graph is tiny, and per-layer KV/SSM
cache slicing stays trivial).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerGroup, LayerKind, ModelConfig, layer_groups
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import abstract_mlp, abstract_rmsnorm, mlp, rmsnorm
from repro.sharding import Annotated


# ---------------------------------------------------------------------------
# abstract params
# ---------------------------------------------------------------------------

def abstract_layer(kind: LayerKind, cfg: ModelConfig, enc_dec_cross: bool = False):
    p: dict[str, Any] = {"ln1": abstract_rmsnorm(cfg.d_model, cfg)}
    if kind.mixer == "mamba":
        p["mixer"] = ssm_mod.abstract_mamba(cfg)
    else:
        p["mixer"] = attn.abstract_attention(cfg, cross=(kind.mixer == "cross_attn"))
    if enc_dec_cross:
        p["ln_cross"] = abstract_rmsnorm(cfg.d_model, cfg)
        p["cross"] = attn.abstract_attention(cfg, cross=True)
    if kind.ffn != "none":
        p["ln2"] = abstract_rmsnorm(cfg.d_model, cfg)
        p["ffn"] = abstract_mlp(cfg) if kind.ffn == "mlp" else moe_mod.abstract_moe(cfg)
    return p


def _stack(tree, n: int):
    return jax.tree.map(
        lambda a: Annotated((n,) + a.shape, ("layers",) + a.logical, a.dtype, a.init),
        tree,
        is_leaf=lambda x: isinstance(x, Annotated),
    )


def abstract_stack(groups: list[LayerGroup], cfg, enc_dec_cross: bool = False):
    """[per-group] list of [per-pattern-position] stacked layer trees."""
    out = []
    for g in groups:
        out.append(
            [_stack(abstract_layer(k, cfg, enc_dec_cross), g.repeats) for k in g.pattern]
        )
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def apply_layer(
    kind: LayerKind,
    p,
    x,
    cfg: ModelConfig,
    *,
    positions,
    ctx=None,
    causal: bool = True,
    collect_kv: bool = False,
):
    """One layer (full-sequence path).  Returns (x, kv | None, aux)."""
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    kv = None
    aux = jnp.zeros((), jnp.float32)
    if kind.mixer == "mamba":
        mix = ssm_mod.mamba(p["mixer"], h, cfg)
    elif kind.mixer == "cross_attn":
        q = attn.project_q(p["mixer"], h, cfg, positions, rope=False)
        k, v = attn.project_kv(p["mixer"], ctx, cfg, None, rope=False)
        o = attn.blockwise_attention(q, k, v, causal=False)
        mix = attn.output_proj(p["mixer"], o)
        mix = mix * jnp.tanh(p["mixer"]["gate_attn"].astype(mix.dtype))
    else:
        window = cfg.sliding_window if kind.mixer == "attn_local" else None
        q = attn.project_q(p["mixer"], h, cfg, positions)
        k, v = attn.project_kv(p["mixer"], h, cfg, positions)
        q, k, v = attn.shard_heads_for_tp(q, k, v)
        # cost-accounting mode (unroll_layers): every attention tile must be
        # visible to cost_analysis, so the kv scan is unrolled — with
        # coarser tiles (S/8) to keep the compile graph bounded at 32k seq.
        # Tile granularity only affects the causal-waste rectangle (<13%
        # pessimism on the quadratic term), documented in EXPERIMENTS.md.
        blk = max(1024, q.shape[1] // 8) if cfg.unroll_layers else 1024
        o = attn.blockwise_attention(
            q, k, v, causal=causal, window=window, unroll=cfg.unroll_layers,
            q_block=blk, kv_block=blk,
        )
        mix = attn.output_proj(p["mixer"], o)
        if collect_kv:
            B, S = k.shape[:2]
            kv = (k.reshape(B, S, -1), v.reshape(B, S, -1))
    x = x + mix
    if "cross" in p:  # encoder-decoder cross-attention sub-block
        h = rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        q = attn.project_q(p["cross"], h, cfg, positions, rope=False)
        k, v = attn.project_kv(p["cross"], ctx, cfg, None, rope=False)
        o = attn.blockwise_attention(q, k, v, causal=False)
        x = x + attn.output_proj(p["cross"], o)
    if kind.ffn != "none":
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind.ffn == "mlp":
            f = mlp(p["ffn"], h)
        else:
            f, aux = moe_mod.moe(p["ffn"], h, cfg)
        x = x + f
    return x, kv, aux


def run_stack(
    stack_params,
    groups: list[LayerGroup],
    x,
    cfg: ModelConfig,
    *,
    positions,
    ctx=None,
    causal: bool = True,
    collect_kv: bool = False,
    hook=None,
):
    """Scan each group; returns (x, kv_per_attn_layer list, aux_sum).

    ``hook``: a training step's gradient taps on ``stack_params``, placed
    on each layer's parameter slice; a group the hook unrolls is applied
    layer by layer."""
    aux_total = jnp.zeros((), jnp.float32)
    kv_all: list = []
    for gi, (g, gp) in enumerate(zip(groups, stack_params)):
        gh = None if hook is None else hook.at(gi)
        xs = tuple(gp) if gh is None else gh.scan(gp)

        unrolled = (g.repeats == 1 or cfg.unroll_layers
                    or (gh is not None and gh.unrolls(g.repeats)))

        def layer_params(xs_r, pos):
            return xs_r[pos] if gh is None else gh.layer(xs_r, pos)

        if unrolled:
            # tail group / unrolled mode: apply layers directly
            def one_layer(kind, p, x):
                return apply_layer(
                    kind, p, x, cfg, positions=positions, ctx=ctx,
                    causal=causal, collect_kv=collect_kv,
                )

            for rep in range(g.repeats):
                xs_r = jax.tree.map(lambda a: a[rep], xs)
                for pos, kind in enumerate(g.pattern):
                    p = layer_params(xs_r, pos)
                    fn = (
                        jax.checkpoint(one_layer, static_argnums=(0,))
                        if cfg.remat
                        else one_layer
                    )
                    x, kv, aux = fn(kind, p, x)
                    aux_total = aux_total + aux
                    if kv is not None:
                        kv_all.append((kv[0][:, None], kv[1][:, None]))
            continue

        def body(carry, xs_r):
            h, aux_c = carry
            ys = []
            for pos, kind in enumerate(g.pattern):
                h, kv, aux = apply_layer(
                    kind, layer_params(xs_r, pos), h, cfg,
                    positions=positions, ctx=ctx,
                    causal=causal, collect_kv=collect_kv,
                )
                aux_c = aux_c + aux
                if kv is not None:
                    ys.append(kv)
            return (h, aux_c), tuple(ys)

        body_fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux_total), ys = jax.lax.scan(body_fn, (x, aux_total), xs)
        # ys: tuple over attn-positions of (k, v) with leading dim R.
        # Layer order within the group is repeat-major: interleave.
        if collect_kv and ys:
            ks = jnp.stack([kv[0] for kv in ys], axis=1)  # (R, npos, B, S, KH)
            vs = jnp.stack([kv[1] for kv in ys], axis=1)
            R, npos = ks.shape[:2]
            ks = ks.reshape(R * npos, *ks.shape[2:]).transpose(1, 0, 2, 3)
            vs = vs.reshape(R * npos, *vs.shape[2:]).transpose(1, 0, 2, 3)
            kv_all.append((ks, vs))  # (B, R*npos, S, KH)
    return x, kv_all, aux_total


def attn_layer_indices(cfg: ModelConfig) -> list[int]:
    """Indices of layers that own a self-attention KV cache."""
    from repro.configs.base import layer_kinds

    return [
        i
        for i, k in enumerate(layer_kinds(cfg))
        if k.mixer in ("attn", "attn_local")
    ]


def mamba_layer_indices(cfg: ModelConfig) -> list[int]:
    from repro.configs.base import layer_kinds

    return [i for i, k in enumerate(layer_kinds(cfg)) if k.mixer == "mamba"]
