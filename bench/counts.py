"""Operations and bytes that a piece of work needs, counted from its
shapes, for roofline shares and model FLOP utilisation.

Each count is of what the algorithm needs, not of what one
implementation happens to do: a kernel that streams its input twice, or
recomputes a layer, is slower against the same count.
"""
from __future__ import annotations

F32 = 4


def gram_factors_cost(n_rows: int, d: int, steps: int, k: int = 256) -> dict:
    """The gram plane's precompute for one sweep call: the extended data
    matrix R (n_rows, d) in float32 is read once; G = R R^T costs
    2 n_rows^2 d operations; each of the ``steps`` CountSketch tables
    adds every signed element of R into its bucket (a sign select and an
    add: 2 n_rows d operations a table).  Written: G, and the (steps,
    n_rows, k) tables.

    Returns {"flops", "bytes"}."""
    flops = 2 * n_rows * n_rows * d + 2 * steps * n_rows * d
    nbytes = F32 * (n_rows * d + n_rows * n_rows + steps * n_rows * k)
    return {"flops": flops, "bytes": nbytes}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``cost``: the larger of
    operations over the bf16 peak and bytes over the HBM bandwidth, and
    which of the two bounds it ("compute" or "memory")."""
    t_ops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def dense_params(cfg: dict) -> dict:
    """Matmul parameters of a dense decoder with grouped-query attention
    and a gated MLP, from a Hugging Face style ``config.json``:
    per layer q, k, v, o and the three MLP matrices; the embedding."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    layer = {"q": d * h * hd, "k": d * kv * hd, "v": d * kv * hd,
             "o": h * hd * d, "mlp": 3 * d * ff}
    return {"layer": layer, "per_layer": sum(layer.values()),
            "embedding": cfg["vocab_size"] * d}


def dense_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs per token (forward and backward) of a dense decoder:
    6 x the matmul parameters of every layer and of the LM head (a tied
    head is the embedding used once more as a matmul), plus the attention
    scores, 12 x layers x heads x head_dim x seq_len (the count of the
    PaLM paper, appendix B: both QK^T and AV, forward and backward, with
    no saving for the causal mask).  Recomputed activations are not
    counted."""
    p = dense_params(cfg)
    layers = cfg["num_hidden_layers"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    matmul = layers * p["per_layer"] + p["embedding"]
    return 6.0 * matmul + 12.0 * layers * h * hd * seq_len
