"""Operations and bytes the algorithms need, from shapes alone.

A forward pass costs 2 x the non-embedding parameters x the real tokens,
plus attention scores and values (causal pairs), the Mamba-2 recurrence
(state update and read-out), and the unembedding at each position whose
logits are used. Rows that only pad a batch, and decode steps whose
logits are thrown away, are not counted.
"""
from __future__ import annotations


def dense_layer_params(cfg: dict) -> int:
    D, H, KV, hd, F = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    return 2 * D * H * hd + 2 * D * KV * hd + 3 * D * F


def ssm_layer_params(cfg: dict) -> int:
    D = cfg["d_model"]
    di = cfg["ssm_expand"] * D
    H = di // cfg["ssm_head_dim"]
    GN = cfg["ssm_groups"] * cfg["ssm_state"]
    return D * (2 * di + 2 * GN + H) + di * D


def _attn_pairs(start: int, n: int) -> int:
    """Causal (query, key) pairs of ``n`` new tokens after ``start``."""
    return sum(start + i + 1 for i in range(n))


def tier_flops(cfg: dict, start: int, n_tokens: int, n_logits: int) -> float:
    """One sequence: ``n_tokens`` new tokens after ``start`` cached ones,
    logits at ``n_logits`` positions."""
    L, D, V = cfg["num_layers"], cfg["d_model"], cfg["vocab_size"]
    if cfg["family"] == "ssm":
        di = cfg["ssm_expand"] * D
        H, P, N = di // cfg["ssm_head_dim"], cfg["ssm_head_dim"], \
            cfg["ssm_state"]
        conv_ch = di + 2 * cfg["ssm_groups"] * cfg["ssm_state"]
        per_tok = 2 * ssm_layer_params(cfg) + 4 * H * P * N + \
            2 * cfg["d_conv"] * conv_ch
        body = L * per_tok * n_tokens
    else:
        H, hd = cfg["num_heads"], cfg["head_dim"]
        body = L * (2 * dense_layer_params(cfg) * n_tokens
                    + 4 * H * hd * _attn_pairs(start, n_tokens))
    return float(body + 2 * V * D * n_logits)


def generate_flops(cfg: dict, prompt_len: int, max_new: int) -> float:
    """Greedy generation of ``max_new`` tokens after a ``prompt_len``
    prompt: the prefill, then the ``max_new - 1`` decode steps whose
    logits pick a token."""
    f = tier_flops(cfg, 0, prompt_len, 1)
    for j in range(max_new - 1):
        f += tier_flops(cfg, prompt_len + j, 1, 1)
    return f


def embedder_flops(cfg: dict, seq_len: int) -> float:
    """One bidirectional encoder pass over ``seq_len`` tokens, pooled and
    projected."""
    d, F, L = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    per_layer = 2 * (4 * d * d + 3 * d * F) * seq_len + \
        4 * d * seq_len * seq_len
    return float(L * per_layer + 2 * d * cfg["embed_dim"])


def topk_bytes(capacity: int, embed_dim: int, batch: int) -> float:
    """Bytes one top-k read of the store needs from memory: every
    embedding (float32), one int32 of valid and guide bits per row, and
    the queries."""
    return float(capacity * embed_dim * 4 + capacity * 4
                 + batch * embed_dim * 4)


def topk_flops(capacity: int, embed_dim: int, batch: int) -> float:
    return float(2 * capacity * embed_dim * batch)
