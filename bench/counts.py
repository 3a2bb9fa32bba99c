"""Operation and byte counts from shapes, for utilization and roofline
shares.  They count the work the algorithm needs, not what one
implementation of it happens to do, so a kernel that fuses passes or skips
materializing an intermediate is measured against the same yardstick."""
from __future__ import annotations


def granite_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one training token (forward and backward) of the
    MoE transformer in ``cfg`` at sequence length ``seq_len``.

    6 x the matmul parameters one token activates (attention projections,
    the router, ``num_experts_per_tok`` SwiGLU experts, the LM head; the
    embedding is a lookup), plus causal attention's score and value
    products: a token at position i attends to i + 1 keys, 4*H*hd FLOPs
    each forward, so 2*H*hd*(S+1) per token on average, times 3 for the
    backward.  Recomputation is not counted."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    F = cfg["intermediate_size"]
    E = cfg["num_local_experts"]
    K = cfg["num_experts_per_tok"]
    V = cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    moe = D * E + K * 3 * D * F
    matmul = L * (attn + moe) + V * D
    attention = L * 3 * 2 * H * hd * (seq_len + 1)
    return 6.0 * matmul + attention


def agg_decode_bytes(senders: int, padded: int, bits: int, nb: int) -> int:
    """Least HBM bytes of decoding ``senders`` packed payloads of
    ``padded`` coordinates and summing their integer coordinates: each
    payload's packed words and per-bucket sides are read once, the shared
    dither and decode reference once, and one int32 sum is written."""
    per = 32 // bits
    words = -(-padded // per) * 4
    return senders * (words + 4 * nb) + 3 * 4 * padded
