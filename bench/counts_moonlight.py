"""Operation counts of the Moonlight cells, from shapes and the routing,
as ``bench.counts`` counts granite's: the work the algorithm needs, with
no recomputation."""
from __future__ import annotations


def flops_per_token(cfg: dict, seq_len: int, held_rows_per_token: float
                    ) -> float:
    """Model FLOPs of one training token on this chip (forward and
    backward): 6 x the matmul parameters the token activates here (latent
    attention's four projections in every layer, the leading dense
    layers' SwiGLU, each MoE layer's router and shared experts, the held
    experts the token was routed to, ``held_rows_per_token`` summed over
    the MoE layers and taken from the loads, and the LM head; the
    embedding is a lookup), plus causal attention's products: a token at
    position i attends to i + 1 keys at 2*H*(qk_nope + qk_rope) FLOPs for
    the score and 2*H*v_head_dim for the value, forward, so
    H*(qk + v)*(S+1) per token on average, times 3 for the backward."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    R = cfg["kv_lora_rank"]
    r = cfg["qk_rope_head_dim"]
    F = cfg["moe_intermediate_size"]
    Ld = cfg["first_k_dense_replace"]
    L = cfg["num_hidden_layers"]
    mla = D * H * qk + D * (R + r) + R * H * (cfg["qk_nope_head_dim"] + vd) \
        + H * vd * D
    moe = D * cfg["published_n_routed_experts"] \
        + 3 * D * cfg["n_shared_experts"] * F
    matmul = (L * mla + Ld * 3 * D * cfg["intermediate_size"]
              + (L - Ld) * moe + held_rows_per_token * 3 * D * F
              + cfg["vocab_size"] * D)
    attention = L * 3 * H * (qk + vd) * (seq_len + 1)
    return 6.0 * matmul + attention


def gmm_flops(cfg: dict, held_rows: float) -> float:
    """FLOPs of the held experts' grouped matmuls for ``held_rows`` rows
    routed to them: three products of 2*D*F a row forward, twice that
    backward (the rows' and the weights' gradients)."""
    return 18.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * held_rows
