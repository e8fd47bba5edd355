"""Operations and bytes the DeepSeek-V2 family's served model needs, from
the configuration's shapes alone: the numerators of `decode_mfu_pct` and
`paged_attention_roofline` (see glm_costs.py for the interface). What the
algorithm needs, counted in the absorbed form the program serves: a
token's KV is one latent vector of kv_lora + rope, read as K and, in its
first kv_lora columns, as V; an MoE layer multiplies the router, the
shared experts and, on average, top_k * held / experts of this share's
experts (a held expert that no row picked is not counted).
"""
from __future__ import annotations

BF16 = 2   # bytes per element of weights, KV and q/out in the served model


def attention_params(s) -> int:
    """One layer's attention weights in the absorbed form: q, kv_a, W_UK,
    W_UV and the output projection."""
    H, C = s.heads, s.kv_lora
    return s.hidden * H * (s.nope + s.rope) + s.hidden * (C + s.rope) \
        + H * s.nope * C + H * C * s.v_head + H * s.v_head * s.hidden


def matmul_params(s) -> int:
    """Weights that every token multiplies, on average: every layer's
    attention, the dense layers' MLP, each MoE layer's router, shared
    experts and its share of picks on held experts, and the head. The
    embedding is a gather, not a matmul, and is left out."""
    expert = 3 * s.hidden * s.moe_ffn
    moe = s.hidden * s.experts + s.shared * expert \
        + s.top_k * s.held / s.experts * expert
    n_moe = s.layers - s.dense_layers
    return round(s.layers * attention_params(s)
                 + s.dense_layers * 3 * s.hidden * s.ffn
                 + n_moe * moe + s.hidden * s.vocab)


def latent_dim(s) -> int:
    return s.kv_lora + s.rope


def attention_call(s, ctx: int, block_tokens: int):
    """(FLOPs, bytes) one lane's decode attention needs in one layer: the
    live latent blocks holding its `ctx` positions, the absorbed q of
    every head in, and its latent values out. Scores and values: 2 FLOPs
    per multiply-add over kv_lora + rope and over kv_lora."""
    blocks = -(-ctx // block_tokens)
    flops = 2 * s.heads * (latent_dim(s) + s.kv_lora) * ctx
    block = block_tokens * latent_dim(s) * BF16
    q_out = s.heads * (latent_dim(s) + s.kv_lora) * BF16
    return flops, blocks * block + q_out


def token_flops(s, ctx: int) -> int:
    """Model FLOPs of one token at a position that attends to `ctx`
    positions (itself included), over every layer and the head."""
    return 2 * matmul_params(s) + s.layers * attention_call(s, ctx, 1)[0]


def attention_layers(s, ctx: int, block_tokens: int):
    """[(FLOPs, bytes)] of one lane's decode attention, one entry per
    layer: every layer, the dense ones too, attends alike."""
    return [attention_call(s, ctx, block_tokens)] * s.layers
