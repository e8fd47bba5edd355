"""Operations and bytes the GLM family's served model needs, from the
configuration's shapes alone. These are the numerators of
`decode_mfu_pct` and `paged_attention_roofline`: what the algorithm
needs, not what the program happens to do (dead blocks, idle lanes and
overshoot steps are not counted).

A family's costs module gives the readers two functions, found by name
like its program and reference modules (see bench/harness.py):
`token_flops(s, ctx)` and `attention_layers(s, ctx, block_tokens)`.
`Shapes` is the family reference's own (glm_reference.py); only its
sizes are read here.
"""
from __future__ import annotations

BF16 = 2   # bytes per element of weights, KV and q/out in the served model


def matmul_params(s) -> int:
    """Weights that every token multiplies: per layer q, k, v, attention
    out and the three FFN matrices, plus the head. The embedding is a
    gather, not a matmul, and is left out."""
    nq, nkv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    per_layer = s.hidden * nq + 2 * s.hidden * nkv + nq * s.hidden \
        + 3 * s.hidden * s.ffn
    return s.layers * per_layer + s.hidden * s.vocab


def attention_flops(s, ctx: int) -> int:
    """Scores and weighted values of one query token over `ctx` keys, in
    every layer: 2 FLOPs per multiply-add, QK^T and PV."""
    return s.layers * 4 * s.heads * s.head_dim * ctx


def token_flops(s, ctx: int) -> int:
    """Model FLOPs of one token at a position that attends to `ctx`
    positions (itself included), over every layer and the head."""
    return 2 * matmul_params(s) + attention_flops(s, ctx)


def kv_block_bytes(s, block_tokens: int) -> int:
    """One paged KV block of one layer: K and V of every kv head."""
    return 2 * s.kv_heads * block_tokens * s.head_dim * BF16


def attention_call(s, ctx: int, block_tokens: int):
    """(FLOPs, bytes) that one lane's decode attention needs in one layer:
    the live KV blocks holding its `ctx` positions, q in and out back."""
    blocks = -(-ctx // block_tokens)
    q_out = 2 * s.heads * s.head_dim * BF16
    flops = 4 * s.heads * s.head_dim * ctx
    return flops, blocks * kv_block_bytes(s, block_tokens) + q_out


def attention_layers(s, ctx: int, block_tokens: int):
    """[(FLOPs, bytes)] of one lane's decode attention, one entry per
    layer: every GLM layer attends to all `ctx` positions alike."""
    return [attention_call(s, ctx, block_tokens)] * s.layers
