"""Mixture-of-Experts with sort-based (scatter/gather) dispatch.

Why not the classic one-hot dispatch einsum: `[B,S,E,C] x [B,S,D]` costs
O(B*S^2*k*D) real matmul FLOPs and would dominate the roofline at 4k+
sequence lengths. Here dispatch is a sort + scatter (bytes, not FLOPs), and
expert compute is a ragged-padded batched matmul `[E,G,D] x [E,D,F]` whose
FLOPs are exactly active-expert FLOPs x capacity_factor — what a production
grouped-GEMM (megablox) implementation costs.

Sharding: expert tensors are sharded on the expert axis when E >= the model
axis size (olmoe: 64e -> 4/device), else on d_ff within each expert
(mixtral: 8e, TP-2 per expert pair). Chosen by launch/shardings.py.

HADES hook: `expert_counts` (tokens routed per expert this step) is returned
as the expert-level access bitmap — the frontend's Object Collector consumes
it to classify hot/cold experts (DESIGN.md §3.1).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

# ---------------------------------------------------------------------------
# Optional activation-sharding hints (§Perf cell A). jit in_shardings only
# pin ARGUMENTS; XLA picks intermediate shardings itself and (measured:
# iteration 1) ignores weight-spec nudges inside the scanned body. These
# with_sharding_constraint hints pin the dispatched-token tensors so the
# partitioner must all-gather WEIGHTS (layer-sized) instead of
# all-reducing partial sums of ACTIVATIONS (batch*seq*d_ff-sized).
# ---------------------------------------------------------------------------
_SHARDING_HINTS = None


def set_sharding_hints(hints) -> None:
    """hints: {"dispatch": PartitionSpec for [E,G,D]-like tensors,
    "hidden": PartitionSpec for [E,G,F]} or None to disable."""
    global _SHARDING_HINTS
    _SHARDING_HINTS = hints


def _hint(x, name):
    if _SHARDING_HINTS and name in _SHARDING_HINTS:
        return jax.lax.with_sharding_constraint(x, _SHARDING_HINTS[name])
    return x


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, e = cfg.d_model, cfg.num_experts
    f = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, f ** -0.5
    return {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "wi": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
        "wg": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
        "wo": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dtype),
    }


def capacity(t: int, cfg: ModelConfig,
             capacity_factor: float = 1.25) -> int:
    """Per-expert token capacity G used by `moe_block`'s dispatch for `t`
    tokens — the drop threshold: a layer whose routing counts exceed it
    silently drops the overflow (their contribution is zero; the residual
    stream carries them). Exposed so tests/diagnostics can attribute
    decode/prefill divergence to capacity drops."""
    e, k = cfg.num_experts, cfg.experts_per_token
    g = int(max(8, -(-t * k // e) * capacity_factor))  # ceil with slack
    return -(-g // 8) * 8                              # pad to 8


def moe_block(p: dict, x: jax.Array, cfg: ModelConfig,
              capacity_factor: float = 1.25
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar, expert_counts [E]).

    Top-k routing with softmax-renormalized weights; sort-based dispatch
    into a [E, G, D] buffer (G = per-expert capacity); tokens over capacity
    are dropped (their contribution is zero — residual stream carries them).
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    topk_w, topk_e = jax.lax.top_k(gates, k)                   # [T, k]
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)

    # ---- load-balancing aux loss (Switch/Mixtral style) ----
    me = jnp.mean(gates, axis=0)                               # [E]
    ce = jnp.zeros((e,), jnp.float32).at[topk_e.reshape(-1)].add(1.0) / (t * k)
    aux_loss = e * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    n = t * k
    flat_e = topk_e.reshape(n)                                 # expert id per slot
    flat_w = topk_w.reshape(n)
    flat_tok = jnp.repeat(jnp.arange(t), k)                    # token id per slot
    order = jnp.argsort(flat_e)                                # stable
    se, sw, st = flat_e[order], flat_w[order], flat_tok[order]

    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)      # [E]
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n, dtype=jnp.int32) - starts[se]         # intra-expert rank

    g = capacity(t, cfg, capacity_factor)
    keep = rank < g
    dest = jnp.where(keep, se * g + rank, n)                   # n = drop bin

    # scatter tokens -> [E*G, D] (extra row absorbs drops, then sliced off)
    buf = jnp.zeros((e * g + 1, d), x.dtype).at[dest].set(xf[st], mode="drop")
    buf = _hint(buf[:-1].reshape(e, g, d), "dispatch")

    # ---- expert compute (grouped GEMM) ----
    h = _hint(jnp.einsum("egd,edf->egf", buf, p["wi"]), "hidden")
    gate = _hint(jnp.einsum("egd,edf->egf", buf, p["wg"]), "hidden")
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * h
    y = _hint(jnp.einsum("egf,efd->egd", h, p["wo"]),
              "dispatch").reshape(e * g, d)

    # ---- gather back + weighted combine over k ----
    src = jnp.where(keep, se * g + rank, 0)
    contrib = y[src] * jnp.where(keep, sw, 0.0)[:, None].astype(y.dtype)
    out = jnp.zeros((t, d), y.dtype).at[st].add(contrib)
    return out.reshape(b, s, d), aux_loss, counts


def moe_block_gathered(p: dict, x: jax.Array, cfg: ModelConfig
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Decode-path MoE: gather ONLY the routed experts' weights (the
    HADES hot-expert principle applied to the weight stream). Exact —
    same math as moe_block with no capacity drops. Profitable when
    T*k < E (e.g. batch-1 long-context decode); the dense/dispatch path
    wins for large T.

    x: [B, S, D] with small T = B*S."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    xf = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_e = jax.lax.top_k(gates, k)                  # [T, k]
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)

    wi = p["wi"][topk_e]                                      # [T, k, D, F]
    wg = p["wg"][topk_e]
    wo = p["wo"][topk_e]                                      # [T, k, F, D]
    h = jnp.einsum("td,tkdf->tkf", xf, wi)
    g = jnp.einsum("td,tkdf->tkf", xf, wg)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    y = jnp.einsum("tkf,tkfd->tkd", h, wo)
    out = jnp.einsum("tk,tkd->td", topk_w.astype(y.dtype), y)
    counts = jnp.zeros((e,), jnp.int32).at[topk_e.reshape(-1)].add(1)
    return out.reshape(b, s, d), jnp.zeros((), jnp.float32), counts


def held_experts_block(p: dict, x: jax.Array, cfg: ModelConfig,
                       lanes=None) -> Tuple[jax.Array, jax.Array]:
    """Decode expert layer of a chip that holds `cfg.held_experts` =
    (first, count) of the router's `num_experts` (expert parallelism;
    on one chip the exchange is absent). The router scores all of them
    and keeps the top k; every held expert runs on every row, weighted by
    its gate where the row picked it and by zero elsewhere and on
    inactive lanes (`lanes` [B] bool). Exact, and no token is dropped: at
    decode batch sizes each held expert's weights are read once whatever
    the routing, so this costs what a grouped matmul would. Gates follow
    `norm_topk_prob` (renormalised over the k) or else are scaled by
    `routed_scaling_factor`; `n_shared_experts` add one always-on MLP
    (`p["shared"]`), once.

    p: {"router" [D, E], "wi", "wg" [count, D, F], "wo" [count, F, D],
    "shared": {"wi", "wg", "wo"}}. x: [B, S, D] -> (out [B, S, D], picks
    [count] int32: top-k picks of active rows per held expert). Scopes
    `router`, `experts` and `shared_experts` label its ops."""
    b, s, d = x.shape
    first, count = cfg.held_experts
    xf = x.reshape(b * s, d)
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                               cfg.experts_per_token)           # [T, k]
        if cfg.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        else:
            w = w * cfg.routed_scaling_factor
        hit = (idx[..., None] - first) == jnp.arange(count)     # [T, k, n]
        if lanes is not None:
            hit &= jnp.repeat(lanes, s)[:, None, None]
        gates = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)
        picks = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("experts"):
        h = jnp.einsum("td,edf->etf", xf, p["wi"])
        g = jnp.einsum("td,edf->etf", xf, p["wg"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
        y = jnp.einsum("etf,efd->etd", h, p["wo"])
        out = jnp.einsum("te,etd->td", gates, y.astype(jnp.float32))
    if "shared" in p:
        with jax.named_scope("shared_experts"):
            out = out + L.mlp(p["shared"], xf, True).astype(jnp.float32)
    return out.astype(x.dtype).reshape(b, s, d), picks


def moe_block_ref(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Oracle: compute every expert densely, combine by top-k gates.
    O(E x full FLOPs) — tiny shapes only. No capacity drops, so it matches
    moe_block exactly only when no token exceeds capacity."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    xf = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_e = jax.lax.top_k(gates, k)
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
    w = jnp.zeros_like(gates).at[jnp.arange(gates.shape[0])[:, None],
                                 topk_e].set(topk_w)           # [T, E]
    h = jnp.einsum("td,edf->etf", xf, p["wi"])
    g = jnp.einsum("td,edf->etf", xf, p["wg"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    y = jnp.einsum("etf,efd->etd", h, p["wo"])                 # [E, T, D]
    out = jnp.einsum("te,etd->td", w.astype(y.dtype), y)
    return out.reshape(b, s, d)
