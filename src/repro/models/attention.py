"""Attention: full (oracle), blockwise (memory-efficient online-softmax,
the XLA analog of flash attention), sliding-window, decode (single-token
vs a KV cache, with distributed flash-decoding combine), and cross-attention.

Shapes convention:
  q: [B, S, H, D]    k/v: [B, S_kv, KV, D]   (KV = num kv heads, GQA groups
  are expanded inside — H % KV == 0).

`blockwise_attention` is used for training/prefill in the dry-run: it never
materializes the [S, S] score matrix (lax.scan over KV chunks with running
max/denominator), so compile-time memory analysis reflects a production
flash implementation. The Pallas flash kernel (kernels/flash_attention.py)
targets the same math for real TPUs; `full_attention` is the shared oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38  # ~ -bf16 max; matches TPU flash kernels


def _expand_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, KV, D] -> [B, S, KV*n_rep, D] by repeating each kv head."""
    if n_rep == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, d)) \
              .reshape(b, s, kv * n_rep, d)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: int) -> jax.Array:
    """Additive mask bias [.., Sq, Sk] from absolute positions."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]),
                 jnp.bool_)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m &= kp <= qp
    if window > 0:
        m &= kp > qp - window
    return jnp.where(m, 0.0, NEG_INF)


# ---------------------------------------------------------------------------
# Full attention — the oracle (materializes scores; tiny shapes only)
# ---------------------------------------------------------------------------
def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int = 0,
                   q_pos: Optional[jax.Array] = None,
                   k_pos: Optional[jax.Array] = None,
                   softcap: float = 0.0) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    k = _expand_kv(k, h // kv)
    v = _expand_kv(v, h // kv)
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
    if k_pos is None:
        k_pos = jnp.broadcast_to(jnp.arange(sk)[None], (b, sk))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores *= d ** -0.5
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores += _mask_bias(q_pos, k_pos, causal, window)[:, None]
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Blockwise attention — memory-efficient online softmax over KV chunks
# ---------------------------------------------------------------------------
def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        chunk: int = 512,
                        q_pos: Optional[jax.Array] = None,
                        k_pos: Optional[jax.Array] = None,
                        softcap: float = 0.0) -> jax.Array:
    """Never materializes [Sq, Sk]: scans KV in chunks of `chunk`, keeping
    running (max, denom, weighted-sum). Live memory O(Sq*chunk)."""
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    n_rep = h // kv
    if sk % chunk != 0:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if k_pos is None:
            k_pos = jnp.broadcast_to(jnp.arange(sk)[None], (b, sk))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=2 ** 30)
        sk += pad
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
    if k_pos is None:
        k_pos = jnp.broadcast_to(jnp.arange(sk)[None], (b, sk))

    n_chunks = sk // chunk
    kc = k.reshape(b, n_chunks, chunk, kv, d)
    vc = v.reshape(b, n_chunks, chunk, kv, d)
    pc = k_pos.reshape(b, n_chunks, chunk)
    qf = q.astype(jnp.float32) * d ** -0.5

    def body(carry, xs):
        m, l, acc = carry           # [B,H,Sq], [B,H,Sq], [B,Sq,H,D]
        kb, vb, pb = xs             # [B,chunk,KV,D], ..., [B,chunk]
        kb = _expand_kv(kb, n_rep)
        vb = _expand_kv(vb, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s += _mask_bias(q_pos, pb, causal, window)[:, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l_new = l * scale + jnp.sum(p, axis=-1)
        acc_new = acc * jnp.moveaxis(scale, 1, -1)[..., None] + \
            jnp.einsum("bhqk,bkhd->bqhd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, sq, h, d), jnp.float32))
    # scan over chunk axis (moved to front)
    xs = (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
          jnp.moveaxis(pc, 1, 0))
    (m, l, acc), _ = jax.lax.scan(body, init, xs)
    l = jnp.maximum(l, 1e-30)
    out = acc / jnp.moveaxis(l, 1, -1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention — one query token vs a KV cache
# ---------------------------------------------------------------------------
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len, *, window: int = 0,
                     k_pos: Optional[jax.Array] = None,
                     q_pos: Optional[jax.Array] = None) -> jax.Array:
    """q: [B, 1, H, D]; caches: [B, S, KV, D]; cache_len: scalar or [B]
    number of valid entries. Computes masked softmax over the cache in
    fp32 with one pass (O(S) memory, S x D matvec). Window masking uses
    absolute positions when k_pos is given (ring-buffer caches)."""
    b, _, h, d = q.shape
    _, s, kv, _ = k_cache.shape
    k = _expand_kv(k_cache, h // kv)
    v = _expand_kv(v_cache, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores *= d ** -0.5
    idx = jnp.arange(s)[None]                     # [1, S]
    valid = idx < jnp.reshape(cache_len, (-1, 1))
    if window > 0:
        if q_pos is None:
            q_pos = jnp.reshape(cache_len, (-1, 1)) - 1
        kp = idx if k_pos is None else k_pos
        valid &= kp > jnp.reshape(q_pos, (-1, 1)) - window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def decode_attention_partial(q, k_part, v_part, valid_mask, scale=None):
    """Flash-decoding partial: attention over a shard of the KV sequence.
    Returns (unnormalized_out [B,1,H,Dv] fp32, m [B,H,1], l [B,H,1]) so
    that shards combine with `combine_partials` (psum-style merge).
    valid_mask: [B, S_part] bool; `scale` on the scores, default D^-0.5."""
    b, _, h, d = q.shape
    _, s, kv, _ = k_part.shape
    k = _expand_kv(k_part, h // kv)
    v = _expand_kv(v_part, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores *= d ** -0.5 if scale is None else scale
    scores = jnp.where(valid_mask[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                  # [B,H,1]
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)                       # [B,H,1]
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out, m, l


def combine_partials(parts):
    """Merge flash-decoding partials [(out, m, l)] -> [B,1,H,D]."""
    outs, ms, ls = zip(*parts)
    m_all = jnp.max(jnp.stack(ms), axis=0)
    tot_l = 0.0
    tot_o = 0.0
    for o, m, l in parts:
        scale = jnp.exp(m - m_all)                # [B,H,1]
        tot_l = tot_l + l * scale
        tot_o = tot_o + o * jnp.moveaxis(scale, 1, -1)[..., None]
    tot_l = jnp.maximum(tot_l, 1e-30)
    return tot_o / jnp.moveaxis(tot_l, 1, -1)[..., None]


# ---------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# ---------------------------------------------------------------------------
def cross_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    enc_mask: Optional[jax.Array] = None) -> jax.Array:
    """q: [B, Sq, H, D] over encoder memory k/v: [B, Se, KV, D]."""
    b, sq, h, d = q.shape
    _, se, kv, _ = k.shape
    k = _expand_kv(k, h // kv)
    v = _expand_kv(v, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores *= d ** -0.5
    if enc_mask is not None:
        scores = jnp.where(enc_mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
