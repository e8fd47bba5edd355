"""Multi-head latent attention (DeepSeek-V2) for single-token decode, in
its absorbed form, with YaRN rotary scaling.

A token's KV is one vector of kv_lora_rank + qk_rope_head_dim: the
RMS-normed latent `c` (the `kv_a` projection's first kv_lora_rank
columns) and the roped `k_pe` (its last qk_rope_head_dim). The paged pool
caches that vector once per token and layer (`KVCacheConfig.v_dim`), and
the kernel reads it as K and, in its first kv_lora_rank columns, as V.
Absorption moves the per-head up-projections of `kv_b` onto the query and
the output: score_h = (q_nope_h W_UK_h^T) . c + q_pe_h . k_pe, and the
head's value is (softmax . c) W_UV_h. So every head attends to the one
shared vector, as multi-query attention with a 576-wide head.

Parameters of one layer (matrices input-by-output, as in transformer.py):
  wq    [D, H * (nope + rope)]   per head [nope | rope]
  wkv_a [D, C + rope]            [c | k_pe]
  kv_ln [C]                      the latent's RMSNorm (1 + scale)
  wuk   [H, nope, C]             W_UK: kv_b's key half, per head
  wuv   [H, C, v]                W_UV: kv_b's value half, per head
  wo    [H * v, D]
The rope columns rotate in halves (i, i + rope/2), as `layers._rotate`
does; DeepSeek's published weights rotate interleaved pairs (2i, 2i + 1)
after a de-interleave, which a permutation of those columns turns into
this layout (bench/models/deepseek_v2.py).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, YarnScaling
from repro.models import layers as L


# ---------------------------------------------------------------------------
# YaRN (HF modeling_deepseek.py: yarn_find_correction_range,
# yarn_linear_ramp_mask, yarn_get_mscale, DeepseekV2YarnRotaryEmbedding)
# ---------------------------------------------------------------------------
def yarn_find_correction_dim(num_rotations: float, dim: int, base: float,
                             max_position_embeddings: int) -> float:
    return (dim * math.log(max_position_embeddings
                           / (num_rotations * 2 * math.pi))) \
        / (2 * math.log(base))


def yarn_find_correction_range(low_rot: float, high_rot: float, dim: int,
                               base: float, max_position_embeddings: int
                               ) -> Tuple[int, int]:
    low = math.floor(yarn_find_correction_dim(low_rot, dim, base,
                                              max_position_embeddings))
    high = math.ceil(yarn_find_correction_dim(high_rot, dim, base,
                                              max_position_embeddings))
    return max(low, 0), min(high, dim - 1)


def yarn_get_mscale(scale: float = 1.0, mscale: float = 1.0) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_linear_ramp_mask(lo: float, hi: float, dim: int) -> np.ndarray:
    if lo == hi:
        hi += 0.001          # no singularity
    ramp = (np.arange(dim, dtype=np.float32) - lo) / (hi - lo)
    return np.clip(ramp, 0, 1)


def rope_table(dim: int, base: float, yarn: YarnScaling = None
               ) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [dim/2] float32, the factor on cos and sin).
    Without `yarn`, plain RoPE's."""
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = (1.0 / base ** exps).astype(np.float32)
    if yarn is None:
        return extra, 1.0
    inter = (1.0 / (yarn.factor * base ** exps)).astype(np.float32)
    lo, hi = yarn_find_correction_range(
        yarn.beta_fast, yarn.beta_slow, dim, base,
        yarn.original_max_position_embeddings)
    keep = 1.0 - yarn_linear_ramp_mask(lo, hi, dim // 2)
    inv = (inter * (1 - keep) + extra * keep).astype(np.float32)
    m = yarn_get_mscale(yarn.factor, yarn.mscale) \
        / yarn_get_mscale(yarn.factor, yarn.mscale_all_dim)
    return inv, float(m)


def softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-0.5, times mscale_all_dim's mscale squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        m = yarn_get_mscale(y.factor, y.mscale_all_dim)
        scale *= m * m
    return scale


def rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x [B, S, n, rope]; positions [B, S]."""
    inv, m = rope_table(cfg.qk_rope_head_dim, cfg.rope_theta,
                        cfg.rope_scaling)
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv)
    out = L._rotate(x.astype(jnp.float32), ang[:, :, None, :])
    return (out * m if m != 1.0 else out).astype(x.dtype)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def qkv(p: dict, h: jax.Array, cfg: ModelConfig, positions):
    """h [B, S, D] (normed) -> (q [B, S, H, C + rope], latent
    [B, S, 1, C + rope], None): the absorbed query of every head and the
    token's one cached vector, which is its K and holds its V."""
    b, s, _ = h.shape
    nope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c_dim = cfg.kv_lora_rank
    q = jnp.einsum("bsd,de->bse", h, p["wq"]).reshape(
        b, s, cfg.num_heads, nope + r)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv = jnp.einsum("bsd,de->bse", h, p["wkv_a"])
    c = L.rms_norm(kv[..., :c_dim], p["kv_ln"], cfg.norm_eps)
    k_pe = rope(kv[..., None, c_dim:], positions, cfg)
    q_pe = rope(q_pe, positions, cfg)
    q_lat = jnp.einsum("bshn,hnc->bshc", q_nope, p["wuk"])
    return (jnp.concatenate([q_lat, q_pe], -1),
            jnp.concatenate([c[:, :, None], k_pe], -1), None)


def values(p: dict, o: jax.Array) -> jax.Array:
    """Attention over the latents, o [B, S, H, C] -> each head's value
    [B, S, H * v] (W_UV)."""
    b, s = o.shape[:2]
    return jnp.einsum("bshc,hcv->bshv", o, p["wuv"]).reshape(b, s, -1)
