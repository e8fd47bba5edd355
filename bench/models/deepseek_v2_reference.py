"""Plain float32 reference of DeepSeek-V2 (and -Lite), and the benchmark's
weights for it. Imports nothing of the program.

The forward pass follows the published modeling code (`modeling_deepseek.py`
of deepseek-ai/DeepSeek-V2-Lite) in its plain, non-absorbed form:
pre-RMSNorm blocks; multi-head latent attention without a low-rank q
(`q_proj` to heads of qk_nope + qk_rope; `kv_a_proj_with_mqa` to the
latent and one shared k_pe; RMSNorm of the latent; `kv_b_proj` to each
head's k_nope and value; k = [k_nope, k_pe], q = [q_nope, q_pe]); rotary
on the rope columns after their de-interleave ((2i, 2i + 1) to halves),
with YaRN's frequencies and cos/sin factor; causal softmax scaled by
(qk_nope + qk_rope)^-0.5 * mscale(mscale_all_dim)^2; `o_proj`. The first
`first_k_dense_replace` layers have a SwiGLU MLP of `intermediate_size`;
the rest are MoE layers: a softmax router in float32 over all routed
experts, greedy top-k, the k weights renormalised if `norm_topk_prob`
and else scaled by `routed_scaling_factor`, SwiGLU experts of
`moe_intermediate_size`, plus `n_shared_experts` as one MLP of
n * moe_intermediate_size; a final RMSNorm and an untied head.

The expert share. Like the program, this holds the experts [held_first,
held_first + held) of the router's `experts` (the chip's share of an
expert-parallel deployment): the router scores all of them, and only the
held experts' part of the routed sum is computed and carried on; the
shared experts run once. Each expert's weights are drawn from
`fold_in(layer key, global expert id)`, so the shares of different chips
are parts of one model (`routed` and `shared` give the two parts of an
MoE layer).

Matrices are kept input-by-output; `kv_b_proj` is [C, heads * (nope + v)]
with each head's [k_nope | value] columns, as published.

Everything runs in float32 with matmuls at `highest` precision. The
control (`quant="fp8"`) rounds both inputs of every linear layer, the
router and the head to float8_e4m3fn, with a scale per row of
activations and per output column of weights.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                              # float8_e4m3fn
# The router's weights are drawn N(0, (ROUTER_GAIN^2) / hidden), so its
# logits have a std of ROUTER_GAIN on normed inputs. At 2 the softmax over
# 64 experts is peaked as a trained router's is (the top 6 hold about
# 68 % of it, the top 1 about 30 %); at 1 they hold 36 %, and the routed
# experts' part of a layer is too small for the output check to show a
# fault in it with much room.
ROUTER_GAIN = 2.0


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int              # all of them, the dense ones included
    dense_layers: int        # first_k_dense_replace
    hidden: int
    heads: int
    kv_lora: int             # C
    nope: int                # qk_nope_head_dim
    rope: int                # qk_rope_head_dim
    v_head: int
    ffn: int                 # the dense layers' MLP
    moe_ffn: int             # one expert's
    experts: int             # the router's outputs
    held_first: int          # the experts this share holds
    held: int
    shared: int              # n_shared_experts
    top_k: int
    norm_topk: bool
    routed_scale: float
    vocab: int
    eps: float
    theta: float
    # YaRN: (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale, mscale_all_dim), or () for plain RoPE
    yarn: tuple
    dtype: str


def shapes(cfg: dict) -> Shapes:
    """The sizes the reference and the weights need, from a configuration
    file's keys (HF DeepSeek-V2 names, plus the file's `expert_share`)."""
    plain = {"q_lora_rank": None, "attention_bias": False,
             "hidden_act": "silu", "scoring_func": "softmax",
             "topk_method": "greedy", "n_group": 1, "topk_group": 1,
             "moe_layer_freq": 1, "tie_word_embeddings": False}
    for k, want in plain.items():
        if cfg.get(k, want) != want:
            raise ValueError(f"the DeepSeek-V2 reference here needs "
                             f"{k}={want!r}, not {cfg[k]!r}")
    share = cfg["expert_share"]
    if share["held"] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the held share's count")
    y = cfg.get("rope_scaling")
    yarn = () if not y else (
        float(y["factor"]), int(y["original_max_position_embeddings"]),
        float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
        float(y["mscale_all_dim"]))
    if y and y["type"] != "yarn":
        raise ValueError(f"rope_scaling type {y['type']!r}")
    return Shapes(
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_lora=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v_head=cfg["v_head_dim"],
        ffn=cfg["intermediate_size"], moe_ffn=cfg["moe_intermediate_size"],
        experts=share["router_experts"], held_first=share["first"],
        held=share["held"], shared=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        vocab=cfg["vocab_size"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]), yarn=yarn,
        dtype=cfg.get("torch_dtype", "bfloat16"))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_weights(s: Shapes, key) -> dict:
    """Random weights in the served dtype, drawn from `key`. Call under
    jit (see `weights`) so that no float32 copy is ever held."""
    dt = jnp.dtype(s.dtype)
    D, H, C = s.hidden, s.heads, s.kv_lora
    ks = dict(zip(("embed", "head", "dense", "moe", "experts"),
                  jax.random.split(key, 5)))

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def attention(k, n):
        kq, ka, kb, ko = jax.random.split(k, 4)
        return {
            "ln1": jnp.ones((n, D), jnp.float32),
            "ln2": jnp.ones((n, D), jnp.float32),
            "q": normal(kq, (n, D, H * (s.nope + s.rope)), D ** -0.5),
            "kv_a": normal(ka, (n, D, C + s.rope), D ** -0.5),
            "kv_ln": jnp.ones((n, C), jnp.float32),
            "kv_b": normal(kb, (n, C, H * (s.nope + s.v_head)), C ** -0.5),
            "o": normal(ko, (n, H * s.v_head, D), (H * s.v_head) ** -0.5),
        }

    def mlp(k, lead, width):
        kg, ku, kd = jax.random.split(k, 3)
        return {"gate": normal(kg, lead + (D, width), D ** -0.5),
                "up": normal(ku, lead + (D, width), D ** -0.5),
                "down": normal(kd, lead + (width, D), width ** -0.5)}

    def expert(layer_key, e):
        return mlp(jax.random.fold_in(layer_key, e), (), s.moe_ffn)

    n_dense, n_moe = s.dense_layers, s.layers - s.dense_layers
    kd_attn, kd_ffn = jax.random.split(ks["dense"])
    km_attn, km_router, km_shared = jax.random.split(ks["moe"], 3)
    held = s.held_first + jnp.arange(s.held)
    layer_keys = jax.random.split(ks["experts"], n_moe)
    experts = jax.vmap(lambda lk: jax.vmap(
        lambda e: expert(lk, e))(held))(layer_keys)
    return {
        "embed": normal(ks["embed"], (s.vocab, D), 0.02),
        "head": normal(ks["head"], (D, s.vocab), 0.02),
        "final_ln": jnp.ones((D,), jnp.float32),
        "dense": dict(attention(kd_attn, n_dense),
                      ffn=mlp(kd_ffn, (n_dense,), s.ffn)),
        "moe": dict(attention(km_attn, n_moe),
                    router=normal(km_router, (n_moe, D, s.experts),
                                  ROUTER_GAIN * D ** -0.5),
                    shared=mlp(km_shared, (n_moe,), s.moe_ffn * s.shared),
                    experts=experts),
    }


@functools.partial(jax.jit, static_argnums=0)
def weights(s: Shapes, key) -> dict:
    return make_weights(s, key)


# ---------------------------------------------------------------------------
# rotary (YaRN), as DeepseekV2YarnRotaryEmbedding computes it
# ---------------------------------------------------------------------------
def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rotary(s: Shapes):
    """(inverse frequencies [rope / 2], factor on cos and sin)."""
    dim, base = s.rope, s.theta
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / base ** exps
    if not s.yarn:
        return extra.astype(np.float32), 1.0
    factor, orig, fast, slow, mscale, mscale_all = s.yarn

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    lo = max(math.floor(corr(fast)), 0)
    hi = min(math.ceil(corr(slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - lo) / (hi - lo),
                   0, 1)
    mask = 1.0 - ramp
    inv = (1.0 / (factor * base ** exps)) * (1 - mask) + extra * mask
    return inv.astype(np.float32), \
        _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all)


def softmax_scale(s: Shapes) -> float:
    scale = (s.nope + s.rope) ** -0.5
    if s.yarn and s.yarn[5]:
        m = _yarn_mscale(s.yarn[0], s.yarn[5])
        scale *= m * m
    return scale


def _rope(s: Shapes, x, pos):
    """x [S, n, rope] in the published (interleaved) column order."""
    inv, m = rotary(s)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)   # de-interleave
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fp8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, quant):
    """x [S, in] f32 @ w [in, out] (served dtype) in float32."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mlp(m, h, quant):
    return _linear(jax.nn.silu(_linear(h, m["gate"], quant))
                   * _linear(h, m["up"], quant), m["down"], quant)


def _attention(s: Shapes, quant, x, lp):
    n, H = x.shape[0], s.heads
    pos = jnp.arange(n)
    h = _rms(x, lp["ln1"], s.eps)
    q = _linear(h, lp["q"], quant).reshape(n, H, s.nope + s.rope)
    kv_a = _linear(h, lp["kv_a"], quant)
    c = _rms(kv_a[:, :s.kv_lora], lp["kv_ln"], s.eps)
    kv = _linear(c, lp["kv_b"], quant).reshape(n, H, s.nope + s.v_head)
    k_pe = _rope(s, kv_a[:, None, s.kv_lora:], pos)
    q = jnp.concatenate([q[..., :s.nope], _rope(s, q[..., s.nope:], pos)],
                        -1)
    k = jnp.concatenate([kv[..., :s.nope],
                         jnp.broadcast_to(k_pe, (n, H, s.rope))], -1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * softmax_scale(s)
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                   kv[..., s.nope:], precision=HIGHEST).reshape(n, -1)
    return x + _linear(o, lp["o"], quant)


def gates(s: Shapes, lp, h, quant=None):
    """The router's weights of the held experts [S, held]: each row's
    top-k weight where it picked a held expert, else 0."""
    scores = jax.nn.softmax(_linear(h, lp["router"], quant), -1)
    w, idx = jax.lax.top_k(scores, s.top_k)
    if s.norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    else:
        w = w * s.routed_scale
    local = idx - s.held_first
    hit = local[..., None] == jnp.arange(s.held)
    return jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)


def routed(s: Shapes, lp, h, quant=None):
    """The held experts' part of an MoE layer's output, h [S, D] normed."""
    g = gates(s, lp, h, quant)

    def one(carry, e):
        m, ge = e
        return carry + ge[:, None] * _mlp(m, h, quant), None
    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (lp["experts"], g.T))
    return out


def shared(s: Shapes, lp, h, quant=None):
    """The shared experts' part, which every share computes alike."""
    return _mlp(lp["shared"], h, quant)


def _dense_layer(s: Shapes, quant, x, lp):
    x = _attention(s, quant, x, lp)
    return x + _mlp(lp["ffn"], _rms(x, lp["ln2"], s.eps), quant), None


def _moe_layer(s: Shapes, quant, x, lp):
    x = _attention(s, quant, x, lp)
    h = _rms(x, lp["ln2"], s.eps)
    return x + routed(s, lp, h, quant) + shared(s, lp, h, quant), None


def logits(s: Shapes, w: dict, tokens, quant=None):
    """tokens [S] -> float32 logits [S, vocab] of the causal forward."""
    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_dense_layer, s, quant), x,
                        w["dense"])
    x, _ = jax.lax.scan(functools.partial(_moe_layer, s, quant), x,
                        w["moe"])
    return _linear(_rms(x, w["final_ln"], s.eps), w["head"], quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def gaps(s: Shapes, w: dict, tokens, targets, quant=None):
    """Per row, how far a chosen token's reference logit lies below the
    reference's best, at each position that has a target, in standard
    deviations of that position's reference logits.

    tokens [R, S]: prompt then served tokens (the last served one left
    out), zero-padded; targets [R, S]: the served token that position
    produced, or -1. Without `quant` the chosen token is the target (the
    program's). With `quant` it is the token that the quantized forward
    puts first, and the gap is read in the float32 logits. Returns
    [R, S] gaps, 0 where there is no target. Rows run one at a time."""
    def row(args):
        tok, tgt = args
        ref = logits(s, w, tok)
        pick = tgt if quant is None else jnp.argmax(
            logits(s, w, tok, quant), -1)
        chosen = jnp.take_along_axis(ref, jnp.maximum(pick, 0)[:, None],
                                     -1)[:, 0]
        gap = (jnp.max(ref, -1) - chosen) / jnp.std(ref, -1)
        return jnp.where(tgt >= 0, gap, 0.0)
    return jax.lax.map(row, (tokens, targets))
