"""Plain float32 reference of the GLM family (ChatGLM3, GLM-4), and the
benchmark's weights for it. Imports nothing of the program.

The forward pass follows the published modeling code (`modeling_chatglm.py`
of THUDM/chatglm3-6b and THUDM/glm-4-9b): pre-RMSNorm blocks, grouped
query attention (query head h reads kv group h // (heads / groups)),
rotary on the first half of each head with interleaved pairs (2i, 2i+1)
and base 10000 * rope_ratio, softmax over causal scores scaled by
1 / sqrt(head_dim), SwiGLU (silu of the first half of dense_h_to_4h times
the second half), a final RMSNorm and an untied head. Departures, as the
configuration file states them: no qkv bias.

Matrices are kept input-by-output, and the fused published tensors are
held as their parts (query_key_value as q, k, v; dense_h_to_4h as gate,
up): the same numbers under another layout.

Everything runs in float32 with matmuls at `highest` precision. The
control (`quant="fp8"`) rounds both inputs of every linear layer and of
the head to float8_e4m3fn, with a scale per row of activations and per
output column of weights: the step below bfloat16 that a later change
could be tempted by.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                              # float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    eps: float
    theta: float
    dtype: str


def shapes(cfg: dict) -> Shapes:
    """The sizes the reference and the weights need, from a configuration
    file's keys (HF ChatGLM names)."""
    if cfg.get("add_qkv_bias") or cfg.get("add_bias_linear"):
        raise ValueError("the GLM reference here has no linear biases")
    return Shapes(
        layers=cfg["num_layers"], hidden=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["multi_query_group_num"], head_dim=cfg["kv_channels"],
        ffn=cfg["ffn_hidden_size"], vocab=cfg["padded_vocab_size"],
        eps=cfg["layernorm_epsilon"],
        theta=10000.0 * cfg.get("rope_ratio", 1), dtype=cfg["torch_dtype"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_weights(s: Shapes, key) -> dict:
    """Random weights in the served dtype, drawn from `key`. Call under
    jit (see `weights`) so that no float32 copy is ever held."""
    dt = jnp.dtype(s.dtype)
    nq, nkv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    names = ("embed", "head", "q", "k", "v", "dense", "gate", "up", "down")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def normal(name, shape, std):
        return (jax.random.normal(ks[name], shape, jnp.float32) * std
                ).astype(dt)

    L, D, F = s.layers, s.hidden, s.ffn
    return {
        "embed": normal("embed", (s.vocab, D), 0.02),
        "head": normal("head", (D, s.vocab), 0.02),
        "final_ln": jnp.ones((D,), jnp.float32),
        "layers": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "ln2": jnp.ones((L, D), jnp.float32),
            "q": normal("q", (L, D, nq), D ** -0.5),
            "k": normal("k", (L, D, nkv), D ** -0.5),
            "v": normal("v", (L, D, nkv), D ** -0.5),
            "dense": normal("dense", (L, nq, D), nq ** -0.5),
            "gate": normal("gate", (L, D, F), D ** -0.5),
            "up": normal("up", (L, D, F), D ** -0.5),
            "down": normal("down", (L, F, D), F ** -0.5),
        },
    }


@functools.partial(jax.jit, static_argnums=0)
def weights(s: Shapes, key) -> dict:
    return make_weights(s, key)


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


def _rope(x, pos, theta):
    """ChatGLM rotary: the first half of each head in interleaved pairs.
    x [S, n, hd]; pos [S]."""
    rot = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x[..., :rot].reshape(x.shape[:-1] + (rot // 2, 2))
    x0, x1 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)
    return jnp.concatenate([out.reshape(x.shape[:-1] + (rot,)),
                            x[..., rot:]], -1)


def _layer(s: Shapes, quant, x, lp):
    n = x.shape[0]
    pos = jnp.arange(n)
    h = _rms(x, lp["ln1"], s.eps)
    q = _linear(h, lp["q"], quant).reshape(n, s.heads, s.head_dim)
    k = _linear(h, lp["k"], quant).reshape(n, s.kv_heads, s.head_dim)
    v = _linear(h, lp["v"], quant).reshape(n, s.kv_heads, s.head_dim)
    q, k = _rope(q, pos, s.theta), _rope(k, pos, s.theta)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * s.head_dim ** -0.5
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST).reshape(n, -1)
    x = x + _linear(o, lp["dense"], quant)
    h = _rms(x, lp["ln2"], s.eps)
    f = jax.nn.silu(_linear(h, lp["gate"], quant)) * _linear(h, lp["up"],
                                                             quant)
    return x + _linear(f, lp["down"], quant), None


def logits(s: Shapes, w: dict, tokens, quant=None):
    """tokens [S] -> float32 logits [S, vocab] of the causal forward."""
    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, s, quant), x, w["layers"])
    return _linear(_rms(x, w["final_ln"], s.eps), w["head"], quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def gaps(s: Shapes, w: dict, tokens, targets, quant=None):
    """Per row, how far a chosen token's reference logit lies below the
    reference's best, at each position that has a target, in standard
    deviations of that position's reference logits (so that the gap reads
    alike at any width and vocabulary).

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
