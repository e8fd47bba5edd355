"""Model composition: decoder-only LMs (dense / MoE / SWA), encoder-decoder
(seamless-m4t), hybrid SSM+shared-attention (zamba2), and pure SSM
(falcon-mamba). One init + forward + prefill + decode_step per family, all
driven by ModelConfig; layers run under lax.scan with stacked params and an
optional remat policy.

Decode state layout (pytree of stacked-per-layer arrays so decode also scans):
  attention layers: {"k": [L,B,C,KV,Dh], "v": [L,B,C,KV,Dh],
                     "k_pos": [L,B,C] (ring buffers for SWA), "pos": []}
  ssm layers:       {"h": [L,B,...], "conv": [L,B,K-1,C]}
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, MAMBA1, MAMBA2, SHARED_ATTN,
                                ModelConfig)
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

# ---------------------------------------------------------------------------
# Layer-scan control. Production runs keep lax.scan rolled (small HLO,
# fast compiles). The dry-run fully unrolls so compiled.cost_analysis()
# counts every layer (XLA's cost model counts a while-loop body ONCE —
# rolled-scan FLOPs/collectives would be ~L x undercounted).
# ---------------------------------------------------------------------------
_SCAN_UNROLL = False


def set_scan_unroll(on: bool) -> None:
    global _SCAN_UNROLL
    _SCAN_UNROLL = on


def _scan(body, init, xs):
    return jax.lax.scan(body, init, xs, unroll=True if _SCAN_UNROLL else 1)


# ---------------------------------------------------------------------------
# Remat policies
# ---------------------------------------------------------------------------
REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "everything": jax.checkpoint_policies.everything_saveable,
}


def _maybe_remat(fn, remat: str):
    policy = REMAT_POLICIES[remat]
    if remat == "none":
        return fn
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)


# ---------------------------------------------------------------------------
# Attention block (pre-norm attn + FFN/MoE), shared by all families
# ---------------------------------------------------------------------------
def init_attn_layer(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    ks = jax.random.split(key, 10)
    s = d ** -0.5
    p = {
        "ln1": L.init_rms_norm(d),
        "ln2": L.init_rms_norm(d),
        "wq": (jax.random.normal(ks[0], (d, nq)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, nkv)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, nkv)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (nq, d)) * nq ** -0.5).astype(dtype),
    }
    if cfg.num_experts:
        p["moe"] = moe_lib.init_moe(ks[4], cfg, dtype)
    else:
        p["ffn"] = L.init_mlp(ks[4], d, cfg.d_ff, cfg.mlp_gated, dtype)
    if cross:
        p["ln_x"] = L.init_rms_norm(d)
        p["xq"] = (jax.random.normal(ks[5], (d, nq)) * s).astype(dtype)
        p["xk"] = (jax.random.normal(ks[6], (d, nkv)) * s).astype(dtype)
        p["xv"] = (jax.random.normal(ks[7], (d, nkv)) * s).astype(dtype)
        p["xo"] = (jax.random.normal(ks[8], (nq, d)) * nq ** -0.5).astype(dtype)
    return p


def _qkv(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    q = L.positional(cfg, q, positions)
    k = L.positional(cfg, k, positions)
    return q, k, v


def attn_ffn_block(p: dict, x: jax.Array, cfg: ModelConfig, positions,
                   *, causal: bool = True, attn_impl: str = "blockwise",
                   enc_kv=None, enc_mask=None):
    """Full-sequence block. Returns (x, aux_loss, kv, expert_counts)."""
    b, s, _ = x.shape
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(p, h, cfg, positions)
    kwargs = dict(causal=causal, window=cfg.sliding_window,
                  q_pos=_pos2d(positions, b, s), k_pos=_pos2d(positions, b, s))
    if attn_impl == "full":
        o = attn_lib.full_attention(q, k, v, **kwargs)
    elif attn_impl == "blockwise":
        o = attn_lib.blockwise_attention(q, k, v, chunk=min(512, s), **kwargs)
    elif attn_impl == "flash":
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=causal,
                                 window=cfg.sliding_window)
    else:
        raise ValueError(attn_impl)
    o = o.reshape(b, s, -1)
    x = x + jnp.einsum("bse,ed->bsd", o, p["wo"])

    if enc_kv is not None:  # cross attention
        hx = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
        hd = cfg.resolved_head_dim
        qx = jnp.einsum("bsd,de->bse", hx, p["xq"]).reshape(
            b, s, cfg.num_heads, hd)
        ox = attn_lib.cross_attention(qx, enc_kv[0], enc_kv[1], enc_mask)
        x = x + jnp.einsum("bse,ed->bsd", ox.reshape(b, s, -1), p["xo"])

    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)
    if cfg.num_experts:
        f, aux, counts = moe_lib.moe_block(p["moe"], h2, cfg)
    else:
        f = L.mlp(p["ffn"], h2, cfg.mlp_gated)
    return x + f, aux, (k, v), counts


def _pos2d(positions, b, s):
    """Reduce mrope [3,B,S] to primary stream for masking."""
    if positions is None:
        return None
    return positions[0] if positions.ndim == 3 else positions


# ---------------------------------------------------------------------------
# Decode-mode attention block
# ---------------------------------------------------------------------------
def decode_layer_step(p: dict, x: jax.Array, cfg: ModelConfig, positions,
                      attend_fn, enc_kv=None):
    """One decoder layer of single-token decode — THE single place the
    layer math lives, with the KV mechanics supplied by the caller:
    `attn_block_decode` plugs in the dense ring cache, the paged server
    (runtime/server.py) plugs in HadesPool append+attend. `_qkv` runs
    exactly once per layer (the old server derived it twice, and its
    two-phase k/v loop computed deep layers' k/v from the embedding —
    the decode corruption this hoist removes).

    x: [B,1,D]; positions: [B,1] (per-sequence positions, pre-broadcast);
    attend_fn(q, k, v) -> (attn out reshapeable to [B,1,H*Dh], aux) with
    q [B,1,H,Dh], k/v [B,1,KV,Dh]; `aux` is whatever cache/pool state the
    caller must thread onward. Returns (x', aux, expert_counts).

    Named scopes `qkv`, `attention` (attend_fn and the output projection)
    and `ffn` label the layer's ops in the compiled HLO's op_name."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    with jax.named_scope("qkv"):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(p, h, cfg, positions)
    with jax.named_scope("attention"):
        o, aux = attend_fn(q, k, v)
        x = x + jnp.einsum("bse,ed->bsd", o.reshape(b, 1, -1), p["wo"])

    if enc_kv is not None:
        hx = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
        qx = jnp.einsum("bsd,de->bse", hx, p["xq"]).reshape(
            b, 1, cfg.num_heads, hd)
        ox = attn_lib.cross_attention(qx, enc_kv[0], enc_kv[1])
        x = x + jnp.einsum("bse,ed->bsd", ox.reshape(b, 1, -1), p["xo"])

    with jax.named_scope("ffn"):
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        counts = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)
        if cfg.num_experts:
            t = h2.shape[0] * h2.shape[1]
            if cfg.hades.expert_gather_decode and \
                    t * cfg.experts_per_token < cfg.num_experts:
                # HADES hot-expert principle on the weight stream: fetch
                # only the routed experts (exact; wins when T*k < E)
                f, _, counts = moe_lib.moe_block_gathered(p["moe"], h2, cfg)
            else:
                f, _, counts = moe_lib.moe_block(p["moe"], h2, cfg)
        else:
            f = L.mlp(p["ffn"], h2, cfg.mlp_gated)
        return x + f, aux, counts


def attn_block_decode(p: dict, x: jax.Array, cfg: ModelConfig, cache: dict,
                      pos, enc_kv=None):
    """x: [B,1,D]; cache: {"k","v": [B,C,KV,Dh], "k_pos": [B,C]}. Appends the
    new token at slot pos % C (ring for SWA, linear otherwise) and attends.
    Returns (x, new_cache, counts)."""
    b = x.shape[0]
    positions = jnp.broadcast_to(jnp.reshape(pos, (1, 1)), (b, 1))
    c = cache["k"].shape[1]

    def attend(q, k, v):
        slot = pos % c
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        k_pos = jax.lax.dynamic_update_slice_in_dim(
            cache["k_pos"],
            jnp.broadcast_to(jnp.reshape(pos, (1, 1)), (b, 1)),
            slot, axis=1)
        cache_len = jnp.minimum(pos + 1, c)
        o = attn_lib.decode_attention(q, k_cache, v_cache, cache_len,
                                      window=cfg.sliding_window,
                                      k_pos=k_pos, q_pos=pos)
        return o, {"k": k_cache, "v": v_cache, "k_pos": k_pos}

    return decode_layer_step(p, x, cfg, positions, attend, enc_kv=enc_kv)


# ---------------------------------------------------------------------------
# Family: decoder-only LM (dense, MoE, VLM backbone)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=0)
def init_lm(cfg: ModelConfig, key) -> dict:
    """Random parameters from `key`. Jitted: each weight's f32 draw, its
    scale and its cast to cfg.dtype fuse into one program, so no f32 copy
    of a whole stacked weight is ever materialised (an eager chatglm3-6b
    init would hold a 6.3 GB f32 draw and its scaled copy beside the
    bf16 weights)."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    n_attn = sum(1 for k in cfg.blocks if k == ATTN)
    params = {
        "embed": L.init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_ln": L.init_rms_norm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["out"] = L.init_embedding(
            keys[1], cfg.vocab_size, cfg.d_model, dtype).T
    if cfg.family in ("ssm",):
        def mk(k):
            return {"ln": L.init_rms_norm(cfg.d_model),
                    "m": ssm_lib.init_mamba1(k, cfg, dtype)}
        params["layers"] = jax.vmap(mk)(jax.random.split(keys[2], cfg.num_layers))
    elif cfg.family == "hybrid":
        per, groups = _hybrid_shape(cfg)

        def mk(k):
            return {"ln": L.init_rms_norm(cfg.d_model),
                    "m": ssm_lib.init_mamba2(k, cfg, dtype)}
        ks2 = jax.random.split(keys[2], groups * per)
        ks2 = ks2.reshape((groups, per) + ks2.shape[1:])
        params["mamba"] = jax.vmap(jax.vmap(mk))(ks2)
        params["shared_attn"] = init_attn_layer(keys[3], cfg, dtype)
    else:
        params["layers"] = jax.vmap(
            lambda k: init_attn_layer(k, cfg, dtype))(
                jax.random.split(keys[2], cfg.num_layers))
    if cfg.is_encoder_decoder:
        params["enc_layers"] = jax.vmap(
            lambda k: init_attn_layer(k, cfg, dtype))(
                jax.random.split(keys[4], cfg.num_encoder_layers))
        params["enc_ln"] = L.init_rms_norm(cfg.d_model)
        # decoder layers get cross-attention
        params["layers"] = jax.vmap(
            lambda k: init_attn_layer(k, cfg, dtype, cross=True))(
                jax.random.split(keys[2], cfg.num_layers))
    return params


def _hybrid_shape(cfg: ModelConfig) -> Tuple[int, int]:
    """(mamba blocks per group, groups) for the hybrid pattern."""
    every = cfg.shared_attn_every
    assert cfg.num_layers % every == 0
    return every - 1, cfg.num_layers // every


def lm_forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
               positions: Optional[jax.Array] = None,
               extra_embeds: Optional[jax.Array] = None,
               enc_embeds: Optional[jax.Array] = None,
               attn_impl: str = "blockwise", remat: str = "none",
               return_cache: bool = False, return_hiddens: bool = False):
    """tokens: [B, S_txt]. extra_embeds (VLM patches): [B, P, D] prepended.
    enc_embeds (enc-dec audio frames): [B, S_enc, D].
    Returns logits [B, S, V] (+ aux dict). `return_hiddens` (attn-family
    layers only) adds aux["hiddens"] [L, B, S, D] — the post-layer
    residual stream, for per-layer decode/prefill divergence reports."""
    x = L.embed(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    enc_out = None
    if cfg.is_encoder_decoder:
        assert enc_embeds is not None
        enc_out = encoder_forward(params, cfg, enc_embeds,
                                  attn_impl=attn_impl, remat=remat)

    aux_total = jnp.zeros((), jnp.float32)
    counts_total = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)
    cache = None
    counts_per_layer = None
    hs = None

    if cfg.family == "ssm":
        def body(h, lp):
            y, _ = ssm_lib.mamba1_forward(
                lp["m"], L.rms_norm(h, lp["ln"], cfg.norm_eps), cfg)
            return h + y, None
        body = _maybe_remat(body, remat)
        x, _ = _scan(body, x, params["layers"])
    elif cfg.family == "hybrid":
        x, aux_total, counts_total = _hybrid_forward(
            params, cfg, x, positions, attn_impl, remat)
    else:
        kv_all = [] if return_cache else None

        def body(carry, lp):
            h = carry
            h, aux, kv, cnt = attn_ffn_block(
                lp, h, cfg, positions, attn_impl=attn_impl,
                enc_kv=_enc_kv(lp, enc_out, cfg) if enc_out is not None else None)
            return h, (aux, cnt, kv if return_cache else None,
                       h if return_hiddens else None)
        body = _maybe_remat(body, remat)
        x, (auxs, cnts, kvs, hs) = _scan(body, x, params["layers"])
        aux_total = jnp.sum(auxs)
        counts_total = jnp.sum(cnts, axis=0)
        counts_per_layer = cnts
        if return_cache:
            cache = kvs

    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    out_t = params["embed"].T if cfg.tie_embeddings else params["out"]
    logits = L.logits_head(out_t, x)
    aux = {"moe_aux_loss": aux_total, "expert_counts": counts_total}
    if counts_per_layer is not None:
        aux["expert_counts_per_layer"] = counts_per_layer
    if return_cache:
        aux["kv_cache"] = cache
        aux["enc_out"] = enc_out
    if return_hiddens:
        assert hs is not None, "return_hiddens: attn-family layers only"
        aux["hiddens"] = hs
    return logits, aux


def _enc_kv(lp, enc_out, cfg: ModelConfig):
    """Project encoder memory to this decoder layer's cross K/V."""
    b, se, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = jnp.einsum("bsd,de->bse", enc_out, lp["xk"]).reshape(
        b, se, cfg.num_kv_heads, hd)
    v = jnp.einsum("bsd,de->bse", enc_out, lp["xv"]).reshape(
        b, se, cfg.num_kv_heads, hd)
    return (k, v)


def encoder_forward(params, cfg: ModelConfig, enc_embeds, *,
                    attn_impl="blockwise", remat="none"):
    b, s, _ = enc_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = enc_embeds.astype(jnp.dtype(cfg.dtype))

    def body(h, lp):
        h, _, _, _ = attn_ffn_block(lp, h, cfg, positions, causal=False,
                                    attn_impl=attn_impl)
        return h, None
    body = _maybe_remat(body, remat)
    x, _ = _scan(body, x, params["enc_layers"])
    return L.rms_norm(x, params["enc_ln"], cfg.norm_eps)


def _hybrid_forward(params, cfg: ModelConfig, x, positions, attn_impl, remat):
    """zamba2: groups of (every-1) mamba2 blocks + one SHARED attn block."""
    shared = params["shared_attn"]
    b = x.shape[0]

    def group_body(carry, group_params):
        h = carry

        def mamba_body(hh, lp):
            y, _ = ssm_lib.mamba2_forward(
                lp["m"], L.rms_norm(hh, lp["ln"], cfg.norm_eps), cfg)
            return hh + y, None
        h, _ = _scan(mamba_body, h, group_params)
        h, aux, _, cnt = attn_ffn_block(shared, h, cfg, positions,
                                        attn_impl=attn_impl)
        return h, (aux, cnt)
    group_body = _maybe_remat(group_body, remat)
    x, (auxs, cnts) = _scan(group_body, x, params["mamba"])
    return x, jnp.sum(auxs), jnp.sum(cnts, axis=0)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(params, cfg: ModelConfig, tokens, labels, *,
            extra_embeds=None, enc_embeds=None,
            attn_impl="blockwise", remat="none"):
    """Next-token cross entropy; labels == -100 are masked."""
    logits, aux = lm_forward(params, cfg, tokens, extra_embeds=extra_embeds,
                             enc_embeds=enc_embeds, attn_impl=attn_impl,
                             remat=remat)
    if extra_embeds is not None:
        logits = logits[:, extra_embeds.shape[1]:]
    mask = labels != -100
    labels_safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels_safe[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return loss + 0.01 * aux["moe_aux_loss"], aux


# ---------------------------------------------------------------------------
# Decode: state init + step
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      enc_out: Optional[jax.Array] = None) -> dict:
    """Dense (non-paged) decode state. max_len is clipped to the SWA window
    for windowed archs (ring buffer)."""
    dtype = jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    c = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    state: Dict = {"pos": jnp.zeros((), jnp.int32)}

    def kv(n_layers):
        return {
            "k": jnp.zeros((n_layers, batch, c, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((n_layers, batch, c, cfg.num_kv_heads, hd), dtype),
            "k_pos": jnp.full((n_layers, batch, c), -1, jnp.int32),
        }
    if cfg.family == "ssm":
        state["ssm"] = jax.vmap(
            lambda _: ssm_lib.mamba1_init_state(cfg, batch, dtype))(
                jnp.arange(cfg.num_layers))
    elif cfg.family == "hybrid":
        per, groups = _hybrid_shape(cfg)
        state["ssm"] = jax.vmap(jax.vmap(
            lambda _: ssm_lib.mamba2_init_state(cfg, batch, dtype)))(
                jnp.arange(groups * per).reshape(groups, per))
        state["kv"] = kv(groups)  # one cache per shared-attn occurrence
    else:
        state["kv"] = kv(cfg.num_layers)
    if cfg.is_encoder_decoder:
        assert enc_out is not None
        state["enc_out"] = enc_out
    return state


def lm_decode_step(params: dict, cfg: ModelConfig, state: dict,
                   tokens: jax.Array, *, return_hiddens: bool = False):
    """tokens: [B] -> (logits [B, V], new state). One token per sequence.
    `return_hiddens` (attn family only) appends a third output: the
    post-layer residual stream [L, B, 1, D] for divergence reports."""
    b = tokens.shape[0]
    x = L.embed(params["embed"], tokens)[:, None, :]  # [B,1,D]
    pos = state["pos"]
    counts_total = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)

    if cfg.family == "ssm":
        def body(h, xs):
            lp, st = xs
            y, st2 = ssm_lib.mamba1_step(
                lp["m"], L.rms_norm(h, lp["ln"], cfg.norm_eps), cfg, st)
            return h + y, st2
        x, new_ssm = _scan(body, x, (params["layers"], state["ssm"]))
        state = dict(state, ssm=new_ssm, pos=pos + 1)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group_body(h, xs):
            gp, sst, kvc = xs

            def mamba_body(hh, ys):
                lp, st = ys
                y, st2 = ssm_lib.mamba2_step(
                    lp["m"], L.rms_norm(hh, lp["ln"], cfg.norm_eps), cfg, st)
                return hh + y, st2
            h, new_sst = _scan(mamba_body, h, (gp, sst))
            h, new_kv, cnt = attn_block_decode(shared, h, cfg, kvc, pos)
            return h, (new_sst, new_kv, cnt)
        x, (new_ssm, new_kv, cnts) = _scan(
            group_body, x, (params["mamba"], state["ssm"], state["kv"]))
        counts_total = jnp.sum(cnts, axis=0)
        state = dict(state, ssm=new_ssm, kv=new_kv, pos=pos + 1)
    else:
        enc_out = state.get("enc_out")

        def body(h, xs):
            lp, kvc = xs
            h, new_kv, cnt = attn_block_decode(
                lp, h, cfg, kvc, pos,
                enc_kv=_enc_kv(lp, enc_out, cfg) if enc_out is not None else None)
            return h, (new_kv, cnt, h if return_hiddens else None)
        x, (new_kv, cnts, hs) = _scan(body, x, (params["layers"],
                                                state["kv"]))
        counts_total = jnp.sum(cnts, axis=0)
        state = dict(state, kv=new_kv, pos=pos + 1)

    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    out_t = params["embed"].T if cfg.tie_embeddings else params["out"]
    logits = L.logits_head(out_t, x)[:, 0]
    if return_hiddens:
        assert cfg.family not in ("ssm", "hybrid"), \
            "return_hiddens: attn-family layers only"
        return logits, state, hs
    return logits, state
