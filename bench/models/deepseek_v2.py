"""The DeepSeek-V2 family on the program's side: the program's model
configuration for a configuration file, and the benchmark's weights
(deepseek_v2_reference.make_weights) laid out as the program's
parameters.

Two layouts differ. The program serves latent attention in its absorbed
form (models/mla.py), so `kv_b_proj` is split per head into W_UK
[H, nope, C] and W_UV [H, C, v]. And the program rotates the rope columns
in halves (i, i + rope/2), where the published model de-interleaves its
pairs (2i, 2i + 1) first: permuting the rope columns of q and of k_pe by
`ROPE_PERM` makes the program's roped vectors the reference's, so both
compute the same function of the same weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import deepseek_v2_reference as ref
from repro.configs.base import ModelConfig, YarnScaling


def model_config(name: str, s: ref.Shapes) -> ModelConfig:
    return ModelConfig(
        name=name, family="moe", num_layers=s.layers, d_model=s.hidden,
        num_heads=s.heads, num_kv_heads=s.heads, d_ff=s.ffn,
        vocab_size=s.vocab, head_dim=s.nope + s.rope, rope_theta=s.theta,
        norm_eps=s.eps, dtype=s.dtype, num_experts=s.experts,
        experts_per_token=s.top_k, moe_d_ff=s.moe_ffn,
        n_shared_experts=s.shared, norm_topk_prob=s.norm_topk,
        routed_scaling_factor=s.routed_scale,
        held_experts=(s.held_first, s.held), first_k_dense=s.dense_layers,
        kv_lora_rank=s.kv_lora, qk_nope_head_dim=s.nope,
        qk_rope_head_dim=s.rope, v_head_dim=s.v_head,
        rope_scaling=YarnScaling(*s.yarn) if s.yarn else None)


def rope_perm(rope: int) -> np.ndarray:
    """Program rope column j holds published rope column perm[j]."""
    return np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])


def _attention(s: ref.Shapes, lw: dict) -> dict:
    n, H, C = lw["q"].shape[0], s.heads, s.kv_lora
    per = s.nope + s.rope
    head = np.concatenate([np.arange(s.nope), s.nope + rope_perm(s.rope)])
    q_cols = (np.arange(H)[:, None] * per + head[None]).reshape(-1)
    kv_cols = np.concatenate([np.arange(C), C + rope_perm(s.rope)])
    kv_b = lw["kv_b"].reshape(n, C, H, s.nope + s.v_head)
    return {
        # the program's norms scale by (1 + scale)
        "ln1": lw["ln1"] - 1.0,
        "ln2": lw["ln2"] - 1.0,
        "wq": lw["q"][..., q_cols],
        "wkv_a": lw["kv_a"][..., kv_cols],
        "kv_ln": lw["kv_ln"] - 1.0,
        "wuk": jnp.transpose(kv_b[..., :s.nope], (0, 2, 3, 1)),
        "wuv": jnp.transpose(kv_b[..., s.nope:], (0, 2, 1, 3)),
        "wo": lw["o"],
    }


def _mlp(m: dict) -> dict:
    return {"wg": m["gate"], "wi": m["up"], "wo": m["down"]}


@functools.partial(jax.jit, static_argnums=0)
def program_params(s: ref.Shapes, key) -> dict:
    """The program's parameter tree for the weights `key` draws, made on
    the device in one program."""
    w = ref.make_weights(s, key)
    dense, moe = w["dense"], w["moe"]
    return {
        "embed": w["embed"],
        "out": w["head"],
        "final_ln": w["final_ln"] - 1.0,
        "dense_layers": dict(_attention(s, dense), ffn=_mlp(dense["ffn"])),
        "layers": dict(_attention(s, moe), moe=dict(
            _mlp(moe["experts"]), router=moe["router"],
            shared=_mlp(moe["shared"]))),
    }
