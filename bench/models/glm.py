"""The GLM family on the program's side: the program's model
configuration for a configuration file, and the benchmark's weights
(glm_reference.make_weights) laid out as the program's parameters.

The program rotates the first half of each head in pairs (i, i + half/2)
where GLM pairs (2i, 2i + 1). Permuting the q and k columns of each head
by `ROPE_PERM` turns one into the other, and q.k is unchanged by a
permutation applied to both, so the program and the reference compute
the same function of the same weights.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from bench.models import glm_reference as ref
from repro.configs.base import ModelConfig


def model_config(name: str, s: ref.Shapes) -> ModelConfig:
    return ModelConfig(
        name=name, family="dense", num_layers=s.layers, d_model=s.hidden,
        num_heads=s.heads, num_kv_heads=s.kv_heads, d_ff=s.ffn,
        vocab_size=s.vocab, head_dim=s.head_dim, rope_style="rope2d",
        rope_theta=s.theta, norm_eps=s.eps, dtype=s.dtype)


def rope_perm(head_dim: int) -> np.ndarray:
    """Program column j of a head holds reference column perm[j]."""
    rot = head_dim // 2
    half = rot // 2
    j = np.arange(head_dim)
    return np.where(j < half, 2 * j,
                    np.where(j < rot, 2 * (j - half) + 1, j))


def _heads_permuted(w, n_heads, head_dim):
    perm = rope_perm(head_dim)
    cols = (np.arange(n_heads)[:, None] * head_dim + perm[None]).reshape(-1)
    return w[..., cols]


@functools.partial(jax.jit, static_argnums=0)
def program_params(s: ref.Shapes, key) -> dict:
    """The program's parameter tree for the weights `key` draws, made on
    the device in one program."""
    w = ref.make_weights(s, key)
    lw = w["layers"]
    return {
        "embed": w["embed"],
        "out": w["head"],
        # the program's norms scale by (1 + scale)
        "final_ln": w["final_ln"] - 1.0,
        "layers": {
            "ln1": lw["ln1"] - 1.0,
            "ln2": lw["ln2"] - 1.0,
            "wq": _heads_permuted(lw["q"], s.heads, s.head_dim),
            "wk": _heads_permuted(lw["k"], s.kv_heads, s.head_dim),
            "wv": lw["v"],
            "wo": lw["dense"],
            "ffn": {"wg": lw["gate"], "wi": lw["up"], "wo": lw["down"]},
        },
    }
