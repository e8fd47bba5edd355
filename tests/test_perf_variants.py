"""Correctness of the §Perf beyond-paper variants: the optimizations
must not change the math (or must bound their error)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import attention as attn
from repro.models import moe as moe_lib


def _moe_cfg():
    return get_config("mixtral-8x7b", reduced=True)


def test_expert_gather_matches_dense_dispatch(rng):
    """moe_block_gathered (HADES hot-expert weight stream) is exact vs
    the dense reference for small T."""
    cfg = _moe_cfg()
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 1, cfg.d_model))
                    .astype(np.float32))
    got, aux, counts = moe_lib.moe_block_gathered(p, x, cfg)
    want = moe_lib.moe_block_ref(p, x, cfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert int(counts.sum()) == cfg.experts_per_token


def test_expert_gather_used_only_when_profitable():
    """decode uses the gathered path iff T*k < E (else dispatch wins)."""
    cfg = _moe_cfg()
    assert 1 * cfg.experts_per_token < cfg.num_experts       # B=1: gather
    assert not (64 * cfg.experts_per_token < cfg.num_experts)  # B=64: no


def test_moe_sharding_hints_do_not_change_math(rng):
    """with_sharding_constraint is semantics-preserving; on a 1-device
    mesh the hinted block must be bit-identical."""
    cfg = _moe_cfg()
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model))
                    .astype(np.float32))
    base, _, _ = jax.jit(lambda: moe_lib.moe_block(p, x, cfg))()
    mesh = make_host_mesh()          # one CPU device: a (1, 1) mesh
    from jax.sharding import PartitionSpec as P
    moe_lib.set_sharding_hints({"dispatch": P(None, "data", None),
                                "hidden": P(None, "data", "model")})
    try:
        with mesh:
            hinted, _, _ = jax.jit(lambda: moe_lib.moe_block(p, x, cfg))()
    finally:
        moe_lib.set_sharding_hints(None)
    assert np.array_equal(np.asarray(base), np.asarray(hinted))


def test_int8_kv_quantization_error_bounded(rng):
    """int8 per-block-scale KV: decode attention output error stays
    small (the kv8 §Perf variant's numerical feasibility)."""
    b, s, kv, d = 2, 64, 2, 32
    q = jnp.asarray(rng.normal(size=(b, 1, 4, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)).astype(np.float32))

    def quant(x, block=16):
        xb = np.asarray(x).reshape(b, s // block, block, kv, d)
        scale = np.abs(xb).max(axis=(2, 4), keepdims=True) / 127.0
        qx = np.clip(np.round(xb / np.maximum(scale, 1e-9)), -127, 127)
        return jnp.asarray((qx * scale).reshape(b, s, kv, d)
                           .astype(np.float32))

    want = attn.decode_attention(q, k, v, jnp.full((b,), s))
    got = attn.decode_attention(q, quant(k), quant(v), jnp.full((b,), s))
    err = np.abs(np.asarray(got) - np.asarray(want)).max()
    scale_ref = np.abs(np.asarray(want)).max()
    assert err < 0.05 * scale_ref, f"int8 KV error {err} vs {scale_ref}"


def test_hades_flags_default_off():
    """The paper-faithful baseline keeps the beyond-paper variants off."""
    for arch in ("mixtral-8x7b", "granite-34b"):
        cfg = get_config(arch)
        assert not cfg.hades.expert_gather_decode
        assert cfg.hades.kv_quant_bits == 16
