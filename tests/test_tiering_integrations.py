"""KV-cache / embedding / expert tiering integration tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn
from repro.models import embedding as emb
from repro.models import expert_tiering as et
from repro.models import kvcache as kvc

CFG = kvc.KVCacheConfig(num_layers=2, batch=3, max_blocks=8,
                        block_tokens=4, num_kv_heads=2, head_dim=16,
                        dtype="float32")


def _fill(state, steps, rng):
    ks, vs = [], []
    for _ in range(steps):
        k = jnp.asarray(rng.normal(size=(2, 3, 2, 16)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 3, 2, 16)).astype(np.float32))
        ks.append(k)
        vs.append(v)
        state = kvc.append(CFG, state, k, v)
    return state, ks, vs


@pytest.mark.parametrize("use_pallas", [False, True])
def test_paged_attend_matches_dense(rng, use_pallas):
    """Both attend paths read the pool in place ([n+1, 2, KV, bt, D]
    slots) and match dense attention over the appended k/v."""
    state, ks, vs = _fill(kvc.init(CFG), 11, rng)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)).astype(np.float32))
    for layer in (0, 1):
        out, state = kvc.attend(CFG, state, layer, q, use_pallas=use_pallas)
        K = jnp.stack([k[layer] for k in ks], axis=1)
        V = jnp.stack([v[layer] for v in vs], axis=1)
        want = attn.decode_attention(q[:, None], K, V,
                                     jnp.full((3,), 11))[:, 0]
        assert np.abs(np.asarray(out) - np.asarray(want)).max() < 2e-5


@pytest.mark.parametrize("use_pallas", [False, True])
def test_migration_transparent_to_serving(rng, use_pallas):
    """Collector passes between decode steps must not change attention
    results (the paper's pointer-update guarantee), read through the
    oracle or through the kernel, on the slots the collector moved."""
    state, ks, vs = _fill(kvc.init(CFG), 9, rng)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)).astype(np.float32))
    out0, state = kvc.attend(CFG, state, 1, q, use_pallas=use_pallas)
    # several collector passes (some armed) migrate blocks around
    for i in range(5):
        if i % 2:
            state = kvc.arm(state)
        state, rep = kvc.collect(CFG, state)
    out1, state = kvc.attend(CFG, state, 1, q, use_pallas=use_pallas)
    assert np.abs(np.asarray(out0) - np.asarray(out1)).max() < 1e-5
    assert int(state["pool"]["total_moves"]) > 0, "nothing migrated"


def test_kv_cold_blocks_demote(rng):
    """Blocks never touched again drift to COLD; hot blocks stay dense."""
    from repro.core import object_table as ot
    state, _, _ = _fill(kvc.init(CFG), 32, rng)  # 8 blocks per (L,seq)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)).astype(np.float32))
    # attend only with a short suffix window by shrinking pos? instead:
    # touch all (attend) once, then collect repeatedly with no access.
    out, state = kvc.attend(CFG, state, 0, q)
    for _ in range(6):
        state, rep = kvc.collect(CFG, state)
    tbl = state["pool"]["table"]
    heaps = np.asarray(ot.heap_of(tbl))
    live = heaps != ot.FREE
    assert (heaps[live] == ot.COLD).mean() > 0.9


def test_embedding_cache_coherence(rng):
    cfg = emb.TieredEmbeddingConfig(vocab_size=64, d_model=8, hot_rows=8)
    table = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    s = emb.init(cfg, table)
    toks = jnp.asarray(rng.integers(0, 64, size=(3, 7)), jnp.int32)
    out, s = emb.lookup(cfg, s, toks)
    assert np.allclose(np.asarray(out), np.asarray(table)[np.asarray(toks)])
    # training write: both tiers see the update
    rows = jnp.asarray([0, 33], jnp.int32)
    vals = jnp.ones((2, 8), jnp.float32) * 5
    s = emb.write_rows(s, rows, vals)
    out, s = emb.lookup(cfg, s, rows)
    assert np.allclose(np.asarray(out), 5.0)
    # collect re-elects hot set; reads stay correct
    s, rep = emb.collect(cfg, s)
    out, s = emb.lookup(cfg, s, toks)
    want = np.asarray(s["full"])[np.asarray(toks)]
    assert np.allclose(np.asarray(out), want)
    assert 0 <= float(rep["hot_coverage"]) <= 1


def test_expert_tiering_demotes_and_faults():
    cfg = et.ExpertTieringConfig(num_layers=2, num_experts=8,
                                 bytes_per_expert=100)
    s = et.init(cfg)
    hot = jnp.zeros((2, 8), jnp.int32).at[:, :2].set(50)
    for _ in range(6):
        s = et.observe(cfg, s, hot)
        s, rep = et.collect(cfg, s)
    assert int(rep["resident_experts"]) == 4          # 2 per layer
    # a token routed to a cold expert faults its slab back
    probe = jnp.zeros((2, 8), jnp.int32).at[0, 7].set(1)
    s = et.observe(cfg, s, probe)
    assert int(s["total_faults"]) >= 1
    assert bool(s["resident"][0, 7])
