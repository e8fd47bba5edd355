"""A decode step's access record, taken once over the layer-major object
table after every layer's read, leaves the pool as recording each layer
after its read does, and as the pool's own read op does with the same
ids (`pool.read`, the accounting without the data gather)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import object_table as ot
from repro.core import pool as pl
from repro.models import kvcache as kvc

L, B, MB, BT = 3, 4, 4, 4
CFGS = {
    "kv": kvc.KVCacheConfig(num_layers=L, batch=B, max_blocks=MB,
                            block_tokens=BT, num_kv_heads=2, head_dim=16,
                            dtype="float32", sb_slots=4),
    "latent": kvc.KVCacheConfig(num_layers=L, batch=B, max_blocks=MB,
                                block_tokens=BT, num_kv_heads=1,
                                head_dim=24, v_dim=16, dtype="float32",
                                sb_slots=4),
}
DEAD = (1 * B + 0) * MB + 2        # layer 1, lane 0, block 2
FIELDS = ("table", "slot_ref", "sb_tier", "sb_evict", "win_accesses",
          "win_promos", "win_faults", "total_faults")


def _state(cfg, rng, armed):
    """Lane 0 at capacity, lanes 1-3 at 3 blocks; six collects leave
    most blocks COLD; then half the lanes inactive, every other
    superblock paged out to HOST, random ATCs (some saturated), and one
    block-table entry of lane 0 naming a dead object, as after an
    allocation that found the pool full."""
    state = kvc.init(cfg)
    kv_shape = (L, B, cfg.num_kv_heads, cfg.head_dim)
    for step in range(MB * BT):
        state = dict(state, active=jnp.asarray([True] + [step >= 6] * 3))
        k = jnp.asarray(rng.normal(size=kv_shape).astype(np.float32))
        state = kvc.append(cfg, state, k, k)
    for _ in range(6):
        state, _ = kvc.collect(cfg, state)
    pool = state["pool"]
    pcfg = cfg.pool_config()
    atc = jnp.asarray(rng.integers(0, ot.ATC_SAT + 1, cfg.max_objects),
                      jnp.uint32)
    pool = pl.free(pcfg, pool, jnp.asarray([DEAD], jnp.int32))
    pool = dict(
        pool, table=ot.with_atc(pool["table"], atc),
        sb_tier=jnp.where(jnp.arange(pcfg.n_sbs) % 2 == 1, pl.HOST,
                          pl.HBM).astype(jnp.int8),
        armed=jnp.asarray(armed))
    return dict(state, pool=pool,
                active=jnp.asarray([True, False, True, False]))


@pytest.mark.parametrize("armed", [False, True])
@pytest.mark.parametrize("kind", sorted(CFGS))
def test_step_record_equals_per_layer_record(rng, kind, armed):
    cfg = CFGS[kind]
    state = _state(cfg, rng, armed)
    assert int(state["pos"][0]) == MB * BT            # lane 0 is full
    q = jnp.asarray(rng.normal(size=(B, 2, cfg.head_dim)).astype(np.float32))

    per_layer, outs = state, []
    for layer in range(L):
        out, per_layer = kvc.attend(cfg, per_layer, layer, q)
        outs.append(out)

    reads = [kvc.attend_read(cfg, state, layer, q) for layer in range(L)]
    touched = jnp.stack([t for _, t in reads])
    stepped = kvc.record(cfg, state, touched)

    pool = state["pool"]
    for layer in range(L):
        ids = jnp.where(touched[layer], state["block_tables"][layer], -1)
        _, pool = pl.read(cfg.pool_config(), pool, ids.reshape(-1))

    for (out, _), want in zip(reads, outs):
        assert np.array_equal(np.asarray(out), np.asarray(want))
    for f in FIELDS:
        want = np.asarray(pool[f])
        assert np.array_equal(np.asarray(per_layer["pool"][f]), want), f
        assert np.array_equal(np.asarray(stepped["pool"][f]), want), f

    # every live block of the two active lanes was read, nothing else
    live = np.asarray(state["block_tables"]) >= 0
    assert np.array_equal(np.asarray(touched),
                          live & np.asarray([1, 0, 1, 0], bool)[:, None])
    got = stepped["pool"]
    assert int(got["win_accesses"]) == MB * L + (MB - 1) * L - 1
    assert int(got["win_promos"]) > 0 and int(got["win_faults"]) > 0
    atc = np.asarray(ot.atc_of(got["table"]))
    before = np.asarray(ot.atc_of(state["pool"]["table"]))
    hit = np.zeros(cfg.max_objects, bool)
    hit[np.asarray(state["block_tables"])[np.asarray(touched)]] = True
    hit[DEAD] = False
    bumped = np.minimum(before + 1, ot.ATC_SAT) if armed else before
    assert np.array_equal(atc, np.where(hit, bumped, before))
