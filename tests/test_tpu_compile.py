"""Compile-only checks of the serving path's Pallas kernels for a TPU v5e
(`interpret=False`), at chatglm3-6b serving shapes: 8 lanes, KV 2 x 16
query heads of 128, 16-token blocks, 1024-token lanes, 28 layers of KV in
one pool. The chip's compiler is installed here and compiles for a
described, unattached chip — what interpret mode accepts but Mosaic
refuses (block tiling, unsupported vector ops) fails here at no chip
time. Nothing runs, so this says nothing about results or speed: the
interpret-mode sweeps in test_kernels.py check the numbers.

The kernels are called directly: the `ops` wrappers ask the process's
backend (the CPU here) and would pick interpret mode.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import access_scan, migrate, paged_attention
from repro.models.kvcache import KVCacheConfig

# the server's KV geometry for chatglm3-6b at max_len 1024
KV = dict(num_layers=28, batch=8, max_blocks=64, block_tokens=16,
          num_kv_heads=2, head_dim=128)
REP = 16          # 32 query heads over 2 kv heads
MOVES = 512       # the collector's 2 x move_budget payload copies


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pool_cfg():
    return KVCacheConfig(**KV).pool_config()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_attention_compiles_for_v5e(one_chip, pool_cfg):
    b, mb = KV["batch"], KV["max_blocks"]
    kv, d = KV["num_kv_heads"], KV["head_dim"]
    pages = (pool_cfg.n_slots + 1,) + pool_cfg.row_shape
    assert pages[1:] == (2, kv, KV["block_tokens"], d)
    _compile(lambda q, p, t, n: paged_attention.paged_attention_pallas(
                 q, p, t, n, interpret=False), one_chip,
             ((b, kv, REP, d), jnp.bfloat16), (pages, jnp.bfloat16),
             ((b, mb), jnp.int32), ((b,), jnp.int32))


def test_migrate_compiles_for_v5e(one_chip, pool_cfg):
    assert pool_cfg.slot_words == 8192
    pages = (pool_cfg.n_slots + 1,) + pool_cfg.row_shape
    compiled = _compile(
        lambda data, s, d: migrate.migrate_pallas(data, s, d,
                                                  interpret=False),
        one_chip, (pages, jnp.bfloat16), ((MOVES,), jnp.int32),
        ((MOVES,), jnp.int32))
    # the kernel moves slots in place: no temp the size of the pool
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("with_hist", [False, True])
def test_access_scan_compiles_for_v5e(one_chip, pool_cfg, with_hist):
    n = pool_cfg.max_objects
    assert n == 14336 and pool_cfg.n_sbs == 1344
    # ops.access_scan's tiles for this table: 8 rows with the histogram
    # (unrolled row by row), 56 of the 112 rows without
    rows_tile = 8 if with_hist else 56
    _compile(lambda t, c: access_scan.access_scan_pallas(
                 t, c, pool_cfg.sb_slots, pool_cfg.n_sbs,
                 rows_tile=rows_tile, with_hist=with_hist,
                 interpret=False), one_chip,
             ((n,), jnp.uint32), ((), jnp.float32))
