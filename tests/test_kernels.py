"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import object_table as ot
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _arr(shape, dtype=np.float32, scale=1.0):
    return jnp.asarray((RNG.normal(size=shape) * scale).astype(dtype))


# ---------------------------------------------------------------------------
# migrate
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_slots,w", [(32, 8), (64, 128), (40, 96)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_migrate_sweep(n_slots, w, dtype):
    data = jnp.asarray(RNG.integers(0, 100, (n_slots, w)).astype(dtype))
    n_moves = n_slots // 4
    src = jnp.asarray(RNG.choice(n_slots // 2, n_moves, replace=False),
                      jnp.int32)
    dst = jnp.asarray(n_slots // 2 +
                      RNG.choice(n_slots // 2, n_moves, replace=False),
                      jnp.int32)
    ok = jnp.asarray(RNG.random(n_moves) < 0.7)
    got = ops.migrate(data, src, dst, ok)
    want = ref.migrate(data, src, dst, ok)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_migrate_left_packing_order():
    """Compaction contract: dst[i] <= src[i], ascending — in-place safe."""
    data = jnp.arange(64, dtype=jnp.float32).reshape(16, 4)
    src = jnp.asarray([4, 6, 10, 14], jnp.int32)
    dst = jnp.asarray([0, 1, 2, 3], jnp.int32)
    ok = jnp.ones(4, bool)
    got = ops.migrate(data, src, dst, ok)
    want = ref.migrate(data, src, dst, ok)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# access_scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,sb_slots,n_sbs", [(128, 8, 16), (384, 16, 64),
                                              (256, 32, 8)])
@pytest.mark.parametrize("ct", [0, 3, 30])
def test_access_scan_sweep(n, sb_slots, n_sbs, ct):
    tbl = ot.pack(
        jnp.asarray(RNG.integers(0, sb_slots * n_sbs, n), jnp.uint32),
        jnp.asarray(RNG.integers(0, 4, n), jnp.uint32),
        jnp.asarray(RNG.integers(0, 2, n), jnp.uint32),
        jnp.asarray(RNG.integers(0, 3, n), jnp.uint32),
        jnp.asarray(RNG.integers(0, 32, n), jnp.uint32))
    ctj = jnp.asarray(ct, jnp.uint32)
    got = ops.access_scan(tbl, ctj, sb_slots=sb_slots, n_sbs=n_sbs)
    want = ref.access_scan(tbl, ctj, sb_slots, n_sbs)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.array_equal(np.asarray(got[2]), np.asarray(want[2]))
    assert np.array_equal(np.asarray(got[3]), np.asarray(want[3]))
    # skipped_atc is folded into the sweep (scalar ATC-veto count)
    assert int(got[4]) == int(want[4])


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,h,kv,d", [(1, 128, 4, 4, 32),
                                        (2, 256, 4, 2, 64),
                                        (1, 256, 8, 1, 16)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kv, d, causal, window, dtype):
    q = _arr((b, s, h, d)).astype(dtype)
    k = _arr((b, s, kv, d)).astype(dtype)
    v = _arr((b, s, kv, d)).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window)
    want = ref.flash_attention(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32),
                               causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kv,d,bt,mb,lens", [
    pytest.param(2, 8, 2, 16, 4, 6, None, id="2-8-2-16-4-6"),
    pytest.param(3, 4, 4, 32, 8, 4, None, id="3-4-4-32-8-4"),
    pytest.param(1, 8, 1, 64, 16, 3, None, id="1-8-1-64-16-3"),
    pytest.param(4, 32, 2, 128, 16, 64, None, id="serving-shape"),
    pytest.param(4, 8, 2, 16, 4, 6, (0, 9, 0, 24), id="empty-lanes"),
    pytest.param(3, 8, 2, 32, 8, 8, (8, 16, 40), id="block-boundary"),
    pytest.param(3, 4, 2, 16, 4, 6, (24, 24, 1), id="full-lanes"),
])
def test_paged_attention_sweep(b, h, kv, d, bt, mb, lens):
    """Every slot that no live block maps to holds NaN for the kernel (the
    oracle reads the finite pool): a finite output shows the kernel read
    only live blocks."""
    n_slots = max(32, b * mb)
    q = _arr((b, h, d))
    kv_pages = _arr((n_slots, 2, kv, bt, d))
    if lens is None:
        lens = RNG.integers(1, bt * mb, b)
    lens = jnp.asarray(lens, jnp.int32)
    tables = []
    for i in range(b):
        used = int(np.ceil(int(lens[i]) / bt))
        row = list(RNG.choice(n_slots, used, replace=False)) + \
            [-1] * (mb - used)
        tables.append(row)
    tables = jnp.asarray(tables, jnp.int32)
    dead = np.ones(n_slots, bool)
    dead[np.asarray(tables)[np.asarray(tables) >= 0]] = False
    poisoned = jnp.where(jnp.asarray(dead)[:, None, None, None, None],
                         jnp.nan, kv_pages)
    got_o, got_t = ops.paged_attention(q, poisoned, tables, lens)
    want_o, want_t = ref.paged_attention(q, kv_pages, tables, lens)
    got_o, want_o = np.asarray(got_o), np.asarray(want_o)
    live = np.asarray(lens) > 0
    assert np.isfinite(got_o).all()
    assert np.abs(got_o[live] - want_o[live]).max() < 2e-5
    assert (got_o[~live] == 0).all()
    assert np.array_equal(np.asarray(got_t), np.asarray(want_t))


# ---------------------------------------------------------------------------
# mamba_scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,c,n,chunk,ct", [(1, 64, 8, 16, 16, 4),
                                              (2, 128, 16, 8, 64, 8),
                                              (1, 32, 4, 4, 32, 4)])
def test_mamba_scan_sweep(b, s, c, n, chunk, ct):
    a = jnp.asarray(RNG.uniform(0.3, 1.0, (b, s, c, n)).astype(np.float32))
    bb = _arr((b, s, c, n))
    h0 = _arr((b, c, n))
    got_all, got_last = ops.mamba_scan(a, bb, h0, chunk=chunk, ct=ct)
    want_all, want_last = ref.mamba_scan(a, bb, h0)
    assert np.abs(np.asarray(got_all) - np.asarray(want_all)).max() < 1e-4
    assert np.abs(np.asarray(got_last) - np.asarray(want_last)).max() < 1e-4
