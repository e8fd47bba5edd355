"""Public jit'd wrappers for the Pallas kernels.

Each wrapper: validates/normalizes shapes (lane padding, GQA grouping),
selects interpret mode (Pallas kernels execute in interpret mode on CPU
and compile natively on TPU), and matches the ref.py oracle bit-for-bit
on the unpadded region.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import access_scan as _scan
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import migrate as _mig
from repro.kernels import paged_attention as _pa

LANE = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _tile(n: int, want: int) -> int:
    """Largest divisor of n that is <= want (grid tiles must divide the
    padded extent for any pool geometry)."""
    t = min(want, n)
    while n % t:
        t -= 1
    return t


# ---------------------------------------------------------------------------
# migrate
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("has_scratch_row",))
def migrate(data: jax.Array, src: jax.Array, dst: jax.Array,
            ok: jax.Array, *, has_scratch_row: bool = False) -> jax.Array:
    """data: [n_slots(+1), *row]; src/dst/ok: [n_moves]. Caller contract
    for the ACTIVE moves: disjoint src/dst sets OR left-packing order (see
    migrate.py). Masked moves (ok=False) are routed to a scratch row —
    NOT turned into self-copies, because a masked entry's slot may be an
    earlier move's destination, and a grid step reads the pre-kernel
    value (re-writing stale bytes over the fresh copy).

    `has_scratch_row=True` declares that the caller's pool layout already
    carries a permanent scratch row as data's LAST row (core/pool.py) —
    masked moves copy that row onto itself (a no-op for its all-zero
    invariant) and NO whole-pool pad copy happens; the kernel aliases
    the pool in place. With False (standalone use, kernel sweeps) a
    scratch row is appended, which costs one pool copy per call.

    A flat [n, W] pool is viewed as [n, 1, W] for the kernel's block rule;
    on TPU that view is a relayout copy, which a pool with tile-shaped
    slot rows (`PoolConfig.slot_shape`) never pays."""
    n = data.shape[0]
    if has_scratch_row:
        scratch = jnp.int32(n - 1)
        padded = data
    else:
        scratch = jnp.int32(n)
        padded = jnp.pad(data, ((0, 1),) + ((0, 0),) * (data.ndim - 1))
    view = padded if padded.ndim >= 3 else padded[:, None, :]
    src_eff = jnp.where(ok, src, scratch).astype(jnp.int32)
    dst_eff = jnp.where(ok, dst, scratch).astype(jnp.int32)
    out = _mig.migrate_pallas(view, src_eff, dst_eff,
                              interpret=_interpret())
    return out.reshape(padded.shape)[:n]


# ---------------------------------------------------------------------------
# access_scan
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("sb_slots", "n_sbs",
                                             "with_hist"))
def access_scan(table: jax.Array, ciw_threshold: jax.Array, *,
                sb_slots: int, n_sbs: int, with_hist: bool = True):
    """table: [N] uint32. Returns (new_table, to_hot bool, to_cold bool,
    hist [n_sbs] int32 — zeros when with_hist=False, which statically
    skips the histogram for callers that discard it,
    skipped_atc [] int32 — the ATC-vetoed count, folded into the sweep so
    the collector's use_pallas path never re-reads table fields)."""
    n = table.shape[0]
    # a zero pad word would decode as a live NEW object: pad with FREE
    # words so padding never classifies
    padded = _pad_to(table, LANE, axis=0)
    if padded.shape[0] != n:
        from repro.core import object_table as ot
        padded = padded.at[n:].set(ot.free_word())
    new_t, to_hot, to_cold, hist, skipped = _scan.access_scan_pallas(
        padded, ciw_threshold, sb_slots, n_sbs,
        rows_tile=_tile(padded.shape[0] // LANE, 8 if with_hist else 64),
        with_hist=with_hist, interpret=_interpret())
    return (new_t[:n], to_hot[:n].astype(bool), to_cold[:n].astype(bool),
            hist, skipped)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128) -> jax.Array:
    """q: [B,S,H,D]; k/v: [B,S,KV,D] -> [B,S,H,D]. GQA expanded here;
    D padded to 128 lanes; S must divide by the block sizes (bq/bk are
    clipped to S)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    # expand kv heads to q heads, fold heads into batch
    k_e = jnp.repeat(k, rep, axis=2)
    v_e = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k_e.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v_e.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    qf = _pad_to(qf, LANE, 2)
    kf = _pad_to(kf, LANE, 2)
    vf = _pad_to(vf, LANE, 2)
    out = _fa.flash_attention_pallas(qf, kf, vf, causal=causal,
                                     window=window, bq=bq, bk=bk,
                                     scale=d ** -0.5,
                                     interpret=_interpret())
    out = out[:, :, :d].reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return out


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------
@jax.jit
def paged_attention(q: jax.Array, kv_pages: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """q: [B,H,D]; kv_pages: [n_slots, 2, KV, bt, D] (the paged KV pool,
    read in place); block_tables: [B, MB]; seq_lens: [B].
    Returns (out [B,H,D], touched [B,MB] bool)."""
    b, h, d = q.shape
    kv = kv_pages.shape[2]
    qg = q.reshape(b, kv, h // kv, d)
    out, touched = _pa.paged_attention_pallas(
        qg, kv_pages, block_tables, seq_lens, scale=d ** -0.5,
        interpret=_interpret())
    return out.reshape(b, h, d), touched.astype(bool)


# ---------------------------------------------------------------------------
# mamba_scan
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunk", "ct"))
def mamba_scan(a: jax.Array, b: jax.Array, h0: jax.Array, *,
               chunk: int = 64, ct: int = 8):
    """a,b: [B,S,C,N]; h0: [B,C,N] -> (h_all fp32, h_last fp32)."""
    n = a.shape[-1]
    ap = _pad_to(a.astype(jnp.float32), LANE, 3)
    bp = _pad_to(b.astype(jnp.float32), LANE, 3)
    h0p = _pad_to(h0.astype(jnp.float32), LANE, 2)
    # pad a with 1s would corrupt? a-pad lanes multiply zeros of h0/b: all
    # padded lanes stay 0 regardless of a's pad value (h0,b pads are 0).
    h_all, h_last = _ms.mamba_scan_pallas(ap, bp, h0p, chunk=chunk, ct=ct,
                                          interpret=_interpret())
    return h_all[..., :n], h_last[..., :n]
