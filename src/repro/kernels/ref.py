"""Pure-jnp oracles for every Pallas kernel (the ground truth the kernel
shape/dtype sweeps assert against)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import object_table as ot
from repro.models import attention as attn_lib


# ---------------------------------------------------------------------------
# migrate — the Object Collector's data mover
# ---------------------------------------------------------------------------
def migrate(data: jax.Array, src: jax.Array, dst: jax.Array,
            ok: jax.Array) -> jax.Array:
    """Copy data[src[i]] -> data[dst[i]] where ok[i] (batched indirection
    copy over [n_slots, slot_words])."""
    n_slots = data.shape[0]
    return data.at[jnp.where(ok, dst, n_slots)].set(
        data[src], mode="drop")


# ---------------------------------------------------------------------------
# access_scan — collector bitmap scan + CIW update + per-sb histogram
# ---------------------------------------------------------------------------
def access_scan(table: jax.Array, ciw_threshold: jax.Array, sb_slots: int,
                n_sbs: int) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """One pass over packed table words.
    Returns (new_table [N] with CIW updated,
             to_hot [N] bool, to_cold [N] bool,
             sb_hot_hist [n_sbs] int32 — accessed-object count per
             superblock of the object's *current* slot,
             skipped_atc [] int32 — live objects the classifier wanted to
             act on but the ATC lock-free rule vetoed this pass)."""
    live = ot.is_live(table)
    acc = (ot.access_of(table) == 1) & live
    atc = ot.atc_of(table)
    heap = ot.heap_of(table)
    ciw = ot.ciw_of(table)
    ciw = jnp.where(acc, 0, jnp.minimum(ciw + 1, ot.CIW_SAT))
    ciw = jnp.where(live, ciw, 0)
    ct = ciw_threshold.astype(jnp.uint32)
    movable = live & (atc == 0)
    to_hot = acc & ((heap == ot.NEW) | (heap == ot.COLD)) & movable
    to_cold = (~acc) & (ciw > ct) & ((heap == ot.NEW) | (heap == ot.HOT)) \
        & movable
    new_table = (table & ~(ot.CIW_MASK << ot.CIW_SHIFT)) | \
        (ciw.astype(jnp.uint32) << ot.CIW_SHIFT)
    sb = (ot.slot_of(table) // sb_slots).astype(jnp.int32)
    hist = jnp.zeros((n_sbs,), jnp.int32).at[
        jnp.where(acc, sb, n_sbs)].add(1, mode="drop")
    skipped = jnp.sum(live & (atc > 0) &
                      (acc | ((ciw > ct) & (heap != ot.COLD)))
                      ).astype(jnp.int32)
    return new_table, to_hot, to_cold, hist, skipped


# ---------------------------------------------------------------------------
# flash_attention — training attention (causal, optional sliding window)
# ---------------------------------------------------------------------------
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0) -> jax.Array:
    """q: [B,S,H,D], k/v: [B,S,KV,D] -> [B,S,H,D]."""
    return attn_lib.full_attention(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# paged_attention — decode through the object table (block-paged KV)
# ---------------------------------------------------------------------------
def paged_attention(q: jax.Array, kv_pages: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """q: [B,H,D] one token per sequence.
    kv_pages: [n_slots, 2, KV, block_tokens, D] — the HadesPool data (K at
    index 0 of axis 1, V at 1). block_tables: [B, max_blocks] physical
    slot per logical KV block (-1 = unused). seq_lens: [B].
    Returns (out [B,H,D], touched [B, max_blocks] bool — the access bits
    the fused tracking would record)."""
    b, h, d = q.shape
    n_slots, _, kv, bt, _ = kv_pages.shape
    mb = block_tables.shape[1]
    safe = jnp.maximum(block_tables, 0)
    pages = kv_pages[safe]                  # [B, mb, 2, KV, bt, D]
    k = pages[:, :, 0].transpose(0, 1, 3, 2, 4).reshape(b, mb * bt, kv, d)
    v = pages[:, :, 1].transpose(0, 1, 3, 2, 4).reshape(b, mb * bt, kv, d)
    pos = jnp.arange(mb * bt)[None]
    valid = (pos < seq_lens[:, None]) & \
        (jnp.repeat(block_tables >= 0, bt, axis=1))
    out, m, l = attn_lib.decode_attention_partial(
        q[:, None], k, v, valid)
    out = out / jnp.moveaxis(jnp.maximum(l, 1e-30), 1, -1)[..., None]
    n_blocks_used = (seq_lens + bt - 1) // bt
    touched = (jnp.arange(mb)[None] < n_blocks_used[:, None]) & \
        (block_tables >= 0)
    return out[:, 0].astype(q.dtype), touched


# ---------------------------------------------------------------------------
# mamba_scan — selective-SSM recurrence h_t = a_t * h_{t-1} + b_t
# ---------------------------------------------------------------------------
def mamba_scan(a: jax.Array, b: jax.Array, h0: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """a, b: [B, S, C, N]; h0: [B, C, N] -> (h_all [B,S,C,N], h_last)."""
    def step(h, xs):
        ai, bi = xs
        h = ai * h + bi
        return h, h
    h_last, h_all = jax.lax.scan(
        step, h0, (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)))
    return jnp.moveaxis(h_all, 0, 1), h_last
