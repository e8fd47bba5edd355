"""Pallas TPU kernel: `access_scan` — the Object Collector's table sweep.

One memory-bound pass over the packed object-table words (paper §4: the
collector "periodically scans a sparse bitmap"): unpack access/heap/ATC
bits, update the CIW lanes, emit migration candidate masks, and build the
per-superblock hot-object histogram the backends consume.

TPU shape: the table is viewed as [rows, 128] lanes of int32 (the
uint32 words' bits; every field is extracted with a shift and a mask, so
no unsigned vector op is needed). The histogram is accumulated one table
row at a time against a [n_sbs, 128] superblock iota — a compare and a
select per row, then one lane reduction per tile — because scatter-add is
not a TPU-native primitive and a [rows, 128] tile cannot be flattened
into one one-hot contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import object_table as ot

LANE = 128

# python-int copies of the packing constants (Pallas kernel bodies must
# not capture traced jnp constants)
_SLOT_MASK = (1 << ot.SLOT_BITS) - 1
_HEAP_MASK = (1 << ot.HEAP_BITS) - 1
_ATC_MASK = (1 << ot.ATC_BITS) - 1
_CIW_MASK = (1 << ot.CIW_BITS) - 1
_BELOW_CIW = (1 << ot.CIW_SHIFT) - 1     # every field but CIW (top bits)


def _kernel(ct_ref, table_ref, new_table_ref, to_hot_ref, to_cold_ref,
            hist_ref, skipped_ref, *, sb_slots: int, with_hist: bool):
    i = pl.program_id(0)
    w = table_ref[...]                       # [rows_tile, 128] int32
    heap = (w >> ot.HEAP_SHIFT) & _HEAP_MASK
    live = heap != ot.FREE
    acc = (((w >> ot.ACCESS_SHIFT) & 1) == 1) & live
    atc = (w >> ot.ATC_SHIFT) & _ATC_MASK
    ciw = (w >> ot.CIW_SHIFT) & _CIW_MASK
    ciw = jnp.where(acc, 0, jnp.minimum(ciw + 1, ot.CIW_SAT))
    ciw = jnp.where(live, ciw, 0)

    ct = ct_ref[0]
    movable = live & (atc == 0)
    to_hot = acc & ((heap == ot.NEW) | (heap == ot.COLD)) & movable
    to_cold = (~acc) & (ciw > ct) & ((heap == ot.NEW) | (heap == ot.HOT)) \
        & movable

    new_table_ref[...] = (w & _BELOW_CIW) | (ciw << ot.CIW_SHIFT)
    to_hot_ref[...] = to_hot.astype(jnp.int32)
    to_cold_ref[...] = to_cold.astype(jnp.int32)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)
        skipped_ref[...] = jnp.zeros_like(skipped_ref)

    # ATC-vetoed diagnostic, accumulated across tiles: objects the Fig. 5
    # machine wanted to act on (accessed, or idle past the threshold and
    # not already COLD) that the lock-free rule skipped this pass. Folded
    # into the sweep so the collector never re-reads table fields in jnp.
    skipped = live & (atc > 0) & \
        (acc | ((ciw > ct) & (heap != ot.COLD)))
    skipped_ref[...] += jnp.sum(skipped.astype(jnp.int32)).reshape(1, 1)

    if with_hist:
        # per-superblock hot histogram, one table row at a time against
        # the superblock iota; statically skipped when the caller
        # discards it (the collector recomputes referenced bits
        # post-migration)
        n_sbs = hist_ref.shape[0]
        sb = ((w >> ot.SLOT_SHIFT) & _SLOT_MASK) // sb_slots
        acc_i = acc.astype(jnp.int32)
        sb_iota = jax.lax.broadcasted_iota(jnp.int32, (n_sbs, LANE), 0)
        part = jnp.zeros((n_sbs, LANE), jnp.int32)
        for r in range(w.shape[0]):
            part += jnp.where(sb_iota == sb[r:r + 1], acc_i[r:r + 1], 0)
        hist_ref[...] += jnp.sum(part, axis=1, keepdims=True)


def access_scan_pallas(table: jax.Array, ciw_threshold: jax.Array,
                       sb_slots: int, n_sbs: int, *, rows_tile: int = 64,
                       with_hist: bool = True, interpret: bool = True):
    """table: [N] uint32 (N % 128 == 0). Returns (new_table [N],
    to_hot [N] int32, to_cold [N] int32, hist [n_sbs] int32,
    skipped_atc [] int32; hist is all-zero when with_hist=False — the
    histogram is statically skipped). With the histogram on, a tile is
    unrolled row by row: keep rows_tile small (8 is one sublane tile)."""
    n = table.shape[0]
    assert n % LANE == 0, f"table len {n} not lane-aligned"
    rows = n // LANE
    rows_tile = min(rows_tile, rows)
    assert rows % rows_tile == 0
    t2 = jax.lax.bitcast_convert_type(table, jnp.int32).reshape(rows, LANE)
    # CIW saturates at CIW_SAT, so any threshold >= CIW_SAT classifies
    # the same as CIW_SAT: clamping keeps the compare in int32
    ct = jnp.minimum(ciw_threshold.astype(jnp.uint32), ot.CIW_SAT)
    ct = jnp.reshape(ct.astype(jnp.int32), (1,))

    tile = pl.BlockSpec((rows_tile, LANE), lambda i, ct: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // rows_tile,),
        in_specs=[tile],
        out_specs=[
            tile, tile, tile,
            pl.BlockSpec((n_sbs, 1), lambda i, ct: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, ct: (0, 0)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, sb_slots=sb_slots, with_hist=with_hist),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct((n_sbs, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
        name="access_scan",
    )
    new_t, to_hot, to_cold, hist, skipped = fn(ct, t2)
    new_t = jax.lax.bitcast_convert_type(new_t, jnp.uint32)
    return (new_t.reshape(n), to_hot.reshape(n), to_cold.reshape(n),
            hist[:, 0], skipped[0, 0])
