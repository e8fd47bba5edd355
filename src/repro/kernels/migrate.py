"""Pallas TPU kernel: `migrate` — the Object Collector's data mover.

The paper's hot loop when tidying: copy object payloads from their old
slots to their new (dense) slots. On TPU this is a batched indirection
copy through VMEM: move indices are *scalar-prefetched* so the index math
runs ahead of the data DMAs (PrefetchScalarGridSpec), each grid step
streams one whole slot HBM->VMEM->HBM, and the pool array is aliased
in/out so unmoved slots cost nothing.

Layout: the pool is [n_slots, *row] with row of rank >= 2, and a grid
step's block is (1, *row): its last two dims are the array's own, which
TPU's (8, 128) block rule always admits. A pool whose slots are
tile-shaped (the paged KV pool's [2, KV, bt, D] rows) is read in place;
the ops wrapper views a flat [n_slots, W] pool as [n_slots, 1, W].

In-place safety contract (enforced by callers — ops.migrate routes
masked-out moves to a scratch row to honor it): grid steps run in
ascending move order and READ THE PRE-KERNEL VALUE of their source, so
no move may read a slot a previous move overwrote. Sufficient
conditions: (a) src and dst slot sets are disjoint (cross-heap
migration: dst slots are free), or (b) moves are sorted so
dst[i] <= src[i] (left-packing compaction). A self-move (src == dst)
is NOT automatically safe: if its slot is an earlier move's
destination, it rewrites stale bytes over the fresh copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, data_ref, out_ref):
    # idx_ref is the scalar-prefetch ref (unused in the body: the gather/
    # scatter happens in the index_maps); the body is a pure VMEM copy.
    out_ref[...] = data_ref[...]


def migrate_pallas(data: jax.Array, src: jax.Array, dst: jax.Array,
                   *, interpret: bool = True) -> jax.Array:
    """data: [n_slots, *row] (row of rank >= 2), src/dst: [n_moves]
    int32. Returns data with data[dst[i]] = data[src[i]] applied in move
    order; each move reads its source's PRE-kernel value (see the module
    docstring for the aliasing contract).
    """
    assert data.ndim >= 3, f"slot rows must have rank >= 2: {data.shape}"
    row = data.shape[1:]
    zeros = (0,) * len(row)
    idx = jnp.stack([src, dst], axis=0).astype(jnp.int32)  # [2, n_moves]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(src.shape[0],),
        in_specs=[pl.BlockSpec((1,) + row,
                               lambda i, idx: (idx[0, i],) + zeros)],
        out_specs=pl.BlockSpec((1,) + row,
                               lambda i, idx: (idx[1, i],) + zeros),
    )
    fn = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(data.shape, data.dtype),
        input_output_aliases={1: 0},   # pool array aliased in/out
        interpret=interpret,
        name="migrate",
    )
    return fn(idx, data)
