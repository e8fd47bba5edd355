"""Pallas TPU kernel: `paged_attention` — decode through the object table.

The HADES serving hot loop: one query token per sequence attends over a
KV cache whose blocks live in HadesPool slots. The block table (logical
block -> physical slot) is *scalar-prefetched*, so each grid step's KV
block DMA is issued from the indirection without a gather materializing;
the online-softmax runs in VMEM scratch.

The paper's access-bit recording is FUSED: the kernel emits one touched
bit per (sequence, block) as a by-product of the DMA it already did —
this is how tracking overhead stays at "4-5 ns / skip-if-set" (§4): the
tracking rides the read.

GQA layout: q is [B, KV, REP, D] (q heads grouped by kv head); each grid
step contracts the [bt, D] block against all REP q-heads of its kv head.

Pool layout: one KV block is one pool slot of shape [2, KV, bt, D] (K/V
first, then kv head), so the pool is `kv_pages` [n_slots, 2, KV, bt, D]
and one grid step fetches the K and the V tile of its kv head in ONE
(1, 2, 1, bt, D) block. The tiled dims are (bt, D) — TPU's (8, 128) rule
holds for bt % 8 == 0 and D % 128 == 0 (or D the whole head) — and the
kernel reads the pool in place: no K/V split or relayout of the pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _kernel(bt_ref, lens_ref, q_ref, kv_ref, o_ref, touched_ref,
            m_scr, l_scr, acc_scr, *, block_tokens: int, n_blocks: int,
            scale: float):
    b = pl.program_id(0)
    kvh = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # [REP, D]
    k = kv_ref[0, 0, 0].astype(jnp.float32)           # [bt, D]
    v = kv_ref[0, 1, 0].astype(jnp.float32)           # [bt, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [REP, bt]

    # validity: token position within seq_len AND block mapped
    pos = j * block_tokens + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    valid = (pos < lens_ref[b]) & (bt_ref[b, j] >= 0)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    scale_prev = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * scale_prev + jnp.sum(p, -1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * scale_prev + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # fused access-bit recording: this block was dereferenced
    was_used = (j * block_tokens < lens_ref[b]) & (bt_ref[b, j] >= 0)
    touched_ref[b, j] = was_used.astype(jnp.int32)

    @pl.when(j == n_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, kv_pages: jax.Array,
                           block_tables: jax.Array, seq_lens: jax.Array,
                           *, scale: float = None, interpret: bool = True):
    """q: [B, KV, REP, D]; kv_pages: [n_slots, 2, KV, bt, D] (K at index
    0, V at 1 of axis 1); block_tables: [B, MB] int32 physical slot ids
    (-1 unused); seq_lens: [B] int32.
    Returns (out [B, KV, REP, D], touched [B, MB] int32)."""
    b, kv, rep, d = q.shape
    n_slots, two, kv2, bt, d2 = kv_pages.shape
    assert (two, kv, d) == (2, kv2, d2)
    mb = block_tables.shape[1]
    safe_tables = jnp.where(block_tables >= 0, block_tables, 0) \
        .astype(jnp.int32)

    grid = (b, kv, mb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # block_tables, seq_lens
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rep, d),
                         lambda i, h, j, tbl, lens: (i, h, 0, 0)),
            pl.BlockSpec((1, 2, 1, bt, d),
                         lambda i, h, j, tbl, lens: (tbl[i, j], 0, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rep, d),
                         lambda i, h, j, tbl, lens: (i, h, 0, 0)),
            # the access bits are scalars: the whole [B, MB] map stays in
            # SMEM for the kernel's lifetime, one store per grid step
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
    )
    kern = functools.partial(
        _kernel, block_tokens=bt, n_blocks=mb,
        scale=scale if scale is not None else d ** -0.5)
    out, touched = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, rep, d), q.dtype),
            jax.ShapeDtypeStruct((b, mb), jnp.int32),
        ],
        interpret=interpret,
        name="paged_attention",
    )(safe_tables, seq_lens.astype(jnp.int32), q, kv_pages)
    return out, touched
