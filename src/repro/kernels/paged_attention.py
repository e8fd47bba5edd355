"""Pallas TPU kernel: `paged_attention` — decode through the object table.

The HADES serving hot loop: one query token per sequence attends over a
KV cache whose blocks live in HadesPool slots. The block table (logical
block -> physical slot) and the sequence lengths are *scalar-prefetched*
into SMEM; the kernel moves the slots it needs itself, so no gather
materializes and no dead block is read.

Pool layout: one KV block is one pool slot of shape [2, KV, bt, D] (K/V
first, then kv head), so the pool is `kv_pages` [n_slots, 2, KV, bt, D].
It stays in HBM (`memory_space=ANY`); one slot — K and V of every kv head
— is one contiguous DMA into VMEM. No K/V split or relayout of the pool.

K=V mode (`v_dim`, static): latent attention (models/mla.py) caches one
vector a token, so a slot is [1, KV, bt, D] and its one tile is read as K
and, in its first `v_dim` columns, as V; the output is [.., v_dim]. The
walk, the DMAs, the softmax and the access bits are the same; without
`v_dim` the kernel is the separate-tile kernel it was.

Grid and walk: the grid runs over lanes only, `grid = (B,)`. Lane b has
`n_live = ceil(seq_lens[b] / bt)` live blocks, and its step loops over
`ceil(n_live / P)` chunks of P slots (a dynamic trip count), where P is
the largest divisor of MB that keeps a chunk at <= 128 tokens (8 slots at
bt = 16). Blocks at or past `n_live`, and blocks mapped to -1, are not
fetched and cost no arithmetic beyond their chunk's masking; a lane with
`seq_lens == 0` starts nothing and writes zeros. Each chunk contracts
[P*bt, D] of K and of V against the REP q-heads of every kv head, with
the f32 online softmax carried across chunks.

Overlap: the chunks are double-buffered in a [2, P, 2, KV, bt, D] VMEM
buffer. Chunk c+1's DMAs start before chunk c is computed, and a lane's
last chunk starts the first chunk of the next lane with live tokens, so
the DMA latency is paid once per call, not once per lane. The buffer
index crosses grid steps in SMEM scratch, hence a sequential grid.

The paper's access-bit recording is FUSED: the step that starts a
block's DMA writes its touched bit, `touched[b, j] = (j*bt < seq_lens[b])
& (block_tables[b, j] >= 0)`, into an SMEM map whose other entries hold
0. So `touched` is exactly the set of slots fetched, and the
collector's per-window `win_accesses` counts the blocks this kernel read:
the tracking rides the read (§4, "4-5 ns / skip-if-set").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
CHUNK_TOKENS = 128   # tokens per compute chunk: one MXU-wide K^T tile


def _chunk_slots(max_blocks: int, block_tokens: int) -> int:
    """P: the largest divisor of MB with P * bt <= CHUNK_TOKENS (at least
    1), so a lane's chunks never run past its block-table row."""
    p = min(max_blocks, max(1, CHUNK_TOKENS // block_tokens))
    while max_blocks % p:
        p -= 1
    return p


def _kernel(tbl_ref, lens_ref, q_ref, kv_hbm, o_ref, touched_ref,
            buf, sems, buf_idx, *, block_tokens: int, n_blocks: int,
            n_chunk_slots: int, scale: float, v_dim=None):
    bt, mb, p = block_tokens, n_blocks, n_chunk_slots
    _, _, _, kv, _, d = buf.shape
    rep = q_ref.shape[2]
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)

    def n_live(lane):
        return jnp.minimum((lens_ref[lane] + bt - 1) // bt, mb)

    def fetches(lane, chunk):
        """Per slot of the chunk: (block j, whether it is fetched, slot)."""
        live = n_live(lane)
        out = []
        for i in range(p):
            j = chunk * p + i
            phys = tbl_ref[lane, j]
            out.append((j, (j < live) & (phys >= 0), jnp.maximum(phys, 0)))
        return out

    def copy(slot, half, i):
        return pltpu.make_async_copy(kv_hbm.at[slot], buf.at[half, i],
                                     sems.at[half])

    def start(lane, chunk, half):
        for i, (j, fetch, slot) in enumerate(fetches(lane, chunk)):
            @pl.when(fetch)
            def _():
                copy(slot, half, i).start()
            # fused access bit, set by the step that started the DMA
            touched_ref[lane, j] = fetch.astype(jnp.int32)

    def first_live_lane(after):
        """Smallest lane > `after` with live tokens, else n_lanes."""
        def body(k, nxt):
            lane = n_lanes - 1 - k
            return jnp.where(lens_ref[lane] > 0, lane, nxt)
        return jax.lax.fori_loop(0, n_lanes - 1 - after, body, n_lanes)

    @pl.when(b == 0)
    def _prefetch_first():
        buf_idx[0] = 0
        first = first_live_lane(-1)

        @pl.when(first < n_lanes)
        def _():
            start(first, 0, 0)

    n_chunks = (n_live(b) + p - 1) // p
    next_lane = first_live_lane(b)
    q = q_ref[0].astype(jnp.float32) * scale             # [KV, REP, D]
    t_row = jax.lax.broadcasted_iota(jnp.int32, (1, p * bt), 1)

    def chunk_body(c, carry):
        half, heads = carry

        @pl.when(c + 1 < n_chunks)
        def _():
            start(b, c + 1, 1 - half)

        @pl.when((c + 1 == n_chunks) & (next_lane < n_lanes))
        def _():
            start(next_lane, 0, 1 - half)

        # a valid token is within seq_len and in a fetched (mapped) block
        in_fetched = t_row < 0
        fetched = []
        for i, (_, fetch, slot) in enumerate(fetches(b, c)):
            @pl.when(fetch)
            def _():
                copy(slot, half, i).wait()
            fetched.append(fetch)
            in_fetched |= (t_row // bt == i) & fetch
        row_ok = in_fetched & (c * (p * bt) + t_row < lens_ref[b])

        new_heads = []
        for h in range(kv):
            m_prev, l_prev, acc_prev = heads[h]
            k = buf[half, :, 0, h].astype(jnp.float32).reshape(p * bt, d)
            # a slot not fetched holds stale VMEM: zero its V, so that its
            # zero weights cannot meet a NaN there
            if v_dim is None:
                v = buf[half, :, 1, h].astype(jnp.float32)
            else:
                v = buf[half, :, 0, h, :, :v_dim].astype(jnp.float32)
            v = jnp.concatenate([jnp.where(fetch, v[i], 0.0)
                                 for i, fetch in enumerate(fetched)])
            s = jax.lax.dot_general(q[h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(row_ok, s, NEG_INF)              # [REP, P*bt]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.where(row_ok, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(pr, -1, keepdims=True)
            acc_new = acc_prev * alpha + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            new_heads.append((m_new, l_new, acc_new))
        return 1 - half, tuple(new_heads)

    init = tuple((jnp.full((rep, 1), NEG_INF, jnp.float32),
                  jnp.zeros((rep, 1), jnp.float32),
                  jnp.zeros((rep, v_dim or d), jnp.float32))
                 for _ in range(kv))
    half, heads = jax.lax.fori_loop(0, n_chunks, chunk_body,
                                    (buf_idx[0], init))
    buf_idx[0] = half

    # blocks no chunk visited: not read, so not touched
    def zero_chunk(c, _):
        for i in range(p):
            touched_ref[b, c * p + i] = jnp.int32(0)
        return 0
    jax.lax.fori_loop(n_chunks, mb // p, zero_chunk, 0)

    for h in range(kv):
        _, l, acc = heads[h]
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, kv_pages: jax.Array,
                           block_tables: jax.Array, seq_lens: jax.Array,
                           *, scale: float = None, v_dim: int = None,
                           interpret: bool = True):
    """q: [B, KV, REP, D]; kv_pages: [n_slots, 2, KV, bt, D] (K at index
    0, V at 1 of axis 1), or with `v_dim` [n_slots, 1, KV, bt, D] (one
    tile: K, and V in its first v_dim columns); block_tables: [B, MB]
    int32 physical slot ids (-1 unused); seq_lens: [B] int32, at most
    MB * bt. Returns (out [B, KV, REP, v_dim or D], touched [B, MB]
    int32)."""
    b, kv, rep, d = q.shape
    n_slots, two, kv2, bt, d2 = kv_pages.shape
    assert (two, kv, d) == (1 if v_dim else 2, kv2, d2)
    dv = v_dim or d
    mb = block_tables.shape[1]
    p = _chunk_slots(mb, bt)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # block_tables, seq_lens
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kv, rep, d), lambda i, tbl, lens: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, kv, rep, dv), lambda i, tbl, lens: (i, 0, 0, 0)),
            # the access bits are scalars: the whole [B, MB] map stays in
            # SMEM for the kernel's lifetime
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, p, two, kv, bt, d), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kern = functools.partial(
        _kernel, block_tokens=bt, n_blocks=mb, n_chunk_slots=p,
        scale=scale if scale is not None else d ** -0.5, v_dim=v_dim)
    out, touched = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, rep, dv), q.dtype),
            jax.ShapeDtypeStruct((b, mb), jnp.int32),
        ],
        # a lane's step prefetches the next lane's first chunk: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), q,
      kv_pages)
    return out, touched
