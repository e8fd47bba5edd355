"""Paged KV cache managed by the HADES frontend.

The representative framework application of the paper (DESIGN.md §3.1):
decode-time KV blocks are *objects* in a HadesPool — each block is
`block_tokens` of K+V for one layer of one sequence. All reads go through
the object table (the dereference); on TPU the Pallas `paged_attention`
kernel records access bits as a by-product of its DMAs (on CPU the jnp
oracle computes the same bits — interpret-mode kernel emulation is
correctness-only, see `attend`), and the Object Collector densifies hot
blocks (recent windows, attention sinks) into HOT superblocks while cold
prefixes drift to COLD and get paged to host.

Logical object id = ((layer * batch) + seq) * max_blocks + block_idx.
Block tables hold LOGICAL ids; physical slots are resolved through the
pool table right before the kernel — which is what makes migration
transparent to the serving loop (the paper's pointer-update guarantee).

Everything here is functional and jit-safe; the serving loop in
runtime/server.py drives (append -> attend_read per layer -> record once a
step -> collect).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import backend as be
from repro.core import collector as col
from repro.core import engine as eng
from repro.core import object_table as ot
from repro.core import pool as pl
from repro.kernels import ops as kops

LANE = 128      # a TPU vreg's lanes: the minor tile of an HBM array


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    num_layers: int
    batch: int
    max_blocks: int          # per (layer, sequence)
    block_tokens: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"
    sb_slots: int = 16       # superblock granularity (blocks per madvise)
    slack: float = 1.5
    # 0: a slot holds K and V as separate tiles. > 0: latent attention's
    # one vector a token (models/mla.py), cached once and read as K, its
    # first v_dim columns as V
    v_dim: int = 0

    @property
    def max_objects(self) -> int:
        return self.num_layers * self.batch * self.max_blocks

    @property
    def slot_shape(self) -> Tuple[int, int, int, int]:
        """One KV block's slot: K/V, kv head, token, head dim. The pool
        data is then the paged-attention kernel's `kv_pages` as it
        stands: its tiled dims are (block_tokens, head_dim). A latent
        slot (`v_dim`) holds one K=V tile, (1, kv heads, bt, `slot_dim`)."""
        return (1 if self.v_dim else 2, self.num_kv_heads,
                self.block_tokens, self.slot_dim)

    @property
    def slot_dim(self) -> int:
        """A tile's last dim: head_dim, or for a latent slot head_dim
        rounded up to the 128-lane tile. HBM pads it so anyway, and the
        kernel's DMA can only move whole tiles of it (576 -> 640); the pad
        columns hold zeros in the pool and in q, so scores are unchanged."""
        if not self.v_dim:
            return self.head_dim
        return -(-self.head_dim // LANE) * LANE

    @property
    def slot_words(self) -> int:
        return math.prod(self.slot_shape)

    def obj_id(self, layer, seq, block):
        return (layer * self.batch + seq) * self.max_blocks + block

    def pool_config(self) -> pl.PoolConfig:
        return pl.make_config(
            self.max_objects, self.slot_words, sb_slots=self.sb_slots,
            page_slots=max(self.sb_slots // 4, 1), slack=self.slack,
            dtype=self.dtype, slot_shape=self.slot_shape)


def init(cfg: KVCacheConfig, backend: Optional[be.Backend] = None,
         active: bool = True) -> Dict:
    """Fresh serving state. Pass the tiering backend so its carried
    state (`pool["bstate"]`) is seeded for the fused collect+backend
    path; omit it only when no backend will run (stateless backends
    tolerate the default empty carry). The pool carry also seeds the
    free-slot rings + occupancy counters (docs/allocator.md), so every
    `append_layer` allocation inside the decode scan is O(batch), and
    the server's jitted programs donate the whole carry (the paged pool
    updates in place across decode windows).

    Lanes carry a per-lane lifecycle (`active` [B] bool + per-lane
    `pos`): inactive lanes never append, allocate, or record accesses —
    their attends run over zero keys and return zeros. `active=False`
    starts every lane empty for a continuous-batching driver that
    admits lanes via `admit_lanes` (Server.serve); the default keeps
    every lane live, the fixed-batch `generate` contract."""
    pool = pl.init(cfg.pool_config())
    if backend is not None:
        pool = dict(pool, bstate=backend.init(cfg.pool_config()))
    return {
        "pool": pool,
        # logical block table: -1 = unallocated
        "block_tables": jnp.full(
            (cfg.num_layers, cfg.batch, cfg.max_blocks), -1, jnp.int32),
        "pos": jnp.zeros((cfg.batch,), jnp.int32),
        "active": jnp.full((cfg.batch,), bool(active), jnp.bool_),
    }


# ---------------------------------------------------------------------------
# append — write this step's k/v for ALL layers at the current position
# ---------------------------------------------------------------------------
def append(cfg: KVCacheConfig, state: Dict, k: jax.Array, v: jax.Array
           ) -> Dict:
    """k/v: [L, B, KV, D] (one new token per sequence). A layer-major
    loop over `append_layer` (ONE capacity-guard/overflow-drop
    implementation — the slot assignment is identical either way) plus
    the step's pos advance. Tokens past cfg.max_blocks capacity are
    DROPPED (never written) — an unguarded write would clamp into a live
    object's slot and corrupt another sequence's KV."""
    for li in range(cfg.num_layers):
        state = append_layer(cfg, state, li, k[li], v[li])
    return advance_pos(state)


@jax.named_scope("kv_append")
def append_layer(cfg: KVCacheConfig, state: Dict, layer, k: jax.Array,
                 v: Optional[jax.Array] = None) -> Dict:
    """k/v: [B, KV, D] — ONE layer's k/v for the current token (a latent
    slot, `cfg.v_dim`, takes the one K=V vector as k and no v), for the
    server's fused per-layer decode transition (qkv -> append -> attend
    with `h` advanced through each layer, which `append` cannot express:
    it needs all layers' k/v up front). `layer` may be a traced index
    (the decode layer scan). Does NOT advance `pos` — the caller calls
    `advance_pos` once per step, after all layers. Slot assignment is
    identical to `append`'s (allocations are layer-major either way);
    tokens past cfg.max_blocks capacity are dropped, like `append`."""
    pcfg = cfg.pool_config()
    pos = state["pos"]                       # [B]
    blk = pos // cfg.block_tokens
    off = pos % cfg.block_tokens
    # capacity guard + lane lifecycle: inactive lanes (no live request
    # on the lane) neither allocate nor write
    fits = (blk < cfg.max_blocks) & state["active"]     # [B]
    b_idx = jnp.arange(cfg.batch)
    obj = ((layer * cfg.batch + b_idx) * cfg.max_blocks + blk
           ).astype(jnp.int32)               # [B]

    need = (off == 0) & fits
    pool = state["pool"]
    zeros = jnp.zeros((cfg.batch, pcfg.slot_words), pool["data"].dtype)
    pool = pl.alloc(pcfg, pool, jnp.where(need, obj, -1), zeros)
    blk_safe = jnp.minimum(blk, cfg.max_blocks - 1)
    bt = state["block_tables"].at[layer, b_idx, blk].set(
        jnp.where(need, obj,
                  state["block_tables"][layer, b_idx, blk_safe]),
        mode="drop")

    words = pool["table"][jnp.minimum(obj, cfg.max_objects - 1)]
    slots = ot.slot_of(words).astype(jnp.int32)         # [B]
    data = pool["data"]                       # [n_slots + 1, 2|1, KV, bt, D]
    # overflow/inactive lanes write ZEROS onto the pool's all-zero
    # scratch row (its invariant holds), never onto a live slot
    slots = jnp.where(fits, slots, pcfg.n_slots)
    kv_tok = jnp.where(fits[:, None, None, None],
                       _pad_to_slot(cfg, k)[:, None] if cfg.v_dim
                       else jnp.stack([k, v], axis=1), 0).astype(data.dtype)
    # one in-place row write per lane: a batched scatter at a token
    # offset inside the tiled (bt, D) dims makes XLA:TPU copy the whole
    # pool into another layout and back around it, in every layer
    for i in range(cfg.batch):
        data = jax.lax.dynamic_update_slice(
            data, kv_tok[i][None, :, :, None, :],
            (slots[i], 0, 0, off[i], 0))
    pool = dict(pool, data=data)
    return dict(state, pool=pool, block_tables=bt)


def advance_pos(state: Dict) -> Dict:
    """One decode step consumed (all layers appended): pos += 1 on
    active lanes; an inactive lane's clock holds at its reset value."""
    return dict(state, pos=state["pos"] + state["active"].astype(jnp.int32))


# ---------------------------------------------------------------------------
# lane lifecycle — continuous batching's finish/refill transitions
# ---------------------------------------------------------------------------
def free_lanes(cfg: KVCacheConfig, state: Dict, lanes: jax.Array) -> Dict:
    """Finish the masked lanes: free ALL their KV objects through the
    pool op stream. lanes: [B] bool.

    The release is ONE batched `pool.free` over every (layer, block)
    object id the lane could own — K = layers * batch * max_blocks ids,
    the O(K) free-ring path (slots push back onto their region's rings,
    `sb_occ` decrements, `slot_ref`/table words clear; no payload, so
    the pool's `data` is not written); ids the lane never allocated are
    dead and dropped by the op, so partially-filled lanes free exactly
    their live blocks. The lane's block-table row resets to -1, its pos
    to 0, and its active bit clears — the freed cold blocks are now the
    fragmentation the collector must tidy so the backend can reclaim
    their superblocks."""
    pcfg = cfg.pool_config()
    li = jnp.arange(cfg.num_layers, dtype=jnp.int32)[:, None, None]
    bi = jnp.arange(cfg.batch, dtype=jnp.int32)[None, :, None]
    ki = jnp.arange(cfg.max_blocks, dtype=jnp.int32)[None, None, :]
    obj = (li * cfg.batch + bi) * cfg.max_blocks + ki   # [L, B, MB]
    ids = jnp.where(lanes[None, :, None], obj, -1).reshape(-1)
    return dict(state,
                pool=pl.free(pcfg, state["pool"], ids),
                block_tables=jnp.where(lanes[None, :, None], -1,
                                       state["block_tables"]),
                pos=jnp.where(lanes, 0, state["pos"]),
                active=state["active"] & ~lanes)


def admit_lanes(state: Dict, lanes: jax.Array) -> Dict:
    """Activate the masked lanes for fresh sequences: pos 0, active set.
    Admit touches no pool state — any previous occupant must already be
    freed (`free_lanes`); a lane may be freed and re-admitted in the
    same window-boundary event."""
    return dict(state,
                pos=jnp.where(lanes, 0, state["pos"]),
                active=state["active"] | lanes)


# ---------------------------------------------------------------------------
# attend — decode attention through the table (Pallas kernel) + tracking
# ---------------------------------------------------------------------------
def attend(cfg: KVCacheConfig, state: Dict, layer: int, q: jax.Array,
           *, seq_lens: Optional[jax.Array] = None,
           use_pallas: Optional[bool] = None,
           scale: Optional[float] = None) -> Tuple[jax.Array, Dict]:
    """q: [B, H, D] -> (out, state with this layer's accesses recorded):
    `attend_read`, then `record` of its one layer. The server's decode
    step instead reads every layer and records once, after its layer
    scans (`runtime/server.py` `_model_step`); the pool comes out the
    same either way (docs/serving.md)."""
    out, touched = attend_read(cfg, state, layer, q, seq_lens=seq_lens,
                               use_pallas=use_pallas, scale=scale)
    step = jnp.zeros((cfg.num_layers, cfg.batch, cfg.max_blocks),
                     jnp.bool_).at[layer].set(touched)
    return out, record(cfg, state, step)


def attend_read(cfg: KVCacheConfig, state: Dict, layer: int, q: jax.Array,
                *, seq_lens: Optional[jax.Array] = None,
                use_pallas: Optional[bool] = None,
                scale: Optional[float] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """q: [B, H, D] -> (out [B, H, D], touched [B, MB] bool); a latent
    slot (`cfg.v_dim`) gives out [B, H, v_dim]. `touched` marks the
    layer's block-table entries the read fetched (live, on active
    lanes); nothing is recorded — `record` takes it. `scale` multiplies
    the scores (default D^-0.5).
    `layer` may be a traced index (the server's decode layer scan).
    `seq_lens` defaults to state["pos"] — correct when the caller has
    already advanced pos past the appended token (`append`); the
    per-layer flow (`append_layer`, pos still pointing AT the new token)
    must pass pos + 1 so the token attends to itself.

    `use_pallas=None` picks the implementation by backend, mirroring the
    collector's CollectorConfig(use_pallas) split: the Pallas kernel
    (with its fused access bits) compiles natively on TPU, while
    CPU runs the pure-jnp oracle — interpret-mode kernel emulation is
    correctness-only and orders of magnitude too slow for the serving
    hot path (tests/test_kernels.py keeps the two bit-compatible on the
    touched bits and within fp tolerance on the outputs). Both read the
    pool data in place as [n_slots + 1, 2|1, KV, bt, D] pages."""
    pool = state["pool"]
    tbl = state["block_tables"][layer]               # [B, MB] logical ids
    live = tbl >= 0
    words = pool["table"][jnp.maximum(tbl, 0)]
    slots = jnp.where(live, ot.slot_of(words).astype(jnp.int32), -1)
    lens = state["pos"] if seq_lens is None else seq_lens
    # inactive lanes attend over zero keys -> zeros out, nothing touched
    lens = jnp.where(state["active"], lens, 0)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if cfg.v_dim:
        q = _pad_to_slot(cfg, q)
        scale = cfg.head_dim ** -0.5 if scale is None else scale

    v_dim = cfg.v_dim or None
    if use_pallas:
        out, touched = kops.paged_attention(q, pool["data"], slots, lens,
                                            scale=scale, v_dim=v_dim)
    else:
        from repro.kernels import ref as kref
        out, touched = kref.paged_attention(q, pool["data"], slots, lens,
                                            scale=scale, v_dim=v_dim)

    # inactive lanes really do return ZEROS: with lens == 0 the Pallas
    # kernel writes zeros, but the oracle's all-masked softmax degenerates
    # to a mean over slot 0's payload (a live neighbor's KV) — mask it out
    # rather than leak it
    out = jnp.where(state["active"][:, None, None], out, 0)
    return out, touched & live & state["active"][:, None]


def _pad_to_slot(cfg: KVCacheConfig, x: jax.Array) -> jax.Array:
    """[..., head_dim] -> [..., slot_dim], zeros in the pad columns."""
    pad = cfg.slot_dim - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


@jax.named_scope("record")
def record(cfg: KVCacheConfig, state: Dict, touched: jax.Array) -> Dict:
    """pool.read's accounting without the data gather (the kernel already
    did the reads): access bits, ATC when armed, promo/fault counters.
    touched: [L, B, MB] bool, the `attend_read` masks of a decode step's
    layers.

    Dense over the object table: a block-table entry is -1 or its own
    flat index (`obj_id`), so the stacked masks are already the table's
    hit mask, and each object is hit at most once. Recording a step's
    layers at once gives the pool that recording each layer after its
    read gives: nothing between two layers' reads of a step reads or
    writes the accounting of another layer's objects."""
    pcfg = cfg.pool_config()
    pool = state["pool"]
    table = pool["table"]
    hit = touched.reshape(-1) & ot.is_live(table)
    # the hit objects' slots: one scatter of the step's ids into a fresh
    # mask (a gather of `hit` through `slot_owner` gives the same bits,
    # at twice the device time on a v5e)
    slot_hit = jnp.zeros((pcfg.n_slots,), jnp.bool_).at[
        jnp.where(hit, ot.slot_of(table).astype(jnp.int32), pcfg.n_slots)
    ].set(True, mode="drop")
    fault = slot_hit.reshape(pcfg.n_sbs, pcfg.sb_slots).any(1) & \
        (pool["sb_tier"] == pl.HOST)
    n_faults = jnp.sum(fault).astype(jnp.int32)
    promos = jnp.sum(hit & (ot.heap_of(table) == ot.COLD)).astype(jnp.int32)
    pool = dict(
        pool, table=ot.mark_access(table, hit, armed=pool["armed"]),
        slot_ref=pool["slot_ref"] | slot_hit,
        sb_tier=jnp.where(fault, pl.HBM, pool["sb_tier"]).astype(jnp.int8),
        sb_evict=jnp.where(fault, pl.NORMAL,
                           pool["sb_evict"]).astype(jnp.int8),
        win_accesses=pool["win_accesses"] + jnp.sum(hit),
        win_promos=pool["win_promos"] + promos,
        win_faults=pool["win_faults"] + n_faults,
        total_faults=pool["total_faults"] + n_faults)
    return dict(state, pool=pool)


# ---------------------------------------------------------------------------
# collect — run the Object Collector + backend over the KV pool
# ---------------------------------------------------------------------------
def collect(cfg: KVCacheConfig, state: Dict,
            col_cfg: Optional[col.CollectorConfig] = None
            ) -> Tuple[Dict, Dict]:
    pcfg = cfg.pool_config()
    pool, report = col.collect(pcfg, col_cfg or col.CollectorConfig(),
                               state["pool"])
    return dict(state, pool=pool), report


def collect_and_backend(cfg: KVCacheConfig, col_cfg: col.CollectorConfig,
                        backend: be.Backend, state: Dict
                        ) -> Tuple[Dict, Dict]:
    """Collector + backend over the KV pool as ONE fused transition (the
    engine's serving-window path) — replaces the old collect-dispatch /
    stats-pop / backend-dispatch sequence in the server loop. The
    backend's carried state rides `state["pool"]["bstate"]` through the
    decode-window scan (seed it via `init(cfg, backend=...)`)."""
    pool, report = eng.collect_and_backend(cfg.pool_config(), col_cfg,
                                           backend, state["pool"])
    return dict(state, pool=pool), report


def arm(state: Dict) -> Dict:
    return dict(state, pool=col.arm(state["pool"]))


def kv_bytes(cfg: KVCacheConfig) -> int:
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return cfg.max_objects * cfg.slot_words * itemsize
