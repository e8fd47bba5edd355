"""HadesPool — the managed object heap (fixed-size objects, jit-native).

This is the framework-facing realization of the paper's custom allocator +
three-heap layout (Fig. 5). One pool manages `max_objects` logical objects,
each occupying exactly one physical slot of `slot_words` elements (KV blocks,
embedding rows and expert slabs are all fixed-size objects, so the
fixed-slot restriction costs nothing in the framework; the byte-granular
CrestKV simulator in `core/simheap.py` handles variable-size objects for the
paper's YCSB evaluation).

Address-space layout (slot indices):

    [0 .............. new_end) NEW   heap  — fresh allocations
    [new_end ........ hot_end) HOT   heap  — dense, "huge-page" region
    [hot_end ........ n_slots) COLD  heap  — uniform-cold, reclaim target

Regions are superblock-aligned; a superblock (`sb_slots` contiguous slots)
is the reclamation/hugepage unit — the "page" that backends manage. The
entire pool state is a pytree of arrays, so every operation jits and shards.

Tier/fault model (CPU-runnable stand-in for HBM/host tiers; on a real TPU
the demotion would be a device_put to `memory_kind="pinned_host"`):
  sb_tier:  0 = HBM, 1 = HOST (paged out)
  sb_evict: 0 = NORMAL, 1 = CANDIDATE (MADV_COLD), 2 = PAGED_OUT (PAGEOUT)
Reading a slot whose superblock is HOST-resident is a *page fault*: the
superblock is promoted back to HBM and the fault counter increments — the
signal the MIAD policy keeps below its target.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import freelist as fl
from repro.core import object_table as ot

# tiers / evict states
HBM, HOST = 0, 1
NORMAL, CANDIDATE, PAGED_OUT = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    """Static geometry (hashable; closed over by jitted fns)."""
    max_objects: int
    slot_words: int            # elements per object slot
    sb_slots: int              # slots per superblock (reclamation unit)
    page_slots: int            # slots per 4-KiB-analog page (metric unit)
    new_sbs: int               # superblocks in the NEW region
    hot_sbs: int               # superblocks in the HOT region
    cold_sbs: int              # superblocks in the COLD region
    dtype: str = "float32"
    word_bytes: int = 4
    # shape of one slot's row in `data` (its product is slot_words); ()
    # keeps the flat [n_slots + 1, slot_words] heap. A client whose
    # kernels read slots in place gives the tiled shape it reads them
    # in (the paged KV pool: [2, KV, block_tokens, head_dim])
    slot_shape: Tuple[int, ...] = ()

    @property
    def row_shape(self) -> Tuple[int, ...]:
        return self.slot_shape or (self.slot_words,)

    @property
    def n_sbs(self) -> int:
        return self.new_sbs + self.hot_sbs + self.cold_sbs

    @property
    def n_slots(self) -> int:
        return self.n_sbs * self.sb_slots

    @property
    def sb_bytes(self) -> int:
        return self.sb_slots * self.slot_words * self.word_bytes

    @property
    def slot_bytes(self) -> int:
        return self.slot_words * self.word_bytes

    def region(self, heap: int) -> Tuple[int, int]:
        """[start, end) slot range of a heap region."""
        new_end = self.new_sbs * self.sb_slots
        hot_end = new_end + self.hot_sbs * self.sb_slots
        if heap == ot.NEW:
            return 0, new_end
        if heap == ot.HOT:
            return new_end, hot_end
        if heap == ot.COLD:
            return hot_end, self.n_slots
        raise ValueError(heap)

    def sb_region_ids(self) -> jnp.ndarray:
        """Per-superblock heap-region id [n_sbs]."""
        return jnp.concatenate([
            jnp.full((self.new_sbs,), ot.NEW, jnp.int8),
            jnp.full((self.hot_sbs,), ot.HOT, jnp.int8),
            jnp.full((self.cold_sbs,), ot.COLD, jnp.int8)])


def make_config(max_objects: int, slot_words: int, *, sb_slots: int = 64,
                page_slots: int = 8, new_frac: float = 0.125,
                hot_frac: float = 0.375, slack: float = 1.5,
                dtype: str = "float32",
                slot_shape: Tuple[int, ...] = ()) -> PoolConfig:
    """Size a pool with `slack`x physical slots over max_objects, split into
    NEW/HOT/COLD regions by fraction."""
    n_slots = int(max_objects * slack)
    n_sbs = max(3, -(-n_slots // sb_slots))
    new_sbs = max(1, int(n_sbs * new_frac))
    hot_sbs = max(1, int(n_sbs * hot_frac))
    cold_sbs = max(1, n_sbs - new_sbs - hot_sbs)
    word_bytes = jnp.dtype(dtype).itemsize
    if slot_shape and math.prod(slot_shape) != slot_words:
        raise ValueError(f"slot_shape {slot_shape} does not hold "
                         f"{slot_words} words")
    return PoolConfig(max_objects=max_objects, slot_words=slot_words,
                      sb_slots=sb_slots, page_slots=page_slots,
                      new_sbs=new_sbs, hot_sbs=hot_sbs, cold_sbs=cold_sbs,
                      dtype=dtype, word_bytes=word_bytes,
                      slot_shape=tuple(slot_shape))


def init(cfg: PoolConfig) -> Dict[str, jax.Array]:
    """Fresh pool state (a pytree dict — shardable, checkpointable).

    The data array carries ONE extra row (index `n_slots`) — a permanent
    scratch row for the migrate kernel's masked moves, so the collector
    never pays a whole-pool pad copy to append one per pass. Invariant:
    the scratch row is all-zero at rest. Every masked/dead scatter that
    targets index `n_slots` must therefore write zeros (or copy the
    scratch row onto itself), keeping the jnp oracle and the Pallas mover
    bit-identical including the scratch bytes.

    Allocator/occupancy state is CARRIED (docs/allocator.md): the
    per-region free-slot rings (`free_q`/`free_head`/`free_count`,
    core/freelist.py) make alloc/free O(K) in the batch size, and
    `sb_occ` tracks per-superblock live-slot counts incrementally
    (alloc +1 / free -1 / migrate +-1), so the RSS/host gauges and
    `superblock_stats` read O(n_sbs) counters instead of re-scanning
    all slots."""
    free_q, free_head, free_count = fl.seed(cfg)
    return {
        "data": jnp.zeros((cfg.n_slots + 1,) + cfg.row_shape,
                          jnp.dtype(cfg.dtype)),
        "table": ot.make_table(cfg.max_objects),
        "slot_owner": jnp.full((cfg.n_slots,), -1, jnp.int32),
        # carried free-slot rings (core/freelist.py): O(K) alloc/free,
        # restocked dense-first by the collector each window
        "free_q": free_q,
        "free_head": free_head,
        "free_count": free_count,
        # carried per-superblock live-slot counts (incremental)
        "sb_occ": jnp.zeros((cfg.n_sbs,), jnp.int32),
        # carried per-slot referenced bits: set at access time (O(K)),
        # moved with migrations, zeroed each collect — makes the
        # backend's per-superblock `referenced` stats an elementwise
        # reshape instead of an O(n_slots) gather+scatter per window
        "slot_ref": jnp.zeros((cfg.n_slots,), jnp.bool_),
        "sb_tier": jnp.zeros((cfg.n_sbs,), jnp.int8),
        "sb_evict": jnp.zeros((cfg.n_sbs,), jnp.int8),
        # MIAD-controlled demotion threshold C_t (float for mult. updates)
        "ciw_threshold": jnp.asarray(3.0, jnp.float32),
        # escalation gate: consecutive windows with promotion rate < target
        "calm_windows": jnp.zeros((), jnp.int32),
        "epoch": jnp.zeros((), jnp.int32),
        "armed": jnp.zeros((), jnp.bool_),   # migration window armed (ATC on)
        # window counters (reset each collect)
        "win_accesses": jnp.zeros((), jnp.int32),
        "win_promos": jnp.zeros((), jnp.int32),   # COLD-heap hits
        "win_faults": jnp.zeros((), jnp.int32),   # HOST-tier page faults
        # lifetime counters
        "total_faults": jnp.zeros((), jnp.int32),
        "total_moves": jnp.zeros((), jnp.int32),
        # tiering-backend carried state (backend.Backend protocol). Empty
        # for stateless backends; Engine.init / kvcache.init replace it
        # with backend.init(cfg) so stateful backends (mglru, promote)
        # ride the fused-window scan carry. Every pool op passes it
        # through untouched.
        "bstate": {},
    }


# ---------------------------------------------------------------------------
# Pool ops — ONE mask-parameterized transition (O(K) per op)
# ---------------------------------------------------------------------------
# op codes (also the engine's batched-trace encoding)
OP_READ, OP_WRITE, OP_ALLOC, OP_FREE = 0, 1, 2, 3


def heap_of_slot(cfg: PoolConfig, slot: jax.Array) -> jax.Array:
    """Region id a physical slot belongs to (static boundaries)."""
    return fl.region_of_slot(cfg, slot).astype(jnp.uint32)


def apply_op(cfg: PoolConfig, state: Dict, op, obj_ids: jax.Array,
             values: jax.Array) -> Tuple[Dict, jax.Array]:
    """All four pool ops as ONE op-code-parameterized transition.
    `op` may be a TRACED scalar (the engine's batched traces) or a python
    constant (the per-op wrappers below — XLA folds the masks and
    recovers each op's minimal program). Returns (state, read_vals [k,W];
    zeros for non-read ops and dead/padding lanes).

    Why not `lax.switch` over four per-op branches: branches that update
    different subsets of the state pytree break XLA's in-place aliasing
    of the surrounding scan carry, which silently re-copies the heap
    (`data`, O(n_slots)) EVERY step. As a single branch-free program,
    every update is a K-sized scatter on the same buffers — masked-off
    lanes route to drop indices — so per-op cost is O(K) in the batch
    size and independent of pool size (docs/allocator.md).

    Op semantics (ids < 0 are padding everywhere):
      read   gather payloads; access bit + ATC-when-armed; COLD-hit
             promotion count; fault-in HOST superblocks
      write  scatter payloads to live ids (a store is also an access)
      alloc  claim a slot per dead id — NEW heap first, spilling COLD
             then HOT off the carried free rings (`freelist.pop`); live
             ids are re-written in place (update semantics); a
             duplicated id claims ONE slot (first occurrence wins)
      free   release live ids: slot pushed on its region's free ring
             (tail; dense-first order returns at the next restock),
             occupancy -1; duplicates in one batch free once"""
    op = jnp.asarray(op, jnp.int32)
    is_read, is_write = op == OP_READ, op == OP_WRITE
    is_alloc, is_free = op == OP_ALLOC, op == OP_FREE

    valid = obj_ids >= 0
    ids = jnp.maximum(obj_ids, 0)
    words = state["table"][ids]
    live = ot.is_live(words) & valid
    first = fl.first_occurrence(obj_ids)
    slots = ot.slot_of(words).astype(jnp.int32)

    # Ordering rule for every carried buffer below: SCATTER BEFORE
    # GATHER. A gather followed by a scatter on the same scan-carried
    # array makes XLA's copy-insertion preserve the pre-scatter view by
    # copying the whole buffer every step (O(n_slots) for `data`);
    # scatter-then-gather aliases in place. Each op kind uses only one
    # side (reads never scatter data, allocs/frees never gather it), so
    # the reordering is semantically free.

    # --- free: push released slots (mask is empty otherwise) ---
    f_mask = is_free & live & first
    free_q, free_head, free_count = fl.push(
        cfg, state["free_q"], state["free_head"], state["free_count"],
        slots, f_mask)

    # --- alloc: pop fresh slots off the rings (need is empty otherwise;
    # an op is either alloc or free, so push/pop order is immaterial) ---
    need = is_alloc & (~live) & valid & first
    new_slot, ok_new, free_head, free_count = fl.pop(
        cfg, free_q, free_head, free_count, need)
    a_do = (is_alloc & live) | ok_new        # lanes an alloc writes
    a_slot = jnp.where(ok_new, new_slot, slots)

    # --- data: one scatter serves write + alloc (dead/padding lanes
    # route to the scratch row and must write ZEROS — its invariant) ---
    d_mask = (is_write & live) | a_do
    d_slot = jnp.where(is_alloc, a_slot, slots)
    k = obj_ids.shape[0]
    rows = values.astype(state["data"].dtype).reshape((k,) + cfg.row_shape)
    row_mask = d_mask.reshape((k,) + (1,) * len(cfg.row_shape))
    data = state["data"].at[jnp.where(d_mask, d_slot, cfg.n_slots)].set(
        jnp.where(row_mask, rows, 0), mode="drop")

    # --- read output: gathered AFTER the (empty-on-read) scatter ---
    vals = jnp.where((is_read & live)[:, None],
                     data[slots].reshape(k, cfg.slot_words), 0)

    # --- table: dereference access bits (+ATC when armed), alloc words,
    # free words. The alloc/free rewrites go through fresh K-scattered
    # mask/value arrays + an elementwise select (same no-gather-then-
    # scatter rule; record_access does likewise internally) ---
    rw_live = (is_read | is_write) & live
    tbl = ot.record_access(state["table"],
                           jnp.where(rw_live, obj_ids, -1),
                           armed=state["armed"])
    alloc_words = jnp.where(
        ok_new, ot.pack(a_slot.astype(jnp.uint32),
                        heap_of_slot(cfg, a_slot), access=1),
        # alloc of a live id: in-place update, set the access bit
        words | (ot.ACCESS_MASK << ot.ACCESS_SHIFT))
    a_dst = jnp.where(a_do, ids, cfg.max_objects)
    hit_a = jnp.zeros((cfg.max_objects,), jnp.bool_).at[a_dst].set(
        True, mode="drop")
    word_a = jnp.zeros((cfg.max_objects,), jnp.uint32).at[a_dst].set(
        alloc_words, mode="drop")
    hit_f = jnp.zeros((cfg.max_objects,), jnp.bool_).at[
        jnp.where(f_mask, ids, cfg.max_objects)].set(True, mode="drop")
    tbl = jnp.where(hit_f, ot.free_word(),
                    jnp.where(hit_a, word_a, tbl))

    # --- slot ownership + carried occupancy/referenced ---
    owner = state["slot_owner"] \
        .at[jnp.where(ok_new, a_slot, cfg.n_slots)].set(
            jnp.where(ok_new, obj_ids, -1), mode="drop") \
        .at[jnp.where(f_mask, slots, cfg.n_slots)].set(-1, mode="drop")
    sb_occ = state["sb_occ"] \
        .at[jnp.where(ok_new, a_slot // cfg.sb_slots, cfg.n_sbs)].add(
            1, mode="drop") \
        .at[jnp.where(f_mask, slots // cfg.sb_slots, cfg.n_sbs)].add(
            -1, mode="drop")
    touch = rw_live | a_do
    slot_ref = state["slot_ref"] \
        .at[jnp.where(touch, jnp.where(is_alloc, a_slot, slots),
                      cfg.n_slots)].set(True, mode="drop") \
        .at[jnp.where(f_mask, slots, cfg.n_slots)].set(False, mode="drop")

    # --- fault accounting (reads fault HOST superblocks back in) ---
    sbs = slots // cfg.sb_slots
    on_host = is_read & live & (state["sb_tier"][sbs] == HOST)
    fault_mask = jnp.zeros((cfg.n_sbs,), jnp.bool_).at[
        jnp.where(on_host, sbs, cfg.n_sbs)].set(True, mode="drop")
    n_faults = jnp.sum(fault_mask).astype(jnp.int32)
    sb_tier = jnp.where(fault_mask, HBM, state["sb_tier"]).astype(jnp.int8)
    sb_evict = jnp.where(fault_mask, NORMAL,
                         state["sb_evict"]).astype(jnp.int8)

    # --- window counters (free ticks no counters; the op clock lives in
    # the engine) ---
    accs = jnp.sum(rw_live) + jnp.sum(a_do)
    promos = jnp.sum(rw_live & (ot.heap_of(words) == ot.COLD)
                     ).astype(jnp.int32)
    state = dict(state, data=data, table=tbl, slot_owner=owner,
                 free_q=free_q, free_head=free_head,
                 free_count=free_count, sb_occ=sb_occ, slot_ref=slot_ref,
                 sb_tier=sb_tier, sb_evict=sb_evict,
                 win_accesses=state["win_accesses"] + accs,
                 win_promos=state["win_promos"] + promos,
                 win_faults=state["win_faults"] + n_faults,
                 total_faults=state["total_faults"] + n_faults)
    return state, vals


def _zero_values(cfg: PoolConfig, obj_ids: jax.Array) -> jax.Array:
    return jnp.zeros((obj_ids.shape[0], cfg.slot_words),
                     jnp.dtype(cfg.dtype))


def alloc(cfg: PoolConfig, state: Dict, obj_ids: jax.Array,
          values: jax.Array) -> Dict:
    """Allocate `obj_ids` [k] (see `apply_op`: NEW->COLD->HOT spill off
    the carried rings, O(k), first-occurrence-wins on duplicates)."""
    state, _ = apply_op(cfg, state, OP_ALLOC, obj_ids, values)
    return state


def read(cfg: PoolConfig, state: Dict, obj_ids: jax.Array
         ) -> Tuple[jax.Array, Dict]:
    """Gather object payloads for `obj_ids` [k] (−1 entries return zeros).
    This is the paper's pointer dereference — see `apply_op`."""
    state, vals = apply_op(cfg, state, OP_READ, obj_ids,
                           _zero_values(cfg, obj_ids))
    return vals, state


def write(cfg: PoolConfig, state: Dict, obj_ids: jax.Array,
          values: jax.Array) -> Dict:
    """Scatter payloads to live objects (a store is also an access)."""
    state, _ = apply_op(cfg, state, OP_WRITE, obj_ids, values)
    return state


def free(cfg: PoolConfig, state: Dict, obj_ids: jax.Array) -> Dict:
    """Release objects (slot returns to its region's free ring) — see
    `apply_op`."""
    state, _ = apply_op(cfg, state, OP_FREE, obj_ids,
                        _zero_values(cfg, obj_ids))
    return state


# ---------------------------------------------------------------------------
# Superblock summaries (the ONLY view backends get — object-oblivious)
# ---------------------------------------------------------------------------
def sb_occupancy(cfg: PoolConfig, state: Dict) -> jax.Array:
    """Per-superblock live-slot count [n_sbs] — the CARRIED `sb_occ`
    counters (alloc +1 / free -1 / migrate +-1), an O(n_sbs) read with no
    scatter-add over all slots. `recompute_sb_occupancy` is the O(n_slots)
    oracle (tests assert the carry never drifts)."""
    return state["sb_occ"]


def recompute_sb_occupancy(cfg: PoolConfig,
                           slot_owner: jax.Array) -> jax.Array:
    """O(n_slots) occupancy from the slot-owner array — the consistency
    oracle for the carried counters, and the rebuild used by maintenance
    passes that rewrite whole regions (`collector.compact_heap`)."""
    live_slot = slot_owner >= 0
    sb_of_slot = jnp.arange(cfg.n_slots) // cfg.sb_slots
    return jnp.zeros((cfg.n_sbs,), jnp.int32).at[sb_of_slot].add(
        live_slot.astype(jnp.int32))


def superblock_stats(cfg: PoolConfig, state: Dict) -> Dict[str, jax.Array]:
    """Per-superblock: occupancy, referenced (any access bit within),
    region id, tier, evict state. This is the page-table-level view the
    paper's unmodified backends consume. Both expensive columns are
    carried (occupancy counters + per-slot referenced bits), so the view
    is O(n_sbs) reads + one elementwise reshape — no per-window
    gather/scatter over all slots."""
    ref = state["slot_ref"].reshape(cfg.n_sbs, cfg.sb_slots).any(axis=1)
    return {"occupancy": sb_occupancy(cfg, state), "referenced": ref,
            "region": cfg.sb_region_ids(),
            "tier": state["sb_tier"], "evict": state["sb_evict"]}


def rss_bytes(cfg: PoolConfig, state: Dict) -> jax.Array:
    """Resident (HBM-tier) bytes: occupied superblocks still in HBM."""
    occ = sb_occupancy(cfg, state)
    resident = (occ > 0) & (state["sb_tier"] == HBM)
    return jnp.sum(resident).astype(jnp.float32) * float(cfg.sb_bytes)


def host_bytes(cfg: PoolConfig, state: Dict) -> jax.Array:
    occ = sb_occupancy(cfg, state)
    out = (occ > 0) & (state["sb_tier"] == HOST)
    return jnp.sum(out).astype(jnp.float32) * float(cfg.sb_bytes)
