"""Object Collector — periodic scan + lock-free migration (paper §4).

Each collect pass, run between application steps (the migration window):

  1. Scan every table word: read access bits; update per-object CIW
     (Consecutive Inactive Windows).
  2. Classify (Fig. 5 state machine):
        accessed & heap in {NEW, COLD}         -> migrate to HOT
        ~accessed & CIW > C_t & heap in {NEW,HOT} -> migrate to COLD
  3. Migrate: an object moves ONLY if its ATC is zero (the paper's
     optimistic lock-free rule — an object observed in active use during
     the armed window is skipped and retried next pass; forward progress
     is never blocked).
  4. Destination slots are taken densely from the start of the target
     region, so HOT stays compact (huge-page-promotable) and COLD
     superblocks become uniformly cold.
  5. MIAD updates C_t from the window's promotion rate; access bits and
     ATCs are cleared; the epoch advances.

Everything is a fixed-shape array program: "no objects to move" is the
all-false mask, so the pass jits once and runs every window.

Execution shape: classification is one table sweep (`classify`, optionally
the Pallas `access_scan` kernel when `CollectorConfig.use_pallas`), and the
two-direction migration is one fused plan — destination slots for HOT and
COLD movers are computed back-to-back on the slot-owner array, then ALL
payload copies execute as a single data movement (the Pallas `migrate`
kernel, or one functional scatter on the jnp oracle path). Hot moves are
ordered before cold moves, which keeps the kernel's sequential-grid
contract: a cold mover may land in a slot a hot mover vacated, but no move
reads a slot an earlier move overwrote.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import freelist as fl
from repro.core import object_table as ot
from repro.core import policy
from repro.core import pool as pl


@dataclasses.dataclass(frozen=True)
class CollectorConfig:
    miad: policy.MiadConfig = dataclasses.field(default_factory=policy.MiadConfig)
    # keep NEW objects in NEW until they show a verdict (paper: NEW heap
    # absorbs fresh allocations; they migrate on first classification)
    promote_new_on_access: bool = True
    # route the table sweep + payload copies through the Pallas kernels
    # (access_scan / migrate); False keeps the pure-jnp oracle path. Both
    # paths are bit-identical (tests/test_engine.py asserts it).
    use_pallas: bool = False
    # max migrations per direction per collect (kswapd-style scan
    # budget): bounds the payload move and ALL per-mover metadata
    # updates to a pool-size-independent constant — movers beyond the
    # budget keep their masks' eligibility and retry next window (the
    # same deferral as a full destination region). 0 = unbounded.
    move_budget: int = 256


def classify(pool_cfg: pl.PoolConfig, col_cfg: CollectorConfig,
             state: Dict) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array]:
    """One sweep over the table: update CIW lanes, emit migration masks
    (Fig. 5 state machine, ATC lock-free rule folded in) and count the
    ATC-vetoed objects. Returns (table_with_new_ciw, to_hot, to_cold,
    skipped_atc) — the full classification comes from ONE table sweep on
    both paths (the Pallas kernel emits skipped_atc itself, so no table
    field is re-read in jnp)."""
    tbl = state["table"]
    if col_cfg.use_pallas:
        from repro.kernels import ops as kops
        # with_hist=False: the carried slot_ref bits already hold the
        # per-slot referenced view (and migrate moves them with the
        # objects), so the kernel's pre-move histogram would be dead work
        new_tbl, to_hot, to_cold, _, skipped = kops.access_scan(
            tbl, state["ciw_threshold"], sb_slots=pool_cfg.sb_slots,
            n_sbs=pool_cfg.n_sbs, with_hist=False)
        if not col_cfg.promote_new_on_access:
            # kernel bakes in NEW-promotes-on-access; mask it back out
            to_hot &= ot.heap_of(tbl) != ot.NEW
        return new_tbl, to_hot, to_cold, skipped

    live = ot.is_live(tbl)
    acc = (ot.access_of(tbl) == 1) & live
    atc = ot.atc_of(tbl)
    heap = ot.heap_of(tbl)
    ct = jnp.floor(state["ciw_threshold"]).astype(jnp.uint32)

    # --- CIW update (accessed -> 0; idle -> +1, saturating) ---
    ciw = ot.ciw_of(tbl)
    ciw = jnp.where(acc, 0, jnp.minimum(ciw + 1, ot.CIW_SAT))
    ciw = jnp.where(live, ciw, 0)

    # --- classification (Fig. 5) ---
    to_hot = acc & ((heap == ot.COLD) |
                    ((heap == ot.NEW) & col_cfg.promote_new_on_access))
    to_cold = (~acc) & (ciw > ct) & ((heap == ot.NEW) | (heap == ot.HOT))
    movable = live & (atc == 0)          # the lock-free rule
    to_hot &= movable
    to_cold &= movable
    skipped = jnp.sum(live & (atc > 0) &
                      (acc | ((ciw > ct) & (heap != ot.COLD)))
                      ).astype(jnp.int32)

    new_tbl = (tbl & ~(ot.CIW_MASK << ot.CIW_SHIFT)) | \
        (ciw.astype(jnp.uint32) << ot.CIW_SHIFT)
    return new_tbl, to_hot, to_cold, skipped


def _select_movers(to_hot: jax.Array, to_cold: jax.Array, m: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Compress the two boolean mover masks [n] into fixed-size object-id
    lists [m] (ascending id, first-m-win — the budget's deferral order)
    with ONE sort over the table: hot movers key as their id, cold movers
    as id+n, everything else sorts past both. Returns
    (ids_hot, ok_hot, ids_cold, ok_cold). O(n log n) elementwise+sort —
    no O(n)-update scatter (the CPU-cost pig) anywhere."""
    n = to_hot.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    key = jnp.where(to_hot, idx, jnp.where(to_cold, idx + n, idx + 2 * n))
    skey = jnp.sort(key)
    n_hot = jnp.sum(to_hot.astype(jnp.int32))
    n_cold = jnp.sum(to_cold.astype(jnp.int32))
    j = jnp.arange(m, dtype=jnp.int32)
    ok_h = j < n_hot
    ids_h = jnp.where(ok_h, skey[jnp.minimum(j, n - 1)], 0)
    ok_c = j < n_cold
    ids_c = jnp.where(ok_c, skey[jnp.clip(n_hot + j, 0, n - 1)] - n, 0)
    return ids_h, ok_h, ids_c, ok_c


def _plan_moves(cfg: pl.PoolConfig, state: Dict, ids_m: jax.Array,
                ok_m: jax.Array, dest_heap: int
                ) -> Tuple[Dict, jax.Array, jax.Array, jax.Array]:
    """Assign destination slots in `dest_heap`'s region to the budgeted
    mover list `ids_m[ok_m]` (movers that find the region full are
    dropped — retried next window). Destinations POP off the region's
    free ring (dense-first as of the last restock) and vacated sources
    PUSH onto their regions' rings, so a later plan can claim slots this
    one vacates — all O(m). Updates metadata only; the payload copy is
    deferred to the fused data mover. Returns (state, src, dst, ok)."""
    tbl = state["table"]
    src = ot.slot_of(tbl[ids_m]).astype(jnp.int32)
    dst, ok_pop, head, count = fl.pop_region(
        cfg, state["free_q"], state["free_head"], state["free_count"],
        dest_heap, ok_m)
    ok = ok_m & ok_pop
    dst = jnp.where(ok, dst, src)

    # slot ownership: clear src, claim dst
    owner = state["slot_owner"] \
        .at[jnp.where(ok, src, cfg.n_slots)].set(-1, mode="drop") \
        .at[jnp.where(ok, dst, cfg.n_slots)].set(ids_m, mode="drop")
    # table word: new slot + heap (flags preserved; cleared later in pass)
    new_words = ot.with_heap(ot.with_slot(tbl[ids_m], dst.astype(jnp.uint32)),
                             dest_heap)
    tbl = tbl.at[jnp.where(ok, ids_m, cfg.max_objects)].set(
        new_words, mode="drop")
    # vacated sources back on their rings; occupancy + referenced bits
    # travel with the objects
    free_q, head, count = fl.push(cfg, state["free_q"], head, count,
                                  src, ok)
    sb_occ = state["sb_occ"] \
        .at[jnp.where(ok, src // cfg.sb_slots, cfg.n_sbs)].add(
            -1, mode="drop") \
        .at[jnp.where(ok, dst // cfg.sb_slots, cfg.n_sbs)].add(
            1, mode="drop")
    ref_src = state["slot_ref"][jnp.clip(src, 0, cfg.n_slots - 1)]
    slot_ref = state["slot_ref"] \
        .at[jnp.where(ok, src, cfg.n_slots)].set(False, mode="drop") \
        .at[jnp.where(ok, dst, cfg.n_slots)].set(ref_src, mode="drop")
    state = dict(state, table=tbl, slot_owner=owner, free_q=free_q,
                 free_head=head, free_count=count, sb_occ=sb_occ,
                 slot_ref=slot_ref)
    return state, src, dst, ok


@jax.named_scope("migrate")
def migrate(cfg: pl.PoolConfig, state: Dict, to_hot: jax.Array,
            to_cold: jax.Array, *, use_pallas: bool = False,
            move_budget: int = 256) -> Tuple[Dict, jax.Array, jax.Array]:
    """Fused two-direction migration: compress the masks to budgeted
    mover lists (one sort), plan HOT then COLD destinations off the free
    rings (so cold movers can claim slots hot movers vacate, same as the
    old sequential passes), then execute every payload copy in ONE data
    movement of 2*budget rows. Returns (state, n_hot, n_cold).

    Work is compute-proportional: besides the classification masks (an
    elementwise table sweep) and the selection sort, every gather/scatter
    here is O(move_budget) — pool size only enters through the closing
    restock. Movers beyond the budget stay eligible and move on a later
    window (the same deferral as a full destination region).

    Safety of the single copy: hot dsts are free HOT-region slots and cold
    dsts are free (possibly just-vacated) COLD-region slots, so all dsts
    are distinct; no cold src is ever a hot dst, so in hot-then-cold order
    no move reads a slot an earlier move wrote — the `migrate` kernel's
    sequential-grid contract, and trivially true for the functional jnp
    scatter (which gathers all sources pre-write).

    Carried allocator state stays consistent: the per-superblock
    occupancy counters and per-slot referenced bits move with the objects
    (src -1 / dst +1), and the free-slot rings are RESTOCKED from the
    post-move slot-owner array in ascending slot order — the
    once-per-window sweep that restores the dense-first allocation bias
    (docs/allocator.md)."""
    m = int(move_budget) or cfg.max_objects
    m = max(1, min(m, cfg.max_objects))
    ids_h, okm_h, ids_c, okm_c = _select_movers(to_hot, to_cold, m)
    state, src_h, dst_h, ok_h = _plan_moves(cfg, state, ids_h, okm_h,
                                            ot.HOT)
    state, src_c, dst_c, ok_c = _plan_moves(cfg, state, ids_c, okm_c,
                                            ot.COLD)
    src = jnp.concatenate([src_h, src_c])
    dst = jnp.concatenate([dst_h, dst_c])
    ok = jnp.concatenate([ok_h, ok_c])
    # masked moves route BOTH ends to the pool's permanent scratch row
    # (index n_slots, all-zero at rest): the kernel copies the scratch row
    # onto itself and the jnp oracle scatters zeros onto it, so the row
    # stays zero and both paths remain bit-identical with no per-pass pad
    # copy of the pool
    if use_pallas:
        from repro.kernels import ops as kops
        data = kops.migrate(state["data"], src, dst, ok,
                            has_scratch_row=True)
    else:
        data = state["data"].at[jnp.where(ok, dst, cfg.n_slots)].set(
            state["data"][jnp.where(ok, src, cfg.n_slots)], mode="drop")
    free_q, free_head, free_count = fl.restock(cfg, state["free_q"],
                                               state["slot_owner"])
    state = dict(state, data=data, free_q=free_q, free_head=free_head,
                 free_count=free_count)
    return state, jnp.sum(ok_h), jnp.sum(ok_c)


@jax.named_scope("collect")
def collect(pool_cfg: pl.PoolConfig, col_cfg: CollectorConfig,
            state: Dict) -> Tuple[Dict, Dict[str, jax.Array]]:
    """One Object Collector pass, under the named scope `collect` (its
    migration under `migrate`). Returns (state, report)."""
    # one table sweep: CIW update + migration masks + ATC-veto diagnostic
    # (the access_scan kernel emits all four on the use_pallas path)
    new_tbl, to_hot, to_cold, skipped_atc = classify(pool_cfg, col_cfg,
                                                     state)
    state = dict(state, table=new_tbl)

    # fused two-direction migration, one data movement
    state, n_hot, n_cold = migrate(pool_cfg, state, to_hot, to_cold,
                                   use_pallas=col_cfg.use_pallas,
                                   move_budget=col_cfg.move_budget)

    # --- MIAD on the window's promotion rate ---
    new_ct, calm, rate, proactive_ok = policy.update(
        col_cfg.miad, state["ciw_threshold"], state["calm_windows"],
        state["win_promos"], state["win_accesses"])

    # --- mark uniformly-cold COLD-region superblocks as MADV_COLD
    #     candidates (frontend -> backend signal) ---
    stats = pl.superblock_stats(pool_cfg, state)
    cold_uniform = (stats["region"] == ot.COLD) & (stats["occupancy"] > 0) \
        & (~stats["referenced"]) & (state["sb_tier"] == pl.HBM)
    sb_evict = jnp.where(cold_uniform & (state["sb_evict"] == pl.NORMAL),
                         pl.CANDIDATE, state["sb_evict"]).astype(jnp.int8)

    # --- clear access bits + ATCs; advance epoch; reset window counters ---
    # (stats above were computed PRE-clear: backends must see the closing
    # window's referenced bits, or kswapd degenerates into the cap; the
    # carried slot_ref bits reset with the access bits they mirror)
    tbl = ot.clear_access_and_atc(state["table"])
    report = {
        "moved_to_hot": n_hot, "moved_to_cold": n_cold,
        "skipped_atc": skipped_atc,
        "promotion_rate": rate, "proactive_ok": proactive_ok,
        "ciw_threshold": new_ct,
        "win_accesses": state["win_accesses"],
        "win_faults": state["win_faults"],
        "sb_stats": dict(stats, evict=sb_evict),
    }
    state = dict(
        state, table=tbl, sb_evict=sb_evict, ciw_threshold=new_ct,
        calm_windows=calm, epoch=state["epoch"] + 1,
        slot_ref=jnp.zeros_like(state["slot_ref"]),
        armed=jnp.zeros((), jnp.bool_),
        win_accesses=jnp.zeros((), jnp.int32),
        win_promos=jnp.zeros((), jnp.int32),
        win_faults=jnp.zeros((), jnp.int32),
        total_moves=state["total_moves"] + (n_hot + n_cold).astype(jnp.int32))
    return state, report


def arm(state: Dict) -> Dict:
    """Arm the migration window: subsequent reads bump ATCs (the epoch-based
    activation of tracking — zero overhead when unarmed, paper §4)."""
    return dict(state, armed=jnp.ones((), jnp.bool_))


def compact_heap(pool_cfg: pl.PoolConfig, state: Dict, heap: int) -> Dict:
    """Repack a region densely (objects to region start, holes to the end).
    Out-of-place permutation — safe under any aliasing. A maintenance
    pass (not on the per-op path), so it rebuilds the carried allocator
    state wholesale: free rings restocked from the compacted owner array,
    occupancy recomputed from scratch."""
    lo, hi = pool_cfg.region(heap)
    owner = state["slot_owner"]
    seg = owner[lo:hi]
    live = seg >= 0
    csum = jnp.cumsum(live.astype(jnp.int32))
    new_rel = jnp.where(live, csum - 1, -1)
    src = jnp.arange(lo, hi, dtype=jnp.int32)
    dst = jnp.where(live, new_rel + lo, pool_cfg.n_slots)

    # dead entries target the scratch row; copy the (all-zero) scratch row
    # onto itself so its invariant survives the scatter
    data = state["data"].at[dst].set(
        state["data"][jnp.where(live, src, pool_cfg.n_slots)], mode="drop")
    new_seg_owner = jnp.full_like(seg, -1).at[
        jnp.where(live, new_rel, hi - lo)].set(seg, mode="drop")
    owner = owner.at[lo:hi].set(new_seg_owner)
    tbl = state["table"].at[jnp.where(live, seg, pool_cfg.max_objects)].set(
        ot.with_slot(state["table"][jnp.maximum(seg, 0)],
                     (new_rel + lo).astype(jnp.uint32)), mode="drop")
    # referenced bits ride the permutation
    seg_ref = state["slot_ref"][lo:hi]
    new_seg_ref = jnp.zeros_like(seg_ref).at[
        jnp.where(live, new_rel, hi - lo)].set(seg_ref, mode="drop")
    slot_ref = state["slot_ref"].at[lo:hi].set(new_seg_ref)
    free_q, free_head, free_count = fl.restock(pool_cfg, state["free_q"],
                                               owner)
    return dict(state, data=data, slot_owner=owner, table=tbl,
                slot_ref=slot_ref, free_q=free_q, free_head=free_head,
                free_count=free_count,
                sb_occ=pl.recompute_sb_occupancy(pool_cfg, owner))
