"""Object table — the JAX analog of HADES' tagged pointers ("guides").

Each managed object has one packed uint32 word. The paper packs metadata into
unused high-order bits of the 64-bit pointer; here all access flows through an
explicit logical-id -> physical-slot indirection, so the metadata lives in the
indirection word itself:

    [ ciw:5 | atc:4 | access:1 | heap:2 | slot:20 ]   (MSB..LSB)

  slot   — physical slot index in the pool (up to 2^20 slots)
  heap   — NEW(0) / HOT(1) / COLD(2) / FREE(3)
  access — access bit, set on dereference (idempotent scatter-or)
  atc    — Active Thread Count analog: saturating counter of accesses while a
           migration window is armed; an object with atc > 0 is never moved
           (the paper's optimistic lock-free rule)
  ciw    — Consecutive Inactive Windows, saturating at 31

All ops are vectorized over uint32 arrays and jit-safe.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SLOT_BITS = 20
HEAP_BITS = 2
ACCESS_BITS = 1
ATC_BITS = 4
CIW_BITS = 5
assert SLOT_BITS + HEAP_BITS + ACCESS_BITS + ATC_BITS + CIW_BITS == 32

SLOT_SHIFT = 0
HEAP_SHIFT = SLOT_BITS
ACCESS_SHIFT = HEAP_SHIFT + HEAP_BITS
ATC_SHIFT = ACCESS_SHIFT + ACCESS_BITS
CIW_SHIFT = ATC_SHIFT + ATC_BITS

SLOT_MASK = jnp.uint32((1 << SLOT_BITS) - 1)
HEAP_MASK = jnp.uint32((1 << HEAP_BITS) - 1)
ACCESS_MASK = jnp.uint32(1)
ATC_MASK = jnp.uint32((1 << ATC_BITS) - 1)
CIW_MASK = jnp.uint32((1 << CIW_BITS) - 1)

MAX_SLOTS = 1 << SLOT_BITS
CIW_SAT = (1 << CIW_BITS) - 1
ATC_SAT = (1 << ATC_BITS) - 1

# heap ids
NEW, HOT, COLD, FREE = 0, 1, 2, 3


def pack(slot, heap, access=0, atc=0, ciw=0) -> jax.Array:
    """Pack fields -> uint32 word(s)."""
    slot = jnp.asarray(slot, jnp.uint32)
    heap = jnp.asarray(heap, jnp.uint32)
    access = jnp.asarray(access, jnp.uint32)
    atc = jnp.asarray(atc, jnp.uint32)
    ciw = jnp.asarray(ciw, jnp.uint32)
    return ((slot & SLOT_MASK)
            | ((heap & HEAP_MASK) << HEAP_SHIFT)
            | ((access & ACCESS_MASK) << ACCESS_SHIFT)
            | ((atc & ATC_MASK) << ATC_SHIFT)
            | ((ciw & CIW_MASK) << CIW_SHIFT))


def slot_of(w): return (w >> SLOT_SHIFT) & SLOT_MASK
def heap_of(w): return (w >> HEAP_SHIFT) & HEAP_MASK
def access_of(w): return (w >> ACCESS_SHIFT) & ACCESS_MASK
def atc_of(w): return (w >> ATC_SHIFT) & ATC_MASK
def ciw_of(w): return (w >> CIW_SHIFT) & CIW_MASK


def with_slot(w, slot):
    return (w & ~SLOT_MASK) | (jnp.asarray(slot, jnp.uint32) & SLOT_MASK)


def with_heap(w, heap):
    return (w & ~(HEAP_MASK << HEAP_SHIFT)) | \
        ((jnp.asarray(heap, jnp.uint32) & HEAP_MASK) << HEAP_SHIFT)


def with_access(w, access):
    return (w & ~(ACCESS_MASK << ACCESS_SHIFT)) | \
        ((jnp.asarray(access, jnp.uint32) & ACCESS_MASK) << ACCESS_SHIFT)


def with_atc(w, atc):
    return (w & ~(ATC_MASK << ATC_SHIFT)) | \
        ((jnp.asarray(atc, jnp.uint32) & ATC_MASK) << ATC_SHIFT)


def with_ciw(w, ciw):
    return (w & ~(CIW_MASK << CIW_SHIFT)) | \
        ((jnp.asarray(ciw, jnp.uint32) & CIW_MASK) << CIW_SHIFT)


def free_word() -> jax.Array:
    """A table word denoting 'no object' (heap=FREE, slot=0)."""
    return pack(0, FREE)


def make_table(num_objects: int) -> jax.Array:
    return jnp.full((num_objects,), free_word(), jnp.uint32)


def is_live(w) -> jax.Array:
    return heap_of(w) != FREE


def record_access(table: jax.Array, obj_ids: jax.Array,
                  armed: bool | jax.Array = False) -> jax.Array:
    """Set access bits for obj_ids (idempotent — the paper skips the
    store when already set). When a migration window is `armed`, also
    bump the saturating ATC — the scope-guard analog. Invalid ids (< 0)
    are dropped (NOT redirected to id 0 with a no-op update: a batch
    holding both a padding entry and a real access to object 0 would
    otherwise write conflicting words to index 0).

    Shape of the update: one K-sized scatter into a FRESH boolean hit
    mask, then an elementwise rewrite of the table. Scattering into the
    table directly would read-and-write a scan-carried buffer in one
    step, which defeats XLA's in-place aliasing of the carry (the whole
    table gets copied every step); the armed branch is folded in as a
    mask instead of a `lax.cond` for the same reason. Duplicate ids bump
    the ATC once per batch, exactly like the old scatter-max."""
    n = table.shape[0]
    dst = jnp.where(obj_ids >= 0, obj_ids, n)
    hit = jnp.zeros((n,), jnp.bool_).at[dst].set(True, mode="drop")
    return mark_access(table, hit, armed)


def mark_access(table: jax.Array, hit: jax.Array,
                armed: bool | jax.Array = False) -> jax.Array:
    """`record_access` given its hit mask: hit [n] bool, one entry per
    table word. Elementwise: the access bit of every hit word, and its
    saturating ATC bump when `armed`."""
    word = table | (ACCESS_MASK << ACCESS_SHIFT)
    bump = hit & jnp.asarray(armed).astype(bool)
    word = jnp.where(bump, with_atc(word, jnp.minimum(atc_of(word) + 1,
                                                      ATC_SAT)), word)
    return jnp.where(hit, word, table)


def clear_access_and_atc(table: jax.Array) -> jax.Array:
    mask = ~((ACCESS_MASK << ACCESS_SHIFT) | (ATC_MASK << ATC_SHIFT))
    return table & mask
