"""Blocked KV cache structures.

* ``KVCache`` — the full cache: [L, B, S_max, Hk, Dh] with per-sequence
  lengths.  S_max is a multiple of the SpecPV block size so the cache is
  implicitly paged at block granularity (vLLM-style, but 128-token blocks
  for TPU tiling).
* ``BlockSummaries`` — per-block elementwise key max/min (paper eq. (1)),
  maintained for the full cache and used for Quest-style retrieval.
* ``PartialKV`` — the *materialised* partial cache (sink + retrieval +
  local + buffer), per layer and per kv-head (retrieval is query-aware per
  head).  Token order is preserved; the buffer occupies the tail slots.

Paged variant (``page_table`` key present in the cache dict):

* the full cache is a *shared block pool* ``k/v: [L, NumPages, block,
  Hk*Dh]`` with per-slot page tables ``[B, S_max/block] int32`` mapping
  logical blocks to physical pages, so resident memory scales with the
  tokens actually held, not ``B x S_max``.  The pool is stored
  lane-dense — kv head ``h`` owns columns ``h*Dh:(h+1)*Dh`` — because
  that is the layout the Pallas attention kernels stream (``(block,
  Dh)`` tiles, ``kernels/sparse_attention.py``): they read the stored
  stack in place, and only the helpers below that touch a few pages
  split the heads out;
* summaries are keyed by *physical* page: ``kmax/kmin: [L, NumPages, Hk,
  Dh]``;
* page 0 is the reserved null page — unallocated table entries point at
  it, stray writes are routed into it, and it is never read unmasked;
* page ownership lives host-side in ``PageAllocator`` and is
  *refcounted*: several slots (and the host-side ``PrefixCache``) may
  reference one physical page, writes into shared pages go through
  copy-on-write, and a page is reclaimed only when its last reference
  drops; the device only ever sees the tables;
* the draft cache can be paged the same way over a second, smaller pool
  (single draft layer): ``k/v: [NumPagesD, block, Hk, Dh]`` + per-slot
  tables, so draft residency also scales with live tokens.

The full subsystem — ownership rules, copy-on-write, prefix-cache
hashing/LRU, and the high-water accounting — is documented in
docs/paged_kv.md, whose symbol references CI checks against this file
(tools/check_docs.py).
"""
from __future__ import annotations

import bisect
import hashlib
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SpecPVConfig
from repro.utils import pytree_dataclass, cdiv


@pytree_dataclass
class KVCache:
    k: jax.Array        # [L, B, S_max, Hk, Dh]
    v: jax.Array        # [L, B, S_max, Hk, Dh]
    length: jax.Array   # [B] int32 — tokens currently resident

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]


def init_kv_cache(num_layers: int, batch: int, max_len: int, num_kv_heads: int,
                  head_dim: int, dtype) -> KVCache:
    shape = (num_layers, batch, max_len, num_kv_heads, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((batch,), jnp.int32))


def append_layer_kv(k_layer, v_layer, new_k, new_v, length):
    """Write new tokens into one layer's cache at per-sequence offsets.

    k_layer: [B, S, Hk, Dh]; new_k: [B, T, Hk, Dh]; length: [B].
    Returns updated (k_layer, v_layer).  (Length bookkeeping is external —
    verification may keep only a prefix of what was written.)
    """
    def upd(buf, new, off):
        return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                            (off, 0, 0))
    k_layer = jax.vmap(upd)(k_layer, new_k, length)
    v_layer = jax.vmap(upd)(v_layer, new_v, length)
    return k_layer, v_layer


# ---------------------------------------------------------------------------
# block summaries (paper eq. (1))
# ---------------------------------------------------------------------------

@pytree_dataclass
class BlockSummaries:
    kmax: jax.Array     # [L, B, NB, Hk, Dh]
    kmin: jax.Array     # [L, B, NB, Hk, Dh]

    @property
    def num_blocks(self) -> int:
        return self.kmax.shape[2]


def init_summaries(num_layers: int, batch: int, max_len: int, block: int,
                   num_kv_heads: int, head_dim: int) -> BlockSummaries:
    nb = cdiv(max_len, block)
    shape = (num_layers, batch, nb, num_kv_heads, head_dim)
    # neutral zeros: unwritten blocks score ~0 and retrieval masks them out
    # explicitly (select_and_gather_partial candidate mask)
    return BlockSummaries(kmax=jnp.zeros(shape, jnp.float32),
                          kmin=jnp.zeros(shape, jnp.float32))


def update_layer_summaries(kmax_l, kmin_l, k_layer, start, end, block: int):
    """Recompute summaries for the blocks covering tokens [start, end) of one
    layer's cache.  All shapes static; start/end dynamic scalars.

    kmax_l/kmin_l: [B, NB, Hk, Dh]; k_layer: [B, S, Hk, Dh].
    We recompute *every* block but only write those intersecting the range
    (cheap enough at update time; the Pallas kernel in repro/kernels does the
    fused version used on-device).
    """
    b, s, hk, dh = k_layer.shape
    nb = kmax_l.shape[1]
    if s < nb * block:  # cache smaller than the rounded block span
        k_layer = jnp.pad(k_layer, ((0, 0), (0, nb * block - s),
                                    (0, 0), (0, 0)))
    kb = k_layer[:, : nb * block].reshape(b, nb, block, hk, dh)
    tok_idx = (jnp.arange(nb)[:, None] * block
               + jnp.arange(block)[None, :])                 # [NB, blk]
    valid = (tok_idx[None] < end[:, None, None])             # [B, NB, blk]
    validb = valid[..., None, None]
    kf = kb.astype(jnp.float32)
    kmax_new = jnp.max(jnp.where(validb, kf, -1e30), axis=2)
    kmin_new = jnp.min(jnp.where(validb, kf, 1e30), axis=2)
    blk_lo = start // block
    blk_hi = (end + block - 1) // block
    blk = jnp.arange(nb)
    touched = (blk[None] >= blk_lo[:, None]) & (blk[None] < blk_hi[:, None])
    tb = touched[..., None, None]
    return (jnp.where(tb, kmax_new, kmax_l),
            jnp.where(tb, kmin_new, kmin_l))


# ---------------------------------------------------------------------------
# partial cache (materialised)
# ---------------------------------------------------------------------------

@pytree_dataclass
class PartialKV:
    k: jax.Array        # [L, B, Hk, P, Dh]   P = partial tokens + buffer
    v: jax.Array        # [L, B, Hk, P, Dh]
    pos: jax.Array      # [L, B, Hk, P] int32 absolute position, -1 = invalid
    length: jax.Array   # [B] int32 — valid partial tokens (sink+ret+local)
    buf_len: jax.Array  # [B] int32 — buffered partially-verified tokens

    @property
    def max_slots(self) -> int:
        return self.k.shape[3]


def init_partial_kv(num_layers: int, batch: int, num_kv_heads: int,
                    head_dim: int, spec: SpecPVConfig, dtype) -> PartialKV:
    p = spec.partial_budget_tokens + spec.buffer_size
    shape = (num_layers, batch, num_kv_heads, p, head_dim)
    return PartialKV(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        pos=jnp.full(shape[:-1], -1, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
        buf_len=jnp.zeros((batch,), jnp.int32))


def partial_valid_mask(pkv: PartialKV, layer=None) -> jax.Array:
    """[B, Hk, P] bool — slots holding real tokens (partial body + buffer)."""
    pos = pkv.pos if layer is None else pkv.pos[layer]
    return pos >= 0


# ---------------------------------------------------------------------------
# paged block pool
# ---------------------------------------------------------------------------

PAGED_POOL_KEYS = ("k", "v", "kmax", "kmin")   # no batch axis when paged


class PageAllocator:
    """Host-side refcounted allocator over the shared block pool.

    Page 0 is the reserved null page: unallocated page-table entries point
    at it and it is never handed out, so ``capacity == num_pages - 1``.
    The allocator is pure host state (the device only sees page tables);
    it never touches pool contents, so an over-draw raises instead of
    corrupting pages.

    Ownership is *refcounted*: a physical page may back the same logical
    block of several slots (``fork``/``attach``) and carry an extra
    reference from the host-side ``PrefixCache`` (``add_ref``).  A page
    returns to the free list only when its refcount drops to zero, and a
    write into a page with refcount > 1 must first go through
    ``cow_write`` (copy-on-write: the writer gets a private page and
    releases its share of the old one).

    Invariant: ``_slot_pages[slot][j]`` is the physical page backing
    logical block ``j`` of that slot — every mutation (alloc growth,
    attach of a matched prefix, in-place ``cow_write`` replacement)
    preserves logical-block order, so callers may mirror page tables
    from it.

    Residency tiers (``kvcache/offload.py TierManager``): a logical
    block whose bytes were offloaded to host RAM is *demoted* — its
    device page returns to the free list and the slot's entry becomes
    the null page (0), matching what the device page table shows — and
    ``_hosted`` remembers which blocks the slot is owed.  ``promote``
    seats a hosted block on a fresh page.  Only exclusively-owned pages
    (refcount 1, no prefix-cache pin) are ``demotable``; demoted slots
    cannot fork (a fork would have to add_ref the null page).

    Sharded serving (``distributed/``): with ``shards > 1`` the
    allocatable pages split into per-shard contiguous ranges — shard
    ``s`` owns ``[max(1, s*NP//shards), (s+1)*NP//shards)``, the exact
    ranges a ``data``-axis device sharding of the pool's page dimension
    places on host ``s`` (the reserved null page 0 rides with shard 0).
    Every slot maps to one shard (``slot_shard``) and draws pages only
    from its own range, so a host's resident pages are bounded by its
    range — no host ever materializes the whole cache.  Per-shard free
    lists stay LIFO; ``high_water_by`` tracks each shard's peak
    committed pages (the per-host truth ``peak_pages_per_host`` reports).
    """

    def __init__(self, num_pages: int, *, shards: int = 1,
                 slot_shard=None):
        assert num_pages >= 2, "need at least one allocatable page"
        assert shards >= 1 and shards <= num_pages - 1, \
            f"cannot split {num_pages - 1} allocatable pages over {shards}"
        self.num_pages = num_pages
        self.shards = shards
        # shard s owns pages [_bounds[s], _bounds[s+1])
        self._bounds = [max(1, (s * num_pages) // shards)
                        for s in range(shards)] + [num_pages]
        self._slot_shard_fn = slot_shard or (lambda slot: 0)
        self.high_water = 0             # peak committed (live working set)
        self.resident_high_water = 0    # peak physical (incl. idle cached)
        self.high_water_by = [0] * shards   # per-shard peak committed
        self.reset()

    def reset(self) -> None:
        # LIFO free lists (one per shard): freshly freed pages are
        # reused first (warm HBM); pop() hands out lowest pages first
        self._free_by: List[List[int]] = [
            list(range(self._bounds[s + 1] - 1, self._bounds[s] - 1, -1))
            for s in range(self.shards)]
        self._free_set = {p for fl in self._free_by for p in fl}
        self._ref = np.zeros((self.num_pages,), np.int32)
        self._cache_ref = np.zeros((self.num_pages,), np.int32)
        self._slot_pages: dict = {}
        self._hosted: dict = {}         # slot -> set of demoted blocks
        # zero-copy partial pins: pages a slot's partial page table
        # routes through between refreshes.  A pin is a REAL reference
        # (add_ref) plus this counter, so a pinned page can never reach
        # the free list — demote/rebind additionally refuse it outright.
        self._pin_ref = np.zeros((self.num_pages,), np.int32)
        self._slot_pins: dict = {}      # slot -> np.ndarray of pages

    # -- shard topology -----------------------------------------------
    def slot_shard(self, slot: int) -> int:
        """The shard `slot` draws its pages from."""
        return 0 if self.shards == 1 else self._slot_shard_fn(slot) % self.shards

    def page_shard(self, page: int) -> int:
        """The shard owning physical `page` (pages never migrate)."""
        assert page != 0, "the null page belongs to no shard's budget"
        return bisect.bisect_right(self._bounds, page) - 1

    def shard_capacity(self, shard: int) -> int:
        return self._bounds[shard + 1] - self._bounds[shard]

    def free_in(self, shard: int) -> int:
        return len(self._free_by[shard])

    @property
    def capacity(self) -> int:
        return self.num_pages - 1

    @property
    def free(self) -> int:
        return sum(len(fl) for fl in self._free_by)

    @property
    def in_use(self) -> int:
        """Physical pages off the free list (incl. idle cached ones)."""
        return self.capacity - self.free

    @property
    def idle(self) -> int:
        """Pages held *only* by cache references (no live slot) — fully
        reclaimable at zero cost via LRU prefix eviction."""
        return int(np.sum((self._ref > 0) & (self._ref == self._cache_ref)))

    @property
    def committed(self) -> int:
        """Pages some live slot references — the working set a smaller
        pool could not do without.  ``high_water`` tracks its peak."""
        return self.in_use - self.idle

    def count(self, slot: int) -> int:
        """Pages currently held by `slot`."""
        return len(self._slot_pages.get(slot, ()))

    def pages_of(self, slot: int) -> List[int]:
        return list(self._slot_pages.get(slot, ()))

    def page_at(self, slot: int, block: int) -> int:
        """Physical page backing logical block `block` of `slot`."""
        return self._slot_pages[slot][block]

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def slot_holds_shared(self, slot: int) -> bool:
        """Does `slot` hold any page it does not own exclusively?"""
        return any(self._ref[p] > 1 for p in self._slot_pages.get(slot, ()))

    # -- residency tiers (see class docstring / offload.TierManager) ---
    def hosted_count(self, slot: int) -> int:
        """Demoted blocks `slot` is owed — the pages a promotion ahead
        of its next full-cache read must be able to seat."""
        return len(self._hosted.get(slot, ()))

    def hosted_blocks(self, slot: int) -> List[int]:
        return sorted(self._hosted.get(slot, ()))

    @property
    def hosted_total(self) -> int:
        return sum(len(v) for v in self._hosted.values())

    def max_hosted(self) -> int:
        """Largest single-slot promotion debt (admission headroom)."""
        return max((len(v) for v in self._hosted.values()), default=0)

    def demotable(self, slot: int, block: int) -> bool:
        """May logical `block` of `slot` leave the device?  Only pages
        the slot owns exclusively: a shared page is some other holder's
        (or the prefix cache's) responsibility and must stay servable
        without a host round-trip."""
        pages = self._slot_pages.get(slot)
        if pages is None or block >= len(pages):
            return False
        p = pages[block]
        return (p != 0 and self._ref[p] == 1 and self._cache_ref[p] == 0
                and self._pin_ref[p] == 0)

    def demote(self, slot: int, block: int) -> int:
        """Release the device page behind a host-offloaded block: the
        page returns to the free list, the slot's entry becomes the null
        page (exactly what the device table must show), and the block
        joins the slot's hosted set.  Returns the recycled page.  The
        caller must have captured the page's bytes first."""
        assert self.demotable(slot, block), \
            f"demote of non-exclusive block {block} of slot {slot}"
        p = self._slot_pages[slot][block]
        self._slot_pages[slot][block] = 0
        self._ref[p] = 0
        self._free_by[self.page_shard(p)].append(p)
        self._free_set.add(p)
        self._hosted.setdefault(slot, set()).add(block)
        return p

    def promote(self, slot: int, block: int) -> int:
        """Seat a hosted block on a fresh device page (refcount 1) and
        clear its promotion debt.  Raises on pool exhaustion with state
        unchanged (``_take``); the caller fills the page's bytes and
        repoints the device table."""
        hosted = self._hosted.get(slot, set())
        assert block in hosted, \
            f"promote of non-hosted block {block} of slot {slot}"
        [p] = self._take(1, self.slot_shard(slot))
        self._slot_pages[slot][block] = p
        hosted.discard(block)
        if not hosted:
            self._hosted.pop(slot, None)
        return p

    # -- high_water tracks peak *committed* pages (live-slot working
    # -- set): it moves only in _track(), called where a page can become
    # -- slot-referenced — never in fork (which shares existing refs and
    # -- allocates nothing), so forking can never skew it.
    def _track(self) -> None:
        self.high_water = max(self.high_water, self.committed)
        self.resident_high_water = max(self.resident_high_water, self.in_use)
        if self.shards > 1:
            for s in range(self.shards):
                lo, hi = self._bounds[s], self._bounds[s + 1]
                in_use = (hi - lo) - len(self._free_by[s])
                idle = int(np.sum((self._ref[lo:hi] > 0)
                                  & (self._ref[lo:hi]
                                     == self._cache_ref[lo:hi])))
                self.high_water_by[s] = max(self.high_water_by[s],
                                            in_use - idle)
        else:
            self.high_water_by[0] = self.high_water

    @property
    def peak_pages_per_host(self) -> int:
        """Worst single-shard peak committed pages — the per-host memory
        truth a global average would hide (one shard == one host)."""
        return max(self.high_water_by)

    # -- page-grab primitive: the ONLY place pages leave the free list
    def _take(self, n: int, shard: int = 0) -> List[int]:
        fl = self._free_by[shard]
        if n > len(fl):
            where = f" (shard {shard})" if self.shards > 1 else ""
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(fl)} "
                f"free of {self.shard_capacity(shard)}{where}")
        pages = [fl.pop() for _ in range(n)]
        for p in pages:
            assert self._ref[p] == 0, f"free page {p} had refcount"
            self._free_set.discard(p)
            self._ref[p] = 1
        self._track()
        return pages

    def alloc(self, slot: int, n: int) -> np.ndarray:
        """Hand `n` fresh (refcount-1) pages to `slot` from its shard's
        range.  Raises on over-draw (state unchanged), so exhaustion can
        never hand out a page twice."""
        pages = self._take(n, self.slot_shard(slot))
        self._slot_pages.setdefault(slot, []).extend(pages)
        return np.asarray(pages, np.int32)

    def alloc_cache(self, n: int, shard: int = 0) -> List[int]:
        """Take `n` pages held *only* by the prefix cache (refcount 1,
        all of it a cache reference — immediately idle/reclaimable):
        the restore path of ``PrefixCache.load_state`` seats snapshot
        pages this way before any slot references them."""
        pages = self._take(n, shard)
        for p in pages:
            self._cache_ref[p] = 1
        return pages

    def add_ref(self, pages, *, cache: bool = False) -> None:
        """Take an extra reference on already-allocated pages.  ``cache``
        marks it as a prefix-cache (idle-capable) reference: pages held
        only by such references count as reclaimable, not committed."""
        for p in pages:
            assert self._ref[p] > 0, f"add_ref on free page {p}"
            self._ref[p] += 1
            if cache:
                self._cache_ref[p] += 1

    def dec_ref(self, pages, *, cache: bool = False) -> List[int]:
        """Release one reference per page; pages whose refcount drops to
        zero return to the free list.  Returns the pages actually freed."""
        freed: List[int] = []
        for p in pages:
            assert p != 0, "refcount op on the reserved null page"
            assert p not in self._free_set, \
                f"double free: page {p} is already on the free list"
            assert self._ref[p] > 0, f"refcount underflow on page {p}"
            self._ref[p] -= 1
            if cache:
                assert self._cache_ref[p] > 0
                self._cache_ref[p] -= 1
            assert not (self._ref[p] == 0 and self._pin_ref[p] > 0), \
                f"page {p} freed while partial-pinned"
            if self._ref[p] == 0:
                self._free_by[self.page_shard(p)].append(p)
                self._free_set.add(p)
                freed.append(p)
        return freed

    def attach(self, slot: int, pages) -> None:
        """Share existing pages into `slot` (appended in logical-block
        order): prefix-cache hits attach the matched leading blocks by
        reference instead of allocating + re-prefilling them.  An idle
        cached page becomes committed again here."""
        self.add_ref(pages)
        self._slot_pages.setdefault(slot, []).extend(int(p) for p in pages)
        self._track()

    def rebind_block(self, slot: int, block: int, page: int) -> List[int]:
        """Repoint logical `block` of `slot` onto an existing shared
        `page` (prefix-cache dedupe of concurrently prefilled blocks):
        the slot takes a reference on `page` and releases its own —
        the duplicate returns to the free list once no one holds it.
        Returns the pages actually freed."""
        old = self._slot_pages[slot][block]
        assert old != page, "rebind onto the page already held"
        assert old != 0 and block not in self._hosted.get(slot, ()), \
            "rebind of a hosted/null block"
        assert self._pin_ref[old] == 0, \
            f"rebind of partial-pinned page {old}: a live partial view " \
            f"routes through it until the slot's next refresh"
        self.add_ref([page])
        self._slot_pages[slot][block] = page
        return self.dec_ref([old])

    def fork(self, src: int, dst: int) -> List[int]:
        """`dst` becomes a full reference-holder of `src`'s pages
        (copy-on-write fork).  `dst` must not hold pages."""
        assert not self._slot_pages.get(dst), \
            f"fork target slot {dst} still holds pages"
        assert not self._hosted.get(src) and not self._hosted.get(dst), \
            "cannot fork a slot with host-demoted blocks (promote first)"
        assert self.slot_shard(src) == self.slot_shard(dst), \
            (f"cross-shard fork {src}->{dst}: a fork shares pages by "
             f"reference, so both slots must live on one shard")
        pages = self.pages_of(src)
        self.attach(dst, pages)
        # the replica's partial view routes through the same physical
        # pages as the source's until its next refresh, so it must hold
        # its own pins on them (evicting src cannot strand dst's view)
        src_pins = self._slot_pins.get(src)
        if src_pins is not None and len(src_pins):
            self.pin_slot_pages(dst, src_pins)
        return pages

    def cow_write(self, slot: int, block: int) -> Tuple[int, int]:
        """Make logical block `block` of `slot` exclusively writable.

        Returns ``(old_page, new_page)``; ``old == new`` when the slot
        already owned the page alone.  Otherwise a private page is taken
        (the caller must copy pool contents old -> new and update the
        device page table) and the shared page loses one reference.

        The slot's share of the old page is dropped *before* the new
        page is taken (after an explicit free-list check, so exhaustion
        still raises with state unchanged): the copy replaces a page
        1:1, and counting both sides simultaneously would bump the
        committed high-water for a working set that never grew — e.g. a
        tail-entry registration whose old page becomes cache-only."""
        old = self._slot_pages[slot][block]
        if self._ref[old] == 1:
            return old, old
        shard = self.slot_shard(slot)
        if not self._free_by[shard]:
            where = f" (shard {shard})" if self.shards > 1 else ""
            raise RuntimeError(
                f"page pool exhausted: want 1, have 0 free of "
                f"{self.shard_capacity(shard)}{where}")
        self._ref[old] -= 1             # ref > 1, so never frees here
        [new] = self._take(1, shard)
        self._slot_pages[slot][block] = new
        return old, new

    def free_slot(self, slot: int) -> List[int]:
        """Release `slot`'s references (idempotent).  Returns only the
        pages actually freed — pages still shared with another slot or
        with the prefix cache stay resident.  Host-demoted blocks (null
        entries) hold no device page and simply drop their debt; the
        host-side bytes are the ``TierManager``'s to discard."""
        self.unpin_slot(slot)
        pages = self._slot_pages.pop(slot, [])
        self._hosted.pop(slot, None)
        return self.dec_ref([p for p in pages if p != 0])

    # -- zero-copy partial pins (see docs/paged_kv.md#partial-pins) ----
    def pin_slot_pages(self, slot: int, pages) -> None:
        """Replace `slot`'s partial-pin set with `pages` (the physical
        pages its freshly written partial page table routes through).
        Each pin is a real reference plus a ``_pin_ref`` count, so a
        pinned page is a legal CoW *source* but can never be freed,
        rebound, or demoted until the slot's next refresh (or eviction)
        drops the pin.  New pins are taken BEFORE the old set is
        released, so a page in both sets never transiently frees."""
        new = np.unique(np.asarray(list(pages), np.int64)).astype(np.int32)
        assert not np.any(new == 0), "pin of the reserved null page"
        self.add_ref(new)
        self._pin_ref[new] += 1
        old = self._slot_pins.get(slot)
        self._slot_pins[slot] = new
        if old is not None and len(old):
            self._pin_ref[old] -= 1
            assert np.all(self._pin_ref >= 0), "pin refcount underflow"
            self.dec_ref(old)

    def unpin_slot(self, slot: int) -> None:
        """Drop `slot`'s partial pins (idempotent) — refresh epilogue
        re-pin, slot eviction, and ``free_slot`` all funnel here."""
        old = self._slot_pins.pop(slot, None)
        if old is not None and len(old):
            self._pin_ref[old] -= 1
            assert np.all(self._pin_ref >= 0), "pin refcount underflow"
            self.dec_ref(old)

    def pins_of(self, slot: int) -> List[int]:
        return list(self._slot_pins.get(slot, ()))

    @property
    def pinned_pages(self) -> int:
        """Distinct physical pages with a live partial pin."""
        return int(np.sum(self._pin_ref > 0))


# ---------------------------------------------------------------------------
# prefix cache (host side)
# ---------------------------------------------------------------------------

class _PrefixEntry:
    __slots__ = ("key", "depth", "page", "draft_page", "feat", "tick",
                 "tokens", "parent")

    def __init__(self, key, depth, page, draft_page, feat, tick,
                 tokens=None, parent=None):
        self.key = key              # chain hash of blocks [0..depth]
        self.depth = depth          # logical block index
        self.page = page            # trunk pool page (all layers)
        self.draft_page = draft_page
        self.feat = feat            # fused feature of the block's last
                                    # token (tail-prefill continuation)
        self.tick = tick            # LRU stamp
        self.tokens = tokens        # the block's prompt tokens (save/
        self.parent = parent        # load provenance: key must equal
                                    # _digest(parent, tokens))


class _TailEntry:
    """A whole-prompt entry for a prompt ending in a *partial* block:
    the pages holding the final sub-block tokens plus the boot state a
    greedy admission needs to skip prefill entirely (the fused boundary
    feature at the last prompt position and the argmax first token)."""

    __slots__ = ("key", "depth", "tail_len", "page", "draft_page", "feat",
                 "first_token", "tick")

    def __init__(self, key, depth, tail_len, page, draft_page, feat,
                 first_token, tick):
        self.key = key              # digest(parent chain key, tail tokens)
        self.depth = depth          # logical block index of the tail block
        self.tail_len = tail_len    # prompt tokens inside the tail block
        self.page = page
        self.draft_page = draft_page
        self.feat = feat
        self.first_token = first_token
        self.tick = tick


class PrefixCache:
    """Host-side prompt-prefix index over the paged pools.

    Keyed by a *chained* hash of block-aligned prompt-token chunks
    (blake2b over ``parent_digest || block_tokens``), so a hit at block
    ``i`` certifies the entire prefix ``[0, (i+1)*block)`` matches.  Each
    entry pins one trunk page + one draft page (one ``add_ref`` each) and
    carries the fused boundary feature needed to resume chunked prefill
    right after the matched region.

    Entries are evicted LRU-oldest-first — but only when nothing besides
    the cache references their pages, so eviction under pool pressure
    reclaims exactly the idle prefixes.  A matched chain is re-stamped as
    one unit, which keeps every child entry no newer than its parent;
    ties break deepest-first so a chain never loses an interior block
    before its tail.
    """

    def __init__(self, block_size: int):
        self.block = block_size
        self._entries: Dict[bytes, _PrefixEntry] = {}
        self._tails: Dict[bytes, _TailEntry] = {}
        self._tick = 0
        self.lookups = 0
        self.blocks_matched = 0
        self.blocks_seen = 0
        self.inserted = 0
        self.evicted = 0
        self.tail_lookups = 0
        self.tail_hits = 0
        self.tails_inserted = 0
        self.tails_evicted = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _digest(parent: bytes, tokens: np.ndarray) -> bytes:
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(np.ascontiguousarray(tokens, np.int64).tobytes())
        return h.digest()

    def chain_keys(self, prompt: np.ndarray, n_blocks: int) -> List[bytes]:
        """Chain hashes of the first `n_blocks` full blocks of `prompt`."""
        bs = self.block
        keys, parent = [], b"specpv-prefix"
        for j in range(n_blocks):
            parent = self._digest(parent, prompt[j * bs: (j + 1) * bs])
            keys.append(parent)
        return keys

    def match(self, prompt: np.ndarray, max_blocks: int,
              *, touch: bool = True, count: bool = True
              ) -> List[_PrefixEntry]:
        """Longest cached chain over the leading full blocks of `prompt`
        (at most `max_blocks`).  ``touch`` re-stamps the matched chain
        MRU; ``count=False`` makes this a side-effect-free probe for
        admission accounting."""
        bs = self.block
        n = min(max_blocks, len(prompt) // bs)
        out: List[_PrefixEntry] = []
        parent = b"specpv-prefix"
        for j in range(n):
            parent = self._digest(parent, prompt[j * bs: (j + 1) * bs])
            e = self._entries.get(parent)
            if e is None:
                break
            out.append(e)
        if touch and out:
            self._tick += 1
            for e in out:
                e.tick = self._tick
        if count:
            self.lookups += 1
            self.blocks_seen += n
            self.blocks_matched += len(out)
        return out

    def new_tick(self) -> int:
        """Fresh LRU stamp.  One registration (or match) stamps its whole
        chain with a single tick, so the deepest-first tie-break keeps
        the invariant 'no child newer than its parent' — eviction can
        then never orphan a chain head before its tail (which would pin
        unreachable pages)."""
        self._tick += 1
        return self._tick

    def insert(self, key: bytes, depth: int, page: int, draft_page: int,
               feat, trunk_alloc: PageAllocator,
               draft_alloc: PageAllocator,
               tick: Optional[int] = None,
               tokens: Optional[np.ndarray] = None,
               parent: Optional[bytes] = None) -> Optional[_PrefixEntry]:
        """Register one completed prefill block.  Takes one reference on
        each pool page; returns the new entry, or None (taking nothing)
        when the chain hash is already cached — ``entry(key)`` then
        fetches the existing one.  Pass one ``new_tick()`` for all
        blocks of a chain registered together.  ``tokens``/``parent``
        record the block's provenance (``key == _digest(parent,
        tokens)``) so ``save_state`` can persist a verifiable chain."""
        if key in self._entries:
            return None
        trunk_alloc.add_ref([page], cache=True)
        draft_alloc.add_ref([draft_page], cache=True)
        e = _PrefixEntry(key, depth, int(page), int(draft_page), feat,
                         self.new_tick() if tick is None else tick,
                         None if tokens is None
                         else np.ascontiguousarray(tokens, np.int64),
                         parent)
        self._entries[key] = e
        self.inserted += 1
        return e

    def entry(self, key: bytes) -> Optional[_PrefixEntry]:
        """The cached entry for a chain hash, if any (no LRU touch)."""
        return self._entries.get(key)

    # -- speculative last-partial-block sharing --------------------------
    _ROOT = b"specpv-prefix"

    def _tail_key(self, parent: bytes, tail_tokens: np.ndarray) -> bytes:
        return self._digest(b"tail:" + parent, tail_tokens)

    def register_tail(self, parent: bytes, tail_tokens: np.ndarray,
                      depth: int, page: int, draft_page: int, feat,
                      first_token: int, trunk_alloc: PageAllocator,
                      draft_alloc: PageAllocator) -> Optional[_TailEntry]:
        """Register a prompt's final *partial* block (the sub-block tail
        a block-aligned chain can never cover).  Keyed by the parent
        chain hash plus the exact tail tokens, so a hit certifies the
        whole prompt; the entry additionally stores the boot state
        (boundary feature + greedy first token) that lets an identical
        admission skip its prefill entirely.  Takes one cache reference
        per pool page; the caller must immediately hand the registering
        slot a private copy of the block (``PageAllocator.cow_write``) —
        its very next decode commit writes *into* this block, and the
        cached KV must stay frozen.  Returns None when already cached."""
        key = self._tail_key(parent, tail_tokens)
        if key in self._tails:
            return None
        trunk_alloc.add_ref([page], cache=True)
        draft_alloc.add_ref([draft_page], cache=True)
        e = _TailEntry(key, depth, len(tail_tokens), int(page),
                       int(draft_page), feat, int(first_token),
                       self.new_tick())
        self._tails[key] = e
        self.tails_inserted += 1
        return e

    def match_tail(self, prompt: np.ndarray, *, touch: bool = True,
                   count: bool = True
                   ) -> Optional[Tuple[List[_PrefixEntry], _TailEntry]]:
        """Whole-prompt lookup for a prompt ending in a partial block:
        hit iff every full block chains AND a tail entry matches the
        exact remaining tokens.  Returns (chain entries, tail entry) on
        hit; ``touch`` re-stamps chain + tail as one unit (LRU keeps a
        parent no older than its tail)."""
        bs = self.block
        n_full = len(prompt) // bs
        rem = len(prompt) - n_full * bs
        if rem == 0:
            return None
        if count:
            self.tail_lookups += 1
        chain = self.match(prompt, n_full, touch=False, count=False)
        if len(chain) < n_full:
            return None
        parent = chain[-1].key if n_full else self._ROOT
        e = self._tails.get(self._tail_key(parent, prompt[n_full * bs:]))
        if e is None:
            return None
        if count:
            self.tail_hits += 1
        if touch:
            tick = self.new_tick()
            for c in chain:
                c.tick = tick
            e.tick = tick
        return chain, e

    def evict_lru(self, trunk_alloc: PageAllocator,
                  draft_alloc: PageAllocator, n_pages: int) -> int:
        """Drop least-recently-used *unreferenced* entries (pages held
        only by the cache) until `n_pages` trunk pages have been freed or
        no candidate remains.  Returns trunk pages freed.

        Tail entries compete in the same LRU order; their depth sorts
        just below their parent block's (deepest-first tie-break), so
        within one stamp a tail always evicts before the chain that
        certifies it."""
        freed = 0
        cands = sorted(
            list(self._entries.values()) + list(self._tails.values()),
            key=lambda e: (e.tick, -e.depth, 0 if isinstance(e, _TailEntry)
                           else 1))
        for e in cands:
            if freed >= n_pages:
                break
            if (trunk_alloc.refcount(e.page) == 1
                    and draft_alloc.refcount(e.draft_page) == 1):
                if isinstance(e, _TailEntry):
                    del self._tails[e.key]
                    self.tails_evicted += 1
                else:
                    del self._entries[e.key]
                    self.evicted += 1
                freed += len(trunk_alloc.dec_ref([e.page], cache=True))
                draft_alloc.dec_ref([e.draft_page], cache=True)
        return freed

    def clear(self, trunk_alloc: PageAllocator,
              draft_alloc: PageAllocator) -> None:
        """Release every entry's references (engine reset)."""
        for e in list(self._entries.values()) + list(self._tails.values()):
            trunk_alloc.dec_ref([e.page], cache=True)
            draft_alloc.dec_ref([e.draft_page], cache=True)
        self._entries.clear()
        self._tails.clear()

    # -- persistence across engine rebuilds ------------------------------
    def save_state(self, page_bytes=None) -> dict:
        """Host-side snapshot of the chain entries (parents first).

        Only entries carrying ``tokens``/``parent`` provenance are
        persisted — the snapshot must be re-verifiable — and tail
        entries are skipped (their boot state is only sound against the
        exact pool bytes they were registered with).  ``page_bytes`` is
        an optional callable ``(page, draft_page) -> blob`` capturing
        the pool contents device-to-host (the engine passes a closure
        over its pools); without it the snapshot carries structure only
        and cannot be re-seated."""
        ents = []
        for e in self._entries.values():
            if e.tokens is None or e.parent is None:
                continue
            ents.append(dict(
                key=e.key, parent=e.parent, depth=int(e.depth),
                tokens=np.ascontiguousarray(e.tokens, np.int64),
                feat=None if e.feat is None else np.asarray(e.feat),
                tick=int(e.tick),
                pages=None if page_bytes is None
                else page_bytes(e.page, e.draft_page)))
        ents.sort(key=lambda d: d["depth"])
        return {"block": self.block, "tick": self._tick, "entries": ents}

    def load_state(self, snap: dict, trunk_alloc: PageAllocator,
                   draft_alloc: PageAllocator, seat_pages,
                   shard: int = 0) -> int:
        """Re-attach a ``save_state`` snapshot after an engine rebuild.

        Every entry **re-verifies its chain hash before first use**:
        ``_digest(parent, tokens)`` must reproduce the stored key AND the
        parent itself must have verified (or be the chain root), so a
        corrupted or truncated snapshot can never certify a prefix it
        does not hold.  ``seat_pages(entry_dict, shard) -> (page,
        draft_page)`` allocates cache-only pages (``alloc_cache``) and
        writes the blob back into the pools; it may raise to stop early
        (pool pressure) — already-seated entries stay valid.  Returns
        the number of entries restored."""
        if snap.get("block") != self.block:
            return 0
        ok = {self._ROOT}
        restored = 0
        for d in snap["entries"]:
            if d["parent"] not in ok and d["parent"] not in self._entries:
                continue                      # orphaned — parent refused
            if self._digest(d["parent"], d["tokens"]) != d["key"]:
                continue                      # chain hash mismatch
            if d["key"] in self._entries:
                ok.add(d["key"])
                continue                      # already live
            if d.get("pages") is None:
                continue                      # structure-only snapshot
            try:
                page, draft_page = seat_pages(d, shard)
            except RuntimeError:
                break                         # pool pressure: stop early
            e = _PrefixEntry(d["key"], d["depth"], int(page),
                             int(draft_page), d["feat"], d["tick"],
                             d["tokens"], d["parent"])
            self._entries[d["key"]] = e
            self._tick = max(self._tick, e.tick)
            ok.add(d["key"])
            restored += 1
        return restored

    def stats(self) -> Dict[str, int]:
        return dict(entries=len(self._entries), lookups=self.lookups,
                    blocks_matched=self.blocks_matched,
                    blocks_seen=self.blocks_seen,
                    tokens_reused=self.blocks_matched * self.block,
                    inserted=self.inserted, evicted=self.evicted,
                    tails=len(self._tails),
                    tail_lookups=self.tail_lookups,
                    tail_hits=self.tail_hits,
                    tails_inserted=self.tails_inserted,
                    tails_evicted=self.tails_evicted)

    def reset_stats(self) -> None:
        """Zero the hit/reuse counters (benchmark warmup); entries and
        LRU state are untouched."""
        self.lookups = 0
        self.blocks_matched = 0
        self.blocks_seen = 0
        self.inserted = 0
        self.evicted = 0
        self.tail_lookups = 0
        self.tail_hits = 0
        self.tails_inserted = 0
        self.tails_evicted = 0


def init_paged_pool(num_layers: int, num_pages: int, block: int,
                    num_kv_heads: int, head_dim: int, dtype) -> dict:
    """Shared lane-dense pool ``[L, NP, block, Hk*Dh]`` + physical-page
    summaries ``[L, NP, Hk, Dh]`` (no page tables)."""
    kv_shape = (num_layers, num_pages, block, num_kv_heads * head_dim)
    sm_shape = (num_layers, num_pages, num_kv_heads, head_dim)
    return {"k": jnp.zeros(kv_shape, dtype), "v": jnp.zeros(kv_shape, dtype),
            "kmax": jnp.zeros(sm_shape, jnp.float32),
            "kmin": jnp.zeros(sm_shape, jnp.float32)}


def gather_page_view(pool_l: jax.Array, page_table: jax.Array,
                     head_dim: Optional[int] = None) -> jax.Array:
    """One layer's logical contiguous view through the page table.

    pool_l: [NP, block, ...] (the trunk's lane-dense [NP, block, Hk*Dh]
    or the draft's [NP, block, Hk, Dh]); page_table: [B, NB] ->
    [B, NB*block, ...], with the trailing axis split into heads of
    ``head_dim`` when given ([B, NB*block, Hk, Dh]).  Entries mapping to
    the null page read whatever it holds — callers mask by position
    validity."""
    b, nb = page_table.shape
    v = pool_l[page_table]                       # [B, NB, block, ...]
    tail = pool_l.shape[2:] if head_dim is None else (-1, head_dim)
    return v.reshape((b, nb * pool_l.shape[1]) + tail)


def gather_head_blocks(pool: jax.Array, layer, pages: jax.Array,
                       head_dim: int) -> jax.Array:
    """Per-kv-head block tiles of a lane-dense pool stack, read in place.

    pool: [L, NP, block, Hk*Dh]; layer: int32 scalar; pages: [B, Hk, NS]
    physical page ids -> [B, Hk, NS, block, Dh], entry ``[b, h, s]``
    the ``(block, Dh)`` tile of page ``pages[b, h, s]`` at kv head
    ``h``'s columns — the tile the attention kernel streams, and
    nothing more of the pool."""
    b, hk, ns = pages.shape
    blk = pool.shape[2]
    col = jnp.broadcast_to(jnp.arange(hk, dtype=jnp.int32)[None, :, None]
                           * head_dim, pages.shape)
    lyr = jnp.asarray(layer, jnp.int32)

    def tile(page, c):
        return jax.lax.dynamic_slice(pool, (lyr, page, 0, c),
                                     (1, 1, blk, head_dim))[0, 0]

    out = jax.vmap(tile)(pages.reshape(-1).astype(jnp.int32),
                         col.reshape(-1))
    return out.reshape(b, hk, ns, blk, head_dim)


def _token_rows(page_table: jax.Array, start, t: int, blk: int,
                valid=None) -> jax.Array:
    """[B*T] pool rows (``page * block + slot``) of ``t`` tokens written
    at per-row logical offsets ``start`` through the table (clamping and
    null-page routing as ``paged_write_tokens`` documents)."""
    nb = page_table.shape[1]
    idx = start[:, None] + jnp.arange(t)[None]               # [B, T] logical
    idx = jnp.minimum(idx, nb * blk - 1)
    pg = jnp.take_along_axis(page_table, idx // blk, axis=1)
    if valid is not None:
        pg = jnp.where(valid, pg, 0)
    return (pg * blk + idx % blk).reshape(-1)


def paged_write_tokens(pool_l: jax.Array, page_table: jax.Array, start,
                       new: jax.Array, valid=None) -> jax.Array:
    """Scatter `new` tokens at per-row logical offsets through the table.

    pool_l: [NP, block, ...]; page_table: [B, NB]; start: [B];
    new: [B, T, Hk, Dh], stored in the pool's trailing layout (the
    trunk's lane-dense ``Hk*Dh``).  Positions beyond the table span are
    clamped into the last logical block (an upstream admission error);
    positions whose table entry is unallocated land in the null page
    and are never read unmasked.  ``valid`` ([B, T] bool, optional)
    routes ragged pad positions into the null page instead — fused
    multi-cursor prefill packs rows of unequal chunk lengths and must
    not let a short row's zero-padding clobber an allocated page."""
    np_, blk = pool_l.shape[:2]
    b, t = new.shape[:2]
    flat = _token_rows(page_table, start, t, blk, valid)
    pool_flat = pool_l.reshape((np_ * blk,) + pool_l.shape[2:])
    pool_flat = pool_flat.at[flat].set(
        new.astype(pool_l.dtype).reshape((b * t,) + pool_l.shape[2:]))
    return pool_flat.reshape(pool_l.shape)


def paged_write_layers(pool: jax.Array, page_table: jax.Array, start,
                       new: jax.Array) -> jax.Array:
    """``paged_write_tokens`` into every layer of a pool stack at once.

    pool: [L, NP, block, Hk*Dh]; new: [L, B, T, Hk, Dh].  One row
    scatter over the stack seen as ``[L*NP*block, Hk*Dh]`` rows (a
    bitcast): on TPU a per-layer ``vmap`` of the row scatter makes XLA
    re-lay the whole stack out around it, twice per step."""
    nl, np_, blk = pool.shape[:3]
    b, t = new.shape[1:3]
    flat = _token_rows(page_table, start, t, blk)
    rows = (jnp.arange(nl)[:, None] * (np_ * blk) + flat[None]).reshape(-1)
    width = pool.shape[3:]
    out = pool.reshape((nl * np_ * blk,) + width).at[rows].set(
        new.astype(pool.dtype).reshape((nl * b * t,) + width))
    return out.reshape(pool.shape)


def paged_update_summaries(kmax_p, kmin_p, pool_l, page_table, start, end,
                           n_touch: int):
    """Recompute physical-page summaries for the logical blocks covering
    [start, end) of each row (paged counterpart of
    ``update_layer_summaries``; same masked max/min, keyed by page).

    kmax_p/kmin_p: [NP, Hk, Dh]; pool_l: [NP, block, Hk*Dh];
    page_table: [B, NB]; start/end: [B]; n_touch: static upper bound on
    touched blocks per row (cdiv(T, block) + 1)."""
    blk = pool_l.shape[1]
    hk, dh = kmax_p.shape[1:]
    b, nb = page_table.shape
    blk_lo = start // blk
    tb = blk_lo[:, None] + jnp.arange(n_touch)[None]         # [B, NT] logical
    in_range = (tb < (end[:, None] + blk - 1) // blk) & (tb < nb)
    tbc = jnp.minimum(tb, nb - 1)
    pg = jnp.take_along_axis(page_table, tbc, axis=1)        # [B, NT]
    # whole pages as (1, block, Hk*Dh) slices: an index gather of the
    # lane-dense pool, vmapped over layers, is re-laid out on TPU
    keys = jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(pool_l, p, 1)[0])(
        pg.reshape(-1))
    keys = keys.astype(jnp.float32).reshape(
        pg.shape + (blk, hk, dh))                            # [B,NT,blk,Hk,Dh]
    pos = tbc[:, :, None] * blk + jnp.arange(blk)[None, None]
    valid = (pos < end[:, None, None])[..., None, None]
    kmax_new = jnp.max(jnp.where(valid, keys, -1e30), axis=2)
    kmin_new = jnp.min(jnp.where(valid, keys, 1e30), axis=2)
    tgt = jnp.where(in_range & (pg > 0), pg, 0).reshape(-1)
    kmax_p = kmax_p.at[tgt].set(kmax_new.reshape(-1, hk, dh))
    kmin_p = kmin_p.at[tgt].set(kmin_new.reshape(-1, hk, dh))
    # the null page collects every routed-away write; keep it neutral so
    # gathered views of unallocated entries read all-zero summaries
    # (bit-identical to the contiguous layout's unwritten blocks)
    kmax_p = kmax_p.at[0].set(0.0)
    kmin_p = kmin_p.at[0].set(0.0)
    return kmax_p, kmin_p


# ---------------------------------------------------------------------------
# per-slot (batch-row) surgery — continuous batching support
#
# The blocked layout makes slot == batch row everywhere, so per-slot cache
# reset / admission is a row write at a dynamic batch index.  The full-cache
# dict keys carry the batch on axis 1 (leading layer axis) except `length`;
# draft-cache and engine per-slot scalars carry it on axis 0.  Paged caches
# carry the batch only on `page_table`/`length` (axis 0) — the pool keys
# are shared and merged at page granularity instead.
# ---------------------------------------------------------------------------

CACHE_BATCH_AXIS = {"k": 1, "v": 1, "kmax": 1, "kmin": 1,
                    "cross_k": 1, "cross_v": 1,
                    "page_table": 0, "length": 0}


def write_row(dst: jax.Array, src: jax.Array, slot, axis: int) -> jax.Array:
    """Write `src` (one row, with a size-1 batch dim at `axis`) into
    `dst` at batch index `slot` (dynamic scalar)."""
    start = [0] * dst.ndim
    start[axis] = slot
    return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)


def select_rows(mask: jax.Array, new: jax.Array, old: jax.Array,
                axis: int) -> jax.Array:
    """Per-row select: rows where mask is True come from `new`."""
    shape = [1] * new.ndim
    shape[axis] = mask.shape[0]
    return jnp.where(mask.reshape(shape), new, old)


def write_cache_slot(dst: dict, src: dict, slot) -> dict:
    """Copy the single batch row of a batch-1 cache dict `src` into row
    `slot` of `dst` (chunked prefill-into-slot commit).

    Paged: `src` carries only per-row keys (page_table/length + any cross
    arrays); the pool keys are shared and pass through from `dst` — a
    paged slot prefill already wrote the slot's pages in place."""
    if "page_table" in dst:
        out = dict(dst)
        for name in src:
            if name in PAGED_POOL_KEYS:
                continue
            out[name] = write_row(dst[name], src[name], slot,
                                  CACHE_BATCH_AXIS.get(name, 0))
        return out
    return {name: write_row(dst[name], src[name], slot,
                            CACHE_BATCH_AXIS.get(name, 0))
            for name in dst}


def _page_on_mask(mask: jax.Array, page_table: jax.Array,
                  num_pages: int) -> jax.Array:
    """[NumPages] bool — pages referenced by an active row's table (null
    page excluded).  With copy-on-write sharing a page may appear in
    several tables; it steps iff *any* active row maps it, which is safe
    because steps only ever write pages the stepping row owns
    exclusively (the engine CoWs shared pages out of the write window
    first)."""
    b, nb = page_table.shape
    row_on = jnp.repeat(mask, nb)
    tgt = jnp.where(row_on, page_table.reshape(-1), 0)
    return (jnp.zeros((num_pages,), bool).at[tgt].set(True)
            .at[0].set(False))


def merge_cache_rows(mask: jax.Array, new: dict, old: dict) -> dict:
    """Per-row merge of two full-cache dicts (masked engine steps).

    Paged: pool keys have no batch axis, so rows are merged at *page*
    granularity — a page takes the stepped (`new`) value iff it belongs
    to an active row's table.  Pages of inactive rows, free pages,
    pages pinned only by the prefix cache, and the null page revert to
    `old`, which keeps untouched slots bit-identical exactly as the row
    merge does for contiguous caches."""
    if "page_table" in new:
        pt = old["page_table"]                       # tables don't step
        num_pages = new["k"].shape[1]
        page_on = _page_on_mask(mask, pt, num_pages)
        out = {}
        for name in new:
            if name in PAGED_POOL_KEYS:
                m = page_on.reshape((1, num_pages)
                                    + (1,) * (new[name].ndim - 2))
                out[name] = jnp.where(m, new[name], old[name])
            else:
                out[name] = select_rows(mask, new[name], old[name],
                                        CACHE_BATCH_AXIS.get(name, 0))
        return out
    return {name: select_rows(mask, new[name], old[name],
                              CACHE_BATCH_AXIS.get(name, 0))
            for name in new}


# ---------------------------------------------------------------------------
# draft-cache surgery — same contracts as the full-cache helpers above,
# but the draft dict carries its batch on axis 0 everywhere and its
# (optional) pool keys ``k``/``v`` are [NumPages, block, Hk, Dh] with no
# leading layer axis (the draft module is a single decoder layer).
# ---------------------------------------------------------------------------

DRAFT_POOL_KEYS = ("k", "v")


def write_draft_slot(dst: dict, src: dict, slot) -> dict:
    """Copy the single batch row of a batch-1 draft-cache dict into row
    `slot` of `dst`.  Paged: pool keys pass through from `dst` (a paged
    slot prefill already wrote the slot's draft pages in place)."""
    if "page_table" in dst:
        out = dict(dst)
        for name in src:
            if name in DRAFT_POOL_KEYS:
                continue
            out[name] = write_row(dst[name], src[name], slot, 0)
        return out
    return {name: write_row(dst[name], src[name], slot, 0) for name in dst}


def merge_draft_rows(mask: jax.Array, new: dict, old: dict) -> dict:
    """Per-row merge of two draft-cache dicts (masked engine steps);
    paged draft pools merge at page granularity like the trunk pool."""
    if "page_table" in new:
        num_pages = new["k"].shape[0]
        page_on = _page_on_mask(mask, old["page_table"], num_pages)
        out = {}
        for name in new:
            if name in DRAFT_POOL_KEYS:
                m = page_on.reshape((num_pages,)
                                    + (1,) * (new[name].ndim - 1))
                out[name] = jnp.where(m, new[name], old[name])
            else:
                out[name] = select_rows(mask, new[name], old[name], 0)
        return out
    return {name: select_rows(mask, new[name], old[name], 0) for name in new}
