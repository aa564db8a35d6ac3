"""KV cache management for the real inference engine.

Two layouts coexist:

- **Paged** (default execution plane): a pool of fixed-size pages
  shared by all sequences.  :class:`PageAllocator` hands out page ids
  from a free list; :class:`PagedKVManager` keeps per-slot page tables
  (logical position ``t`` of slot ``b`` lives at page
  ``table[b, t // page_size]``, offset ``t % page_size``) and grows /
  reclaims them as requests prefill, decode, and retire.  Attention
  K/V storage indexed this way never needs contiguous per-sequence
  rows, so long prompts can't fragment the cache.

- **Slot-based** (legacy / fallback): caches pre-allocated for
  ``n_slots`` sequences of ``max_len`` tokens; :class:`SlotManager`
  tracks occupancy and ``insert_rows``/``clear_rows`` do the tree
  surgery.  Still used for batch-row bookkeeping in both planes and for
  state that is O(1) per sequence (SSM/conv state, sliding-window
  rings), where paging has nothing to win.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


class SlotManager:
    """Batch-row allocator.  The free list is a min-heap, so ``alloc``
    keeps the deterministic lowest-id-first order at O(log n) per
    alloc/free instead of the former O(n log n) re-sort per free."""

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self._free = list(range(n_slots))  # already heap-ordered
        self.owner: dict[int, object] = {}

    def alloc(self, owner=None) -> Optional[int]:
        if not self._free:
            return None
        slot = heapq.heappop(self._free)
        self.owner[slot] = owner
        return slot

    def free(self, slot: int) -> None:
        # a double free would put the same id on the free list twice and
        # eventually hand one slot to two requests — fail loudly instead
        # (mirrors PageAllocator.free)
        assert slot in self.owner, f"double free of slot {slot}"
        del self.owner[slot]
        heapq.heappush(self._free, slot)

    @property
    def n_free(self) -> int:
        return len(self._free)

    def active_slots(self) -> list[int]:
        return sorted(self.owner.keys())


# ---------------------------------------------------------------------------
# Paged allocation
# ---------------------------------------------------------------------------


class PageAllocator:
    """Free-list allocator over a pool of `n_pages` fixed-size pages."""

    def __init__(self, n_pages: int, page_size: int):
        assert n_pages > 0 and page_size > 0
        self.n_pages = n_pages
        self.page_size = page_size
        self._free = list(range(n_pages))
        self._owner: dict[int, object] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self, n: int, owner=None) -> Optional[list[int]]:
        """Allocate `n` pages atomically; None if the pool can't."""
        if n < 0 or n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._owner[p] = owner
        return pages

    def free(self, pages) -> None:
        for p in pages:
            assert p in self._owner, f"double free of page {p}"
            del self._owner[p]
            self._free.append(p)

    def owner_of(self, page: int):
        return self._owner.get(page)


class PagedKVManager:
    """Per-slot page tables over a shared :class:`PageAllocator`.

    The table is a dense ``(n_slots, max_pages)`` int32 array with -1
    for unallocated entries — the exact operand the paged attention
    paths (jnp gather and the Pallas kernel's scalar-prefetch index
    map) consume, so ``jnp.asarray(kv.table)`` is the whole handoff.
    """

    def __init__(self, n_slots: int, max_len: int, page_size: int,
                 n_pages: Optional[int] = None, device=None):
        self.page_size = page_size
        # the engine's home device: the uploaded table lands beside the
        # page pool it indexes (None = JAX's default placement)
        self.device = device
        self.max_pages = -(-max_len // page_size)
        self.n_slots = n_slots
        if n_pages is None:
            n_pages = n_slots * self.max_pages
        self.alloc = PageAllocator(n_pages, page_size)
        self.table = np.full((n_slots, self.max_pages), -1, np.int32)
        self._n_pages_of = np.zeros(n_slots, np.int32)
        # device-mirror invalidation: ensure/release flip this so
        # device_table() re-uploads only when allocation actually
        # changed — steady-state decode blocks reuse the resident copy
        self.dirty = True
        self._table_dev = None
        # optional PrefixCache (attach_prefix_cache): shared prefix
        # pages referenced by slot tables, refcounted by the cache
        self.prefix = None

    @property
    def n_pages(self) -> int:
        return self.alloc.n_pages

    @property
    def n_free_pages(self) -> int:
        return self.alloc.n_free

    @property
    def n_available_pages(self) -> int:
        """Pages a new allocation could obtain: the free list plus
        cached-but-unreferenced prefix pages (evictable on demand)."""
        free = self.alloc.n_free
        if self.prefix is not None:
            free += self.prefix.n_reclaimable
        return free

    # -- prefix cache (page-level KV reuse across requests) ------------------
    def attach_prefix_cache(self, cache) -> None:
        """Wire a :class:`~repro.serving.prefix_cache.PrefixCache` over
        this manager's allocator.  From here on ``release`` arbitrates
        each page with the cache (shared pages deref instead of free)
        and ``ensure`` evicts unreferenced cached pages when the free
        list runs dry."""
        assert cache.alloc is self.alloc, (
            "prefix cache must share this manager's PageAllocator"
        )
        self.prefix = cache

    def lookup_prefix(self, slot: int, token_ids) -> int:
        """Point a *fresh* slot's table at the longest cached prefix of
        ``token_ids`` (pages pinned by the cache); returns the hit
        length in tokens.  The engine then prefills from that offset —
        all subsequent writes land in private pages past the shared
        span (the hit is full-page-aligned by construction)."""
        if self.prefix is None:
            return 0
        assert int(self._n_pages_of[slot]) == 0, (
            f"lookup_prefix needs a fresh slot (slot {slot} holds pages)"
        )
        pages, hit = self.prefix.lookup(token_ids)
        if pages:
            self.table[slot, : len(pages)] = pages
            self._n_pages_of[slot] = len(pages)
            self.dirty = True
        return hit

    def publish_prefix(self, slot: int, token_ids) -> int:
        """Register a prefill-complete slot's full-page prefix span in
        the cache; returns pages newly published."""
        if self.prefix is None:
            return 0
        return self.prefix.publish(self.pages_of(slot), token_ids)

    def peek_prefix(self, token_ids) -> int:
        """Hit length a lookup would return — read-only (the admission
        path budgets with this)."""
        if self.prefix is None or token_ids is None:
            return 0
        return self.prefix.peek(token_ids)

    def pages_of(self, slot: int) -> list[int]:
        return [int(p) for p in
                self.table[slot, : int(self._n_pages_of[slot])]]

    def n_pages_held(self, slot: int) -> int:
        return int(self._n_pages_of[slot])

    def device_table(self):
        """The page table as a device-resident jnp array, re-uploaded
        lazily: only allocation changes (``ensure`` growth /
        ``release``) invalidate the cached copy, so back-to-back
        decode steps hand the SAME buffer to the jitted step — no
        per-token host->device table upload."""
        if self._table_dev is None or self.dirty:
            self._table_dev = jax.device_put(self.table, self.device)
            self.dirty = False
        return self._table_dev

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow slot's table to cover `n_tokens`; False if out of pages
        (the slot's existing pages are untouched on failure)."""
        need = -(-n_tokens // self.page_size)
        if need > self.max_pages:
            return False
        have = int(self._n_pages_of[slot])
        if need <= have:
            return True
        got = self.alloc.alloc(need - have, owner=slot)
        if got is None and self.prefix is not None:
            # free list dry but unreferenced cached pages exist: evict
            # LRU prefix pages back into the pool and retry once
            short = (need - have) - self.alloc.n_free
            if self.prefix.evict(short) >= short:
                got = self.alloc.alloc(need - have, owner=slot)
        if got is None:
            return False
        self.table[slot, have:need] = got
        self._n_pages_of[slot] = need
        self.dirty = True
        return True

    def release(self, slot: int) -> None:
        n = int(self._n_pages_of[slot])
        if n:
            for p in self.table[slot, :n]:
                p = int(p)
                # shared prefix pages deref (the cache decides when the
                # allocator gets them back); private pages free now
                if self.prefix is not None and self.prefix.release_page(p):
                    continue
                self.alloc.free([p])
            self.dirty = True
        self.table[slot, :] = -1
        self._n_pages_of[slot] = 0

    def truncate(self, slot: int, n_tokens: int) -> int:
        """Shrink slot's table to cover exactly ``n_tokens`` — the
        speculative-decode rollback: pages wholly past the accepted
        length go back to the pool (prefix-shared pages deref, exactly
        like :meth:`release`).  Returns pages freed.  A prefix-cache
        hit span is full-page-aligned and the engine never truncates
        below the resident position, so pinned prefix pages are only
        ever touched via the same deref arbitration as release."""
        need = -(-n_tokens // self.page_size) if n_tokens > 0 else 0
        have = int(self._n_pages_of[slot])
        if need >= have:
            return 0
        for p in self.table[slot, need:have]:
            p = int(p)
            if self.prefix is not None and self.prefix.release_page(p):
                continue
            self.alloc.free([p])
        self.table[slot, need:have] = -1
        self._n_pages_of[slot] = need
        self.dirty = True
        return have - need


# ---------------------------------------------------------------------------
# P/D hand-off: materialize / install one sequence's KV state
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KVPayload:
    """One request's cache contents + generation state, materialized
    for a device-to-device hand-off (paper §6).

    ``kv`` mirrors the engine's paged-cache pytree with attention
    leaves linearized to token-major ``(lead..., H, n_tokens, D)`` —
    page-layout-free, so the destination may use a different page size
    — and O(1)-per-sequence state (SSM/conv) as bare slot rows.
    """

    rid: int
    n_tokens: int        # cached tokens (absolute position of the next)
    last_token: int      # feeds the first decode step on the destination
    prefill_progress: int
    kv: list             # per-segment pytree (see above)

    @property
    def nbytes(self) -> int:
        """Actual payload size — what the TLManager should cost."""
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(self.kv)))


def _gather_pages_leaf(leaf, page_ids, n_tokens):
    """(lead..., NP, H, ps, D) -> contiguous (lead..., H, n_tokens, D)."""
    from repro.kernels import ops

    lead = leaf.shape[:-4]
    flat = leaf.reshape((-1,) + leaf.shape[len(lead):])
    out = jax.vmap(lambda p: ops.page_gather(p, page_ids))(flat)
    out = out[:, :, :n_tokens, :]
    return out.reshape(lead + out.shape[1:])


def _scatter_pages_leaf(leaf, page_ids, seq):
    """Install contiguous ``seq`` (lead..., H, T, D) into the pool's
    ``page_ids`` (the destination allocator's choice); T is padded to
    the destination's page multiple, so source and destination page
    sizes may differ."""
    ps = leaf.shape[-2]
    m = page_ids.shape[0]
    t = seq.shape[-2]
    pad = m * ps - t
    assert pad >= 0, (m, ps, t)
    seq = jnp.pad(seq, [(0, 0)] * (seq.ndim - 2) + [(0, pad), (0, 0)])
    chunks = seq.reshape(seq.shape[:-2] + (m, ps, seq.shape[-1]))
    chunks = jnp.swapaxes(chunks, -4, -3)  # (lead..., M, H, ps, D)
    return leaf.at[..., page_ids, :, :, :].set(chunks.astype(leaf.dtype))


def gather_slot_kv(caches, axes, slot: int, page_ids, n_tokens: int):
    """Materialize slot's cache state: paged attention leaves gathered
    contiguous through ``page_ids``; per-slot leaves (axis != None)
    extracted as bare rows."""
    page_ids = jnp.asarray(page_ids, jnp.int32)

    def take(full, ax):
        if ax is None:
            return _gather_pages_leaf(full, page_ids, n_tokens)
        return jax.lax.index_in_dim(full, slot, axis=ax, keepdims=False)

    return jax.tree.map(take, caches, axes)


def scatter_slot_kv(caches, axes, slot: int, page_ids, payload_kv):
    """Inverse of :func:`gather_slot_kv` on the destination engine."""
    page_ids = jnp.asarray(page_ids, jnp.int32)

    def put(full, ax, part):
        if ax is None:
            return _scatter_pages_leaf(full, page_ids, part)
        return jax.lax.dynamic_update_index_in_dim(
            full, part.astype(full.dtype), slot, axis=ax
        )

    return jax.tree.map(put, caches, axes, payload_kv)


# ---------------------------------------------------------------------------
# Slot-layout tree surgery (legacy plane + non-paged leaves)
# ---------------------------------------------------------------------------


def insert_rows(cache, new, axes, slots, src_rows=None):
    """Copy per-sequence rows of `new` into `cache` at `slots`.

    cache/new: same-structure pytrees; axes: pytree of batch-axis ints;
    slots: list of destination slot indices; src_rows: matching source
    row indices in `new` (default 0..len-1).
    """
    if src_rows is None:
        src_rows = list(range(len(slots)))

    def put(full, part, ax):
        for dst, src in zip(slots, src_rows):
            row = jax.lax.index_in_dim(part, src, axis=ax, keepdims=False)
            full = jax.lax.dynamic_update_index_in_dim(
                full, row.astype(full.dtype), dst, axis=ax
            )
        return full

    return jax.tree.map(put, cache, new, axes)


def clear_rows(cache, axes, slots):
    """Zero the given slots (pos arrays get -1).

    Leaves whose axis is None (paged K/V pools: reclaimed by the
    PageAllocator, never by row) pass through untouched.
    """
    def wipe(full, ax):
        if ax is None:
            return full
        for s in slots:
            row = jax.lax.index_in_dim(full, s, axis=ax, keepdims=False)
            fill = (jnp.full_like(row, -1)
                    if full.dtype == jnp.int32 else jnp.zeros_like(row))
            full = jax.lax.dynamic_update_index_in_dim(
                full, fill, s, axis=ax
            )
        return full

    return jax.tree.map(wipe, cache, axes)
