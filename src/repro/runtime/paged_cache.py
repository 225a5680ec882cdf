"""Paged KV cache: fixed-size blocks + per-sequence block tables.

The contiguous serving cache pays ``O(max_len)`` HBM per request the
moment it is admitted — exactly the decoded-operand data movement the
PuM literature says dominates modern workloads.  Here KV lives in a
pool of fixed-size pages (``[num_layers, num_blocks, block_size, n_kv,
hd]``, one pool for K and one for V; or, for latent attention, one
latent pool ``[num_layers, num_blocks, block_size, latent_dim]`` and no
V pool); a sequence owns an ordered list of page ids (its *block
table*), pages are handed out by a free-list allocator as the sequence
actually grows, and retirement returns them to the pool — memory
scales with live tokens, not ``max_len``.

Layout / invariants
- Page 0 is the **trash page**: never allocated, it absorbs writes
  from inactive slots and prefill padding, and block-table entries past
  a sequence's allocation point at it so every gather index is valid.
  Nothing masked-in ever reads it.
- Logical block ``j`` of a sequence holds tokens ``[j*bs, (j+1)*bs)``;
  ``block_tables[slot, j]`` is its physical page.  Token ``t`` lives at
  page ``block_tables[slot, t // bs]``, offset ``t % bs``.
- Every non-trash page is in exactly one of four states: on the free
  list (refcount 0), privately owned by a live slot (refcount 1),
  held by the prefix-cache trie (refcount 1 + one per pinning slot),
  or the trash page.  ``audit_partition`` asserts this partition.
- A slot may only *write* a page it owns exclusively; prefix pages
  pinned from the trie are read-only and the engine copy-on-writes
  (``cow_slot_page``) before the first write into a shared page.

Device state (``k_pages``/``v_pages``; ``v_pages`` is None for a latent
pool) is functionally updated inside
jitted prefill/decode steps; the host keeps the allocator, block
tables, and lengths, and re-materializes the small int32 view tensors
each step.
"""

from __future__ import annotations

import math
import zlib
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

TRASH_PAGE = 0


class PagedView(NamedTuple):
    """The jit-facing slice of the cache: pure arrays, a valid pytree.

    k_pages/v_pages: [L, num_blocks, block_size, n_kv, hd]; a latent
                     pool is k_pages [L, num_blocks, block_size,
                     latent_dim] with v_pages None
    block_tables:    [B, max_blocks_per_seq] int32 (physical page ids)
    lengths:         [B] int32 — tokens already present per sequence
    expert_counts:   what a step returns for an MoE model: rows routed
                     to each held expert, [MoE layers, held experts]
                     int32 (None going in)
    """

    k_pages: jax.Array
    v_pages: jax.Array | None
    block_tables: jax.Array
    lengths: jax.Array
    expert_counts: jax.Array | None = None

    @property
    def block_size(self) -> int:
        return self.k_pages.shape[2]


class BlockAllocator:
    """Free-list page allocator with refcounts and reservations.

    ``reserve(n)`` earmarks capacity (legacy worst-case admission;
    the prefix-cache engine admits unreserved and preempts instead);
    ``alloc(n)`` pops pages at refcount 1.  Sharing — a prefix page
    pinned by several sequences, or held by the trie — is expressed
    via ``incref``/``decref``; a page returns to the free list exactly
    when its refcount drops to zero.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (page 0 is reserved trash)")
        self.num_blocks = num_blocks
        self._free: list[int] = list(range(num_blocks - 1, TRASH_PAGE, -1))
        self._refcount = np.zeros((num_blocks,), np.int32)
        self._reserved = 0
        self.peak_in_use = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    @property
    def reserved(self) -> int:
        return self._reserved

    def can_reserve(self, n: int) -> bool:
        return n <= len(self._free) - self._reserved

    def reserve(self, n: int) -> None:
        if not self.can_reserve(n):
            raise RuntimeError(
                f"reservation of {n} blocks exceeds free capacity "
                f"({len(self._free)} free, {self._reserved} reserved)")
        self._reserved += n

    def release_reservation(self, n: int) -> None:
        assert 0 <= n <= self._reserved, (n, self._reserved)
        self._reserved -= n

    def alloc(self, n: int = 1, *, reserved: bool = True) -> list[int]:
        """Pop ``n`` pages; ``reserved=True`` consumes reservations."""
        if reserved:
            if n > self._reserved:
                raise RuntimeError(f"alloc({n}) exceeds reservation "
                                   f"({self._reserved})")
            self._reserved -= n
        elif n > len(self._free) - self._reserved:
            raise RuntimeError(f"alloc({n}) exceeds unreserved capacity")
        out = [self._free.pop() for _ in range(n)]
        self._refcount[out] = 1
        self.peak_in_use = max(self.peak_in_use, self.blocks_in_use)
        return out

    # --------------------------------------------------------- refcounts
    def refcount(self, block: int) -> int:
        return int(self._refcount[block])

    def incref(self, block: int) -> None:
        assert block != TRASH_PAGE and self._refcount[block] > 0, block
        self._refcount[block] += 1

    def decref(self, block: int) -> None:
        """Drop one reference; the page frees when the count hits 0."""
        assert block != TRASH_PAGE and self._refcount[block] > 0, block
        self._refcount[block] -= 1
        if self._refcount[block] == 0:
            self._free.append(block)

    def free(self, blocks: list[int]) -> None:
        """Release exclusively-held pages (refcount must be 1)."""
        for b in blocks:
            assert b != TRASH_PAGE and b not in self._free, b
            assert self._refcount[b] == 1, (b, self._refcount[b])
            self.decref(b)


class PagedKVCache:
    """Page pool + per-slot block tables for a fixed set of decode slots."""

    def __init__(self, *, num_layers: int, num_kv_heads: int, head_dim: int,
                 num_slots: int, block_size: int, num_blocks: int,
                 max_blocks_per_seq: int, dtype=jnp.float32,
                 latent_dim: int = 0):
        """``latent_dim`` > 0 makes one latent pool of that many numbers
        per token (``num_kv_heads``/``head_dim`` unused)."""
        self.block_size = block_size
        self.num_slots = num_slots
        self.max_blocks_per_seq = max_blocks_per_seq
        self.dtype = jnp.dtype(dtype)
        if latent_dim:
            self.k_pages = jnp.zeros(
                (num_layers, num_blocks, block_size, latent_dim), self.dtype)
            self.v_pages = None
        else:
            shape = (num_layers, num_blocks, block_size, num_kv_heads,
                     head_dim)
            self.k_pages = jnp.zeros(shape, self.dtype)
            self.v_pages = jnp.zeros(shape, self.dtype)
        self.allocator = BlockAllocator(num_blocks)
        # host-side metadata; rows of unused slots point at the trash page
        self.block_tables = np.full((num_slots, max_blocks_per_seq),
                                    TRASH_PAGE, np.int32)
        self.lengths = np.zeros((num_slots,), np.int32)
        self.slot_blocks: list[list[int]] = [[] for _ in range(num_slots)]
        # subset of slot_blocks[i] pinned from the prefix trie: read-only
        # for this slot; a write there must go through cow_slot_page.
        self.slot_shared: list[set[int]] = [set() for _ in range(num_slots)]

    # ------------------------------------------------------------ geometry
    def blocks_for(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.block_size))

    @property
    def pools(self) -> list:
        """The page pools held: K and V, or the one latent pool."""
        return [p for p in (self.k_pages, self.v_pages) if p is not None]

    @property
    def bytes_per_block(self) -> int:
        # every pool's page, every layer
        return (sum(math.prod(p.shape) // p.shape[1] for p in self.pools)
                * self.dtype.itemsize)

    def kv_bytes_in_use(self) -> int:
        return self.allocator.blocks_in_use * self.bytes_per_block

    def peak_kv_bytes(self) -> int:
        return self.allocator.peak_in_use * self.bytes_per_block

    @staticmethod
    def contiguous_bytes(num_seqs: int, max_len: int, num_layers: int,
                         num_kv_heads: int, head_dim: int, dtype) -> int:
        """Footprint of the old `[L, B, max_len, n_kv, hd]` x2 cache."""
        return (2 * num_layers * num_seqs * max_len * num_kv_heads
                * head_dim * jnp.dtype(dtype).itemsize)

    def register_metrics(self, scope) -> None:
        """Register allocator/pool gauges under ``engine.pages.*`` in a
        metrics scope (duck-typed ``telemetry.Scope`` — this module
        never imports the telemetry machinery).  Callback-backed, so
        reads always see the live free list."""
        a = self.allocator
        scope.gauge("engine.pages.free", lambda: a.free_blocks,
                    help="pages on the free list")
        scope.gauge("engine.pages.in_use", lambda: a.blocks_in_use)
        scope.gauge("engine.pages.peak", lambda: a.peak_in_use)
        scope.gauge("engine.pages.bytes_in_use", self.kv_bytes_in_use)
        scope.gauge("engine.pages.peak_bytes", self.peak_kv_bytes)

    # ------------------------------------------------------------ slot ops
    def bind_slot(self, slot: int, prompt_tokens: int,
                  shared: Sequence[int] = (), *,
                  reserved: bool = True) -> list[int]:
        """Install the table row for a new sequence: ``shared`` pages
        (already pinned from the prefix trie, spliced read-only at the
        front) plus freshly allocated pages covering the rest of the
        prompt.  Returns the newly allocated (owned) pages."""
        assert not self.slot_blocks[slot], "slot already bound"
        need = self.blocks_for(prompt_tokens) - len(shared)
        assert need >= 0, (prompt_tokens, len(shared))
        owned = self.allocator.alloc(need, reserved=reserved) if need else []
        blocks = list(shared) + owned
        self.slot_blocks[slot] = blocks
        self.slot_shared[slot] = set(shared)
        self.block_tables[slot, :] = TRASH_PAGE
        self.block_tables[slot, : len(blocks)] = blocks
        self.lengths[slot] = prompt_tokens
        return owned

    def cow_slot_page(self, slot: int, col: int) -> tuple[int, int]:
        """Copy-on-write logical block ``col``: allocate a private page,
        copy the shared page's contents (all layers, K and V), and swap
        the table entry.  The shared page keeps its trie reference (the
        engine unpins it); the slot now owns the copy.  Returns
        ``(old_page, new_page)``."""
        old = self.slot_blocks[slot][col]
        assert old in self.slot_shared[slot], (slot, col, old)
        (new,) = self.allocator.alloc(1, reserved=False)
        self.k_pages = self.k_pages.at[:, new].set(self.k_pages[:, old])
        if self.v_pages is not None:
            self.v_pages = self.v_pages.at[:, new].set(self.v_pages[:, old])
        self.slot_blocks[slot][col] = new
        self.slot_shared[slot].discard(old)
        self.block_tables[slot, col] = new
        return old, new

    def ensure_capacity(self, slot: int, *, reserved: bool = True) -> None:
        """Grow the slot by one page iff the next write crosses into an
        unallocated logical block (lazy)."""
        pos = int(self.lengths[slot])
        owned = len(self.slot_blocks[slot])
        if pos == owned * self.block_size:
            if owned >= self.max_blocks_per_seq:
                raise RuntimeError(
                    f"slot {slot} exceeded max_blocks_per_seq={owned}")
            (blk,) = self.allocator.alloc(1, reserved=reserved)
            self.slot_blocks[slot].append(blk)
            self.block_tables[slot, owned] = blk

    def release_slot(self, slot: int) -> int:
        """Retire a sequence: owned pages go back to the free list;
        shared (trie-pinned) pages are left to the engine's unpin.
        Returns the number of owned pages freed."""
        shared = self.slot_shared[slot]
        owned = [b for b in self.slot_blocks[slot] if b not in shared]
        self.allocator.free(owned)
        self.clear_slot(slot)
        return len(owned)

    def clear_slot(self, slot: int) -> list[int]:
        """Detach a slot without freeing anything (the caller has
        transferred page ownership, e.g. into the prefix trie).
        Returns the block list the slot held."""
        blocks = self.slot_blocks[slot]
        self.slot_blocks[slot] = []
        self.slot_shared[slot] = set()
        self.block_tables[slot, :] = TRASH_PAGE
        self.lengths[slot] = 0
        return blocks

    # ------------------------------------------------------- migration
    def export_slot(self, slot: int) -> tuple[np.ndarray, np.ndarray | None]:
        """Copy a slot's KV pages out of the pool for migration to
        another worker's cache: the disaggregation handoff unit.  The
        result is host-resident (``np``) page *content* in block-table
        order — ``[L, n_pages, bs, n_kv, hd]`` for K and V (a latent
        pool's ``[L, n_pages, bs, latent_dim]`` and None) — exactly
        what :meth:`import_slot` scatters into a peer pool, so the
        decode side never recomputes prefill.  Shared (trie-pinned)
        pages are exported too: the importing pool has no notion of
        this pool's trie, so it gets private copies of everything.
        Positions past ``lengths[slot]`` in the final page ride along
        unmasked-garbage-for-unmasked-garbage; every attend masks by
        length on both sides."""
        blocks = self.slot_blocks[slot]
        assert blocks, f"slot {slot} has no pages to export"
        idx = jnp.asarray(blocks, jnp.int32)
        k = np.asarray(self.k_pages[:, idx])
        v = None if self.v_pages is None else np.asarray(self.v_pages[:, idx])
        return k, v

    def import_slot(self, slot: int, length: int, k_pages: np.ndarray,
                    v_pages: np.ndarray | None, *, reserved: bool = False
                    ) -> list[int]:
        """Adopt migrated KV content into this pool: allocate fresh
        pages, scatter the exported bytes in, and bind the slot as if
        it had prefilled here (owned pages, no shared set).  The
        physical page ids differ from the exporter's — only *content*
        and block-table order migrate, which is all the paged kernels
        read.  Returns the newly allocated block list."""
        assert not self.slot_blocks[slot], "slot already bound"
        n = k_pages.shape[1]
        got = (k_pages.shape[:1] + k_pages.shape[2:], v_pages is None)
        want = (self.k_pages.shape[:1] + self.k_pages.shape[2:],
                self.v_pages is None)
        assert got == want, (
            f"page geometry mismatch: import {got} vs pool {want}")
        assert n == self.blocks_for(length), (n, length, self.block_size)
        assert n <= self.max_blocks_per_seq, (n, self.max_blocks_per_seq)
        blocks = self.allocator.alloc(n, reserved=reserved)
        idx = jnp.asarray(blocks, jnp.int32)
        self.k_pages = self.k_pages.at[:, idx].set(
            jnp.asarray(k_pages, self.dtype))
        if v_pages is not None:
            self.v_pages = self.v_pages.at[:, idx].set(
                jnp.asarray(v_pages, self.dtype))
        self.slot_blocks[slot] = blocks
        self.slot_shared[slot] = set()
        self.block_tables[slot, :] = TRASH_PAGE
        self.block_tables[slot, : n] = blocks
        self.lengths[slot] = length
        return blocks

    # ------------------------------------------------------- checksums
    def page_checksum(self, page: int) -> int:
        """CRC32 over a page's bytes in every pool (K and V, or the
        latent pool), all layers.  The engine's optional per-tick
        checksum audit records this after every legitimate write and
        verifies it before the next dispatch, so a bit flip in stored KV
        is caught before it is ever attended."""
        crc = 0
        for pool in self.pools:
            crc = zlib.crc32(np.asarray(pool[:, page]).tobytes(), crc)
        return crc

    def corrupt_page(self, page: int) -> None:
        """Chaos-test helper: deterministically flip one stored element
        of ``page`` to a value it cannot already hold (7 -> 11, else
        -> 7), guaranteeing the checksum changes in every KV dtype."""
        assert page != TRASH_PAGE, "corrupting the trash page is a no-op"
        at = (0, page) + (0,) * (self.k_pages.ndim - 2)
        cur = self.k_pages[at]
        bad = jnp.where(cur == 7, jnp.asarray(11, self.dtype),
                        jnp.asarray(7, self.dtype))
        self.k_pages = self.k_pages.at[at].set(bad)

    # ------------------------------------------------------------ audit
    def audit_partition(self, trie_pages: set[int],
                        trie_pins: dict[int, int] | None = None) -> None:
        """Assert the page partition invariant: free ∪ slot-owned ∪
        trie ∪ {trash} covers every page exactly once, and refcounts
        agree (owned pages 1; trie pages 1 + one per pinning slot)."""
        alloc = self.allocator
        free = set(alloc._free)
        owned: set[int] = set()
        pins: dict[int, int] = {}
        for slot in range(self.num_slots):
            shared = self.slot_shared[slot]
            for b in self.slot_blocks[slot]:
                if b in shared:
                    assert b in trie_pages, (slot, b, "shared not in trie")
                    pins[b] = pins.get(b, 0) + 1
                else:
                    assert b not in owned, (slot, b, "owned twice")
                    owned.add(b)
        assert TRASH_PAGE not in free | owned | trie_pages
        assert not free & owned, free & owned
        assert not free & trie_pages, free & trie_pages
        assert not owned & trie_pages, owned & trie_pages
        universe = free | owned | trie_pages | {TRASH_PAGE}
        assert universe == set(range(alloc.num_blocks)), (
            set(range(alloc.num_blocks)) - universe)
        for b in free:
            assert alloc.refcount(b) == 0, (b, alloc.refcount(b))
        for b in owned:
            assert alloc.refcount(b) == 1, (b, alloc.refcount(b))
        for b in trie_pages:
            assert alloc.refcount(b) == 1 + pins.get(b, 0), (
                b, alloc.refcount(b), pins.get(b, 0))
        if trie_pins is not None:
            for b, n in pins.items():
                assert trie_pins.get(b, 0) == n, (b, trie_pins.get(b), n)

    # ------------------------------------------------------------ views
    def view(self, slots: list[int] | None = None,
             cols: int | None = None) -> PagedView:
        """Device view of all slots (decode) or a subset (prefill).

        ``cols`` trims the block table to its first ``cols`` logical
        columns — the paged decode kernel's grid is ``(B, cols)``, so
        slicing off dead columns (no live sequence reaches them) skips
        their grid steps entirely."""
        bt, ln = self.block_tables, self.lengths
        if slots is not None:
            bt, ln = bt[slots], ln[slots]
        if cols is not None:
            bt = bt[:, :cols]
        return PagedView(self.k_pages, self.v_pages,
                         jnp.asarray(bt), jnp.asarray(ln))

    def update_pages(self, view: PagedView) -> None:
        """Adopt page arrays returned by a jitted prefill/decode step."""
        self.k_pages = view.k_pages
        self.v_pages = view.v_pages
