"""Paged KV cache: fixed-size blocks in one preallocated device pool.

The single-stream decode path (``generation.init_kv_cache``) reserves
``max_len`` cache slots per sequence up front — fine for one request, fatal
for serving: a 16-token reply and a 2k-token reply would each pin
``max_len`` slots, so heterogeneous traffic wastes most of HBM on slots that
are never written. The paged design (vLLM's PagedAttention, arXiv:2309.06180)
carves ONE preallocated pool into fixed-size blocks:

- device side: ``{"k","v"}: [L, num_blocks, block_size, Hkv, D]`` — allocated
  once at engine start, never resized (no allocation churn, no recompiles);
- host side: :class:`BlockAllocator` — a free list plus per-sequence block
  tables mapping logical block index -> physical block. Sequences grow one
  block at a time (``append``), release everything on completion/eviction
  (``free``), and the freed blocks are immediately reusable by any sequence,
  so memory tracks the LIVE token count instead of the worst case.

Physical block 0 is reserved as the **null block**: inactive batch slots and
padded table entries point at it, so their (masked, never-read) scatter
writes can never corrupt a live sequence's cache.

**Automatic prefix caching** (``prefix_caching=True``, vLLM's automatic
prefix caching applied to this pool): every FULL block is content-addressed
by a hash chained over its token ids (``h_i = H(h_{i-1}, tokens_i)``), so a
block match is a whole-prefix match by construction. Admission looks up the
longest cached block-aligned prefix of the new request's tokens
(:meth:`BlockAllocator.plan_prefix`) and maps those physical blocks straight
into the new block table with a reference count bump — only the uncached
tail is ever prefilled again. Shared blocks are immutable (full, and every
write the engine issues lands at positions at or past the uncached tail);
the one aligned edge case — the whole prefix matches, but the engine still
needs the last position's logits to sample — is handled by **copy-on-write**:
the final matched block is copied into a private block before the sequence
touches it, so a shared block is never written, period. ``free`` decrements
refcounts; a cached block whose count reaches zero parks in an LRU pool
(content intact, still matchable) and is only truly reclaimed when the free
list runs dry — reclaim-before-reject, so caching can never cause an
admission rejection that an uncached pool would have accepted.

This module is the HOST side only. The pool's device side — its format, the
null block, a layer's write and the attention over a row's blocks (Pallas
kernels on the TPU, their XLA gather twin elsewhere) — is
``ops.flash_attention``'s; the constants come from there, never the reverse.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..ops.flash_attention import NULL_BLOCK
from ..telemetry import metrics as _metrics

__all__ = [
    "BlockPoolExhausted",
    "BlockAllocatorError",
    "BlockAllocator",
    "PrefixPlan",
    "PrefixAllocation",
]


class BlockAllocatorError(RuntimeError):
    """Misuse of the allocator: double-free, append/lookup after free."""


class BlockPoolExhausted(RuntimeError):
    """No free block available — the scheduler should preempt or defer."""


def _chain_hash(prev: bytes, block_tokens: np.ndarray) -> bytes:
    """Hash of one full block chained over everything before it: a block's
    identity is (all preceding tokens, its own tokens) — so a single-block
    match IS a whole-prefix match. blake2b-128: collisions are what would
    silently splice one request's KV into another, so a real hash, not CRC."""
    return hashlib.blake2b(
        prev + np.asarray(block_tokens, np.int32).tobytes(), digest_size=16
    ).digest()


@dataclass(frozen=True)
class PrefixPlan:
    """Read-only admission plan for one token prefix (``plan_prefix``).

    ``matched`` are the cached physical blocks covering the longest cached
    block-aligned prefix; ``cached_tokens`` is how many leading tokens need
    NO prefill; ``cow`` flags the aligned edge case (the whole prefix is
    cached — the last matched block will be copied-on-write so the engine
    can recompute the final position's logits in a private block);
    ``fresh_blocks`` is what allocation will actually take from the pool —
    the only number admission accounting should charge. ``lru_pinned``
    counts matched blocks currently sitting in the reclaimable LRU pool:
    they are part of ``available_blocks`` today but this mapping will pin
    them, so admission must charge ``fresh_blocks + lru_pinned`` against
    the availability watermark (or the allocation it green-lit would
    throw)."""

    matched: "tuple[int, ...]"
    hashes: "tuple[bytes, ...]"
    cached_tokens: int
    cow: bool
    fresh_blocks: int
    lru_pinned: int = 0


@dataclass(frozen=True)
class PrefixAllocation:
    """Result of :meth:`BlockAllocator.allocate_with_prefix`: the block
    table, how many leading tokens are already cached (the engine prefills
    only from there), and the copy-on-write pair ``(src, dst)`` the engine
    must apply to the device pool BEFORE any write (``None`` when no COW)."""

    table: "list[int]"
    cached_tokens: int
    cow: "Optional[tuple[int, int]]"


class BlockAllocator:
    """Host-side block bookkeeping for one device pool.

    Free blocks live on a LIFO free list (hot reuse: a just-freed block is
    handed out next, so the working set stays compact). Per-sequence state is
    a block table (physical block ids, logical order) plus the sequence's
    token count; ``append`` grows the table only when the token count crosses
    a block boundary. Fragmentation here is purely INTERNAL (the unwritten
    tail of each sequence's last block) — fixed-size blocks cannot fragment
    externally, which is the point of paging.

    With ``prefix_caching=True`` every block carries a reference count and
    full blocks are content-addressed (module docstring has the full story):
    ``allocate_with_prefix`` maps cached blocks into new tables, ``free``
    only releases a block when its refcount hits zero, and zero-reference
    cached blocks park in an LRU pool reclaimed on demand before any
    exhaustion error. ``prefix_caching=False`` keeps every legacy code path
    byte-identical (refcounts exist but are always exactly one).
    """

    def __init__(self, num_blocks: int, block_size: int, *, prefix_caching: bool = False):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the reserved null block)")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.prefix_caching = prefix_caching
        # LIFO: lowest ids are handed out first at start, re-frees come back
        # on top. Block 0 is never on the list (reserved null block).
        self._free: "list[int]" = list(range(num_blocks - 1, 0, -1))
        self._tables: "dict[object, list[int]]" = {}
        self._tokens: "dict[object, int]" = {}
        # prefix-cache state (inert when prefix_caching is False):
        self._ref: "dict[int, int]" = {}  # physical block -> reference count
        self._cached: "dict[bytes, int]" = {}  # chain hash -> physical block
        self._block_hash: "dict[int, bytes]" = {}  # physical block -> chain hash
        #: cached blocks with zero references, oldest-unreferenced first —
        #: matchable until reclaimed by :meth:`_take_block`
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        #: per-sequence chain hashes of its full blocks registered so far
        self._chain: "dict[object, list[bytes]]" = {}
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.cow_copies = 0
        self.reclaimed_blocks = 0

    # -- capacity ------------------------------------------------------------

    @property
    def usable_blocks(self) -> int:
        """Allocatable blocks (pool minus the null block)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def reclaimable_blocks(self) -> int:
        """Cached-but-unreferenced blocks (the LRU pool): matchable today,
        reclaimed on demand when the free list runs dry."""
        return len(self._lru)

    @property
    def available_blocks(self) -> int:
        """What an allocation can actually draw on: truly free blocks plus
        the reclaimable LRU pool. This is the admission-accounting number —
        caching must never reject a request an uncached pool would admit."""
        return len(self._free) + len(self._lru)

    @property
    def used_blocks(self) -> int:
        """Blocks referenced by live sequences (shared blocks count once)."""
        return self.usable_blocks - self.available_blocks

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens``."""
        return max(1, -(-n_tokens // self.block_size))

    def can_allocate(self, n_tokens: int) -> bool:
        return self.blocks_for(n_tokens) <= self.available_blocks

    # -- prefix cache internals ----------------------------------------------

    def _take_block(self) -> int:
        """Pop a truly free block, reclaiming the least-recently-unreferenced
        cached block when the free list is dry (its index entry dies with it
        — the content is about to be overwritten). Caller checked capacity."""
        if self._free:
            return self._free.pop()
        blk, _ = self._lru.popitem(last=False)  # oldest unreferenced first
        h = self._block_hash.pop(blk)
        del self._cached[h]
        self.reclaimed_blocks += 1
        _metrics.inc("accelerate_blocks_reclaimed_total")
        return blk

    def _unref(self, blk: int) -> None:
        self._ref[blk] = self._ref.get(blk, 1) - 1
        if self._ref[blk] > 0:
            return
        del self._ref[blk]
        if blk in self._block_hash:
            # content-addressed and intact: park in the LRU pool, matchable
            # until the free list runs dry and _take_block reclaims it
            self._lru[blk] = None
        else:
            self._free.append(blk)

    def _match_chain(self, token_ids: np.ndarray) -> "tuple[list[int], list[bytes]]":
        """Walk the chain hash over full blocks of ``token_ids``; stop at the
        first block missing from the content index."""
        blocks: "list[int]" = []
        hashes: "list[bytes]" = []
        prev = b""
        for i in range(len(token_ids) // self.block_size):
            h = _chain_hash(prev, token_ids[i * self.block_size : (i + 1) * self.block_size])
            blk = self._cached.get(h)
            if blk is None:
                break
            blocks.append(blk)
            hashes.append(h)
            prev = h
        return blocks, hashes

    def plan_prefix(self, token_ids) -> PrefixPlan:
        """Read-only: what would ``allocate_with_prefix`` reuse and take for
        this prefix? ``fresh_blocks`` is the pool charge (shared blocks are
        free); admission's watermark check compares it to
        :attr:`available_blocks`. Mutates nothing."""
        token_ids = np.asarray(token_ids, np.int32).reshape(-1)
        n = int(token_ids.size)
        total = self.blocks_for(n)
        if not self.prefix_caching:
            return PrefixPlan((), (), 0, False, total)
        matched, hashes = self._match_chain(token_ids)
        pinned = sum(1 for b in matched if b in self._lru)
        if matched and len(matched) * self.block_size == n:
            # whole prefix cached — COW the last matched block so the engine
            # can recompute the final position's logits in a private block
            return PrefixPlan(
                tuple(matched), tuple(hashes), n - 1, True,
                total - len(matched) + 1, pinned,
            )
        return PrefixPlan(
            tuple(matched), tuple(hashes),
            len(matched) * self.block_size, False, total - len(matched), pinned,
        )

    # -- lifecycle -----------------------------------------------------------

    def allocate(self, seq_id, n_tokens: int) -> "list[int]":
        """Create a sequence holding ``n_tokens`` (its prompt) from fresh
        blocks only; returns the block table. :class:`BlockPoolExhausted`
        when the pool can't cover it (nothing is allocated on failure —
        all-or-nothing). Prefix-aware admission goes through
        :meth:`allocate_with_prefix` instead."""
        if seq_id in self._tables:
            raise BlockAllocatorError(f"sequence {seq_id!r} already allocated")
        need = self.blocks_for(n_tokens)
        if need > self.available_blocks:
            raise BlockPoolExhausted(
                f"need {need} block(s) for {n_tokens} token(s), "
                f"only {self.available_blocks} free"
            )
        table = [self._take_block() for _ in range(need)]
        for blk in table:
            self._ref[blk] = 1
        self._tables[seq_id] = table
        self._tokens[seq_id] = n_tokens
        self._chain[seq_id] = []
        return list(table)

    def allocate_with_prefix(
        self, seq_id, token_ids, plan: "Optional[PrefixPlan]" = None
    ) -> PrefixAllocation:
        """Create a sequence for ``token_ids``, mapping the longest cached
        block-aligned prefix into its table (refcount++) and taking fresh
        blocks only for the uncached tail. All-or-nothing on exhaustion.
        With caching off this is exactly :meth:`allocate`. ``plan`` skips
        re-hashing when the caller just ran :meth:`plan_prefix` for the SAME
        tokens with no allocator mutation in between (the scheduler's
        admission loop) — a stale plan here would map the wrong blocks."""
        token_ids = np.asarray(token_ids, np.int32).reshape(-1)
        n = int(token_ids.size)
        if not self.prefix_caching:
            return PrefixAllocation(self.allocate(seq_id, n), 0, None)
        if seq_id in self._tables:
            raise BlockAllocatorError(f"sequence {seq_id!r} already allocated")
        if plan is None:
            plan = self.plan_prefix(token_ids)
        # matched blocks sitting in the LRU pool are counted available but are
        # about to be pinned by this very mapping — they can't also serve as
        # fresh blocks, so subtract them from what the tail can draw on
        if plan.fresh_blocks > self.available_blocks - plan.lru_pinned:
            raise BlockPoolExhausted(
                f"need {plan.fresh_blocks} fresh block(s) for {n} token(s) "
                f"({len(plan.matched)} cached), only "
                f"{self.available_blocks - plan.lru_pinned} available"
            )
        for blk in plan.matched:
            self._ref[blk] = self._ref.get(blk, 0) + 1
            self._lru.pop(blk, None)
        table = list(plan.matched)
        cow: "Optional[tuple[int, int]]" = None
        if plan.cow:
            dst = self._take_block()
            self._ref[dst] = 1
            src = table[-1]
            table[-1] = dst
            # src keeps the reference we took above until the engine has
            # actually copied its content on device (:meth:`cow_done`) —
            # releasing it now would park it in the LRU pool where another
            # admission in the SAME step could reclaim and overwrite it
            # before the copy reads it (use-after-free)
            cow = (src, dst)
        for _ in range(self.blocks_for(n) - len(table)):
            blk = self._take_block()
            self._ref[blk] = 1
            table.append(blk)
        self._tables[seq_id] = table
        self._tokens[seq_id] = n
        self._chain[seq_id] = list(plan.hashes)
        # content-index the full blocks of the UNCACHED tail right now, not
        # after prefill: a request admitted later in the SAME engine step can
        # then map them, and admission order == prefill order guarantees the
        # writer's prefill lands before any reader's (the engine prefills
        # admitted requests in order, and preemption only runs after the
        # step's prefill phase)
        self.register_full_blocks(seq_id, token_ids)
        self.prefix_lookups += 1
        if plan.cached_tokens:
            self.prefix_hits += 1
            self.prefix_hit_tokens += plan.cached_tokens
        if cow is not None:
            self.cow_copies += 1
            _metrics.inc("accelerate_cow_copies_total")
        return PrefixAllocation(list(table), plan.cached_tokens, cow)

    def cow_done(self, blk: int) -> None:
        """Release the copy-on-write pin on ``blk`` (the ``src`` half of a
        :class:`PrefixAllocation`'s ``cow`` pair). The engine calls this
        exactly once, AFTER the device-side block copy has been issued — the
        pin is what keeps a zero-reference cached source block out of the
        reclaimable pool while a copy still needs its content."""
        self._unref(blk)

    def register_full_blocks(self, seq_id, written_token_ids) -> int:
        """Content-index every full block of ``seq_id`` not yet registered.
        ``written_token_ids`` are the tokens whose KV the engine has actually
        written (prompt + generated-so-far); the engine calls this after
        prefill and whenever decode fills a block. Idempotent and incremental
        (the per-sequence chain state remembers where it left off); a no-op
        with caching off. Returns how many blocks were newly indexed."""
        if not self.prefix_caching:
            return 0
        if seq_id not in self._tables:
            raise BlockAllocatorError(
                f"register on unknown/freed sequence {seq_id!r} (use-after-free?)"
            )
        written = np.asarray(written_token_ids, np.int32).reshape(-1)
        table = self._tables[seq_id]
        chain = self._chain[seq_id]
        n_full = min(int(written.size) // self.block_size, len(table))
        new = 0
        while len(chain) < n_full:
            i = len(chain)
            h = _chain_hash(
                chain[-1] if chain else b"",
                written[i * self.block_size : (i + 1) * self.block_size],
            )
            chain.append(h)
            blk = table[i]
            # first writer wins: identical content registered by another
            # sequence keeps its block; ours stays unregistered (it frees to
            # the free list instead of the LRU pool — no duplicate entries)
            if h not in self._cached and blk not in self._block_hash and blk != NULL_BLOCK:
                self._cached[h] = blk
                self._block_hash[blk] = h
                new += 1
        return new

    def chain_hashes(self, seq_id) -> "list[bytes]":
        """The sequence's registered full-block chain hashes, oldest first —
        the content addresses a KV handoff ships (``serving/disagg.py``).
        Raises on an unknown/freed sequence like every other lookup."""
        if seq_id not in self._tables:
            raise BlockAllocatorError(
                f"chain_hashes of unknown/freed sequence {seq_id!r} (use-after-free?)"
            )
        return list(self._chain.get(seq_id, []))

    def adopt_block(self, chain_hash: bytes) -> Optional[int]:
        """Content-index one externally produced full block (the decode side
        of a prefill→decode KV handoff): take a block, register it under
        ``chain_hash``, and park it UNREFERENCED in the LRU pool — matchable
        by the next admission's :meth:`plan_prefix`, reclaimable under
        pressure like any cached block, so a landing can never strand pool
        capacity. The caller writes the block's device content at the
        returned physical index. Returns ``None`` when the hash is already
        cached (content-addressed dedup: nothing to copy)."""
        if not self.prefix_caching:
            raise BlockAllocatorError("adopt_block requires prefix_caching=True")
        if chain_hash in self._cached:
            return None
        if self.available_blocks < 1:
            raise BlockPoolExhausted(
                "no block available to adopt a handed-off KV block"
            )
        blk = self._take_block()
        self._cached[chain_hash] = blk
        self._block_hash[blk] = chain_hash
        self._lru[blk] = None
        return blk

    def append(self, seq_id, n_tokens: int = 1) -> "list[int]":
        """Grow a sequence by ``n_tokens``; allocates new block(s) only when
        the count crosses a block boundary. Returns the block ids newly
        allocated (often empty). On exhaustion the sequence is left unchanged
        and :class:`BlockPoolExhausted` propagates — the scheduler preempts."""
        if seq_id not in self._tables:
            raise BlockAllocatorError(
                f"append on unknown/freed sequence {seq_id!r} (use-after-free?)"
            )
        have = len(self._tables[seq_id])
        need = self.blocks_for(self._tokens[seq_id] + n_tokens) - have
        if need > self.available_blocks:
            raise BlockPoolExhausted(
                f"sequence {seq_id!r} needs {need} more block(s), "
                f"only {self.available_blocks} free"
            )
        new = [self._take_block() for _ in range(max(0, need))]
        for blk in new:
            self._ref[blk] = 1
        self._tables[seq_id].extend(new)
        self._tokens[seq_id] += n_tokens
        return new

    def free(self, seq_id) -> int:
        """Drop all of a sequence's references; returns how many blocks it
        held. A block is only actually released when its reference count
        hits zero — cached blocks park in the LRU pool (still matchable),
        unregistered ones return to the free list. Double-free raises
        :class:`BlockAllocatorError`."""
        if seq_id not in self._tables:
            raise BlockAllocatorError(f"double free of sequence {seq_id!r}")
        table = self._tables.pop(seq_id)
        del self._tokens[seq_id]
        self._chain.pop(seq_id, None)
        for blk in reversed(table):  # LIFO: first-allocated reused last
            self._unref(blk)
        return len(table)

    # -- views ---------------------------------------------------------------

    def block_table(self, seq_id, pad_to: Optional[int] = None) -> np.ndarray:
        """The sequence's physical block ids (logical order) as int32,
        padded with the null block to ``pad_to`` (the bucketed table width)."""
        if seq_id not in self._tables:
            raise BlockAllocatorError(
                f"block_table of unknown/freed sequence {seq_id!r} (use-after-free?)"
            )
        table = self._tables[seq_id]
        width = len(table) if pad_to is None else pad_to
        if len(table) > width:
            raise ValueError(f"table of {len(table)} block(s) does not fit pad_to={pad_to}")
        out = np.full((width,), NULL_BLOCK, np.int32)
        out[: len(table)] = table
        return out

    def tokens(self, seq_id) -> int:
        if seq_id not in self._tokens:
            raise BlockAllocatorError(f"tokens of unknown/freed sequence {seq_id!r}")
        return self._tokens[seq_id]

    def num_seq_blocks(self, seq_id) -> int:
        if seq_id not in self._tables:
            raise BlockAllocatorError(f"blocks of unknown/freed sequence {seq_id!r}")
        return len(self._tables[seq_id])

    def live_sequences(self) -> "list":
        return list(self._tables)

    def occupancy(self) -> float:
        """Fraction of usable blocks currently allocated."""
        return self.used_blocks / self.usable_blocks

    def fragmentation(self) -> float:
        """Internal fragmentation: fraction of ALLOCATED slots not holding a
        token (the unwritten tails of last blocks). 0.0 when nothing is
        allocated. Shared blocks hold one physical copy serving several
        sequences' logical tokens, so sharing can push the logical count past
        the physical slots — clamp at 0 (sharing is the opposite of waste)."""
        allocated_slots = self.used_blocks * self.block_size
        if not allocated_slots:
            return 0.0
        live_tokens = sum(self._tokens.values())
        return max(0.0, (allocated_slots - live_tokens) / allocated_slots)

    def shared_blocks(self) -> int:
        """Physical blocks currently referenced by more than one sequence."""
        return sum(1 for c in self._ref.values() if c > 1)

    def stats(self) -> dict:
        out = {
            "block_size": self.block_size,
            "usable_blocks": self.usable_blocks,
            "free_blocks": self.free_blocks,
            "used_blocks": self.used_blocks,
            "sequences": len(self._tables),
            "live_tokens": sum(self._tokens.values()),
            "occupancy": round(self.occupancy(), 6),
            "fragmentation": round(self.fragmentation(), 6),
        }
        if self.prefix_caching:
            out.update(
                cached_blocks=len(self._block_hash),
                reclaimable_blocks=self.reclaimable_blocks,
                shared_blocks=self.shared_blocks(),
                prefix_lookups=self.prefix_lookups,
                prefix_hits=self.prefix_hits,
                prefix_hit_tokens=self.prefix_hit_tokens,
                cow_copies=self.cow_copies,
                reclaimed_blocks=self.reclaimed_blocks,
            )
        return out
