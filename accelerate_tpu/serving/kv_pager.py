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

from ..ops.flash_attention import NULL_BLOCK, pooled_row_layout
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

    **Rows.** A sequence's table and counts are a row of three arrays, and
    are kept nowhere else: :attr:`row_tables` ``[max_seqs + 1,
    max_seq_blocks]`` (the table, padded with the null block),
    :attr:`row_blocks` and :attr:`row_tokens`. The allocator writes them and
    nobody else, at the events that change them: the whole table at
    :meth:`allocate` / :meth:`allocate_with_prefix` (the copy-on-write's
    private block included), each new block and the token count at
    :meth:`append`, the null table back at :meth:`free`. The last row holds
    no sequence and stays null: a batch's padding reads it. The scheduler
    gives a sequence its batch slot as its row, so the engine reads a decode
    batch's tables with one index over the running slots;
    :attr:`row_entries_written` counts the table entries written. By default
    there is a row for as many sequences as the pool has blocks, each as
    wide as the pool (every sequence holds a block no other does).

    **Pooled rows** (``pooled=(window, chunk)``: a model that attends exact
    keys inside a window and one pooled pair for every ``chunk`` positions
    behind it, ``models/evabyte.py``). A row then holds blocks of two kinds
    and may hold null entries: ``window / block_size`` raw slots at its head,
    the open window's keys and values, and behind them ``window / (chunk *
    block_size)`` summary blocks a window, the closed windows' and the open
    one's (``ops.flash_attention.pooled_row_layout``; the step programs
    compose the row the kernels walk, pooled entries ahead of raw ones). At
    ``n`` tokens a sequence has ``c = (n - 1) // window`` closed windows and
    holds ``ceil((n - c * window) / block_size)`` raw blocks and ``(c + 1)``
    windows' summary blocks; :attr:`row_blocks` is the row's extent (raw
    slots and summary blocks), not a count of blocks. :meth:`append` closes a
    window when the count crosses into the next one: its raw blocks go back
    to the pool (:attr:`raw_blocks_freed`, :attr:`windows_closed`), the next
    window's summary blocks are appended, and its first raw block is taken.
    :meth:`allocate` takes what a prefill of ``n`` tokens needs at its peak,
    a whole window's raw slots once a window closes in it (the prefill reuses
    them window after window), and :meth:`settle` hands back, after the
    prefill, what the last window does not need. No prefix caching: a hit
    would need the summaries of the prefix's closed windows.
    """

    def __init__(self, num_blocks: int, block_size: int, *, prefix_caching: bool = False,
                 max_seqs: Optional[int] = None, max_seq_blocks: Optional[int] = None,
                 pooled: "Optional[tuple[int, int]]" = None):
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
        self._row: "dict[object, int]" = {}  # live sequence -> its row
        max_seqs = max_seqs or self.usable_blocks
        width = max_seq_blocks or self.usable_blocks
        self.row_tables = np.full((max_seqs + 1, width), NULL_BLOCK, np.int32)
        self.row_blocks = np.zeros((max_seqs + 1,), np.int32)
        self.row_tokens = np.zeros((max_seqs + 1,), np.int32)
        #: table entries written into rows so far (a row's reset to null not counted)
        self.row_entries_written = 0
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
        #: ``(window, raw slots, summary blocks a window)`` of a pooled row, else None
        self.pooled = None
        if pooled is not None:
            if prefix_caching:
                raise ValueError("a pooled row takes no prefix hit: the prefix's closed "
                                 "windows would need their summaries")
            window, chunk = pooled
            self.pooled = (window, *pooled_row_layout(window, chunk, block_size))
        #: pooled rows: windows closed by :meth:`append`, raw blocks handed back
        #: (at a close, and by :meth:`settle` after a prefill)
        self.windows_closed = 0
        self.raw_blocks_freed = 0

    def _free_row(self, row: Optional[int], n_blocks: int) -> int:
        """The row a new sequence of ``n_blocks`` takes: ``row``, else the
        lowest free one. Refuses, before anything is allocated, a row that is
        taken or a table wider than the rows."""
        if row is None:
            free = np.flatnonzero(self.row_blocks[:-1] == 0)
            if not free.size:
                raise BlockAllocatorError("every row holds a sequence")
            row = int(free[0])
        elif self.row_blocks[row]:  # a live sequence holds at least one block
            raise BlockAllocatorError(f"row {row} already holds a sequence")
        self._fits(n_blocks)
        return row

    def _fits(self, n_blocks: int) -> None:
        if n_blocks > self.row_tables.shape[1]:
            raise ValueError(
                f"table of {n_blocks} block(s) does not fit a row of {self.row_tables.shape[1]}")

    def _write_row(self, row: int, blocks: "list[int]", n_tokens: int) -> None:
        """Append ``blocks`` to the row's table and set its token count (a
        plain row; a pooled row's entries stand by kind, :meth:`_put`)."""
        have = int(self.row_blocks[row])
        if blocks:
            self.row_tables[row, have : have + len(blocks)] = blocks
            self.row_blocks[row] = have + len(blocks)
            self.row_entries_written += len(blocks)
        self.row_tokens[row] = n_tokens

    def _table(self, seq_id) -> "list[int]":
        row = self._row[seq_id]
        return self.row_tables[row, : self.row_blocks[row]].tolist()

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
        """Blocks an allocation of ``n_tokens`` takes: those that hold them,
        and for a pooled row the peak of their prefill (class docstring)."""
        if self.pooled is None:
            return max(1, -(-n_tokens // self.block_size))
        _, raw, per_window = self.pooled
        closed = self._closed(n_tokens)
        return per_window * (closed + 1) + (raw if closed else self._raw_for(n_tokens))

    def row_width(self, n_tokens: int) -> int:
        """Table entries a sequence of ``n_tokens`` spans: its blocks, and for
        a pooled row its raw slots and its windows' summary blocks."""
        if self.pooled is None:
            return self.blocks_for(n_tokens)
        _, raw, per_window = self.pooled
        return raw + per_window * (self._closed(n_tokens) + 1)

    def _closed(self, n_tokens: int) -> int:
        """A pooled row's closed windows at ``n_tokens``: those behind the
        window of position ``n_tokens - 1``."""
        return max(0, n_tokens - 1) // self.pooled[0]

    def _raw_for(self, n_tokens: int) -> int:
        """A pooled row's raw blocks at ``n_tokens``: its open window's."""
        return -(-(n_tokens - self._closed(n_tokens) * self.pooled[0]) // self.block_size)

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

    def allocate(self, seq_id, n_tokens: int, row: Optional[int] = None) -> "list[int]":
        """Create a sequence holding ``n_tokens`` (its prompt) from fresh
        blocks only; returns the block table, written whole into ``row``
        (the lowest free row when None; class docstring).
        :class:`BlockPoolExhausted` when the pool can't cover it (nothing is
        allocated on failure — all-or-nothing). Prefix-aware admission goes
        through :meth:`allocate_with_prefix` instead. A pooled row (class
        docstring) is written as the kernels' row is not: summary blocks of
        every window the prefill meets behind the raw slots, the step
        programs composing pooled entries ahead of raw ones."""
        if seq_id in self._row:
            raise BlockAllocatorError(f"sequence {seq_id!r} already allocated")
        need = self.blocks_for(n_tokens)
        if need > self.available_blocks:
            raise BlockPoolExhausted(
                f"need {need} block(s) for {n_tokens} token(s), "
                f"only {self.available_blocks} free"
            )
        row = self._free_row(row, self.row_width(n_tokens))
        table = [self._take_block() for _ in range(need)]
        for blk in table:
            self._ref[blk] = 1
        self._row[seq_id] = row
        if self.pooled is None:
            self._write_row(row, table, n_tokens)
        else:  # the summary blocks of every window the prefill meets, then its raw slots
            raw = self.pooled[1]
            summaries = self.row_width(n_tokens) - raw
            self._put(row, raw, table[:summaries])
            self._put(row, 0, table[summaries:])
            self.row_blocks[row] = raw + summaries
            self.row_tokens[row] = n_tokens
        self._chain[seq_id] = []
        return table

    def _put(self, row: int, at: int, blocks: "list[int]") -> None:
        """Write ``blocks`` into entries ``at..`` of a pooled row."""
        self.row_tables[row, at : at + len(blocks)] = blocks
        self.row_entries_written += len(blocks)

    def _drop_raw(self, row: int, keep: int) -> int:
        """Hand back a pooled row's raw blocks from slot ``keep`` on; their
        entries go back to null. Returns how many."""
        slots = self.row_tables[row, keep : self.pooled[1]]
        held = slots[slots != NULL_BLOCK].tolist()
        slots[:] = NULL_BLOCK
        for blk in reversed(held):
            self._unref(blk)
        self.raw_blocks_freed += len(held)
        return len(held)

    def settle(self, seq_id) -> int:
        """After a sequence's prefill: hand back what it needed and its row
        no longer does (a pooled row's raw slots past its last window's
        blocks). Returns how many blocks; 0 for a plain row. The prefill's
        programs are issued before, and run before whatever takes them next."""
        if self.pooled is None:
            return 0
        row = self._row[seq_id]
        return self._drop_raw(row, self._raw_for(int(self.row_tokens[row])))

    def allocate_with_prefix(
        self, seq_id, token_ids, plan: "Optional[PrefixPlan]" = None,
        row: Optional[int] = None,
    ) -> PrefixAllocation:
        """Create a sequence for ``token_ids``, mapping the longest cached
        block-aligned prefix into its table (refcount++) and taking fresh
        blocks only for the uncached tail; the table, copy-on-write block
        and all, is written whole into ``row`` as by :meth:`allocate`.
        All-or-nothing on exhaustion.
        With caching off this is exactly :meth:`allocate`. ``plan`` skips
        re-hashing when the caller just ran :meth:`plan_prefix` for the SAME
        tokens with no allocator mutation in between (the scheduler's
        admission loop) — a stale plan here would map the wrong blocks."""
        token_ids = np.asarray(token_ids, np.int32).reshape(-1)
        n = int(token_ids.size)
        if not self.prefix_caching:
            return PrefixAllocation(self.allocate(seq_id, n, row), 0, None)
        if seq_id in self._row:
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
        row = self._free_row(row, self.blocks_for(n))
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
        self._row[seq_id] = row
        self._write_row(row, table, n)
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
        if seq_id not in self._row:
            raise BlockAllocatorError(
                f"register on unknown/freed sequence {seq_id!r} (use-after-free?)"
            )
        written = np.asarray(written_token_ids, np.int32).reshape(-1)
        table = self._table(seq_id)
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
        if seq_id not in self._row:
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
        allocated (often empty), written into the sequence's row with its new
        token count; a pooled row closes the windows the count crosses into
        (:meth:`_append_pooled`). On exhaustion the sequence is left unchanged
        and :class:`BlockPoolExhausted` propagates — the scheduler preempts."""
        if seq_id not in self._row:
            raise BlockAllocatorError(
                f"append on unknown/freed sequence {seq_id!r} (use-after-free?)"
            )
        row = self._row[seq_id]
        n = int(self.row_tokens[row]) + n_tokens
        if self.pooled is not None:
            return self._append_pooled(seq_id, row, n)
        have = int(self.row_blocks[row])
        need = self.blocks_for(n) - have
        if need > self.available_blocks:
            raise BlockPoolExhausted(
                f"sequence {seq_id!r} needs {need} more block(s), "
                f"only {self.available_blocks} free"
            )
        if need > 0:
            self._fits(have + need)
        new = [self._take_block() for _ in range(max(0, need))]
        for blk in new:
            self._ref[blk] = 1
        self._write_row(row, new, n)
        return new

    def _append_pooled(self, seq_id, row: int, n: int) -> "list[int]":
        """:meth:`append` of a pooled row to ``n`` tokens: close every window
        it crosses into (hand back the raw blocks, append the summary blocks
        of the windows it enters), then take the raw blocks ``n`` needs.
        All or nothing, as :meth:`append`."""
        _, raw, per_window = self.pooled
        closes = self._closed(n) - self._closed(int(self.row_tokens[row]))
        slots = self.row_tables[row, :raw]
        held = int((slots != NULL_BLOCK).sum())
        missing = self._raw_for(n) if closes else int((slots[: self._raw_for(n)] == NULL_BLOCK).sum())
        take = per_window * closes + missing
        if take - (held if closes else 0) > self.available_blocks:
            raise BlockPoolExhausted(
                f"sequence {seq_id!r} needs {take} more block(s), "
                f"only {self.available_blocks} free")
        self._fits(self.row_width(n))
        if closes:
            self._drop_raw(row, 0)
            self.windows_closed += closes
        new = [self._take_block() for _ in range(take)]
        for blk in new:
            self._ref[blk] = 1
        extent = int(self.row_blocks[row])
        self._put(row, extent, new[: per_window * closes])
        self.row_blocks[row] = extent + per_window * closes
        fresh = iter(new[per_window * closes :])
        for j in np.flatnonzero(slots[: self._raw_for(n)] == NULL_BLOCK):
            self.row_tables[row, j] = next(fresh)
            self.row_entries_written += 1
        self.row_tokens[row] = n
        return new

    def free(self, seq_id) -> int:
        """Drop all of a sequence's references; returns how many blocks it
        held. A block is only actually released when its reference count
        hits zero — cached blocks park in the LRU pool (still matchable),
        unregistered ones return to the free list. The sequence's row goes
        back to the null table. Double-free raises
        :class:`BlockAllocatorError`."""
        if seq_id not in self._row:
            raise BlockAllocatorError(f"double free of sequence {seq_id!r}")
        table = self._table(seq_id)
        row = self._row.pop(seq_id)
        self.row_tables[row, : len(table)] = NULL_BLOCK
        self.row_blocks[row] = self.row_tokens[row] = 0
        self._chain.pop(seq_id, None)
        table = [blk for blk in table if blk != NULL_BLOCK]  # a pooled row's empty raw slots
        for blk in reversed(table):  # LIFO: first-allocated reused last
            self._unref(blk)
        return len(table)

    # -- views ---------------------------------------------------------------

    def block_table(self, seq_id, pad_to: Optional[int] = None) -> np.ndarray:
        """The sequence's physical block ids (logical order) as int32,
        padded with the null block to ``pad_to`` (the bucketed table width,
        at most the rows'): a copy of the front of its row."""
        if seq_id not in self._row:
            raise BlockAllocatorError(
                f"block_table of unknown/freed sequence {seq_id!r} (use-after-free?)"
            )
        row = self._row[seq_id]
        n = int(self.row_blocks[row])
        width = n if pad_to is None else pad_to
        if n > width or width > self.row_tables.shape[1]:
            raise ValueError(f"table of {n} block(s) in a row of "
                             f"{self.row_tables.shape[1]} does not fit pad_to={pad_to}")
        return self.row_tables[row, :width].copy()

    def tokens(self, seq_id) -> int:
        if seq_id not in self._row:
            raise BlockAllocatorError(f"tokens of unknown/freed sequence {seq_id!r}")
        return int(self.row_tokens[self._row[seq_id]])

    def num_seq_blocks(self, seq_id) -> int:
        if seq_id not in self._row:
            raise BlockAllocatorError(f"blocks of unknown/freed sequence {seq_id!r}")
        return int(self.row_blocks[self._row[seq_id]])

    def live_sequences(self) -> "list":
        return list(self._row)

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
        live_tokens = int(self.row_tokens.sum())
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
            "sequences": len(self._row),
            "live_tokens": int(self.row_tokens.sum()),
            "occupancy": round(self.occupancy(), 6),
            "fragmentation": round(self.fragmentation(), 6),
        }
        if self.pooled is not None:
            out.update(windows_closed=self.windows_closed, raw_blocks_freed=self.raw_blocks_freed)
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
