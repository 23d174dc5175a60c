"""Continuous-batching scheduler: admission, slot placement, preemption.

Decisions live here, device work lives in ``engine.py``. The policy is the
in-flight batching loop (Orca/vLLM style):

- **admission** happens at STEP granularity: whenever a batch slot is free
  and the block pool can hold the prompt (plus the configured watermark),
  the next queued request is prefilled and joins the running decode batch —
  no waiting for the current batch to drain;
- **completion/eviction** frees a sequence's blocks immediately and the slot
  is backfilled on the next step;
- **preemption** is the pool's pressure valve: when a running sequence needs
  a block and none is free, the most-recently-admitted OTHER sequence is
  evicted (LIFO — oldest requests keep their progress), its blocks freed and
  the request requeued AT THE FRONT with its prompt + generated tokens
  persisted, so resume re-prefills the full prefix and continues with
  identical output (the preemption parity test proves it).

A request's batch slot is its own from admission to completion or preemption
(``_release`` gives slot and blocks back together), so it also names what the
engine keeps a SEQUENCE rather than a token: for a model with per-sequence
state, row ``slot + 1`` of the engine's ``pool["state"]`` (``engine.py``).

``continuous=False`` turns the same machinery into the static-batching
baseline for the serving benchmark: admission only happens when the engine
is completely idle (gang admission), and finished sequences' slots are NOT
backfilled until the whole batch drains — the classic waste continuous
batching exists to eliminate.
"""

from __future__ import annotations

import enum
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..telemetry import metrics as _metrics
from .kv_pager import BlockAllocator, BlockPoolExhausted

__all__ = ["RequestStatus", "Request", "Scheduler", "SchedulingError"]

_rid_counter = itertools.count()


class SchedulingError(RuntimeError):
    """A request that can never be scheduled (e.g. larger than the pool)."""


class RequestStatus(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    PREEMPTED = "preempted"
    FINISHED = "finished"
    REJECTED = "rejected"  # can never run on this engine; see Request.error


@dataclass(eq=False)  # identity equality: requests are stateful handles
class Request:
    """One generation request plus its full persisted progress.

    ``prompt`` + ``generated`` are the request's durable state: eviction
    drops ONLY device blocks, so a preempted request resumes by
    re-prefilling ``prompt + generated`` and keeps decoding — no tokens are
    lost and the continuation is identical to an uninterrupted run.
    """

    prompt: np.ndarray  # int32 [S]
    max_new_tokens: int
    rid: int = field(default_factory=lambda: next(_rid_counter))
    eos_token_id: Optional[int] = None
    rng_seed: int = 0
    arrival_t: float = 0.0

    # runtime state
    status: RequestStatus = RequestStatus.QUEUED
    generated: "list[int]" = field(default_factory=list)
    slot: Optional[int] = None
    preemptions: int = 0
    # stamps, each read when its event happens (``time.monotonic()``, or the
    # ``now`` the caller of ``step(now=...)`` gave): FIRST admission (a
    # resumed request keeps it) and the engine step that made it, the first
    # token on the host, completion. arrival_t <= admit_t <= first_token_t
    # <= finish_t; admit_t - arrival_t is the queue wait.
    admit_t: Optional[float] = None
    admit_step: Optional[int] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    error: Optional[str] = None  # set when REJECTED
    # prefix-cache admission state, overwritten on EVERY admission (a resume
    # re-plans against the index as it stands then): how many leading prefix
    # tokens are already cached (the engine prefills only past them), and the
    # pending copy-on-write pair the engine must apply before any write
    cached_tokens: int = 0
    cow_block: "Optional[tuple[int, int]]" = None
    # distributed-tracing state (telemetry/tracing.py): the propagated
    # context (None while tracing is disarmed — every check stays one
    # branch) and this request's accumulated span dicts. The engine fills
    # them; router-owned requests ship the spans back over the replica
    # event stream instead of emitting locally.
    trace: Optional[dict] = None
    trace_spans: "list[dict]" = field(default_factory=list)
    # engine-side PRNGKey cache (pure function of rng_seed)
    _key: Optional[np.ndarray] = field(default=None, repr=False, init=False)
    # open trace spans (closed as the request moves through the engine)
    _span_root: Optional[dict] = field(default=None, repr=False, init=False)
    _span_queue: Optional[dict] = field(default=None, repr=False, init=False)
    _trace_owner: bool = field(default=False, repr=False, init=False)

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {self.max_new_tokens}")

    @property
    def prefix_len(self) -> int:
        """Tokens the model has consumed so far: prompt + generated."""
        return int(self.prompt.size) + len(self.generated)

    @property
    def done(self) -> bool:
        if len(self.generated) >= self.max_new_tokens:
            return True
        return (
            self.eos_token_id is not None
            and bool(self.generated)
            and self.generated[-1] == self.eos_token_id
        )

    def output_ids(self) -> np.ndarray:
        """prompt + generated, the same layout ``greedy_generate`` returns."""
        return np.concatenate([self.prompt, np.asarray(self.generated, np.int32)])


class Scheduler:
    """Admission queue + batch-slot table over one :class:`BlockAllocator`."""

    def __init__(
        self,
        allocator: BlockAllocator,
        max_slots: int,
        *,
        continuous: bool = True,
        admit_watermark_blocks: int = 0,
        max_seq_blocks: Optional[int] = None,
        max_seq_tokens: Optional[int] = None,
        admission_gate=None,
    ):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.allocator = allocator
        # optional predicate over the queue head: False holds the request
        # (and everything behind it — admission stays FIFO) without popping
        # it. The disaggregated DecodeEngine gates on "its handed-off KV
        # blocks have landed"; None keeps the legacy path branch-free.
        self.admission_gate = admission_gate
        self.max_slots = max_slots
        self.continuous = continuous
        # hard per-sequence caps, both enforced at ADMISSION on the worst
        # case (prefix + max_new) so nothing crashes or corrupts mid-decode:
        # - blocks: the engine passes its bucket lattice's widest table;
        # - tokens: the engine passes config.max_seq_len — positions past the
        #   RoPE table would be silently CLAMPED by the cos/sin gathers,
        #   corrupting output with no error.
        self.max_seq_blocks = (
            allocator.usable_blocks if max_seq_blocks is None
            else min(max_seq_blocks, allocator.usable_blocks)
        )
        self.max_seq_tokens = max_seq_tokens
        # admission keeps this many blocks free as decode headroom, so a
        # fresh admission doesn't immediately force a preemption
        self.admit_watermark_blocks = admit_watermark_blocks
        self.queue: "deque[Request]" = deque()
        self.slots: "list[Optional[Request]]" = [None] * max_slots
        self._admission_order: "list[Request]" = []  # oldest first
        self.preemption_count = 0
        #: requests that can NEVER run on this pool (prefix larger than the
        #: whole pool) — rejected at admission instead of wedging the queue
        self.rejected: "list[Request]" = []

    # -- views ---------------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    def running(self) -> "list[Request]":
        return [r for r in self.slots if r is not None]

    def idle(self) -> bool:
        return not self.queue and not self.running()

    # -- admission -----------------------------------------------------------

    def submit(self, request: Request) -> Request:
        request.status = RequestStatus.QUEUED
        self.queue.append(request)
        return request

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def admissions(
        self, now: Optional[float] = None, step: Optional[int] = None
    ) -> "list[Request]":
        """Pop and place every request admissible RIGHT NOW (the engine
        prefills each). Continuous mode admits whenever a slot + blocks are
        available; static mode only gang-admits into an idle engine.

        A request's FIRST admission stamps ``admit_t`` (``now``, else the
        monotonic clock) and ``admit_step`` (``step``, the caller's step
        counter); a resume after preemption leaves both alone."""
        if not self.continuous and self.running():
            return []
        admitted = []
        while self.queue:
            slot = self._free_slot()
            if slot is None:
                break
            req = self.queue[0]
            if self.admission_gate is not None and not self.admission_gate(req):
                break  # gated (e.g. KV handoff not landed): FIFO order holds
            prefix_tokens = req.output_ids()
            # admission charges only UNCACHED blocks: the plan maps the
            # longest cached block-aligned prefix for free, and the watermark
            # compares the fresh-tail cost against free + reclaimable blocks
            # (with caching off the plan degenerates to blocks_for(prefix))
            plan = self.allocator.plan_prefix(prefix_tokens)
            # fresh blocks the tail takes, plus LRU-parked matched blocks this
            # mapping will pin (they count as available today but can't also
            # serve as fresh blocks — without the charge the allocation below
            # could throw on a plan admission just green-lit)
            need = plan.fresh_blocks + plan.lru_pinned
            # worst case the sequence can reach: its current prefix plus every
            # remaining token it may generate
            remaining = max(0, req.max_new_tokens - len(req.generated))
            worst_tokens = req.prefix_len + remaining
            # the block-WIDTH cap charges the full table (shared blocks widen
            # the gather exactly like private ones); only the pool check is
            # prefix-aware
            worst = self.allocator.blocks_for(worst_tokens)
            reason = None
            if self.max_seq_tokens is not None and worst_tokens > self.max_seq_tokens:
                reason = (
                    f"worst case {worst_tokens} tokens (prefix {req.prefix_len} "
                    f"+ up to {remaining} new) exceeds the model's "
                    f"max_seq_len of {self.max_seq_tokens}"
                )
            elif worst > self.max_seq_blocks:
                reason = (
                    f"worst case {worst} block(s) (prefix {req.prefix_len} + "
                    f"up to {remaining} new tokens) exceeds the per-sequence "
                    f"cap of {self.max_seq_blocks}"
                )
            if reason is not None:
                # impossible on this engine no matter what drains: reject it
                # rather than wedging the queue behind it forever, crashing
                # mid-decode, or silently clamping RoPE positions
                self.queue.popleft()
                req.status = RequestStatus.REJECTED
                req.error = "rejected: " + reason
                self.rejected.append(req)
                continue
            if need + self.admit_watermark_blocks > self.allocator.available_blocks:
                break  # pool pressure: let running sequences drain first
            self.queue.popleft()
            alloc = self.allocator.allocate_with_prefix(
                req.rid, prefix_tokens, plan=plan
            )
            req.cached_tokens = alloc.cached_tokens
            req.cow_block = alloc.cow
            req.status = RequestStatus.RUNNING
            req.slot = slot
            self.slots[slot] = req
            self._admission_order.append(req)
            if req.admit_t is None:
                req.admit_t = time.monotonic() if now is None else now
                req.admit_step = step
            admitted.append(req)
        return admitted

    # -- progress ------------------------------------------------------------

    def grow(self, request: Request, n_tokens: int = 1) -> None:
        """Reserve pool room for the request's next ``n_tokens`` tokens
        (speculative decoding grows by up to k+1 per step), preempting other
        sequences (LIFO) if the pool is dry. Raises :class:`SchedulingError`
        only when the request cannot fit even with every other sequence
        evicted."""
        if n_tokens <= 0:
            return
        while True:
            try:
                self.allocator.append(request.rid, n_tokens)
                return
            except BlockPoolExhausted:
                if not self._preempt_one(exclude=request):
                    raise SchedulingError(
                        f"request {request.rid} exhausted the pool with no "
                        "other sequence left to evict — the pool is smaller "
                        "than one request's worst case"
                    ) from None

    def _preempt_one(self, exclude: Request) -> bool:
        """Evict the most-recently-admitted running request (except
        ``exclude``): free its blocks, requeue it at the FRONT with its
        progress persisted. False when there is no candidate."""
        for req in reversed(self._admission_order):
            if req is exclude or req.status is not RequestStatus.RUNNING:
                continue
            self._release(req)
            req.status = RequestStatus.PREEMPTED
            req.preemptions += 1
            self.preemption_count += 1
            _metrics.inc("accelerate_preemptions_total")
            self.queue.appendleft(req)
            return True
        return False

    def complete(self, request: Request, now: float) -> None:
        self._release(request)
        request.status = RequestStatus.FINISHED
        request.finish_t = now

    def _release(self, request: Request) -> None:
        self.allocator.free(request.rid)
        if request.slot is not None:
            self.slots[request.slot] = None
            request.slot = None
        self._admission_order.remove(request)
