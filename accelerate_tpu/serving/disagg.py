"""Disaggregated prefill/decode serving: role-split engines, KV handoff.

The monolithic :class:`~accelerate_tpu.serving.engine.ServingEngine` runs
chunked prefill and batched decode on one device program, so a long prompt
stalls every decode slot behind it. The MPMD split (PAPERS.md 2412.14374:
one program per role, point-to-point transfer between them) breaks that
interference:

- :class:`PrefillEngine` — chunked prefill ONLY. Each admitted request is
  prefilled (sampling its first token at fold index 0, exactly like the
  monolith), then leaves the engine as a **content-addressed KV handoff**:
  the prompt's full blocks, identified by the prefix-cache chain hashes
  (``h_i = H(h_{i-1}, tokens_i)``) and carried with their pool content. The
  freed blocks stay registered in the prefill engine's own LRU pool, so a
  shared prompt prefix is prefilled once per prefill replica, ever.
- :class:`DecodeEngine` — batched decode ONLY. A handoff **lands** by
  adopting each block into the decode pool's content index
  (:meth:`~accelerate_tpu.serving.kv_pager.BlockAllocator.adopt_block`) and
  writing its content with one compiled block write (``serving_land``, part
  of the warmup lattice). Admission of the request is GATED until its
  handoff has landed; the normal prefix-cache admission then maps the landed
  blocks and the engine re-prefills only the sub-block tail — resuming via
  the same ``submit(generated=...)`` machinery failover uses, so the decoded
  stream is bitwise-identical to the monolith's.
- :class:`KVTransport` — how handoff bytes move. The shipped
  :class:`LocalBlockCopyTransport` gathers/writes through host memory
  (shared-host tests, LocalReplica fleets); a DCN/ICI implementation slots
  in behind the same two-method surface.
- :class:`DisaggRouter` — two-tier dispatch over one replica fleet: requests
  with no progress go to the prefill tier (fewest outstanding requests, then
  fewest pending prompt tokens), requests carrying progress or a verified
  handoff go to the decode tier (least-outstanding-tokens, the base
  policy). The handoff hop is checksum- and chain-hash-verified at the
  router; a corrupt or dropped handoff re-runs prefill from scratch
  (``generated`` cleared, so the re-run samples fold 0 again) — exactly-once
  and bitwise parity hold across the extra hop, chaos point ``kv_handoff``
  proves it (``make doctor`` check 17).

Wire format: a handoff travels as a JSON-able dict (tokens, hex chain
hashes, base64 float32 block content, CRC32) on BOTH transports, so thread
and process replicas exercise one code path. bf16→f32 widening is exact and
f32→bf16 truncation restores the original bits, so shipping KV as float32
preserves bitwise parity end to end.
"""

from __future__ import annotations

import base64
import time
import zlib
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import events as tel
from ..telemetry import goodput as _goodput
from ..telemetry import metrics as _metrics
from ..telemetry import tracing as _tracing
from ..telemetry import watchdog as _watchdog
from ..ops.flash_attention import NULL_BLOCK
from .engine import ServingEngine
from .kv_pager import BlockPoolExhausted, _chain_hash
from .replica import ReplicaState
from .router import RouterRequestStatus, ServingRouter
from .scheduler import Request

__all__ = [
    "KVHandoff",
    "KVTransport",
    "LocalBlockCopyTransport",
    "PrefillEngine",
    "DecodeEngine",
    "DisaggRouter",
]


def _inject_handoff_fault(step: int) -> bool:
    """Chaos point ``kv_handoff`` (resilience/chaos.py). Returns True when a
    ``corrupt`` fault fired — the caller delivers a deliberately damaged
    payload for the router's verify to catch; ``crash``/``hang``/``slow``
    behave exactly as at any other point (die / wedge / delay)."""
    # lazy import, same reason as engine._chaos_inject: serving must not pay
    # for (or cyclically import) the resilience stack at module load
    from ..resilience import chaos as _chaos

    try:
        _chaos.maybe_inject("kv_handoff", step=step)
    except _chaos.ChaosCorruptionError:
        return True
    return False


# ---------------------------------------------------------------------------
# the transfer unit


@dataclass(eq=False)
class KVHandoff:
    """One request's prefilled KV, content-addressed and self-verifying.

    Covers the PROMPT's full blocks only (``P // block_size`` of them — the
    prefill engine writes KV for prompt positions, and partial tail blocks
    are cheaper to re-prefill than to ship sub-block state). ``hashes`` are
    the prefix-cache chain hashes, recomputable from ``prompt`` alone, so
    the receiver can prove the payload describes this exact prompt; ``crc``
    covers the block content bytes. ``first_token`` is the token the prefill
    engine sampled at fold index 0 — the decode side resumes with
    ``generated=[first_token]`` and samples fold 1 next, exactly the
    monolith's schedule."""

    prompt: np.ndarray            # int32 [P]
    first_token: int
    block_size: int
    hashes: "tuple[bytes, ...]"   # chain hashes over prompt full blocks
    k: np.ndarray                 # float32 [n_blocks, L, block_size, Hkv, D]
    v: np.ndarray
    crc: int
    src_replica: Optional[str] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)

    @property
    def n_blocks(self) -> int:
        return len(self.hashes)

    @property
    def nbytes(self) -> int:
        return int(self.k.nbytes) + int(self.v.nbytes)

    @classmethod
    def capture(cls, engine: ServingEngine, req: Request,
                src_replica: Optional[str] = None) -> "KVHandoff":
        """Gather the request's prompt full blocks out of ``engine``'s pool.
        Must run BEFORE ``scheduler.complete`` releases the sequence (the
        block table lookup raises after the free)."""
        alloc = engine.allocator
        n_full = int(req.prompt.size) // engine.block_size
        hashes = tuple(alloc.chain_hashes(req.rid)[:n_full])
        shape = engine.pool["k"].shape  # [L, num_blocks, B, Hkv, D]
        if hashes:
            idx = np.asarray(
                alloc.block_table(req.rid)[: len(hashes)], np.int32
            )
            # [L, n, B, Hkv, D] -> [n, L, B, Hkv, D]; bf16 -> f32 is exact
            k = np.asarray(jax.device_get(
                engine.pool["k"][:, idx].astype(jnp.float32).transpose(1, 0, 2, 3, 4)
            ))
            v = np.asarray(jax.device_get(
                engine.pool["v"][:, idx].astype(jnp.float32).transpose(1, 0, 2, 3, 4)
            ))
        else:  # prompt shorter than one block: the handoff carries only tok0
            k = np.zeros((0, shape[0], shape[2], shape[3], shape[4]), np.float32)
            v = np.zeros_like(k)
        crc = zlib.crc32(v.tobytes(), zlib.crc32(k.tobytes()))
        return cls(
            prompt=req.prompt,
            first_token=int(req.generated[0]),
            block_size=engine.block_size,
            hashes=hashes,
            k=k,
            v=v,
            crc=crc,
            src_replica=src_replica,
        )

    def verify(self) -> "list[str]":
        """Every way this payload can be wrong, as human-readable problems
        (empty list == intact): CRC over the content bytes, shape/hash-count
        consistency, and the chain hashes recomputed from the prompt — a
        payload claiming blocks the prompt doesn't have cannot pass."""
        problems: "list[str]" = []
        crc = zlib.crc32(self.v.tobytes(), zlib.crc32(self.k.tobytes()))
        if crc != self.crc:
            problems.append(
                f"payload checksum mismatch (got {crc:#010x}, "
                f"declared {self.crc:#010x})"
            )
        if self.k.shape != self.v.shape or self.k.shape[0] != len(self.hashes):
            problems.append(
                f"shape mismatch: k{self.k.shape} v{self.v.shape} "
                f"vs {len(self.hashes)} hash(es)"
            )
        if len(self.hashes) > int(self.prompt.size) // self.block_size:
            problems.append(
                f"{len(self.hashes)} block(s) exceed the prompt's "
                f"{int(self.prompt.size) // self.block_size} full block(s)"
            )
            return problems
        prev = b""
        for i, h in enumerate(self.hashes):
            expect = _chain_hash(
                prev, self.prompt[i * self.block_size : (i + 1) * self.block_size]
            )
            if h != expect:
                problems.append(f"chain hash {i} does not match the prompt")
                break
            prev = h
        return problems

    def to_wire(self) -> dict:
        """JSON-able dict — the form a handoff ALWAYS travels in, so thread
        and process transports exercise one serialization path."""
        return {
            "prompt": [int(t) for t in self.prompt],
            "first_token": int(self.first_token),
            "block_size": int(self.block_size),
            "hashes": [h.hex() for h in self.hashes],
            "shape": [int(s) for s in self.k.shape],
            "k": base64.b64encode(self.k.tobytes()).decode("ascii"),
            "v": base64.b64encode(self.v.tobytes()).decode("ascii"),
            "crc": int(self.crc),
            "src": self.src_replica,
        }

    @classmethod
    def from_wire(cls, wire: dict) -> "KVHandoff":
        shape = tuple(int(s) for s in wire["shape"])
        k = np.frombuffer(base64.b64decode(wire["k"]), np.float32).reshape(shape)
        v = np.frombuffer(base64.b64decode(wire["v"]), np.float32).reshape(shape)
        return cls(
            prompt=np.asarray(wire["prompt"], np.int32),
            first_token=int(wire["first_token"]),
            block_size=int(wire["block_size"]),
            hashes=tuple(bytes.fromhex(h) for h in wire["hashes"]),
            k=k,
            v=v,
            crc=int(wire["crc"]),
            src_replica=wire.get("src"),
        )

    @classmethod
    def verify_wire(
        cls, wire: dict, prompt=None
    ) -> "tuple[Optional[KVHandoff], list[str]]":
        """Decode + verify in one step, never raising: an undecodable wire
        dict is just another corruption verdict (the router re-runs
        prefill either way)."""
        try:
            h = cls.from_wire(wire)
        except Exception as exc:
            return None, [f"undecodable handoff: {type(exc).__name__}: {exc}"]
        problems = h.verify()
        if prompt is not None and not np.array_equal(
            h.prompt, np.asarray(prompt, np.int32).reshape(-1)
        ):
            problems.append("handoff prompt differs from the request's prompt")
        return h, problems


def corrupt_wire(wire: dict) -> dict:
    """Damage a wire-form handoff IN TRANSIT (after its CRC was computed) —
    the ``corrupt`` chaos fault's payload model. Flips one content byte, or
    the CRC itself when the payload is empty, so verification always
    catches it."""
    if wire.get("k"):
        raw = bytearray(base64.b64decode(wire["k"]))
        raw[0] ^= 0xFF
        wire["k"] = base64.b64encode(bytes(raw)).decode("ascii")
    else:
        wire["crc"] = int(wire["crc"]) ^ 1
    return wire


# ---------------------------------------------------------------------------
# transports


class KVTransport:
    """How handoff bytes move from a prefill pool to a decode pool. Two
    methods; implementations may batch, compress, or DMA as they like, as
    long as ``pack`` snapshots before the source sequence is freed and
    ``deliver`` is idempotent per chain hash (re-delivery after a decode
    failover must not duplicate blocks)."""

    def pack(self, engine: ServingEngine, req: Request) -> dict:
        """Snapshot ``req``'s prompt KV out of ``engine`` as a wire dict."""
        raise NotImplementedError

    def deliver(self, handoff: KVHandoff, engine: ServingEngine) -> dict:
        """Land ``handoff`` into ``engine``'s pool; returns stats
        (``landed``/``dedup`` block counts). Raises
        :class:`~accelerate_tpu.serving.kv_pager.BlockPoolExhausted` when the
        pool can't take a block right now (the caller retries later —
        partial progress is safe, adopted blocks dedup on retry)."""
        raise NotImplementedError


class LocalBlockCopyTransport(KVTransport):
    """Host-memory block copy: gather on the prefill side, one compiled
    block write per landed block on the decode side. The shared-host
    reference transport (LocalReplica fleets, ProcessReplica on one
    machine); a DCN/ICI transport replaces the host round-trip, nothing
    else."""

    def pack(self, engine: ServingEngine, req: Request) -> dict:
        name = getattr(engine, "heartbeat_name", None)
        return KVHandoff.capture(engine, req, src_replica=name).to_wire()

    def deliver(self, handoff: KVHandoff, engine: "DecodeEngine") -> dict:
        landed = dedup = 0
        land = engine._aot.get(("land",), engine.land_fn)
        for i, h in enumerate(handoff.hashes):
            blk = engine.allocator.adopt_block(h)
            if blk is None:
                dedup += 1  # content-addressed: this block is already here
                continue
            engine.pool = land(
                engine.pool, np.int32(blk), handoff.k[i], handoff.v[i]
            )
            landed += 1
        return {"landed": landed, "dedup": dedup}


# ---------------------------------------------------------------------------
# role-split engines


def _refuse_sequence_state(engine: ServingEngine) -> None:
    """A handoff carries a request's K/V blocks and nothing else: a model
    that also keeps per-sequence state rows (``ServingEngine.state_shape``)
    would land on the decode tier without them, and a pooled row's blocks
    (``ServingEngine.pooled``) are no prefix's content-addressed blocks."""
    if engine.state_shape is not None:
        raise TypeError(
            f"disaggregated serving hands K/V blocks over, and a "
            f"{type(engine.config).__name__} also keeps per-sequence state rows: "
            "a handoff has no place for them")
    if engine.pooled is not None:
        raise TypeError(
            f"disaggregated serving hands content-addressed K/V blocks over, and a "
            f"{type(engine.config).__name__} pools what lies behind its window: "
            "its summaries are no prefix's blocks")


class PrefillEngine(ServingEngine):
    """Chunked prefill only: every admitted request is prefilled (first
    token sampled at fold 0, the monolith's schedule), packed into a KV
    handoff, and released — the engine never decodes. Completed sequences'
    registered blocks park in this engine's LRU pool, so the prefill tier
    accumulates a warm prompt-prefix cache of its own."""

    def __init__(self, *args, transport: Optional[KVTransport] = None, **kwargs):
        kwargs.setdefault("prefix_cache", True)
        super().__init__(*args, **kwargs)
        _refuse_sequence_state(self)
        if not self.prefix_cache:
            raise ValueError("PrefillEngine requires prefix_cache=True "
                             "(chain hashes ARE the handoff addresses)")
        self.transport = transport or LocalBlockCopyTransport()
        self._handoffs: "list[tuple[Request, dict]]" = []
        self.handoffs_packed = 0
        self.handoffs_corrupted = 0

    def pop_handoffs(self) -> "list[tuple[Request, dict]]":
        """Drain the handoffs packed since the last call (the replica worker
        turns each into a ``handoff`` event)."""
        out, self._handoffs = self._handoffs, []
        return out

    def _step(self) -> "list[Request]":
        # ``ServingEngine.step`` wraps this in the ``atpu.serve.step`` phase;
        # admission and each prefill record their own phases inside it
        step_t0 = time.monotonic()
        finished: "list[Request]" = []
        prefills = 0
        prefill_tokens_before = self.prefill_tokens
        prefix_cached_before = self.prefix_cached_tokens
        for req in self._admit(finished):
            self._prefill_request(req)
            prefills += 1
            # chaos point "kv_handoff": the prefill work is DONE but the
            # handoff has not left yet — a crash here is the dropped-handoff
            # case the router must absorb by re-running prefill elsewhere;
            # a corrupt fault damages the payload we are about to ship
            corrupt = _inject_handoff_fault(self.steps)
            pack_t0 = _tracing.now_ns() if req.trace is not None else 0
            wire = self.transport.pack(self, req)
            if corrupt:
                corrupt_wire(wire)
                self.handoffs_corrupted += 1
            if pack_t0:
                req.trace_spans.append(_tracing.make_span(
                    req.trace, "kv_pack", pack_t0, _tracing.now_ns(),
                    parent_id=req._span_root["span_id"], component="engine",
                    blocks=len(wire.get("hashes", [])),
                ))
            # complete BEFORE shipping: frees the sequence, parking its
            # registered blocks in this engine's LRU (the tier-local prompt
            # cache); the wire dict snapshotted the content already
            t_ns, t = self._clock()
            self.scheduler.complete(req, t)
            self._close_trace(req, "handoff", t_ns)
            self.handoffs_packed += 1
            self._handoffs.append((req, wire))
            if _metrics.is_enabled():
                _metrics.inc("accelerate_engine_requests_total", outcome="handoff")
        self.steps += 1
        if self.scheduler.idle():
            _watchdog.unregister(self.heartbeat_name)
        else:
            _watchdog.beat(self.heartbeat_name, step=self.steps)
        if _metrics.is_enabled():
            _metrics.set_gauge("accelerate_engine_queue_depth",
                               self.scheduler.queue_depth, engine=self.heartbeat_name)
            _metrics.inc("accelerate_prefill_tokens_total",
                         self.prefill_tokens - prefill_tokens_before)
            _metrics.inc("accelerate_prefix_hit_tokens_total",
                         self.prefix_cached_tokens - prefix_cached_before)
            _metrics.maybe_snapshot()
        if tel.is_enabled() and (prefills or finished):
            alloc = self.allocator.stats()
            step_dur = time.monotonic() - step_t0
            tel.emit(
                "serving",
                phase="step",
                dur_s=round(step_dur, 6),
                queue_depth=self.scheduler.queue_depth,
                running=0,
                occupancy=0.0,
                prefills=prefills,
                prefill_tokens=self.prefill_tokens - prefill_tokens_before,
                prefix_hit_tokens=self.prefix_cached_tokens - prefix_cached_before,
                decode_tokens=0,
                preemptions=self.scheduler.preemption_count,
                free_blocks=alloc["free_blocks"],
                live_tokens=alloc["live_tokens"],
                block_occupancy=alloc["occupancy"],
                fragmentation=alloc["fragmentation"],
            )
            _goodput.note_serving_step(
                step_dur,
                computed_tokens=self.prefill_tokens - prefill_tokens_before,
                wasted_tokens=0,
            )
            _goodput.maybe_emit()
        return finished

    def stats(self) -> dict:
        out = super().stats()
        out.update(
            handoffs_packed=self.handoffs_packed,
            handoffs_corrupted=self.handoffs_corrupted,
        )
        return out


class DecodeEngine(ServingEngine):
    """Batched decode only, fed by landed KV handoffs. A handed-off request
    is admission-GATED until its blocks are in the pool's content index;
    the normal prefix-cache admission then maps them (``cached_tokens``
    covers every landed block) and the engine re-prefills only the
    sub-block prompt tail — through the same resume path failover uses, so
    the output stream is bitwise-identical to the monolith's."""

    def __init__(self, *args, transport: Optional[KVTransport] = None, **kwargs):
        kwargs.setdefault("prefix_cache", True)
        super().__init__(*args, **kwargs)
        _refuse_sequence_state(self)
        if not self.prefix_cache:
            raise ValueError("DecodeEngine requires prefix_cache=True "
                             "(handoffs land through the content index)")
        self.transport = transport or LocalBlockCopyTransport()
        #: engine rid -> handoff not yet landed; membership IS the admission
        #: gate (scheduler.admission_gate below)
        self._awaiting: "dict[Any, KVHandoff]" = {}
        self.handoffs_landed = 0
        self.handoff_blocks = 0
        self.handoff_dedup_blocks = 0
        L, _, B, Hkv, D = self.pool["k"].shape
        self._land_shape = (L, B, Hkv, D)

        def _land(pool, blk, k_content, v_content):
            # one block's content (all layers, K and V) into the pool at a
            # dynamic physical index — the decode half of a KV handoff; f32
            # content casts back to the pool dtype bit-exactly (the prefill
            # side widened from that dtype)
            return {
                "k": pool["k"].at[:, blk].set(k_content.astype(pool["k"].dtype)),
                "v": pool["v"].at[:, blk].set(v_content.astype(pool["v"].dtype)),
            }

        self.land_fn = jax.jit(_land, donate_argnums=(0,))
        self.scheduler.admission_gate = lambda r: r.rid not in self._awaiting

    def submit(self, *args, handoff: Optional[dict] = None, **kwargs) -> Request:
        req = super().submit(*args, **kwargs)
        if handoff is not None:
            self._awaiting[req.rid] = (
                handoff if isinstance(handoff, KVHandoff)
                else KVHandoff.from_wire(handoff)
            )
        return req

    def warmup(self) -> dict:
        from .. import compile_cache as _ccache

        # warm the landing write FIRST so the base warmup's telemetry record
        # (and its returned counts, via the jit_cache_sizes override) already
        # include the ``serving_land`` lattice point
        cache = None
        if self.compile_cache_dir is not None:
            cache = _ccache.get_cache(self.compile_cache_dir)
        content = np.zeros(self._land_shape, np.float32)
        args = (self.pool, np.int32(NULL_BLOCK), content, content)
        done = False
        if cache is not None:
            executable, outcome = _ccache.aot_compile(
                "serving_land", self.land_fn, args, mesh=self.mesh, cache=cache,
            )
            self.cache_stats[outcome] = self.cache_stats.get(outcome, 0) + 1
            if executable is not None:
                self._aot[("land",)] = executable
                done = True
        if not done:
            self.pool = self.land_fn(*args)
        return super().warmup()

    def jit_cache_sizes(self) -> dict:
        out = super().jit_cache_sizes()
        out["land_compiles"] = int(self.land_fn._cache_size()) + (
            1 if ("land",) in self._aot else 0
        )
        return out

    def step(self, now: Optional[float] = None) -> "list[Request]":
        self._land_pending()
        return super().step(now)

    def _land_pending(self) -> None:
        """Land every awaiting handoff that fits, in arrival order. A full
        pool defers the rest to the next step (running sequences drain and
        free blocks); if NOTHING is running the wait could never end, so the
        gate opens instead — normal admission re-prefills the whole prompt
        (or rejects it), which is slower but still bitwise-correct."""
        for rid in list(self._awaiting):
            h = self._awaiting[rid]
            try:
                st = self.transport.deliver(h, self)
            except BlockPoolExhausted:
                if not self.scheduler.running():
                    del self._awaiting[rid]
                break
            self.handoffs_landed += 1
            self.handoff_blocks += int(st.get("landed", 0))
            self.handoff_dedup_blocks += int(st.get("dedup", 0))
            del self._awaiting[rid]

    def stats(self) -> dict:
        out = super().stats()
        out.update(
            handoffs_landed=self.handoffs_landed,
            handoff_blocks=self.handoff_blocks,
            handoff_dedup_blocks=self.handoff_dedup_blocks,
            handoffs_awaiting=len(self._awaiting),
        )
        return out


# ---------------------------------------------------------------------------
# the two-tier router


class DisaggRouter(ServingRouter):
    """Role-aware dispatch over a prefill tier + a decode tier.

    A fresh request's first hop goes to the prefill tier; its ``handoff``
    event comes back through :meth:`_on_handoff`, is verified (CRC + chain
    hashes recomputed from the prompt), and the request re-queues toward
    the decode tier carrying the wire-form handoff. Every base-router
    invariant survives the extra hop:

    - **exactly-once**: the handoff event is consumed with the same
      stale-replica dedup as ``done`` events; terminal finalize still
      happens exactly once.
    - **failover**: a prefill replica dying mid-hop clears the request's
      progress (its first token must be re-sampled at fold 0 by the re-run)
      and requeues it to the surviving prefill tier; a decode replica dying
      requeues with progress + handoff intact (re-delivery dedups by chain
      hash). A handoff failing verification counts as a retry and re-runs
      prefill from scratch.
    - **tracing**: one trace_id spans prefill-hop → handoff → decode-hop;
      each hop is a ``dispatch`` span tagged ``hop=prefill|decode``.

    Correctness canaries (serving/canary.py) target only unified
    ``serving``-role replicas: a tier member runs half a request by
    construction, so there is no single replica a golden probe could hold
    to the single-stream reference — on a pure disagg fleet the canary
    plane is a no-op (the end-to-end bitwise invariant is covered by
    tests/test_disagg.py instead).
    """

    def __init__(self, prefill_replicas: "list", decode_replicas: "list",
                 **kwargs):
        if not prefill_replicas or not decode_replicas:
            raise ValueError("need at least one replica per tier")
        super().__init__(list(prefill_replicas) + list(decode_replicas), **kwargs)
        self.handoffs = 0
        self.handoff_corrupt = 0

    # -- tier views ----------------------------------------------------------

    def tier(self, role: str) -> "list":
        want_prefill = role == "prefill"
        return [
            r for r in self.replicas.values()
            if (getattr(r, "role", "serving") == "prefill") == want_prefill
        ]

    def _pending_prompt_tokens(self, name: str) -> int:
        return sum(int(r.prompt.size) for r in self._outstanding(name))

    # -- the handoff hop -----------------------------------------------------

    def _on_handoff(self, name: str, rep, ev: dict, now: float) -> bool:
        req = self._inflight.get(ev.get("rid"))
        if req is None or req.replica != name:
            return False  # stale: this request was failed over already
        del self._inflight[req.rid]
        if req.trace is not None:
            req.trace_spans.extend(ev.get("spans") or [])
            if req._span_dispatch is not None:
                _tracing.span_close(req._span_dispatch, outcome="handoff")
                req._span_dispatch = None
        wire = ev.get("handoff") or {}
        handoff, problems = KVHandoff.verify_wire(wire, prompt=req.prompt)
        if problems:
            # delivered but damaged (the chaos ``corrupt`` model, or any real
            # in-transit corruption): burn a retry and re-run prefill from
            # scratch — progress cleared so the re-run samples fold 0 again
            self.handoff_corrupt += 1
            req.replica = None
            req.retries += 1
            req.generated = []
            req.first_token_t = None
            req._handoff = None
            req.prefill_replica = None
            self._emit_handoff(req, name, wire, now, outcome="corrupt",
                               problems=problems)
            if req.retries > self.max_retries:
                self._finalize(
                    req, RouterRequestStatus.FAILED, now,
                    error=f"failed: handoff corrupt x{req.retries} "
                          f"({problems[0]})",
                )
            else:
                req.status = RouterRequestStatus.QUEUED
                self.admission.requeue_front(req)
            return True
        self.handoffs += 1
        per = self._per_replica[name]
        per["handoffs"] = per.get("handoffs", 0) + 1
        req.prefill_replica = name
        req.prefill_s = now - req._dispatch_t
        req.handoff_t = now
        if not req.generated:
            # the step event normally delivered tok0 already; the handoff's
            # copy is authoritative when it didn't (e.g. event coalescing)
            req.generated = [int(handoff.first_token)]
        if req.first_token_t is None:
            req.first_token_t = now
        self._emit_handoff(req, name, wire, now, outcome="ok")
        if req.done_decoding:
            # max_new_tokens == 1: the prefill hop produced everything
            self.completed += 1
            per["completed"] += 1
            self._finalize(req, RouterRequestStatus.FINISHED, now, count=False)
        else:
            req._handoff = wire
            req.status = RouterRequestStatus.QUEUED
            self.admission.requeue_front(req)
        return True

    def _emit_handoff(self, req, name: str, wire: dict, now: float, *,
                      outcome: str, problems: "Optional[list]" = None) -> None:
        _metrics.inc("accelerate_kv_handoffs_total", outcome=outcome)
        if not tel.is_enabled():
            return
        tel.emit(
            "kv_handoff",
            rid=req.rid,
            prefill_replica=name,
            outcome=outcome,
            blocks=len(wire.get("hashes") or []),
            bytes=len(wire.get("k") or "") + len(wire.get("v") or ""),
            prefill_s=round(now - req._dispatch_t, 6),
            retries=req.retries,
            error="; ".join(problems) if problems else None,
        )

    # -- failover ------------------------------------------------------------

    def _fail_replica(self, rep, reason: str, now: float) -> None:
        if getattr(rep, "role", "serving") == "prefill":
            for req in self._outstanding(rep.name):
                if not req.done_decoding:
                    # tok0 may have streamed back as progress, but the handoff
                    # died with the replica: the re-run must sample at fold 0
                    # again, so the resume state is wiped (keeping generated
                    # would make the prefill re-run resume at fold 1 with no
                    # KV — wrong tokens, silently)
                    req.generated = []
                    req.first_token_t = None
                    req._handoff = None
                    req.prefill_replica = None
        super()._fail_replica(rep, reason, now)

    # -- two-tier dispatch ---------------------------------------------------

    def _dispatch(self, now: float) -> bool:
        live_p = [
            r for r in self.tier("prefill")
            if r.state in (ReplicaState.STARTING, ReplicaState.HEALTHY)
        ]
        live_d = [
            r for r in self.tier("decode")
            if r.state in (ReplicaState.STARTING, ReplicaState.HEALTHY)
        ]
        activity = False
        stash: "list" = []  # popped but undispatchable NOW (tier busy)
        while True:
            req = self.admission.pop_next()
            if req is None:
                break
            if req.deadline_t is not None and req.deadline_t < now:
                self._finalize(
                    req, RouterRequestStatus.EXPIRED, now,
                    error="expired: deadline passed before dispatch",
                )
                activity = True
                continue
            # progress or a verified handoff binds the request to the decode
            # tier (resume must not re-run prefill); a clean request starts
            # at the prefill tier
            decode_bound = bool(req.generated) or req._handoff is not None
            live = live_d if decode_bound else live_p
            hop = "decode" if decode_bound else "prefill"
            if not live:
                if self._heal_pending():
                    stash.append(req)  # a respawn is coming: wait for it
                    continue
                self._finalize(
                    req, RouterRequestStatus.FAILED, now,
                    error=f"failed: no live {hop} replicas",
                )
                activity = True
                continue
            ready = [
                r for r in live
                if r.state is ReplicaState.HEALTHY
                and len(self._outstanding(r.name)) < self._replica_capacity(r)
            ]
            if not ready:
                # this tier is saturated/warming — park the request and keep
                # draining the queue so the OTHER tier is never head-of-line
                # blocked behind it
                stash.append(req)
                continue
            if decode_bound:
                # the base policy: least outstanding tokens, burning replicas
                # lose ties (SLO pressure leans dispatch away from them)
                target = min(
                    ready,
                    key=lambda r: (
                        r.name in self._burning_replicas,
                        self.outstanding_tokens(r.name),
                    ),
                )
            else:
                # prefill cost is prompt-length-proportional: fewest queued
                # requests first, pending prompt tokens as the tiebreak
                target = min(
                    ready,
                    key=lambda r: (
                        len(self._outstanding(r.name)),
                        self._pending_prompt_tokens(r.name),
                    ),
                )
            self._send(req, target, now, hop)
            activity = True
        for req in reversed(stash):  # restore original queue order
            self.admission.requeue_front(req)
        return activity

    def _send(self, req, target, now: float, hop: str) -> None:
        req.replica = target.name
        req._resume_from = len(req.generated)
        req._dispatch_t = now
        req.status = RouterRequestStatus.DISPATCHED
        self._inflight[req.rid] = req
        self.dispatched += 1
        self._per_replica[target.name]["dispatched"] += 1
        payload = {
            "rid": req.rid,
            "prompt": [int(t) for t in req.prompt],
            "max_new": req.max_new_tokens,
            "eos": req.eos_token_id,
            "rng_seed": req.rng_seed,
            "generated": list(req.generated),
        }
        if hop == "decode" and req._handoff is not None:
            payload["handoff"] = req._handoff
        if req.trace is not None:
            req._span_dispatch = _tracing.span_open(
                req.trace, "dispatch", parent_id=req._span_root["span_id"],
                component="router", replica=target.name, hop=hop,
                attempt=int(req.retries),
                resume_tokens=len(req.generated),
            )
            req.trace_spans.append(req._span_dispatch)
            wire_ctx = _tracing.TraceContext(req.trace).child(
                req._span_dispatch["span_id"]
            )
            if req.retries > 0:
                wire_ctx = _tracing.TraceContext(wire_ctx, sampled=True)
            payload["trace"] = dict(wire_ctx)
        target.submit(payload)

    # -- views ---------------------------------------------------------------

    def stats(self) -> dict:
        out = super().stats()
        out.update(
            handoffs=self.handoffs,
            handoff_corrupt=self.handoff_corrupt,
            tiers={
                "prefill": sorted(r.name for r in self.tier("prefill")),
                "decode": sorted(r.name for r in self.tier("decode")),
            },
        )
        return out
