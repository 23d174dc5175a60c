"""The serving engine: continuous batching over a paged KV cache.

One :class:`ServingEngine` owns the device state (params + the paged block
pool) and two compiled step functions:

- **prefill** (per request, batch 1): forward the request's full prefix
  (prompt + any tokens generated before a preemption) in length-bucketed
  chunks — each chunk padded to the smallest covering prefill bucket, so a
  prefix of ANY length stays inside the compiled lattice — writing KV into
  the request's blocks via its block table, and sample the next token from
  the last real position's logits;
- **decode** (batched): one token for every live batch slot in a single
  paged-attention forward at a bucketed (slots, table-width) shape, with
  per-slot positions, per-slot PRNG keys and per-slot fold indices so each
  request's token stream is EXACTLY what a single-stream
  ``generation.greedy_generate`` / ``sample_generate`` call with batch 1
  would produce — batch composition can never leak into a request's output.

Both functions compile only at :class:`~accelerate_tpu.serving.buckets.
BucketLattice` shapes; :meth:`ServingEngine.warmup` pre-compiles every
lattice point so admission/eviction churn after warmup is recompile-free
(guarded by ``tests/test_serving.py`` and ``make doctor`` check 12 via the
telemetry recompile detector).

Multi-chip placement rides the existing generation surface: pass ``mesh``
(params already sharded via ``parallel.sharding``) and the pool is placed by
:func:`~accelerate_tpu.generation.serving_shardings` — KV heads over ``tp``,
the same Megatron decode dataflow as ``generation_shardings``.
"""

from __future__ import annotations

import itertools
import time
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..generation import sample_token_logits, serving_shardings
from ..models.transformer import LlamaConfig, draft_config, draft_params
from ..ops.flash_attention import (
    NULL_BLOCK,
    NULL_STATE_ROW,
    init_block_pool,
    pooled_row_index,
    prefill_tiling,
    prefill_walk_blocks,
)
from ..telemetry import events as tel
from ..telemetry import goodput as _goodput
from ..telemetry import metrics as _metrics
from ..telemetry import tracing as _tracing
from ..telemetry import watchdog as _watchdog
from .buckets import BucketLattice
from .kv_pager import BlockAllocator
from .scheduler import Request, Scheduler

__all__ = ["ServingEngine"]

# the ``engine=`` key of an engine's phases and request records: a sequence
# number unique in the process (``heartbeat_name`` defaults to one string for
# every engine, so it cannot tell two engines' records apart)
_engine_ids = itertools.count()


def _chaos_inject(point: str, step: int) -> None:
    # lazy import: resilience pulls in the supervisor stack, which serving
    # must not pay for (or cyclically import) at module load
    from ..resilience import chaos as _chaos

    _chaos.maybe_inject(point, step=step)


def model_paged_forward(config, block_size: int):
    """The paged forward of the model ``config`` describes, as the engine's
    step programs call it: ``fn(params, ids [B, S], pool, block_tables,
    positions [B, S], valid [B, S]) -> (logits, new pool, counts)``. Every
    model brings its own as ``config.paged_forward`` (``models/transformer.py``,
    ``models/cohere2_moe.py``); ``counts`` is None or a small integer array
    the engine fetches with the step's tokens and records
    (:meth:`ServingEngine._record_counts`). A model with per-sequence state
    (``config.state_shape``, ``models/lfm2.py``) takes one more argument
    after ``valid``: ``rows [B]``, each sequence's row of ``pool["state"]``."""
    if not callable(getattr(config, "paged_forward", None)):
        raise TypeError(
            f"ServingEngine cannot serve a {type(config).__name__}: it has no "
            "paged_forward(params, ids, pool, block_tables, positions, valid, block_size)")
    return partial(config.paged_forward, block_size=block_size)


class ServingEngine:
    """Continuous-batching serving engine over a paged KV cache.

    ``submit`` enqueues requests; each ``step`` admits what fits (prefill in
    length buckets), decodes one token for every live slot, completes/frees
    finished sequences and backfills their slots. Pool pressure preempts the
    youngest request (progress persisted, resumed later with identical
    output). Sampling knobs are engine-level (compiled into the step
    functions — per-request knobs would multiply the compile lattice);
    ``temperature=0`` is greedy. Emits ``serving`` / ``serving_request``
    telemetry records when telemetry is enabled.

    ``spec_tokens=k`` (with ``draft_layers=n``) turns on speculative
    decoding: a truncated-layer self-draft (the verifier's first n layers +
    its head, sharing params AND the paged pool) proposes k tokens per step
    and one batched S=k+1 verify step accepts the longest prefix matching
    the verifier's own per-slot fold-stream emissions — so the output stream
    stays bitwise-identical to non-speculative decode while a good draft
    collapses up to k+1 tokens into one model step (see
    ``docs/serving.md``).

    ``config`` describes the model: the engine reads its ``n_layers``,
    ``n_kv_heads``, ``head_dim`` (the pool's shape), ``max_seq_len`` and, where
    it has one, ``sliding_window``, and runs its paged forward
    (:func:`model_paged_forward`: the config's own method). Speculative
    decoding drafts with a ``LlamaConfig``'s own first layers and refuses
    another model.

    Two kinds of cache. Every model has the paged K/V pool, which grows with a
    sequence: blocks, a block table, the allocator. It has as many layers as
    the model says attend (``config.n_kv_layers``, else ``n_layers``). A model
    with ``config.state_shape`` (``models/lfm2.py``: layers that keep a few
    rows a sequence, whatever its length) also gets ``pool["state"] [layers,
    max_slots + 1, *row]``. A sequence's state row is ``slot + 1`` of the batch
    slot the scheduler gives it at admission and takes back, with its blocks,
    at finish and at preemption (``Scheduler._release`` is the one owner of
    both); row 0 is the null row of idle slots, as block 0 is the null block.
    The engine passes the rows to both step programs, and the model resets a
    row itself: a call whose first position is 0 starts from zeros whatever
    the row's last owner left, and a preempted request is re-prefilled from
    position 0, which rebuilds its state. Each row handed out or taken back is
    an ``atpu.serve.state`` record (``rid``, ``row``, ``why``: admit / finish /
    preempt), ``atpu.serve.build`` carries ``state_rows``, and :meth:`stats`
    has ``state_resets`` and ``state_bytes``.

    What such a model is refused, and why: a prefix-cache hit (a hit skips the
    prompt's head, which is what the state is made of: ``prefix_cache`` is
    switched off, no hit is taken and every prompt is prefilled whole) and
    with it copy-on-write; speculative decoding (a rejected candidate would
    already have moved the state); disaggregated serving (a handoff carries
    blocks, not rows); a ``mesh`` (the pool's sharding is written for K/V).

    A model whose attention POOLS what lies behind a window (``config.window_size``
    and ``config.chunk_size``, ``models/evabyte.py``: exact keys inside the
    window a query is in, one pooled key/value pair for every ``chunk_size``
    positions of the closed windows) keeps a row of two kinds in the one pool
    (``kv_pager.BlockAllocator``'s pooled rows): raw blocks of the open window,
    handed back when it closes, and summary blocks that the model fills as
    chunks complete. The step programs take the stored row as they take any
    other and the model composes what the kernels walk
    (``ops.flash_attention.pooled_row_view``). The engine cuts a prefill at
    window boundaries (a call's queries lie in one window), hands back after it
    what its last window does not need (``allocator.settle``), and records
    each prefill and decode step as an ``atpu.serve.eva`` record
    (``chunks_folded``, ``windows_closed``, ``raw_blocks_freed`` since the
    record before); ``atpu.serve.build`` then carries ``window_entries`` and
    ``summary_entries`` (the raw keys and the pooled pairs the batch attends)
    and ``live_blocks`` counts the composed rows'. Refused: a prefix-cache hit
    (the prefix's closed windows would need their summaries, which the cache
    does not keep: ``prefix_cache`` is switched off), with it copy-on-write and
    disaggregated serving, and speculative decoding.
    """

    def __init__(
        self,
        params,
        config,
        *,
        num_blocks: int = 64,
        block_size: int = 16,
        max_slots: int = 4,
        max_prefill_len: Optional[int] = None,
        max_blocks_per_seq: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        cache_dtype=jnp.bfloat16,
        mesh=None,
        continuous: bool = True,
        admit_watermark_blocks: int = 0,
        lattice: Optional[BucketLattice] = None,
        heartbeat_name: str = "serving_decode",
        compile_cache_dir: Optional[str] = None,
        prefix_cache: bool = True,
        spec_tokens: int = 0,
        draft_layers: Optional[int] = None,
    ):
        self.params = params
        self.config = config
        forward = model_paged_forward(config, block_size)
        #: a window layer of the model reads the last `window` positions only
        self.window = getattr(config, "sliding_window", None)
        self.block_size = block_size
        self.max_slots = max_slots
        self.mesh = mesh
        # speculative decoding: a truncated-layer self-draft proposes
        # ``spec_tokens`` tokens per step and ONE batched S=k+1 verify step
        # accepts the longest prefix that matches the verifier's own
        # fold-stream emissions (bitwise-accept — see _spec_decode_batch)
        self.spec_tokens = int(spec_tokens)
        self.draft_layers = draft_layers
        if self.spec_tokens < 0:
            raise ValueError(f"spec_tokens must be >= 0, got {spec_tokens}")
        if self.spec_tokens > 0 and draft_layers is None:
            raise ValueError("spec_tokens > 0 requires draft_layers (the self-draft depth)")
        if self.spec_tokens > 0 and not isinstance(config, LlamaConfig):
            raise TypeError(
                f"speculative decoding drafts with a LlamaConfig's first layers; "
                f"a {type(config).__name__} has no draft")
        # watchdog heartbeat source for the decode loop: a hang inside a
        # batched decode produces a stall dump naming this engine (replicas
        # suffix their name so a stuck replica is attributable)
        self.heartbeat_name = heartbeat_name
        #: what the model keeps a sequence beside its blocks: ``(layers, *row)``, or None
        self.state_shape = getattr(config, "state_shape", None)
        if self.state_shape is not None and mesh is not None:
            raise TypeError(
                f"a {type(config).__name__} keeps per-sequence state, and the pool's "
                "sharding over a mesh is written for keys and values only")
        #: ``(window_size, chunk_size)`` of a model that pools what lies behind a window, or None
        self.pooled = None
        if getattr(config, "chunk_size", None) is not None:
            self.pooled = (int(config.window_size), int(config.chunk_size))
        # a prefix hit skips the prompt's head, and the state is made of it; a
        # pooled row's closed windows would need their summaries: no hit is taken
        self._prefix_cache_asked = prefix_cache
        prefix_cache = prefix_cache and self.state_shape is None and self.pooled is None
        self.prefix_cache = prefix_cache
        if max_blocks_per_seq is None:
            max_blocks_per_seq = num_blocks - 1  # every block but the null one
        max_prefill_len = max_prefill_len or min(
            config.max_seq_len, max_blocks_per_seq * block_size
        )
        if max_prefill_len > max_blocks_per_seq * block_size:
            raise ValueError(
                f"max_prefill_len={max_prefill_len} exceeds "
                f"{max_blocks_per_seq} block(s) x {block_size} slots"
            )
        self.lattice = lattice or BucketLattice.from_limits(
            max_slots, max_blocks_per_seq, max_prefill_len
        )
        if self.pooled is not None and any(b % self.pooled[1] for b in self.lattice.prefill_buckets):
            raise ValueError(f"prefill buckets {self.lattice.prefill_buckets} are not whole "
                             f"chunks of {self.pooled[1]}: a pooled prefill starts every chunk")
        # a sequence's table lives in the allocator's row of its batch slot,
        # as wide as the widest bucket; the row past the last slot stays null
        self.allocator = BlockAllocator(
            num_blocks, block_size, prefix_caching=prefix_cache,
            max_seqs=max_slots, max_seq_blocks=self.lattice.block_buckets[-1],
            pooled=self.pooled,
        )
        self.scheduler = Scheduler(
            self.allocator, max_slots,
            continuous=continuous, admit_watermark_blocks=admit_watermark_blocks,
            # a sequence's block table can never exceed the lattice's widest
            # bucket, and its positions can never exceed the RoPE table —
            # admission rejects worst cases beyond either up front
            max_seq_blocks=self.lattice.block_buckets[-1],
            max_seq_tokens=config.max_seq_len,
        )
        # a decode batch's inputs, a row a batch slot (the allocator keeps the
        # block tables the same way): written where they change, read with one
        # index over the running slots (:meth:`_decode_inputs`). The row past
        # the last slot is a padding row's, and stays zero
        self._last = np.zeros((max_slots + 1,), np.int32)  # the token the next step reads
        self._positions = np.zeros((max_slots + 1,), np.int32)  # that token's position
        self._keys = np.zeros((max_slots + 1, 2), np.uint32)  # the request's PRNG key
        self._token_idx = np.zeros((max_slots + 1,), np.int32)  # the next token's fold index
        self.pool = init_block_pool(
            config, num_blocks, block_size, cache_dtype, state_rows=max_slots + 1)
        if mesh is not None:
            sharding = serving_shardings(mesh, config)
            self.pool = jax.tree_util.tree_map(
                lambda c: jax.device_put(c, sharding), self.pool
            )

        if temperature == 0.0:
            def select_one(row, key):
                return jnp.argmax(row, axis=-1)
        else:
            def select_one(row, key):
                return sample_token_logits(
                    row[None], key, temperature=temperature, top_k=top_k, top_p=top_p
                )[0]

        def _prefill(params, pool, ids, table, start, last_idx, key, token_idx, *rows):
            # one CHUNK of a prefix: ids [1, Sb] holds the tokens at absolute
            # positions start..start+Sb-1 (the host loop feeds long prefixes
            # through the largest bucket chunk by chunk); the sampled token is
            # meaningful only for the final chunk (last_idx = last real row).
            # `rows` is empty, or the sequence's state row (:meth:`_state_rows`)
            B, Sb = ids.shape
            positions = start + jnp.broadcast_to(jnp.arange(Sb)[None], (B, Sb))
            # the chunk's real tokens; the bucket's padding lies behind `last_idx`
            valid = jnp.broadcast_to(jnp.arange(Sb)[None] <= last_idx, (B, Sb))
            logits, pool, counts = forward(params, ids, pool, table, positions, valid, *rows)
            last = jax.lax.dynamic_index_in_dim(logits, last_idx, axis=1, keepdims=False)
            tok = select_one(last[0], jax.random.fold_in(key, token_idx))
            return pool, (tok.astype(jnp.int32), counts)

        def _decode(params, pool, last_tok, tables, positions, keys, token_idx, *rows):
            # an idle slot's table is all null; a live row's first block never is
            valid = tables[:, :1] != NULL_BLOCK
            logits, pool, counts = forward(
                params, last_tok[:, None], pool, tables, positions[:, None], valid, *rows)
            folded = jax.vmap(jax.random.fold_in)(keys, token_idx)
            tok = jax.vmap(select_one)(logits[:, -1], folded)
            return pool, (tok.astype(jnp.int32), counts)

        def _cow(pool, src, dst):
            # copy-on-write for the aligned prefix-cache edge case: duplicate
            # one physical block (all layers, K and V) into a private block
            # before the new sequence's first write can touch shared content
            if "state" in pool:  # the pool's keys, static under jit  # jaxlint: disable=R1
                raise TypeError("a shared block has no copy of a sequence's state to go with it")
            return {
                "k": pool["k"].at[:, dst].set(pool["k"][:, src]),
                "v": pool["v"].at[:, dst].set(pool["v"][:, src]),
            }

        self.prefill_fn = jax.jit(_prefill, donate_argnums=(1,))
        self.decode_fn = jax.jit(_decode, donate_argnums=(1,))
        self.cow_fn = jax.jit(_cow, donate_argnums=(0,))

        if self.spec_tokens > 0:
            n_draft = int(draft_layers)
            draft_forward = model_paged_forward(draft_config(config, n_draft), block_size)
            # truncated-layer self-draft: layer i IS verifier layer i (shared
            # leaves, no copy), so the verifier's landed KV is valid draft KV
            # and the draft needs no pool/prefill/warmup of its own
            self.draft_params = draft_params(params, n_draft)

            def _draft(dparams, pool, last_tok, tables, positions, keys, token_idx):
                # one S=1 step of the truncated model over the SHARED pool's
                # first n layers. Its KV writes let draft step j+1 attend to
                # draft step j's candidate; the verify step recomputes the
                # same layer-i KV for accepted tokens (identical math), so
                # the overwrite is value-exact, and rejected positions are
                # re-written before any later read (scatter-then-attend).
                logits, pool, _ = draft_forward(
                    dparams, last_tok[:, None], pool, tables, positions[:, None], None)
                folded = jax.vmap(jax.random.fold_in)(keys, token_idx)
                tok = jax.vmap(select_one)(logits[:, -1], folded)
                return pool, tok.astype(jnp.int32)

            def _verify(params, pool, cand, tables, positions, keys, token_idx):
                # cand [B, k+1]: column 0 = the last confirmed token, columns
                # 1..k = draft proposals. ONE batched S=k+1 forward scatter-
                # writes KV for every candidate position and then selects —
                # per (row, column) — the token the NON-speculative stream
                # would emit at fold index token_idx + j. The host accepts
                # the longest prefix where the draft matched those selections
                # exactly, so the emitted stream is bitwise the single-stream
                # one in greedy AND sampled modes.
                B, S = cand.shape
                pos = positions[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
                logits, pool, _ = forward(params, cand, pool, tables, pos, None)
                idx = token_idx[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
                folded = jax.vmap(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))(
                    keys, idx
                )
                sel = jax.vmap(jax.vmap(select_one))(logits, folded)
                return pool, sel.astype(jnp.int32)

            self.draft_fn = jax.jit(_draft, donate_argnums=(1,))
            self.verify_fn = jax.jit(_verify, donate_argnums=(1,))
        # Persistent-compile-cache warm boot: when a cache dir is configured
        # (replacement replicas get it via ReplicaSpec.compile_cache_dir),
        # warmup AOT-compiles every lattice point through the cache — hits
        # load in milliseconds — and the step paths dispatch to these
        # executables; with no dir this stays empty and behavior is
        # byte-identical to the plain jit path.
        self.compile_cache_dir = compile_cache_dir
        self._aot: dict = {}  # ("prefill"|"decode", *bucket shape) -> executable
        self.cache_stats = {"hit": 0, "miss": 0, "corrupt": 0, "uncached": 0, "error": 0}

        # live observability (PR 15): arm tracing/metrics from the env once
        # per engine — both stay None-branch no-ops when unconfigured
        _tracing.maybe_arm_from_env()
        _metrics.maybe_enable_from_env()

        self.engine_id = next(_engine_ids)
        #: the ``now`` the caller gave the step in progress (a simulated
        #: clock), else None: every stamp is then read when its event happens
        self._given_now: Optional[float] = None

        # stats for the telemetry records / bench payloads
        self.steps = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.prefill_calls = 0
        #: prompt tokens whose KV came straight from the prefix cache — i.e.
        #: prefill work NOT done (the bench's ``prefill_tokens_saved``)
        self.prefix_cached_tokens = 0
        #: re-prefilled tokens: KV this engine computed a second time. The
        #: goodput ledger's token-waste attribution — preempt/resume
        #: re-prefills vs failover/handoff resumes seeded via ``generated``
        self.preempt_prefill_tokens = 0
        self.resume_prefill_tokens = 0
        self.max_running = 0
        self._occupancy_sum = 0.0
        self._occupancy_steps = 0
        #: block-table entries the decode batches' rows held (the paged decode
        #: kernel's work) and entries of the bucketed tables they were handed
        self.decode_blocks_live = 0
        self.decode_blocks_walked = 0
        #: block-table entries the allocator wrote into the slots' rows, up to
        #: the last decode batch's build (``rows_written`` on ``atpu.serve.build``)
        self.decode_table_entries_written = 0
        #: of the live ones, the blocks a window layer walked (a model with a window)
        self.decode_blocks_window = 0
        #: blocks the paged prefill kernel's grid visits for one full layer of
        #: the prefill chunks, and entries of the tables its query tiles were
        #: handed (:meth:`_prefill_plan`); for a window layer where the model has one
        self.prefill_blocks_walked = 0
        self.prefill_blocks_table = 0
        self.prefill_blocks_window = 0
        #: a routed model's own counts, summed over its calls and layers
        #: (:meth:`_record_counts`; `moe_*` in :meth:`stats`): real tokens
        #: through the model, (token, expert) pairs on the experts held here,
        #: held experts hit, layer calls, the most pairs one expert got in a call
        self.moe = dict(tokens=0, local_pairs=0, experts_hit=0, calls=0, max_expert_load=0)
        #: a model with per-sequence state: prefills that started a row from
        #: zeros, and the bytes of ``pool["state"]``
        self.state_resets = 0
        self.state_bytes = int(self.pool["state"].nbytes) if self.state_shape is not None else 0
        #: a model with pooled rows: chunks folded, over prefills and decode steps;
        #: the raw keys and pooled pairs the decode batches attended
        self.eva_chunks_folded = 0
        self.decode_window_entries = 0
        self.decode_summary_entries = 0
        #: the allocator's (windows_closed, raw_blocks_freed) at the last
        #: ``atpu.serve.eva`` record: each record carries what changed since
        self._eva_seen = (0, 0)
        #: speculative decoding: draft tokens proposed / accepted, and the
        #: accepted-per-step histogram (index = draft tokens accepted that
        #: slot-step, 0..k) the report's serving section renders
        self.draft_proposed_tokens = 0
        self.draft_accepted_tokens = 0
        self.spec_accept_hist = np.zeros(max(self.spec_tokens, 0) + 1, np.int64)

    # -- lifecycle -----------------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        eos_token_id: Optional[int] = None,
        rng_seed: int = 0,
        arrival_t: Optional[float] = None,
        generated: Optional["list[int]"] = None,
        trace: Optional[dict] = None,
    ) -> Request:
        """Enqueue one request; returns its :class:`Request` handle (live —
        ``generated``/``status`` update as the engine steps).

        ``generated`` seeds the request with tokens already produced by a
        PREVIOUS engine (the router's cross-replica failover resume): the
        prefill covers ``prompt + generated`` and sampling continues at fold
        index ``len(generated)`` — exactly the scheduler's preempt/resume
        state, so the continuation is bitwise-identical to an unfailed run.
        ``max_new_tokens`` stays the request's TOTAL new-token budget.

        ``trace`` is a propagated :class:`~accelerate_tpu.telemetry.tracing.
        TraceContext` dict (the router's dispatch span): engine spans parent
        under it and accumulate on ``Request.trace_spans`` for the owner to
        emit. With no ``trace`` and tracing armed, the engine roots its own
        trace and emits it at completion."""
        req = Request(
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id,
            rng_seed=rng_seed,
            arrival_t=time.monotonic() if arrival_t is None else arrival_t,
        )
        if generated:
            if len(generated) >= max_new_tokens:
                raise ValueError(
                    f"resume with {len(generated)} generated token(s) >= "
                    f"max_new_tokens={max_new_tokens}: nothing left to decode"
                )
            req.generated = [int(t) for t in generated]
        ctx = _tracing.TraceContext.from_wire(trace)
        if ctx is None and _tracing.is_armed():
            ctx = _tracing.new_trace()
            req._trace_owner = True
        if ctx is not None:
            req.trace = ctx
            req._span_root = _tracing.span_open(
                ctx, "engine_request", component="engine", rid=int(req.rid),
                prompt_tokens=int(req.prompt.size),
                resumed_tokens=len(req.generated),
            )
            req._span_queue = _tracing.span_open(
                ctx, "queue_wait", parent_id=req._span_root["span_id"],
                component="engine",
            )
            req.trace_spans += [req._span_root, req._span_queue]
        self.scheduler.submit(req)
        return req

    def warmup(self) -> dict:
        """Compile every lattice point up front (decode (slots, width) cross
        product + per-length prefill) so serving never pays a compile — and so
        the recompile detector's baseline is exact. Returns the per-function
        compile counts; the jit caches must never grow past them.

        With ``compile_cache_dir`` configured (and the cache enabled), every
        point goes through :func:`accelerate_tpu.compile_cache.aot_compile`
        instead: a cached point LOADS in milliseconds (a replacement replica
        boots warm), a missed point compiles once and is exported for the
        next boot. ``cache_stats`` records the per-point outcomes."""
        from .. import compile_cache as _ccache

        warmup_t0 = time.monotonic()
        cache = None
        if self.compile_cache_dir is not None:
            cache = _ccache.get_cache(self.compile_cache_dir)
        key = np.zeros((2,), np.uint32)
        for Sb, W in self.lattice.prefill_points():
            ids = np.zeros((1, Sb), np.int32)
            table = np.full((1, W), NULL_BLOCK, np.int32)
            args = (
                self.params, self.pool, ids, table, np.int32(0), np.int32(0),
                key, np.int32(0), *self._state_rows((), 1),
            )
            if cache is not None:
                executable, outcome = _ccache.aot_compile(
                    f"serving_prefill[{Sb}x{W}]", self.prefill_fn, args,
                    mesh=self.mesh, cache=cache,
                )
                self.cache_stats[outcome] = self.cache_stats.get(outcome, 0) + 1
                if executable is not None:
                    self._aot[("prefill", Sb, W)] = executable
                    continue
            self.pool, _ = self.prefill_fn(*args)
        for Bb, W in self.lattice.decode_points():
            last = np.zeros((Bb,), np.int32)
            tables = np.full((Bb, W), NULL_BLOCK, np.int32)
            positions = np.zeros((Bb,), np.int32)
            keys = np.zeros((Bb, 2), np.uint32)
            token_idx = np.zeros((Bb,), np.int32)
            args = (self.params, self.pool, last, tables, positions, keys, token_idx,
                    *self._state_rows((), Bb))
            if cache is not None:
                executable, outcome = _ccache.aot_compile(
                    f"serving_decode[{Bb}x{W}]", self.decode_fn, args,
                    mesh=self.mesh, cache=cache,
                )
                self.cache_stats[outcome] = self.cache_stats.get(outcome, 0) + 1
                if executable is not None:
                    self._aot[("decode", Bb, W)] = executable
                    continue
            self.pool, _ = self.decode_fn(*args)
        if self.spec_tokens > 0:
            # the draft + k-verify families: one point per decode point each
            # (verify's S=k+1 width is static, so it is one extra warmed
            # shape per (slots, width), not a new lattice axis)
            for Bb, W in self.lattice.decode_points():
                last = np.zeros((Bb,), np.int32)
                tables = np.full((Bb, W), NULL_BLOCK, np.int32)
                positions = np.zeros((Bb,), np.int32)
                keys = np.zeros((Bb, 2), np.uint32)
                token_idx = np.zeros((Bb,), np.int32)
                args = (
                    self.draft_params, self.pool, last, tables, positions,
                    keys, token_idx,
                )
                done = False
                if cache is not None:
                    executable, outcome = _ccache.aot_compile(
                        f"serving_draft[{Bb}x{W}]", self.draft_fn, args,
                        mesh=self.mesh, cache=cache,
                    )
                    self.cache_stats[outcome] = self.cache_stats.get(outcome, 0) + 1
                    if executable is not None:
                        self._aot[("draft", Bb, W)] = executable
                        done = True
                if not done:
                    self.pool, tok = self.draft_fn(*args)
                cand = np.zeros((Bb, self.spec_tokens + 1), np.int32)
                args = (
                    self.params, self.pool, cand, tables, positions, keys, token_idx
                )
                done = False
                if cache is not None:
                    executable, outcome = _ccache.aot_compile(
                        f"serving_verify[{Bb}x{W}]", self.verify_fn, args,
                        mesh=self.mesh, cache=cache,
                    )
                    self.cache_stats[outcome] = self.cache_stats.get(outcome, 0) + 1
                    if executable is not None:
                        self._aot[("verify", Bb, W)] = executable
                        done = True
                if not done:
                    self.pool, tok = self.verify_fn(*args)
        if self.prefix_cache:
            # the COW copy is one more lattice point (a single shape): warm it
            # here — copying the null block onto itself writes nothing live
            args = (self.pool, np.int32(NULL_BLOCK), np.int32(NULL_BLOCK))
            done = False
            if cache is not None:
                executable, outcome = _ccache.aot_compile(
                    "serving_cow", self.cow_fn, args, mesh=self.mesh, cache=cache,
                )
                self.cache_stats[outcome] = self.cache_stats.get(outcome, 0) + 1
                if executable is not None:
                    self._aot[("cow",)] = executable
                    done = True
            if not done:
                self.pool = self.cow_fn(*args)
        jax.block_until_ready(self.pool)
        counts = self.jit_cache_sizes()
        if tel.is_enabled():
            warmup_dur = time.monotonic() - warmup_t0
            tel.emit(
                "serving", phase="warmup", dur_s=round(warmup_dur, 6), **counts,
                **(
                    {"cache_" + k: v for k, v in self.cache_stats.items() if v}
                    if cache is not None else {}
                ),
            )
            _goodput.note("warmup", warmup_dur)
        return counts

    def jit_cache_sizes(self) -> dict:
        """Compiled-entry counts for the two step functions (live jit cache
        plus cache-loaded AOT executables) — after :meth:`warmup` these must
        equal the lattice sizes forever."""
        aot_prefill = sum(1 for k in self._aot if k[0] == "prefill")
        aot_decode = sum(1 for k in self._aot if k[0] == "decode")
        out = {
            "prefill_compiles": int(self.prefill_fn._cache_size()) + aot_prefill,
            "decode_compiles": int(self.decode_fn._cache_size()) + aot_decode,
        }
        if self.prefix_cache:
            out["cow_compiles"] = int(self.cow_fn._cache_size()) + (
                1 if ("cow",) in self._aot else 0
            )
        if self.spec_tokens > 0:
            out["draft_compiles"] = int(self.draft_fn._cache_size()) + sum(
                1 for k in self._aot if k[0] == "draft"
            )
            out["verify_compiles"] = int(self.verify_fn._cache_size()) + sum(
                1 for k in self._aot if k[0] == "verify"
            )
        return out

    # -- the step loop -------------------------------------------------------

    def step(self, now: Optional[float] = None) -> "list[Request]":
        """One engine iteration: admit+prefill, decode one token for every
        live slot, complete/free finished sequences. Returns the requests
        that left the engine this step — status FINISHED, or REJECTED (with
        ``Request.error`` set) for requests whose worst case can never fit
        this engine's pool/lattice.

        The step and its phases are recorded as ``atpu.serve.*`` spans
        (:func:`accelerate_tpu.telemetry.tracing.phase`): ``step`` round
        everything, and inside it the disjoint children ``admit``,
        ``prefill`` (one per admitted request), ``grow``, ``build``,
        ``dispatch``, ``fetch`` and ``emit``; what is left of ``step`` is the
        watchdog beat, metrics and event emission. A ``prefill`` holds its
        own disjoint children, ``prefill_call`` and ``prefill_sync``
        (:meth:`_prefill_request`). ``now`` stands in for the
        clock in every ``Request`` stamp of this step (simulated time)."""
        self._given_now = now
        with self._phase("step"):
            return self._step()

    def _phase(self, name: str, **key):
        return _tracing.phase(
            "atpu.serve." + name, engine=self.engine_id, step=self.steps, **key
        )

    def _clock(self) -> "tuple[int, float]":
        """One read of the clock at an event, as ``(monotonic ns, seconds)``:
        the ns close the request's trace spans, the seconds are the
        ``Request`` stamp (the caller's ``now`` where ``step`` was given
        one), so the two can never disagree."""
        t_ns = _tracing.now_ns()
        return t_ns, (t_ns / 1e9 if self._given_now is None else self._given_now)

    def _admit(self, finished: "list[Request]") -> "list[Request]":
        """The admission phase: place what fits (stamping first admissions
        and closing their ``queue_wait`` spans at the same read) and hand
        back what can never run as REJECTED."""
        with self._phase("admit"):
            t_ns, t = self._clock()
            admitted = self.scheduler.admissions(now=t, step=self.steps)
            for req in admitted:
                if req._span_queue is not None and "t1_ns" not in req._span_queue:
                    _tracing.span_close(req._span_queue, t1_ns=t_ns)
                self._record_state(req, req.slot, "admit", t_ns)
            while self.scheduler.rejected:
                req = self.scheduler.rejected.pop()
                req.finish_t = t
                self._close_trace(req, "rejected", t_ns)
                finished.append(req)  # returned to the caller, status REJECTED
                if _metrics.is_enabled():
                    _metrics.inc("accelerate_engine_requests_total", outcome="rejected")
                if tel.is_enabled():
                    tel.emit(
                        "serving_request", rid=req.rid, error=req.error,
                        new_tokens=0, prompt_tokens=int(req.prompt.size),
                    )
        return admitted

    def _complete_done(self, requests, finished: "list[Request]") -> None:
        """Complete every request of ``requests`` that is done: free its
        blocks, stamp ``finish_t`` and write its records."""
        for req in requests:
            if req.done:
                t_ns, t = self._clock()
                self._record_state(req, req.slot, "finish", t_ns)
                self.scheduler.complete(req, t)
                self._finish_request(req, t_ns)
                finished.append(req)

    def _step(self) -> "list[Request]":
        step_t0 = time.monotonic()
        # chaos fault point: a seeded replica kill/hang/slow lands HERE, mid
        # decode loop (resilience/chaos.py, point "serving_decode") — one
        # ``is None`` check when disarmed
        _chaos_inject("serving_decode", self.steps)
        finished: "list[Request]" = []

        prefills = 0
        prefill_tokens_before = self.prefill_tokens
        prefix_cached_before = self.prefix_cached_tokens
        preempt_before = self.preempt_prefill_tokens
        resume_before = self.resume_prefill_tokens
        decode_before = self.decode_tokens
        proposed_before = self.draft_proposed_tokens
        accepted_before = self.draft_accepted_tokens
        hist_before = self.spec_accept_hist.copy()
        for req in self._admit(finished):
            self._prefill_request(req)
            prefills += 1
            if req.done:  # its whole budget was the prefill's one token
                with self._phase("emit"):
                    self._complete_done([req], finished)

        running = self.scheduler.running()
        if running:
            # reserve the next KV slot(s) for every live sequence FIRST: a
            # grow may preempt the youngest, and the decode batch must be
            # built from the survivors. Speculative decoding reserves up to
            # k+1 positions (the verify step's write span), clamped to the
            # request's remaining budget so admission's worst-case bound
            # still covers the peak; leftover reservations from a short
            # accept are reused, so the per-step delta is what LAST step
            # actually emitted.
            with self._phase("grow"):
                # a model with per-sequence state: whose row a preemption takes back
                slots = [r.slot for r in running] if self.state_shape is not None else ()
                for req in running:
                    if req.slot is not None:
                        if self.spec_tokens > 0:
                            remaining = req.max_new_tokens - len(req.generated)
                            target = (req.prefix_len - 1) + min(
                                self.spec_tokens + 1, remaining
                            )
                            self.scheduler.grow(
                                req, target - self.allocator.tokens(req.rid)
                            )
                        else:
                            self.scheduler.grow(req)
                for req, slot in zip(running, slots):
                    if req.slot is None:  # preempted: the scheduler took slot and blocks back
                        self._record_state(req, slot, "preempt", _tracing.now_ns())
                running = self.scheduler.running()
        if running:
            if self.spec_tokens > 0:
                self._spec_decode_batch(running, finished)
            else:
                self._decode_batch(running, finished)

        self.steps += 1
        if self.scheduler.idle():
            # an idle engine is not a stalled one: deregister so a quiet
            # traffic window can never trip the watchdog (the next step's
            # beat re-registers the source)
            _watchdog.unregister(self.heartbeat_name)
        else:
            _watchdog.beat(self.heartbeat_name, step=self.steps)
        occupancy = len(running) / self.max_slots
        self.max_running = max(self.max_running, len(running))
        self._occupancy_sum += occupancy
        self._occupancy_steps += 1
        if _metrics.is_enabled():
            alloc_occ = self.allocator.occupancy()
            # gauges are last-write-wins: label them per engine so N
            # LocalReplica engines in one process (one shared registry)
            # don't clobber each other's depth (histograms/counters below
            # aggregate across engines by design — fleet-level percentiles)
            _metrics.set_gauge("accelerate_engine_queue_depth",
                               self.scheduler.queue_depth, engine=self.heartbeat_name)
            _metrics.set_gauge("accelerate_engine_running", len(running),
                               engine=self.heartbeat_name)
            _metrics.observe("accelerate_engine_queue_depth_hist", self.scheduler.queue_depth,
                             buckets=_metrics.DEPTH_BUCKETS)
            _metrics.observe("accelerate_batch_occupancy", occupancy,
                             buckets=_metrics.OCCUPANCY_BUCKETS)
            _metrics.observe("accelerate_block_pool_occupancy", alloc_occ,
                             buckets=_metrics.OCCUPANCY_BUCKETS)
            _metrics.inc("accelerate_decode_tokens_total",
                         self.decode_tokens - decode_before)
            _metrics.inc("accelerate_prefill_tokens_total",
                         self.prefill_tokens - prefill_tokens_before)
            _metrics.inc("accelerate_prefix_hit_tokens_total",
                         self.prefix_cached_tokens - prefix_cached_before)
            if running:
                # per-token latency: without speculation every live request
                # earned exactly one token this step, so the step wall IS its
                # token interval; with speculation a request earned
                # (emitted / batch) tokens on average, so divide the wall by
                # that per-request yield
                decode_delta = self.decode_tokens - decode_before
                _metrics.observe(
                    "accelerate_per_token_latency_seconds",
                    (time.monotonic() - step_t0) * len(running)
                    / max(decode_delta, 1),
                )
            _metrics.maybe_snapshot()
        if tel.is_enabled():
            alloc = self.allocator.stats()
            step_dur = time.monotonic() - step_t0
            prefill_delta = self.prefill_tokens - prefill_tokens_before
            preempt_delta = self.preempt_prefill_tokens - preempt_before
            resume_delta = self.resume_prefill_tokens - resume_before
            decode_delta = self.decode_tokens - decode_before
            spec_fields = {}
            rejected_delta = 0
            if self.spec_tokens > 0:
                proposed_delta = self.draft_proposed_tokens - proposed_before
                accepted_delta = self.draft_accepted_tokens - accepted_before
                rejected_delta = proposed_delta - accepted_delta
                spec_fields = dict(
                    draft_proposed_tokens=proposed_delta,
                    draft_accepted_tokens=accepted_delta,
                    draft_rejected_tokens=rejected_delta,
                    # per-step accepted-count histogram delta (index = draft
                    # tokens accepted for one slot-step, 0..k) — the report's
                    # serving section sums these elementwise
                    spec_accept_hist=(self.spec_accept_hist - hist_before).tolist(),
                )
            tel.emit(
                "serving",
                phase="step",
                dur_s=round(step_dur, 6),
                queue_depth=self.scheduler.queue_depth,
                running=len(running),
                occupancy=round(occupancy, 6),
                prefills=prefills,
                prefill_tokens=prefill_delta,
                prefix_hit_tokens=self.prefix_cached_tokens - prefix_cached_before,
                preempt_reprefill_tokens=preempt_delta,
                resume_reprefill_tokens=resume_delta,
                decode_tokens=decode_delta,
                preemptions=self.scheduler.preemption_count,
                free_blocks=alloc["free_blocks"],
                live_tokens=alloc["live_tokens"],
                block_occupancy=alloc["occupancy"],
                fragmentation=alloc["fragmentation"],
                **spec_fields,
            )
            _goodput.note_serving_step(
                step_dur,
                # rejected verify rows were computed but never emitted: they
                # count as computed AND as waste (cause "draft_rejected")
                computed_tokens=prefill_delta + decode_delta + rejected_delta,
                wasted_tokens=preempt_delta + resume_delta + rejected_delta,
            )
            _goodput.maybe_emit()
        return finished

    def run(self, max_steps: int = 100_000) -> "list[Request]":
        """Step until idle (every submitted request finished); returns all
        completions in finish order."""
        done: "list[Request]" = []
        for _ in range(max_steps):
            if self.scheduler.idle():
                return done
            done.extend(self.step())
        raise RuntimeError(f"engine not idle after {max_steps} steps")

    # -- internals -----------------------------------------------------------

    def _request_key(self, req: Request) -> np.ndarray:
        # cached: the key is a pure function of rng_seed, and rebuilding it
        # would add a device dispatch per slot per decode step
        if req._key is None:
            req._key = np.asarray(jax.random.PRNGKey(req.rng_seed), np.uint32)
        return req._key

    def _prefill_request(self, req: Request) -> None:
        """Prefill the request's UNCACHED prefix tail in length-bucketed
        CHUNKS: each chunk runs at the smallest covering prefill bucket (the
        largest bucket for all but the tail), so arbitrarily long prefixes —
        e.g. a resumed request's prompt + generated — stay inside the
        compiled lattice. Only the final chunk's sampled token is kept.

        Prefix-cache admission already mapped the cached blocks into the
        table: ``req.cached_tokens`` leading positions hold valid KV and are
        skipped (the attention inside each chunk reads them through the block
        table, so the math is position-exact and bitwise-identical to an
        unshared run). A pending copy-on-write pair is applied to the pool
        FIRST — the one write this request aims below its uncached tail goes
        into its private copy, never a shared block.

        The whole of it, up to the sampled token's arrival on the host, is
        one ``atpu.serve.prefill`` phase; ``first_token_t`` is read inside
        it, after that sync. Its disjoint children: an
        ``atpu.serve.prefill_call`` round each program call (a chunk's, keyed
        ``bucket`` and ``start``; the copy-on-write's, keyed ``cow``), and
        one ``atpu.serve.prefill_sync`` round the one transfer of the sampled
        token and the chunks' routing counts. What is left is the host's own
        preparation: the table, the ids, the request's key (whose first
        computation is a device program of its own).

        Last, it writes the request's slot of the decode inputs whole (the
        token, its position, the key, the next fold index): admission is the
        one event that sets them; each decode step's emit moves them on."""
        prefix = req.output_ids()
        start = int(req.cached_tokens)
        W = self.lattice.prefill_points()[0][1]
        chunks, walk = self._prefill_plan(start, int(prefix.size), W)
        with self._phase(
            "prefill", rid=int(req.rid), tokens=int(prefix.size) - start, cached=start, **walk
        ) as phase_t0_ns:
            span_prefill = None
            if req.trace is not None:
                span_prefill = _tracing.span_open(
                    req.trace, "prefill", t0_ns=phase_t0_ns,
                    parent_id=req._span_root["span_id"],
                    component="engine", prefix_tokens=int(prefix.size),
                    cached_tokens=start,
                    cow=req.cow_block is not None,
                    resume=req.preemptions > 0,
                )
                req.trace_spans.append(span_prefill)
            if req.cow_block is not None:
                src, dst = req.cow_block
                fn = self._aot.get(("cow",), self.cow_fn)
                with self._phase("prefill_call", cow=True) as call_t0_ns:
                    self.pool = fn(self.pool, np.int32(src), np.int32(dst))
                # the copy is issued (ordered before any later pool op): release
                # the allocator's pin so src can park in the reclaimable pool
                self.allocator.cow_done(src)
                req.cow_block = None
                if span_prefill is not None:
                    req.trace_spans.append(_tracing.make_span(
                        req.trace, "cow_copy", call_t0_ns, _tracing.now_ns(),
                        parent_id=span_prefill["span_id"], component="engine",
                        src_block=int(src), dst_block=int(dst),
                    ))
            table = self.allocator.block_table(req.rid, pad_to=W)[None]
            key = self._request_key(req)
            token_idx = np.int32(len(req.generated))
            self.prefix_cached_tokens += start
            self.prefill_tokens += int(prefix.size) - start
            # token-goodput waste attribution: a prefill covering already-produced
            # work is recomputation. Preempt/resume re-runs carry preemptions>0;
            # a failover/handoff resume arrives with ``generated`` pre-seeded.
            if req.preemptions > 0:
                self.preempt_prefill_tokens += int(prefix.size) - start
            elif req.generated:
                self.resume_prefill_tokens += int(prefix.size) - start
            chunk_counts = []  # a model's own counts, one a chunk: [(tokens, counts)]
            rows = self._state_rows([req.slot], 1)
            if rows and start == 0:  # always: such a model takes no prefix hit
                self.state_resets += 1
            for start, size, Sb in chunks:
                chunk = prefix[start : start + size]
                ids = np.zeros((1, Sb), np.int32)
                ids[0, : chunk.size] = chunk
                fn = self._aot.get(("prefill", Sb, W), self.prefill_fn)
                with self._phase("prefill_call", bucket=Sb, start=start) as call_t0_ns:
                    self.pool, (tok, counts) = fn(
                        self.params, self.pool, ids, table, np.int32(start),
                        np.int32(chunk.size - 1), key, token_idx, *rows,
                    )
                chunk_counts.append((int(chunk.size), counts))
                if span_prefill is not None:
                    req.trace_spans.append(_tracing.make_span(
                        req.trace, "prefill_chunk", call_t0_ns, _tracing.now_ns(),
                        parent_id=span_prefill["span_id"], component="engine",
                        start=int(start), tokens=int(chunk.size), bucket=int(Sb),
                    ))
            self.allocator.settle(req.rid)  # after the calls that needed it are issued
            with self._phase("prefill_sync"):  # the sampled token is on the host from here
                tok, counts = jax.device_get((tok, [c for _, c in chunk_counts]))
                tok = int(tok)
            t_ns, t = self._clock()
            for (tokens, _), chunk_count in zip(chunk_counts, counts):
                self._record_counts("prefill", tokens, chunk_count, t_ns, rid=int(req.rid))
            if self.pooled is not None:  # every chunk the prefix completes
                self._record_eva("prefill", int(prefix.size) // self.pooled[1], t_ns,
                                 rid=int(req.rid))
            if req.first_token_t is None:
                req.first_token_t = t
            if span_prefill is not None:
                _tracing.span_close(span_prefill, t1_ns=t_ns)
        req.generated.append(tok)
        self.prefill_calls += 1
        # the slot's decode inputs, whole: its next step reads this token
        slot = req.slot
        self._last[slot] = tok
        self._positions[slot] = req.prefix_len - 1
        self._keys[slot] = key
        self._token_idx[slot] = len(req.generated)

    def _prefill_plan(self, start: int, end: int, W: int) -> "tuple[list, dict]":
        """The chunks of a prefill of positions ``start..end-1`` as ``(start,
        tokens, bucket)``, each at the smallest covering prefill bucket (the
        largest for all but the tail), and what the paged prefill kernel walks
        for them (``ops.flash_attention.prefill_walk_blocks``, the kernel's own
        arithmetic on the host's integers): ``walked_blocks``, the blocks its
        grid visits for ONE full layer, and ``table_blocks``, the entries of the
        ``W``-wide table its query tiles are handed; for a model with window
        layers also ``window_walked_blocks``, the same for one such layer.
        Summed into ``prefill_blocks_*`` of :meth:`stats`."""
        chunks, cap = [], self.lattice.prefill_buckets[-1]
        while start < end:
            size = min(cap, end - start)
            if self.pooled is not None:  # a call's queries lie in one window
                size = min(size, self.pooled[0] - start % self.pooled[0])
            chunks.append((start, size, self.lattice.prefill_bucket(size)))
            start += size
        c = self.config
        walk = {"walked_blocks": 0, "table_blocks": 0}
        if self.window is not None:
            walk["window_walked_blocks"] = 0
        for at, _, Sb in chunks:
            Sq, N = prefill_tiling(
                Sb, c.n_heads, c.n_kv_heads, c.head_dim, self.block_size, self.pool["k"].dtype, W)
            walk["table_blocks"] += Sb // Sq * W
            walk["walked_blocks"] += prefill_walk_blocks(
                self._row_index(at), Sb, Sq, N, W, self.block_size)
            if self.window is not None:
                walk["window_walked_blocks"] += prefill_walk_blocks(
                    at, Sb, Sq, N, W, self.block_size, self.window)
        self.prefill_blocks_walked += walk["walked_blocks"]
        self.prefill_blocks_table += walk["table_blocks"]
        self.prefill_blocks_window += walk.get("window_walked_blocks", 0)
        return chunks, walk

    def _row_index(self, position):
        """Where position ``position`` stands in the row the paged kernels
        walk: itself, or its index in a pooled row's composed row
        (``ops.flash_attention.pooled_row_index``)."""
        return position if self.pooled is None else pooled_row_index(position, *self.pooled)

    def _decode_bucket(self, slots: np.ndarray) -> "tuple[int, int, dict]":
        """The lattice point of a decode batch over the running ``slots`` and
        the blocks its rows hold: ``(slot_bucket, block_bucket, {"live_blocks":
        ..., "rows_written": ...})``, from the slots' rows alone (the
        allocator's block counts, the engine's positions). The paged decode
        kernel's work follows ``live_blocks``; ``slot_bucket * block_bucket``
        is the table it is handed (``decode_blocks_live`` /
        ``decode_blocks_walked`` in :meth:`stats`). ``rows_written``: the
        table entries the allocator wrote into rows since the last build, a
        whole table an admission and one entry a block boundary crossed
        (``decode_table_entries_written``). For a model with window layers
        the dict also holds ``window_blocks``: the blocks such a layer walks,
        a row's from the one that holds position ``kv_len - window``
        (``decode_blocks_window`` in :meth:`stats`). For a model with pooled
        rows it holds ``window_entries`` and ``summary_entries``, the raw keys
        and the pooled pairs the batch attends, and ``live_blocks`` counts the
        composed rows' blocks (the stored row's extent picks the bucket)."""
        blocks = self.allocator.row_blocks[slots]
        Bb = self.lattice.slot_bucket(len(slots))
        W = self.lattice.block_bucket(int(blocks.max()))
        written = self.allocator.row_entries_written
        held = {"live_blocks": int(blocks.sum()),
                "rows_written": written - self.decode_table_entries_written}
        if self.pooled is not None:
            window, chunk = self.pooled
            positions = self._positions[slots]
            closed = positions // window
            in_window = positions - window * closed + 1
            held["window_entries"] = int(in_window.sum())
            held["summary_entries"] = int((window // chunk * closed).sum())
            held["live_blocks"] = int(-(-(self._row_index(positions) + 1) // self.block_size).sum())
            self.decode_window_entries += held["window_entries"]
            self.decode_summary_entries += held["summary_entries"]
        self.decode_table_entries_written = written
        if self.state_shape is not None:
            held["state_rows"] = len(slots)  # the batch's live rows of `pool["state"]`
        self.decode_blocks_live += held["live_blocks"]
        self.decode_blocks_walked += Bb * W
        if self.window is not None:
            kv_len = self._positions[slots] + 1
            held["window_blocks"] = int(
                (blocks - np.maximum(kv_len - self.window, 0) // self.block_size).sum())
            self.decode_blocks_window += held["window_blocks"]
        return Bb, W, held

    def _decode_inputs(self, slots: np.ndarray, Bb: int, W: int) -> tuple:
        """A decode batch's arrays for the running ``slots``, padded to
        ``Bb`` rows: ``(last, tables [Bb, W], positions, keys, token_idx,
        *rows)``, one index over the per-slot rows. Who writes them: the
        allocator the tables (admission, a block boundary, free); the
        request's prefill token, position, key and fold index
        (:meth:`_prefill_request`); each step's emit the next token, and
        the position and fold index forward (:meth:`_decode_batch`,
        :meth:`_spec_emit`). A padding row reads the row past the last slot:
        token 0, the null table, position 0, a zero key."""
        idx = np.full((Bb,), self.max_slots)
        idx[: len(slots)] = slots
        return (self._last[idx], self.allocator.row_tables[idx, :W], self._positions[idx],
                self._keys[idx], self._token_idx[idx], *self._state_rows(slots, Bb))

    def _state_rows(self, slots, pad_to: int) -> tuple:
        """The last argument of a step program of a model with per-sequence
        state, as a tuple to splat: ``(rows [pad_to],)``, the row of
        ``pool["state"]`` of each of the batch ``slots`` (slot + 1, held from
        admission to finish or preemption) and the null row for the padding.
        Empty for every other model: its programs take no such argument."""
        if self.state_shape is None:
            return ()
        rows = np.full((pad_to,), NULL_STATE_ROW, np.int32)
        rows[: len(slots)] = np.asarray(slots) + 1
        return (rows,)

    def _record_state(self, req: Request, slot: int, why: str, t_ns: int) -> None:
        """One ``atpu.serve.state`` record for a state row handed out
        (``admit``) or taken back (``finish``, ``preempt``) with batch slot
        ``slot``. Nothing for a model without such state."""
        if self.state_shape is not None:
            _tracing.record(
                "atpu.serve.state", t_ns, t_ns, engine=self.engine_id, step=self.steps,
                rid=int(req.rid), row=slot + 1, why=why)

    def _record_counts(self, kind: str, tokens: int, counts, t_ns: int, **key) -> None:
        """One ``atpu.serve.moe`` record for one call of a model whose paged
        forward counts its routing (``counts [n_layers, 3]``, fetched with the
        call's tokens): per layer the (token, expert) pairs that landed on the
        experts held here, the held experts hit, and the most one of them
        got, for the ``tokens`` real tokens of a ``decode`` batch or a
        ``prefill`` chunk; ``held`` and ``top_k`` are the config's
        ``experts_held`` and ``experts_per_token`` (what tells a chip's share
        of a wide router from a layer held whole). Nothing for a model that
        counts nothing."""
        if counts is None:
            return
        counts = np.asarray(counts)
        pairs, hit, load = (counts[:, i].tolist() for i in range(3))
        _tracing.record(
            "atpu.serve.moe", t_ns, t_ns, engine=self.engine_id, step=self.steps, kind=kind,
            tokens=tokens, local_pairs=pairs, experts_hit=hit, max_expert_load=load,
            held=getattr(self.config, "experts_held", None),
            top_k=getattr(self.config, "experts_per_token", None), **key)
        self.moe["tokens"] += tokens
        self.moe["local_pairs"] += sum(pairs)
        self.moe["experts_hit"] += sum(hit)
        self.moe["calls"] += len(pairs)
        self.moe["max_expert_load"] = max(self.moe["max_expert_load"], *load)

    def _record_eva(self, kind: str, chunks_folded: int, t_ns: int, **key) -> None:
        """One ``atpu.serve.eva`` record for a ``prefill`` or a ``decode``
        step of a model with pooled rows: the chunks it folded, and the
        windows the allocator closed and the raw blocks it handed back since
        the record before (a decode step's grow closes; a prefill's settle
        hands back)."""
        closed, freed = self.allocator.windows_closed, self.allocator.raw_blocks_freed
        _tracing.record(
            "atpu.serve.eva", t_ns, t_ns, engine=self.engine_id, step=self.steps, kind=kind,
            chunks_folded=chunks_folded, windows_closed=closed - self._eva_seen[0],
            raw_blocks_freed=freed - self._eva_seen[1], **key)
        self._eva_seen = (closed, freed)
        self.eva_chunks_folded += chunks_folded

    def _decode_batch(self, running: "list[Request]", finished: "list[Request]") -> None:
        """One decode step for the ``running`` requests (in slot order). Its
        build reads the batch's arrays off the per-slot rows
        (:meth:`_decode_inputs`) and touches no request; its emit hands each
        request its token and writes the slots' next token, position + 1 and
        fold index + 1 over the whole batch at once."""
        slots = self.scheduler.running_slots()
        Bb, W, held = self._decode_bucket(slots)
        with self._phase(
            "build", batch=len(slots), slot_bucket=Bb, block_bucket=W, **held
        ):
            inputs = self._decode_inputs(slots, Bb, W)
        fn = self._aot.get(("decode", Bb, W), self.decode_fn)
        with self._phase("dispatch") as decode_t0:
            self.pool, out = fn(self.params, self.pool, *inputs)
        with self._phase("fetch"):  # the host waits for the device here
            toks, counts = jax.device_get(out)  # the step's one fetch
            toks = np.asarray(toks)
        with self._phase("emit") as decode_t1:
            if self.pooled is not None:  # the rows that wrote a chunk's last position
                chunk = self.pooled[1]
                folded = int((self._positions[slots] % chunk == chunk - 1).sum())
                self._record_eva("decode", folded, decode_t1)
            self._last[slots] = toks[: len(slots)]
            self._positions[slots] += 1
            self._token_idx[slots] += 1
            self._record_counts("decode", len(running), counts, decode_t1)
            for i, req in enumerate(running):
                # gate on the request's own context, not the local arming state
                # (a ProcessReplica child traces whenever the router propagated a
                # ctx), and only for SAMPLED traces: per-token decode spans are the
                # bulk of a trace's cost, and an unsampled trace keeps only its
                # cheap structural spans (the router flips sampled on for failover
                # redispatches, whose forced emission needs the detail)
                if req.trace is not None and req.trace.get("sampled"):
                    req.trace_spans.append(_tracing.make_span(
                        req.trace, "decode_step", decode_t0, decode_t1,
                        parent_id=req._span_root["span_id"], component="engine",
                        step=int(self.steps), batch=len(running),
                        token_idx=len(req.generated),
                    ))
                req.generated.append(int(toks[i]))
                if self.prefix_cache:
                    # this decode wrote position prefix_len-2's token (the last
                    # PREVIOUS token) — when the written count crosses a block
                    # boundary, the just-filled block becomes immutable and
                    # content-indexable for future prefix matches
                    written = req.prefix_len - 1
                    if written > 0 and written % self.block_size == 0:
                        self.allocator.register_full_blocks(
                            req.rid, req.output_ids()[:-1]
                        )
            self.decode_tokens += len(running)
            self._complete_done(running, finished)

    def _spec_decode_batch(self, running: "list[Request]", finished: "list[Request]") -> None:
        """One speculative decode round for every live slot: k sequential S=1
        steps of the truncated self-draft propose candidates, ONE batched
        S=k+1 verify forward (which dispatches to the chunked-prefill paged
        kernel) scatter-writes their KV and computes — per candidate row —
        the token the non-speculative fold stream would emit there, and the
        host accepts the longest candidate prefix matching those emissions
        EXACTLY (bitwise accept: greedy argmax or sampled rejection off the
        per-slot fold streams, both byte-equal to single-stream decode).

        Every request emits at least the verifier's own token (row 0), so a
        0%-accept workload degrades to one-token-per-step decode, never
        stalls. KV safety: rejected rows' pool writes sit past the emitted
        prefix and are position-masked out of every read until the next
        step's scatter overwrites them."""
        k = self.spec_tokens
        slots = self.scheduler.running_slots()
        Bb, W, held = self._decode_bucket(slots)
        with self._phase(
            "build", batch=len(slots), slot_bucket=Bb, block_bucket=W, **held
        ):
            last, tables, positions, keys, token_idx = self._decode_inputs(slots, Bb, W)
            # emit at most as many rows as the grow phase reserved KV room
            # for (clamped by the request's remaining new-token budget)
            rows = self.allocator.row_tokens[slots] - self._positions[slots]
            cand = np.zeros((Bb, k + 1), np.int32)
            cand[:, 0] = last
        dfn = self._aot.get(("draft", Bb, W), self.draft_fn)
        d_last, d_pos, d_idx = last, positions, token_idx
        decode_t0 = None  # the first draft's dispatch
        for j in range(k):  # one dispatch and one fetch per draft round
            with self._phase("dispatch") as t0_ns:
                self.pool, d_tok = dfn(
                    self.draft_params, self.pool, d_last, tables, d_pos, keys, d_idx
                )
            decode_t0 = decode_t0 or t0_ns
            with self._phase("fetch"):
                d_tok = np.asarray(jax.device_get(d_tok)).astype(np.int32)
            cand[:, j + 1] = d_tok
            d_last, d_pos, d_idx = d_tok, d_pos + 1, d_idx + 1
        vfn = self._aot.get(("verify", Bb, W), self.verify_fn)
        with self._phase("dispatch"):
            self.pool, sel = vfn(
                self.params, self.pool, cand, tables, positions, keys, token_idx
            )
        with self._phase("fetch"):
            sel = np.asarray(jax.device_get(sel))
        with self._phase("emit") as decode_t1:
            self._spec_emit(running, slots, finished, sel, cand, rows, token_idx,
                            decode_t0, decode_t1)

    def _spec_emit(self, running, slots, finished, sel, cand, rows, token_idx, decode_t0,
                   decode_t1) -> None:
        """The host half of a speculative round: accept, per request, the
        longest candidate prefix the verifier's own selections confirm, and
        move the ``slots``' rows past what each emitted."""
        k = self.spec_tokens
        counts = np.zeros((len(running),), np.int32)  # tokens each row emitted
        for i, req in enumerate(running):
            r_i = int(min(rows[i], k + 1))
            before = req.prefix_len - 1
            n_acc = 0
            for j in range(r_i):
                tok = int(sel[i, j])
                req.generated.append(tok)
                if req.done:
                    break
                if j + 1 < r_i and int(cand[i, j + 1]) == tok:
                    n_acc += 1
                    continue
                break
            counts[i] = req.prefix_len - 1 - before
            self._last[slots[i]] = req.generated[-1]
            if req.trace is not None and req.trace.get("sampled"):  # as in _decode_batch
                req.trace_spans.append(_tracing.make_span(
                    req.trace, "decode_step", decode_t0, decode_t1,
                    parent_id=req._span_root["span_id"], component="engine",
                    step=int(self.steps), batch=len(running),
                    token_idx=int(token_idx[i]), k_accepted=n_acc,
                ))
            self.draft_proposed_tokens += max(r_i - 1, 0)
            self.draft_accepted_tokens += n_acc
            self.spec_accept_hist[n_acc] += 1
            if _metrics.is_enabled():
                _metrics.observe(
                    "accelerate_spec_accepted_tokens", float(n_acc),
                    buckets=tuple(float(b) for b in range(k + 1)),
                )
            if self.prefix_cache:
                written = req.prefix_len - 1
                if written // self.block_size > before // self.block_size:
                    # a multi-token accept can cross MORE than one block
                    # boundary in one step; registration is incremental, so
                    # one call covers them all
                    self.allocator.register_full_blocks(
                        req.rid, req.output_ids()[:-1]
                    )
        self._positions[slots] += counts
        self._token_idx[slots] += counts
        self.decode_tokens += int(counts.sum())
        self._complete_done(running, finished)

    def _close_trace(self, req: Request, outcome: str, t1_ns: Optional[int] = None) -> None:
        """Close the request's open spans with the terminal ``outcome``; the
        trace's OWNER emits — this engine when it rooted the trace, the
        router (via the replica event stream) when the context was
        propagated in."""
        if req.trace is None:
            return
        if req._span_queue is not None and "t1_ns" not in req._span_queue:
            _tracing.span_close(req._span_queue, t1_ns=t1_ns)
        if req._span_root is not None and "t1_ns" not in req._span_root:
            _tracing.span_close(
                req._span_root, t1_ns=t1_ns, outcome=outcome,
                tokens=len(req.generated), preemptions=int(req.preemptions),
            )
        if req._trace_owner:
            _tracing.finish_trace(
                req.trace, req.trace_spans, forced=outcome != "finished"
            )

    def _finish_request(self, req: Request, t_ns: int) -> None:
        """The records of a request that has just completed (``finish_t`` is
        stamped; ``t_ns`` is that same read in ns): the trace's root span,
        the ``atpu.request`` record of its stamps in the phase ring, the
        latency histograms and the ``serving_request`` event."""
        self._close_trace(req, "finished", t_ns)
        _tracing.record(
            "atpu.request", t_ns, t_ns, engine=self.engine_id, rid=int(req.rid),
            arrival_t=req.arrival_t, admit_t=req.admit_t,
            first_token_t=req.first_token_t, finish_t=req.finish_t,
            admit_step=req.admit_step, prompt_tokens=int(req.prompt.size),
            new_tokens=len(req.generated), preemptions=int(req.preemptions),
        )
        if _metrics.is_enabled():
            _metrics.inc("accelerate_engine_requests_total", outcome="finished")
            if req.first_token_t is not None:
                _metrics.observe("accelerate_engine_ttft_seconds",
                                 req.first_token_t - req.arrival_t)
            _metrics.observe("accelerate_engine_request_latency_seconds",
                             req.finish_t - req.arrival_t)
        self._emit_completion(req)

    def _emit_completion(self, req: Request) -> None:
        if not tel.is_enabled():
            return
        tel.emit(
            "serving_request",
            rid=req.rid,
            prompt_tokens=int(req.prompt.size),
            new_tokens=len(req.generated),
            latency_s=round((req.finish_t or 0.0) - req.arrival_t, 6),
            ttft_s=round((req.first_token_t or 0.0) - req.arrival_t, 6)
            if req.first_token_t is not None
            else None,
            queue_s=round(req.admit_t - req.arrival_t, 6)
            if req.admit_t is not None
            else None,
            preemptions=req.preemptions,
        )

    def stats(self) -> dict:
        out = {
            "steps": self.steps,
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            "prefill_calls": self.prefill_calls,
            "preempt_prefill_tokens": self.preempt_prefill_tokens,
            "resume_prefill_tokens": self.resume_prefill_tokens,
            "preemptions": self.scheduler.preemption_count,
            "max_running": self.max_running,
            "mean_occupancy": round(
                self._occupancy_sum / max(self._occupancy_steps, 1), 6
            ),
            "decode_blocks_live": self.decode_blocks_live,
            "decode_blocks_walked": self.decode_blocks_walked,
            "decode_table_entries_written": self.decode_table_entries_written,
            "prefill_blocks_walked": self.prefill_blocks_walked,
            "prefill_blocks_table": self.prefill_blocks_table,
            **self.jit_cache_sizes(),
            **self.allocator.stats(),
        }
        if self.window is not None:
            out["decode_blocks_window"] = self.decode_blocks_window
            out["prefill_blocks_window"] = self.prefill_blocks_window
        if self.moe["calls"]:
            out.update({"moe_" + name: total for name, total in self.moe.items()})
        if self.state_shape is not None:
            out.update(state_resets=self.state_resets, state_bytes=self.state_bytes)
        if self.pooled is not None:  # beside the allocator's windows_closed / raw_blocks_freed
            out.update(eva_chunks_folded=self.eva_chunks_folded,
                       decode_window_entries=self.decode_window_entries,
                       decode_summary_entries=self.decode_summary_entries)
        if self.spec_tokens > 0:
            out.update(
                spec_tokens=self.spec_tokens,
                draft_layers=self.draft_layers,
                draft_proposed_tokens=self.draft_proposed_tokens,
                draft_accepted_tokens=self.draft_accepted_tokens,
                draft_rejected_tokens=(
                    self.draft_proposed_tokens - self.draft_accepted_tokens
                ),
                spec_accept_rate=round(
                    self.draft_accepted_tokens / self.draft_proposed_tokens, 6
                )
                if self.draft_proposed_tokens
                else 0.0,
                spec_accept_hist=self.spec_accept_hist.tolist(),
            )
        if self._prefix_cache_asked:  # asked for and refused (per-sequence state): 0 saved
            # hit rate over PROMPT tokens: cached / (cached + actually
            # prefilled) — the fraction of prefill work the cache deleted
            total = self.prefix_cached_tokens + self.prefill_tokens
            # cow_copies rides in from allocator.stats() above — the
            # allocator's count is the single source (every allocated COW
            # pair is applied in the same step's prefill phase)
            out.update(
                prefill_tokens_saved=self.prefix_cached_tokens,
                prefix_hit_rate=round(self.prefix_cached_tokens / total, 6)
                if total else 0.0,
            )
        return out
