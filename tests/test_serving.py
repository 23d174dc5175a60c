"""Serving subsystem tests (ISSUE 11): paged KV cache + continuous batching.

The two acceptance lines these tests hold:

- paged decode through the engine is IDENTICAL to the single-stream
  ``generation`` decode for every admitted request — greedy and sampled
  (fixed key), including sequences whose blocks are non-contiguous in the
  pool and sequences that were preempted and resumed;
- admission/completion/eviction churn after bucket warmup never grows the
  jit caches (the telemetry recompile detector is the oracle).
"""

import collections
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import _cached_attention, greedy_generate, sample_generate
from accelerate_tpu.models import LlamaConfig, init_llama
from accelerate_tpu.serving import (
    NULL_BLOCK,
    BlockAllocator,
    BlockAllocatorError,
    BlockPoolExhausted,
    BucketLattice,
    Request,
    RequestStatus,
    Scheduler,
    SchedulingError,
    ServingEngine,
    paged_attention,
)
from accelerate_tpu.telemetry import tracing

CONFIG = LlamaConfig.tiny()
SMALL_LATTICE = BucketLattice(slot_buckets=(2, 4), block_buckets=(4,), prefill_buckets=(32,))


@pytest.fixture(scope="module")
def params():
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(CONFIG, jax.random.PRNGKey(0))
    )


@pytest.fixture(scope="module")
def greedy_engine(params):
    engine = ServingEngine(
        params, CONFIG, num_blocks=33, block_size=8, max_slots=4, lattice=SMALL_LATTICE
    )
    engine.warmup()
    return engine


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CONFIG.vocab_size, (n,)).astype(np.int32) for n in lengths]


# ---------------------------------------------------------------------------
# block allocator


@pytest.mark.smoke
def test_allocator_lifecycle_and_accounting():
    alloc = BlockAllocator(num_blocks=9, block_size=4)
    assert alloc.usable_blocks == 8 and alloc.free_blocks == 8
    table = alloc.allocate("a", 6)  # 6 tokens -> 2 blocks
    assert len(table) == 2 and NULL_BLOCK not in table
    assert alloc.used_blocks == 2 and alloc.tokens("a") == 6
    # internal fragmentation: 8 allocated slots, 6 live tokens
    assert alloc.fragmentation() == pytest.approx(2 / 8)
    assert alloc.occupancy() == pytest.approx(2 / 8)
    # append within the last block allocates nothing; crossing allocates one
    assert alloc.append("a", 2) == []
    new = alloc.append("a", 1)
    assert len(new) == 1 and alloc.num_seq_blocks("a") == 3
    assert alloc.free("a") == 3
    assert alloc.free_blocks == 8 and alloc.stats()["live_tokens"] == 0


def test_allocator_free_list_reuse_and_nonmonotonic_tables():
    alloc = BlockAllocator(num_blocks=9, block_size=4)
    (x,) = alloc.allocate("x", 1)
    (y,) = alloc.allocate("y", 1)
    (z,) = alloc.allocate("z", 1)
    alloc.free("y")
    # LIFO free list: the just-freed block is handed out next...
    grown = alloc.append("z", 4)
    assert grown == [y]
    # ...which makes z's table non-monotonic in physical block ids
    table = alloc.block_table("z")
    assert table.tolist() == [z, y] and z > y
    # padding fills with the null block
    assert alloc.block_table("z", pad_to=4).tolist() == [z, y, NULL_BLOCK, NULL_BLOCK]


def test_allocator_errors():
    alloc = BlockAllocator(num_blocks=4, block_size=2)
    alloc.allocate("a", 2)
    with pytest.raises(BlockAllocatorError, match="already allocated"):
        alloc.allocate("a", 1)
    with pytest.raises(BlockPoolExhausted):
        alloc.allocate("big", 100)
    assert "big" not in alloc.live_sequences()  # all-or-nothing
    alloc.free("a")
    with pytest.raises(BlockAllocatorError, match="double free"):
        alloc.free("a")
    with pytest.raises(BlockAllocatorError, match="use-after-free"):
        alloc.append("a", 1)
    with pytest.raises(BlockAllocatorError, match="use-after-free"):
        alloc.block_table("a")


def test_allocator_exhaustion_leaves_sequence_unchanged():
    alloc = BlockAllocator(num_blocks=3, block_size=2)
    alloc.allocate("a", 2)
    alloc.allocate("b", 2)
    with pytest.raises(BlockPoolExhausted):
        alloc.append("a", 4)  # needs 2 more blocks, 0 free
    assert alloc.tokens("a") == 2 and alloc.num_seq_blocks("a") == 1


def test_allocator_keeps_each_sequences_table_in_its_row():
    """A sequence lives in its row, and nowhere else, from admission to
    free: the whole table (copy-on-write block included) at allocation, one
    entry a block boundary, the null table back at free; ``block_table``,
    ``tokens`` and ``num_seq_blocks`` read the row, a sequence given no row
    takes the lowest free one, and the row past the last stays null."""
    alloc = BlockAllocator(num_blocks=17, block_size=4, prefix_caching=True,
                           max_seqs=3, max_seq_blocks=4)
    assert alloc.row_tables.shape == (4, 4)
    toks = np.arange(8, dtype=np.int32)  # two full blocks
    a = alloc.allocate_with_prefix("a", toks, row=0)
    assert alloc.row_tables[0].tolist() == a.table + [NULL_BLOCK] * 2
    assert (alloc.row_blocks[0], alloc.row_tokens[0], alloc.row_entries_written) == (2, 8, 2)
    b = alloc.allocate_with_prefix("b", toks.copy(), row=2)  # all cached: copy-on-write
    assert b.cow is not None and b.table[-1] == b.cow[1] != a.table[-1]
    assert alloc.row_tables[2, :2].tolist() == b.table and alloc.row_entries_written == 4
    alloc.append("a", 1)  # a third block
    assert alloc.row_tables[0, :3].tolist() == alloc.block_table("a").tolist()
    assert alloc.row_entries_written == 5 and alloc.num_seq_blocks("a") == 3
    alloc.append("a", 1)  # the same block
    assert (alloc.row_entries_written, alloc.row_tokens[0], alloc.tokens("a")) == (5, 10, 10)
    assert alloc.block_table("a", pad_to=4).tolist() == alloc.row_tables[0].tolist()
    with pytest.raises(ValueError, match="pad_to=6"):
        alloc.block_table("a", pad_to=6)
    with pytest.raises(BlockAllocatorError, match="row 0"):
        alloc.allocate("c", 4, row=0)
    with pytest.raises(ValueError, match="does not fit a row of 4"):
        alloc.allocate("c", 17, row=1)  # five blocks
    e = alloc.allocate("e", 16, row=1)
    with pytest.raises(ValueError, match="does not fit a row of 4"):
        alloc.append("e", 1)
    assert "c" not in alloc.live_sequences() and alloc.tokens("e") == 16
    assert alloc.row_tables[1].tolist() == e and alloc.row_entries_written == 9
    free_before = alloc.free_blocks
    alloc.free("a")
    assert alloc.free_blocks > free_before
    assert (alloc.row_tables[0] == NULL_BLOCK).all() and alloc.row_blocks[0] == alloc.row_tokens[0] == 0
    (d,) = alloc.allocate("d", 4)  # no row given: the lowest free one
    assert alloc.row_tables[0].tolist() == [d] + [NULL_BLOCK] * 3 and alloc.num_seq_blocks("d") == 1
    assert alloc.row_entries_written == 10
    with pytest.raises(BlockAllocatorError, match="every row"):
        alloc.allocate("f", 4)
    assert (alloc.row_tables[3] == NULL_BLOCK).all() and alloc.row_blocks[3] == 0


# ---------------------------------------------------------------------------
# bucket lattice


def test_bucket_lattice_rounding_and_limits():
    lat = BucketLattice.from_limits(max_slots=6, max_blocks_per_seq=5, max_prefill_len=48)
    assert lat.slot_buckets == (1, 2, 4, 6)
    assert lat.block_buckets == (1, 2, 4, 5)
    assert lat.prefill_buckets == (8, 16, 32, 48)
    assert lat.slot_bucket(3) == 4 and lat.slot_bucket(0) == 1
    assert lat.block_bucket(5) == 5
    assert lat.prefill_bucket(9) == 16
    with pytest.raises(ValueError, match="exceeds the bucket lattice"):
        lat.prefill_bucket(49)
    # every prefill point pairs with the single widest block bucket
    assert lat.prefill_points() == [(8, 5), (16, 5), (32, 5), (48, 5)]
    assert lat.size() == len(lat.decode_points()) + len(lat.prefill_points())


# ---------------------------------------------------------------------------
# paged attention parity (the bitwise micro-proof)


def test_paged_attention_bitwise_matches_contiguous_on_scrambled_blocks():
    """A sequence scattered over non-contiguous, out-of-order physical blocks
    must attend bitwise-identically to the same values in a contiguous cache
    — gather correctness plus exact-zero masking of null/stale slots."""
    rng = np.random.default_rng(0)
    B, S, H, D, Hkv = 1, 3, 4, 32, 2
    max_len, bs = 24, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32)).astype(jnp.bfloat16)
    k_full = rng.normal(size=(B, max_len, Hkv, D)).astype(np.float32)
    v_full = rng.normal(size=(B, max_len, Hkv, D)).astype(np.float32)
    seq_len = 19
    q_positions = jnp.asarray([[seq_len - 3, seq_len - 2, seq_len - 1]], jnp.int32)
    ref = jax.jit(_cached_attention)(
        q,
        jnp.asarray(k_full).astype(jnp.bfloat16),
        jnp.asarray(v_full).astype(jnp.bfloat16),
        q_positions[0],
    )
    # scatter the 19 live tokens into scrambled blocks; garbage elsewhere
    nb = 6
    pool_k = rng.normal(size=(nb, bs, Hkv, D)).astype(np.float32)
    pool_v = rng.normal(size=(nb, bs, Hkv, D)).astype(np.float32)
    table = [5, 2, 4]  # logical block i -> scrambled physical id
    for i in range(seq_len):
        blk, off = divmod(i, bs)
        pool_k[table[blk], off] = k_full[0, i]
        pool_v[table[blk], off] = v_full[0, i]
    out = jax.jit(paged_attention)(
        q,
        jnp.asarray(pool_k).astype(jnp.bfloat16),
        jnp.asarray(pool_v).astype(jnp.bfloat16),
        jnp.asarray([table + [NULL_BLOCK]], jnp.int32),  # null-padded width 4
        q_positions,
    )
    assert np.array_equal(
        np.asarray(ref, np.float32), np.asarray(out, np.float32)
    ), "paged attention diverged from the contiguous cache"


# ---------------------------------------------------------------------------
# engine decode parity vs the single-stream reference


def test_engine_greedy_parity_with_noncontiguous_blocks(params, greedy_engine):
    engine = greedy_engine
    prompts = _prompts(0, (5, 13, 21, 9))
    max_new = (7, 11, 5, 9)
    reqs = [
        engine.submit(p, m, rng_seed=i) for i, (p, m) in enumerate(zip(prompts, max_new))
    ]
    # step until mid-flight, then prove at least one live sequence's blocks
    # are non-contiguous (concurrent growth interleaves the pool)
    noncontiguous = False
    for _ in range(4):
        engine.step()
        for req in engine.scheduler.running():
            table = engine.allocator.block_table(req.rid)
            if len(table) > 1 and np.any(np.diff(table) != 1):
                noncontiguous = True
    engine.run()
    assert noncontiguous, "concurrent requests never interleaved pool blocks"
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        ref = greedy_generate(params, p[None], CONFIG, max_new_tokens=m)
        assert np.array_equal(np.asarray(ref[0]), reqs[i].output_ids()), f"request {i}"


def test_engine_chunked_prefill_parity_beyond_largest_bucket(params):
    """A prefix longer than the largest prefill bucket must chunk through it
    (length-bucketed chunked prefill) and still match the single-stream
    reference exactly."""
    engine = ServingEngine(
        params, CONFIG, num_blocks=17, block_size=8, max_slots=2,
        max_blocks_per_seq=8,
        lattice=BucketLattice(slot_buckets=(2,), block_buckets=(8,),
                              prefill_buckets=(16, 32)),
    )
    engine.warmup()
    prompt = _prompts(9, (45,))[0]  # 45 > the 32-wide largest prefill bucket
    req = engine.submit(prompt, 6, rng_seed=3)
    engine.run()
    ref = greedy_generate(params, prompt[None], CONFIG, max_new_tokens=6)
    assert np.array_equal(np.asarray(ref[0]), req.output_ids())
    # chunking stayed inside the warmed lattice: no new compiles
    assert engine.jit_cache_sizes() == {
        "prefill_compiles": 2, "decode_compiles": 1, "cow_compiles": 1
    }


def test_engine_sampled_parity_fixed_keys(params):
    knobs = dict(temperature=0.8, top_k=7, top_p=0.95)
    engine = ServingEngine(
        params, CONFIG, num_blocks=33, block_size=8, max_slots=4,
        lattice=SMALL_LATTICE, **knobs,
    )
    engine.warmup()
    prompts = _prompts(1, (6, 17, 11))
    max_new = (9, 6, 12)
    reqs = [
        engine.submit(p, m, rng_seed=100 + i)
        for i, (p, m) in enumerate(zip(prompts, max_new))
    ]
    engine.run()
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        ref = sample_generate(
            params, p[None], CONFIG, max_new_tokens=m,
            rng_key=jax.random.PRNGKey(100 + i), **knobs,
        )
        assert np.array_equal(np.asarray(ref[0]), reqs[i].output_ids()), f"request {i}"


def test_engine_preemption_resumes_with_identical_output(params):
    """Pool pressure must evict the youngest request and resume it later with
    output identical to an uninterrupted single-stream run."""
    engine = ServingEngine(
        params, CONFIG, num_blocks=10, block_size=8, max_slots=4,
        max_blocks_per_seq=8,
        lattice=BucketLattice(slot_buckets=(1, 2, 4), block_buckets=(4, 8),
                              prefill_buckets=(32,)),
    )
    engine.warmup()
    prompts = _prompts(2, (16, 14, 15))
    reqs = [engine.submit(p, 16, rng_seed=i) for i, p in enumerate(prompts)]
    engine.run()
    assert engine.scheduler.preemption_count >= 1
    assert any(r.preemptions >= 1 for r in reqs)
    for i, p in enumerate(prompts):
        ref = greedy_generate(params, p[None], CONFIG, max_new_tokens=16)
        assert np.array_equal(np.asarray(ref[0]), reqs[i].output_ids()), f"request {i}"


def test_engine_eos_frees_slot_and_backfills(params, greedy_engine):
    """A request hitting eos stops early and its slot is backfilled by the
    queue at the next step (continuous batching's whole point)."""
    engine = greedy_engine
    prompts = _prompts(3, (8, 8, 8, 8, 8, 8))
    # learn what token the model actually emits first, then use it as eos
    probe = engine.submit(prompts[0], 2, rng_seed=0)
    engine.run()
    eos = probe.generated[0]
    reqs = [engine.submit(p, 12, eos_token_id=eos, rng_seed=i) for i, p in enumerate(prompts[1:])]
    done = engine.run()
    assert len(done) == len(reqs)
    for req in reqs:
        assert req.generated[-1] == eos or len(req.generated) == 12
        ref = greedy_generate(
            params, req.prompt[None], CONFIG, max_new_tokens=12, eos_token_id=eos
        )
        # reference pads with eos after finishing; the engine stops — compare
        # the engine's tokens against the reference prefix
        n = req.output_ids().size
        assert np.array_equal(np.asarray(ref[0])[:n], req.output_ids())


def test_engine_rejects_impossible_request(params):
    big = _prompts(4, (26,))[0]  # 26 + 4 tokens -> 4 blocks, cap is 2
    small = ServingEngine(
        params, CONFIG, num_blocks=3, block_size=8, max_slots=2,
        lattice=BucketLattice(slot_buckets=(2,), block_buckets=(2,), prefill_buckets=(32,)),
    )
    small.warmup()
    req = small.submit(big, 4)
    ok = small.submit(_prompts(5, (6,))[0], 3)
    done = small.run()
    # the impossible request is returned with a REJECTED status + reason,
    # never silently dropped; the queue behind it still drains
    assert req in done and req.status is RequestStatus.REJECTED
    assert req.generated == [] and "per-sequence cap" in req.error
    assert ok in done and len(ok.generated) == 3


def test_engine_rejects_request_outgrowing_the_block_lattice(params):
    """A request whose prompt fits but whose worst case (prompt + max_new)
    outgrows the lattice's widest block table must be rejected at ADMISSION
    — not crash the engine mid-decode with blocks leaked."""
    engine = ServingEngine(
        params, CONFIG, num_blocks=33, block_size=8, max_slots=2,
        lattice=BucketLattice(slot_buckets=(2,), block_buckets=(4,),
                              prefill_buckets=(16,)),
    )
    engine.warmup()
    # 10 + 30 = 40 tokens -> 5 blocks: fits the 32-block pool, NOT the
    # 4-wide table cap (the review finding's reproducer)
    doomed = engine.submit(_prompts(6, (10,))[0], 30)
    ok = engine.submit(_prompts(7, (10,))[0], 8)
    done = engine.run()
    assert doomed.status is RequestStatus.REJECTED and doomed in done
    assert ok in done and len(ok.generated) == 8
    assert engine.allocator.stats()["sequences"] == 0  # nothing leaked


def test_scheduler_static_mode_gang_admission():
    alloc = BlockAllocator(num_blocks=17, block_size=8)
    sched = Scheduler(alloc, max_slots=2, continuous=False)
    reqs = [Request(prompt=np.arange(4) + 1, max_new_tokens=4) for _ in range(3)]
    for r in reqs:
        sched.submit(r)
    first = sched.admissions()
    assert len(first) == 2  # gang of two
    # nothing admits while the gang is running — even with a free slot
    sched.complete(first[0], now=0.0)
    assert sched.admissions() == []
    sched.complete(first[1], now=0.0)
    assert sched.admissions() == [reqs[2]]  # only on a fully drained engine


def test_engine_rejects_request_beyond_rope_table(params):
    """Worst case (prefix + max_new) past config.max_seq_len must be rejected
    at admission: positions past the RoPE table would be silently clamped by
    the cos/sin gathers, corrupting output with no error."""
    engine = ServingEngine(
        params, CONFIG, num_blocks=40, block_size=8, max_slots=1,
        lattice=BucketLattice(slot_buckets=(1,), block_buckets=(39,),
                              prefill_buckets=(32,)),
    )
    # 30 + 250 = 280 tokens: fits the 39-block cap (35 blocks) but exceeds
    # tiny's max_seq_len of 256 — the token rule, not the block rule, fires
    doomed = engine.submit(_prompts(10, (30,))[0], 250)
    done = engine.run()
    assert doomed in done and doomed.status is RequestStatus.REJECTED
    assert "max_seq_len" in doomed.error


def test_scheduler_grow_error_is_a_guarded_backstop():
    """Admission's worst-case check makes grow()'s pool-exhaustion path
    unreachable through the engine, but the scheduler keeps it as a backstop:
    a sequence that somehow outgrows the pool with nothing left to evict
    raises a clear SchedulingError instead of a deep allocator error."""
    alloc = BlockAllocator(num_blocks=3, block_size=2)
    sched = Scheduler(alloc, max_slots=2)
    req = Request(prompt=np.arange(2) + 1, max_new_tokens=1)  # worst 3 tokens: admits
    sched.submit(req)
    assert sched.admissions() == [req]
    with pytest.raises(SchedulingError, match="no other sequence left to evict"):
        for _ in range(8):  # grown past its declared max_new, past the pool
            sched.grow(req)


# ---------------------------------------------------------------------------
# zero-recompile churn guard (telemetry recompile detector as the oracle)


def test_zero_recompiles_through_admission_churn(params):
    from accelerate_tpu.telemetry.step_profiler import RecompileWatcher

    engine = ServingEngine(
        params, CONFIG, num_blocks=17, block_size=4, max_slots=4,
        max_blocks_per_seq=8,
        lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(4, 8),
                              prefill_buckets=(16, 32)),
    )
    warmed = engine.warmup()
    assert warmed["decode_compiles"] == len(engine.lattice.decode_points())
    assert warmed["prefill_compiles"] == len(engine.lattice.prefill_points())
    watcher = RecompileWatcher()
    watcher.register("serving_prefill", engine.prefill_fn)
    watcher.register("serving_decode", engine.decode_fn)

    # churn across every bucket: light load (1 slot), full load (4 slots),
    # short and long prompts (both prefill buckets), sequences crossing the
    # 4->8 block-width boundary, eviction pressure, staggered arrivals
    rng = np.random.default_rng(7)
    lengths = [3, 14, 30, 9, 22, 5, 28, 12]
    news = [4, 9, 2, 14, 6, 11, 3, 8]
    reqs = []
    for i in range(0, len(lengths), 2):
        for j in (i, i + 1):
            prompt = rng.integers(0, CONFIG.vocab_size, (lengths[j],)).astype(np.int32)
            reqs.append(engine.submit(prompt, news[j], rng_seed=j))
        engine.step()
    engine.run()
    assert all(r.done for r in reqs)

    # the oracle: jit caches frozen at the warmed counts, watcher sees zero
    # cache misses after warmup
    assert engine.jit_cache_sizes() == warmed
    assert watcher.poll(emit=False) == {}


# ---------------------------------------------------------------------------
# telemetry + report


def test_serving_telemetry_and_report_section(params, tmp_path):
    from accelerate_tpu.telemetry import events as tel
    from accelerate_tpu.telemetry.report import build_report, format_report

    tel.enable(out_dir=str(tmp_path), run_id="serving-test")
    try:
        engine = ServingEngine(
            params, CONFIG, num_blocks=33, block_size=8, max_slots=4,
            lattice=SMALL_LATTICE,
        )
        engine.warmup()
        for i, (p, m) in enumerate(zip(_prompts(8, (5, 12, 9)), (6, 4, 8))):
            engine.submit(p, m, rng_seed=i)
        engine.run()
    finally:
        tel.disable()

    report = build_report([str(tmp_path)])
    serving = report["serving"]
    assert serving["steps"] == engine.steps
    assert serving["requests"]["completed"] == 3
    assert serving["requests"]["new_tokens"] == 6 + 4 + 8
    assert serving["decode_tokens"] == engine.decode_tokens
    assert serving["prefill_tokens"] == engine.prefill_tokens
    # prefix-cache schema fields are always present (zero for this
    # unshared workload) and mirror the engine's own counters
    assert serving["prefill_tokens_saved"] == engine.prefix_cached_tokens
    assert 0.0 <= serving["prefix_hit_rate"] <= 1.0
    assert serving["occupancy"]["max"] > 0.5  # batched, not serialized
    assert serving["requests"]["latency_s"]["count"] == 3
    text = format_report(report)
    assert "serving:" in text and "batch occupancy" in text and "requests: 3 completed" in text


def test_report_without_serving_records_omits_section(tmp_path):
    from accelerate_tpu.telemetry.report import build_report, format_report

    (tmp_path / "events-rank0.jsonl").write_text(
        '{"kind": "meta", "schema": 1, "run_id": "r", "process_index": 0, '
        '"num_processes": 1}\n'
    )
    report = build_report([str(tmp_path)])
    assert report["serving"] is None
    assert "serving:" not in format_report(report)


# ---------------------------------------------------------------------------
# prefix cache: refcounted block sharing + copy-on-write (ISSUE 14)


def test_prefix_allocator_shares_blocks_and_refcounts():
    alloc = BlockAllocator(num_blocks=17, block_size=4, prefix_caching=True)
    toks = np.arange(10, dtype=np.int32)  # 2 full blocks + a 2-token tail
    t_a = alloc.allocate_with_prefix("a", toks)
    assert t_a.cached_tokens == 0 and t_a.cow is None
    # same prefix, longer tail: the two full blocks are MAPPED, not copied
    t_b = alloc.allocate_with_prefix("b", np.concatenate([toks, toks[:3]]))
    assert t_b.cached_tokens == 8
    assert t_b.table[:2] == t_a.table[:2]
    assert t_b.table[2:] != t_a.table[2:]  # private tails
    assert alloc.shared_blocks() == 2
    # free one sharer: shared blocks stay live for the other (no
    # use-after-free); a's PARTIAL tail block is not content-indexed so it
    # goes straight back to the free list, while the full blocks stay
    # referenced by b (nothing parks in the LRU pool yet)
    free_before = alloc.free_blocks
    alloc.free("a")
    assert alloc.block_table("b")[0] == t_b.table[0]
    assert alloc.shared_blocks() == 0 and alloc.reclaimable_blocks == 0
    assert alloc.free_blocks == free_before + 1
    # a third request still matches the chain through b's references
    t_c = alloc.allocate_with_prefix("c", toks.copy())
    assert t_c.cached_tokens == 8 and t_c.table[:2] == t_b.table[:2]
    # freeing the LAST referents parks the registered blocks, matchable until
    # reclaimed
    alloc.free("b")
    alloc.free("c")
    assert alloc.reclaimable_blocks >= 2
    t_d = alloc.allocate_with_prefix("d", toks.copy())
    assert t_d.cached_tokens == 8


def test_prefix_allocator_full_match_is_copy_on_write():
    alloc = BlockAllocator(num_blocks=17, block_size=4, prefix_caching=True)
    toks = np.arange(8, dtype=np.int32)  # exactly 2 blocks: the aligned case
    t_a = alloc.allocate_with_prefix("a", toks)
    t_b = alloc.allocate_with_prefix("b", toks.copy())
    # all but the last position come from the cache; the last matched block
    # is replaced by a private copy target so no shared block is ever written
    assert t_b.cached_tokens == 7
    assert t_b.cow is not None
    src, dst = t_b.cow
    assert src == t_a.table[-1] and dst == t_b.table[-1] and dst != src
    assert t_b.table[0] == t_a.table[0]
    # the src pin: until the engine confirms the device copy, src must not be
    # reclaimable even though no live table holds it beyond a's
    alloc.free("a")
    free_before = alloc.free_blocks
    while alloc.free_blocks:  # drain the free list completely
        alloc.allocate(f"f{alloc.free_blocks}", alloc.block_size)
    with pytest.raises(BlockPoolExhausted):
        # the only reclaimable candidates are pinned/referenced: must refuse,
        # never hand out the COW source
        alloc.allocate("overflow", 10 * alloc.block_size)
    alloc.cow_done(src)
    assert alloc.reclaimable_blocks >= 1  # pin released: src parks in LRU
    assert free_before >= 0


def test_prefix_allocator_reclaims_lru_before_rejecting():
    alloc = BlockAllocator(num_blocks=9, block_size=4, prefix_caching=True)
    toks = np.arange(32, dtype=np.int32)  # all 8 usable blocks
    alloc.allocate_with_prefix("a", toks)
    alloc.free("a")  # every block cached + unreferenced (LRU pool)
    assert alloc.free_blocks == 0 and alloc.reclaimable_blocks == 8
    assert alloc.available_blocks == 8  # caching never shrinks capacity
    # a new unrelated allocation must reclaim from the LRU pool, not reject
    table = alloc.allocate_with_prefix("b", 100 + np.arange(12, dtype=np.int32))
    assert len(table.table) == 3 and alloc.reclaimed_blocks == 3
    # 3 reclaimed entries left the content index; b's 3 full blocks joined it
    assert alloc.stats()["cached_blocks"] == 8 - 3 + 3


def test_prefix_allocator_off_keeps_legacy_behavior():
    on = BlockAllocator(num_blocks=9, block_size=4, prefix_caching=False)
    toks = np.arange(8, dtype=np.int32)
    t1 = on.allocate_with_prefix("a", toks)
    assert t1.cached_tokens == 0 and t1.cow is None
    t2 = on.allocate_with_prefix("b", toks.copy())  # no index: no sharing
    assert set(t1.table).isdisjoint(t2.table)
    on.free("a")
    assert on.reclaimable_blocks == 0  # nothing parks: straight to free list
    plan = on.plan_prefix(toks)
    assert plan.fresh_blocks == 2 and not plan.matched


def test_prefix_plan_charges_lru_pinned_blocks():
    """A plan whose matched blocks sit in the LRU pool must charge them to
    admission (they count as available but this mapping pins them) — without
    the charge, admission green-lights an allocation that then throws."""
    alloc = BlockAllocator(num_blocks=5, block_size=4, prefix_caching=True)
    toks = np.arange(8, dtype=np.int32)
    alloc.allocate_with_prefix("a", toks)
    alloc.free("a")  # 2 cached blocks in LRU, 2 free
    plan = alloc.plan_prefix(np.concatenate([toks, np.arange(100, 112, dtype=np.int32)]))
    assert len(plan.matched) == 2 and plan.lru_pinned == 2
    # total charge = 3 fresh + 2 pinned = 5 > 4 available: inadmissible
    assert plan.fresh_blocks == 3
    assert plan.fresh_blocks + plan.lru_pinned > alloc.available_blocks
    with pytest.raises(BlockPoolExhausted):
        alloc.allocate_with_prefix("b", np.concatenate(
            [toks, np.arange(100, 112, dtype=np.int32)]
        ))


def test_engine_prefix_cache_bitwise_parity_and_savings(params):
    """Staggered requests sharing a long system prompt: the cached engine
    must produce BITWISE-identical outputs to the cache-off engine while
    skipping a large share of prefill work, with the jit caches frozen at
    the warmup counts (the zero-recompile oracle keeps holding)."""
    from accelerate_tpu.telemetry.step_profiler import RecompileWatcher

    rng = np.random.default_rng(11)
    shared = rng.integers(0, CONFIG.vocab_size, (24,)).astype(np.int32)
    suffixes = [rng.integers(0, CONFIG.vocab_size, (n,)).astype(np.int32)
                for n in (5, 9, 3, 7)]
    prompts = [np.concatenate([shared, s]) for s in suffixes]

    def run(prefix_cache):
        engine = ServingEngine(
            params, CONFIG, num_blocks=65, block_size=8, max_slots=4,
            lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(8,),
                                  prefill_buckets=(32,)),
            prefix_cache=prefix_cache,
        )
        warmed = engine.warmup()
        watcher = RecompileWatcher()
        watcher.register("prefill", engine.prefill_fn)
        watcher.register("decode", engine.decode_fn)
        reqs = [engine.submit(prompts[0], 8, rng_seed=0),
                engine.submit(prompts[1], 6, rng_seed=1)]
        for i in (2, 3):  # staggered: arrive after the first prefills landed
            engine.step()
            reqs.append(engine.submit(prompts[i], 5 + i, rng_seed=i))
        engine.run()
        assert engine.jit_cache_sizes() == warmed
        assert watcher.poll(emit=False) == {}
        return engine, [r.output_ids() for r in reqs]

    cached_engine, cached_out = run(True)
    plain_engine, plain_out = run(False)
    for i, (a, b) in enumerate(zip(cached_out, plain_out)):
        assert np.array_equal(a, b), f"request {i} diverged under prefix caching"
    stats = cached_engine.stats()
    assert stats["prefix_hit_rate"] > 0.3
    assert stats["prefill_tokens_saved"] >= 3 * 24 - 24  # later reqs skip the shared part
    assert "prefix_hit_rate" not in plain_engine.stats()


def test_engine_prefix_cache_cow_divergence_parity(params):
    """Block-aligned duplicate prompts hit the full-match COW path: each
    request recomputes its final position in a PRIVATE copy and decodes its
    own continuation — outputs bitwise-equal to unshared runs, shared blocks
    never written (proven by request 0 finishing first and request 1 still
    matching its reference afterwards)."""
    rng = np.random.default_rng(12)
    p32 = rng.integers(0, CONFIG.vocab_size, (32,)).astype(np.int32)  # 4 blocks

    def run(prefix_cache):
        engine = ServingEngine(
            params, CONFIG, num_blocks=65, block_size=8, max_slots=4,
            lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(8,),
                                  prefill_buckets=(32,)),
            prefix_cache=prefix_cache,
        )
        engine.warmup()
        a = engine.submit(p32, 4, rng_seed=0)
        engine.step()  # a prefilled + indexed before b arrives
        b = engine.submit(p32.copy(), 12, rng_seed=0)
        engine.run()
        return engine, a.output_ids(), b.output_ids()

    engine, a_cached, b_cached = run(True)
    _, a_plain, b_plain = run(False)
    assert np.array_equal(a_cached, a_plain)
    assert np.array_equal(b_cached, b_plain)
    assert engine.allocator.cow_copies == 1
    assert engine.stats()["cow_copies"] == 1
    # same seed + same prompt -> identical streams; the divergence point is
    # covered by kernel-level aliased-table tests (different seeds would
    # sample different tokens into the two PRIVATE last blocks)
    assert np.array_equal(a_cached, b_cached[: a_cached.size])


def test_engine_preemption_resume_rides_the_prefix_cache(params):
    """A preempted request's blocks park in the LRU pool; its resume re-plans
    and maps them back instead of re-prefilling — with output identical to
    the uninterrupted single-stream reference (the PR-13 failover waste the
    motivation names)."""
    engine = ServingEngine(
        params, CONFIG, num_blocks=10, block_size=8, max_slots=4,
        max_blocks_per_seq=8,
        lattice=BucketLattice(slot_buckets=(1, 2, 4), block_buckets=(4, 8),
                              prefill_buckets=(32,)),
    )
    engine.warmup()
    prompts = _prompts(2, (16, 14, 15))
    reqs = [engine.submit(p, 16, rng_seed=i) for i, p in enumerate(prompts)]
    engine.run()
    assert engine.scheduler.preemption_count >= 1
    for i, p in enumerate(prompts):
        ref = greedy_generate(params, p[None], CONFIG, max_new_tokens=16)
        assert np.array_equal(np.asarray(ref[0]), reqs[i].output_ids()), f"request {i}"
    # at least one resume found its own KV still cached
    assert engine.allocator.prefix_hit_tokens > 0


# ---------------------------------------------------------------------------
# multi-chip placement surface


def test_serving_shardings_places_kv_heads_on_tp():
    from jax.sharding import Mesh, PartitionSpec as P

    from accelerate_tpu.generation import serving_shardings

    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devices, ("dp", "tp"))
    sharding = serving_shardings(mesh, CONFIG)  # tiny config: 2 kv heads % tp=2 == 0
    # CANONICAL form (trailing Nones trimmed): anything else re-specializes
    # the first warmed prefill bucket on its first steady-state call (the
    # PR 14 "4x2 recompile" — the dispatch cache compares specs, and GSPMD
    # hands back the canonical form on every step output)
    assert sharding.spec == P(None, None, None, "tp")
    # indivisible kv heads stay replicated
    import dataclasses

    odd = dataclasses.replace(CONFIG, n_heads=3, n_kv_heads=3)
    assert serving_shardings(mesh, odd).spec == P()
    # it is the pool's rows that are placed: two key heads of 64 share one (`kv_lane_pack`),
    # and one row does not divide over tp=2 where its two heads would have
    packed = dataclasses.replace(CONFIG, dim=128, n_heads=2, n_kv_heads=2)
    assert packed.head_dim == 64 and serving_shardings(mesh, packed).spec == P()
    four = dataclasses.replace(CONFIG, dim=256, n_heads=4, n_kv_heads=4)
    assert serving_shardings(mesh, four).spec == P(None, None, None, "tp")


def test_zero_recompiles_through_churn_on_multidevice_mesh():
    """The 4x2-mesh churn regression (ISSUE 15 satellite): with the pool
    placed by ``serving_shardings`` on a multi-device mesh, post-warmup
    churn — including a prompt that CHUNKS past the largest prefill bucket
    and a small-bucket prefill against a steady-state pool (the exact shape
    that re-specialized before the canonicalization fix) — must keep every
    jit cache frozen at the warmed counts, with outputs bitwise-equal to
    the single-device single-stream reference.

    float32 weights and cache: the tp-sharded pool changes the summation
    order of the ``wo`` contraction, and the module's bf16 weights give
    request 0 a first token whose top-2 logits are EQUAL in bf16 (0.640625
    twice; 6e-4 apart in f32) — a tie the reorder flips. f32 has no tie."""
    from jax.sharding import Mesh

    from accelerate_tpu.telemetry.step_profiler import RecompileWatcher

    params = init_llama(CONFIG, jax.random.PRNGKey(0))
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devices, ("dp", "tp"))
    engine = ServingEngine(
        params, CONFIG, num_blocks=33, block_size=8, max_slots=4,
        cache_dtype=jnp.float32,
        lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(8,),
                              prefill_buckets=(16, 32)),
        mesh=mesh,
    )
    warmed = engine.warmup()
    watcher = RecompileWatcher()
    watcher.register("mesh_prefill", engine.prefill_fn)
    watcher.register("mesh_decode", engine.decode_fn)
    rng = np.random.default_rng(21)
    reqs = []
    # (9, _) hits the SMALL prefill bucket against a steady-state pool;
    # (45, _) chunks past the largest (32) bucket; staggered arrivals churn
    # slot and width buckets
    for i, (n, new) in enumerate([(9, 4), (45, 6), (30, 4), (5, 8)]):
        prompt = rng.integers(0, CONFIG.vocab_size, (n,)).astype(np.int32)
        reqs.append(engine.submit(prompt, new, rng_seed=i))
        engine.step()
    engine.run()
    assert all(r.done for r in reqs)
    assert engine.jit_cache_sizes() == warmed
    assert watcher.poll(emit=False) == {}
    for i, r in enumerate(reqs):
        ref = greedy_generate(params, r.prompt[None], CONFIG,
                              max_new_tokens=r.max_new_tokens,
                              cache_dtype=jnp.float32)
        assert np.array_equal(np.asarray(ref[0]), r.output_ids()), f"request {i}"


# ---------------------------------------------------------------------------
# the engine's own measurement (PR 25): step phases, request stamps, the ring

PHASE = "atpu.serve."
CHILDREN = {"admit", "prefill", "grow", "build", "dispatch", "fetch", "emit"}
PREFILL_CHILDREN = {"prefill_call", "prefill_sync"}  # a `prefill`'s own


def _engine_records(engine, prefix=PHASE):
    return [r for r in tracing.recorded(prefix) if r[3]["engine"] == engine.engine_id]


def _mixed_engine(params, **kwargs):
    engine = ServingEngine(
        params, CONFIG, num_blocks=10, block_size=8, max_slots=4, max_blocks_per_seq=8,
        lattice=BucketLattice(slot_buckets=(1, 2, 4), block_buckets=(4, 8),
                              prefill_buckets=(16, 32)),
        **kwargs,
    )
    engine.warmup()
    return engine


def _run_staggered(engine, prompts, max_new):
    """Submit two requests up front and one more after each of the next steps
    (so some requests wait in the queue), then run the engine dry."""
    reqs = [engine.submit(p, max_new, rng_seed=i) for i, p in enumerate(prompts[:2])]
    for i, p in enumerate(prompts[2:], start=2):
        engine.step()
        reqs.append(engine.submit(p, max_new, rng_seed=i))
    engine.run()
    return reqs


def test_request_stamps_are_ordered_and_first_token_lies_in_its_own_prefill(params):
    """arrival <= admit <= first token <= finish for every request of a mixed
    workload (chunked prefills, queueing, a preemption), ``first_token_t`` is
    read inside the request's own ``atpu.serve.prefill`` span, and each
    finished request leaves one ``atpu.request`` record of the same stamps."""
    engine = _mixed_engine(params)
    reqs = _run_staggered(engine, _prompts(7, (16, 40, 14, 15, 9)), 16)
    assert engine.scheduler.preemption_count >= 1
    prefills = {}
    for name, t0_ns, t1_ns, key in _engine_records(engine, PHASE + "prefill"):
        if name != PHASE + "prefill":  # its children, `prefill_call` and `prefill_sync`
            continue
        prefills.setdefault(key["rid"], []).append((t0_ns, t1_ns, key))
    finished = {r[3]["rid"]: r[3] for r in _engine_records(engine, "atpu.request")}
    for req in reqs:
        assert req.arrival_t <= req.admit_t <= req.first_token_t <= req.finish_t
        t0_ns, t1_ns, key = prefills[req.rid][0]  # the first: a resume re-prefills
        assert t0_ns / 1e9 <= req.first_token_t <= t1_ns / 1e9
        assert key["step"] == req.admit_step and key["cached"] == 0
        assert key["tokens"] == req.prompt.size
        assert len(prefills[req.rid]) == 1 + req.preemptions
        stamps = finished[req.rid]
        assert stamps == dict(
            engine=engine.engine_id, rid=req.rid, arrival_t=req.arrival_t,
            admit_t=req.admit_t, first_token_t=req.first_token_t, finish_t=req.finish_t,
            admit_step=req.admit_step, prompt_tokens=int(req.prompt.size),
            new_tokens=len(req.generated), preemptions=req.preemptions)
    assert any(r.admit_step > 0 and r.admit_t > r.arrival_t for r in reqs)  # one queued


def test_first_token_stamp_follows_the_tokens_sync_not_the_steps_start(params):
    """With a prefill function the test makes slow, ``first_token_t - admit_t``
    is at least that slow: the stamp is read after the token's sync. It was 0
    while the stamp was the clock read at the top of ``step()``."""
    engine = _mixed_engine(params)
    fast, slow_s = engine.prefill_fn, 0.05

    def slow_prefill(*args):
        time.sleep(slow_s)
        return fast(*args)

    engine.prefill_fn = slow_prefill
    reqs = [engine.submit(p, 3, rng_seed=i) for i, p in enumerate(_prompts(8, (12, 40)))]
    engine.run()
    assert reqs[0].first_token_t - reqs[0].admit_t >= slow_s
    # the second was admitted at the same read, waited for the first's prefill, and
    # its own ran in two chunks (40 tokens over a 32 bucket)
    assert reqs[1].admit_t == reqs[0].admit_t
    assert reqs[1].first_token_t - reqs[0].first_token_t >= 2 * slow_s
    for req in reqs:
        assert req.first_token_t <= req.finish_t


def test_preempted_request_keeps_its_first_stamps_and_a_given_now_is_every_stamp(params):
    """A resume after preemption leaves ``admit_t``, ``admit_step`` and
    ``first_token_t`` alone; under ``step(now=...)`` every stamp is the
    caller's clock, so a simulated clock stays deterministic."""
    engine = _mixed_engine(params)
    reqs = [engine.submit(p, 16, rng_seed=i, arrival_t=100.0)
            for i, p in enumerate(_prompts(2, (16, 14, 15)))]
    now, first = 100.0, {}
    while not engine.scheduler.idle():
        now += 1.0
        engine.step(now=now)
        for req in reqs:
            if req.admit_t is not None:
                first.setdefault(req.rid, (req.admit_t, req.admit_step, req.first_token_t))
    assert engine.scheduler.preemption_count >= 1
    for req in reqs:
        assert (req.admit_t, req.admit_step, req.first_token_t) == first[req.rid]
        assert req.admit_t == req.first_token_t == 101.0 + req.admit_step
        assert req.finish_t == float(int(req.finish_t)) and req.finish_t > req.first_token_t


@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["decode", "spec-decode"])
def test_step_phases_are_disjoint_children_of_their_step(params, spec_tokens):
    """Every ``atpu.serve.step`` holds its children: each carries the step's
    number, lies inside it, and overlaps no sibling; a step that decoded has
    one ``build`` and as many ``fetch`` as ``dispatch`` phases. A ``prefill``
    holds its own likewise: a ``prefill_call`` a chunk, then one
    ``prefill_sync``."""
    kwargs = dict(spec_tokens=spec_tokens, draft_layers=1) if spec_tokens else {}
    engine = _mixed_engine(params, **kwargs)
    _run_staggered(engine, _prompts(9, (16, 40, 14, 9)), 10)
    by_step = {}
    for name, t0_ns, t1_ns, key in _engine_records(engine):
        assert t0_ns <= t1_ns
        by_step.setdefault(key["step"], []).append((name[len(PHASE):], t0_ns, t1_ns, key))
    assert sorted(by_step) == list(range(engine.steps))
    for step, spans in by_step.items():
        (whole,) = [s for s in spans if s[0] == "step"]
        inner = sorted((s for s in spans if s[0] in PREFILL_CHILDREN), key=lambda s: s[1])
        children = sorted((s for s in spans if s[0] != "step" and s not in inner),
                          key=lambda s: s[1])
        for prefill in (c for c in children if c[0] == "prefill"):
            own = [s for s in inner if prefill[1] <= s[1] and s[2] <= prefill[2]]
            names = [s[0] for s in own]
            assert names[-1] == "prefill_sync" and names.count("prefill_sync") == 1
            assert names.count("prefill_call") >= 1
            assert [s[3]["start"] for s in own[:-1]] == sorted(s[3]["start"] for s in own[:-1])
            for a, b in zip(own, own[1:]):
                assert a[2] <= b[1], (step, a[0], b[0])
        assert len(inner) == sum(
            len([s for s in inner if p[1] <= s[1] and s[2] <= p[2]])
            for p in children if p[0] == "prefill")  # each inside its prefill
        assert {c[0] for c in children} <= CHILDREN and children[0][0] == "admit"
        assert whole[1] <= children[0][1] and children[-1][2] <= whole[2]
        for a, b in zip(children, children[1:]):
            assert a[2] <= b[1], (step, a[0], b[0])
        names = [c[0] for c in children]
        if "dispatch" in names:
            assert names.count("build") == 1 and names.count("grow") == 1
            assert names.count("fetch") == names.count("dispatch") == 1 + spec_tokens
            (build,) = [c for c in children if c[0] == "build"]
            assert build[3]["slot_bucket"] >= build[3]["batch"] >= 1
            assert build[3]["block_bucket"] in (4, 8)
            assert names[-1] == "emit"


@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["decode", "spec-decode"])
def test_build_phase_counts_the_live_blocks_of_its_rows(params, spec_tokens):
    """Every ``atpu.serve.build`` carries ``live_blocks``, the sum of its
    running rows' block counts (what the paged decode kernel walks), beside the
    bucketed table it is handed; ``stats()`` keeps both totals."""
    kwargs = dict(spec_tokens=spec_tokens, draft_layers=1) if spec_tokens else {}
    engine = _mixed_engine(params, **kwargs)
    held = {}  # step -> blocks the decode batch's rows held, counted from outside
    batch_name = "_spec_decode_batch" if spec_tokens else "_decode_batch"
    real_batch = getattr(engine, batch_name)

    def counting(running, finished):
        held[engine.steps] = sum(engine.allocator.num_seq_blocks(r.rid) for r in running)
        return real_batch(running, finished)

    setattr(engine, batch_name, counting)
    _run_staggered(engine, _prompts(11, (16, 40, 14, 9)), 10)
    builds = {key["step"]: key for _, _, _, key in _engine_records(engine, PHASE + "build")}
    assert builds and sorted(builds) == sorted(held)
    for step, key in builds.items():
        assert key["live_blocks"] == held[step] >= key["batch"]
        assert key["live_blocks"] <= key["slot_bucket"] * key["block_bucket"]
    stats = engine.stats()
    assert stats["decode_blocks_live"] == sum(held.values())
    assert stats["decode_blocks_walked"] == sum(
        key["slot_bucket"] * key["block_bucket"] for key in builds.values())
    assert 0 < stats["decode_blocks_live"] <= stats["decode_blocks_walked"]


def test_phase_ring_is_bounded_and_two_engines_keep_apart(params, monkeypatch):
    import collections

    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=64))
    a, b = _mixed_engine(params), _mixed_engine(params)
    assert a.engine_id != b.engine_id and a.heartbeat_name == b.heartbeat_name
    for engine in (a, b):
        for i, p in enumerate(_prompts(4, (9, 12))):
            engine.submit(p, 12, rng_seed=i)
        engine.run()
    assert len(tracing.recorded()) == 64  # the oldest fell out, the newest are there
    assert tracing._RING.maxlen == 64 and tracing.RING_MAXLEN == 1 << 16
    assert not _engine_records(a, PHASE + "admit")[:1] or _engine_records(a)[0][3]["step"] > 0
    assert _engine_records(b)[-1][0] == PHASE + "step"
    assert _engine_records(b)[-1][3]["step"] == b.steps - 1
    with pytest.raises(RuntimeError, match="boom"):
        with tracing.phase("atpu.test.raises", engine=-1):
            raise RuntimeError("boom")
    assert tracing.recorded("atpu.test.")[-1][0] == "atpu.test.raises"  # recorded all the same


def test_phases_are_in_the_profilers_trace_one_for_one_with_the_ring(params, tmp_path):
    """Under ``jax.profiler`` every ``atpu.serve.*`` phase of an engine is in
    the trace too, on the profiler's clock, with ``engine`` and ``step`` as
    the event's stats: the two records match one for one by (name, step) and
    their durations agree within 50 us (2-3 us at the median on a quiet CPU;
    one pair in a hundred may lose the core between the two clock reads, and
    has 1 ms); in the trace, each prefill's ``prefill_call`` and
    ``prefill_sync`` lie inside it and apart."""
    import gc
    import glob

    from jax.profiler import ProfileData

    engine = _mixed_engine(params)
    gc.disable()  # a collection between the two reads is the test's noise, not the engine's
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_staggered(engine, _prompts(9, (16, 40, 14, 9)), 10)
    finally:
        jax.profiler.stop_trace()
        gc.enable()
    assert engine.scheduler.preemption_count >= 1  # a resume's prefill among them
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    traced = {}
    for plane in ProfileData.from_file(path).planes:
        for e in (e for line in plane.lines for e in line.events if e.name.startswith(PHASE)):
            stats = dict(e.stats)
            if stats["engine"] == engine.engine_id:
                traced.setdefault((e.name, stats["step"]), []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    ring = {}
    for name, t0_ns, t1_ns, key in _engine_records(engine):
        ring.setdefault((name, key["step"]), []).append(t1_ns - t0_ns)
    assert traced.keys() == ring.keys() and len(ring) > 5 * engine.steps
    assert {name for name, _ in ring} >= {PHASE + "prefill_call", PHASE + "prefill_sync"}
    apart = []
    for k, durations in ring.items():
        spans = sorted(traced[k])
        assert len(spans) == len(durations), k
        apart += [abs((e - s) - d) for (s, e), d in zip(spans, durations)]
    assert sum(ns >= 50_000 for ns in apart) <= len(apart) // 100 and max(apart) < 1_000_000
    for (name, step), prefills in traced.items():
        if name != PHASE + "prefill":
            continue
        inner = sorted(traced.get((PHASE + "prefill_call", step), [])
                       + traced.get((PHASE + "prefill_sync", step), []))
        for s, e in prefills:
            own = [(a, b) for a, b in inner if s <= a and b <= e]
            assert len(own) >= 2 and own[-1] in traced[(PHASE + "prefill_sync", step)]
            assert all(b <= a for (_, b), (a, _) in zip(own, own[1:]))
        assert sum(1 for a, b in inner if any(s <= a and b <= e for s, e in prefills)) == len(inner)


GOLDEN_GREEDY = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "golden", "serving_greedy_pr23.json")


def fixed_greedy_workload(kernel_mode: str) -> "list[list[int]]":
    """The fixed workload behind ``golden/serving_greedy_pr23.json``: float32
    weights and cache, six staggered greedy requests through chunked prefill,
    queueing and a preemption. Returns each request's prompt + output."""
    os.environ.pop("ACCELERATE_PAGED_KERNEL", None)  # "xla": off the TPU the default path
    if kernel_mode == "interpret":
        os.environ["ACCELERATE_PAGED_KERNEL"] = kernel_mode
    try:
        params = init_llama(CONFIG, jax.random.PRNGKey(0))
        engine = ServingEngine(
            params, CONFIG, num_blocks=12, block_size=8, max_slots=4, max_blocks_per_seq=8,
            cache_dtype=jnp.float32,
            lattice=BucketLattice(slot_buckets=(1, 2, 4), block_buckets=(4, 8),
                                  prefill_buckets=(16, 32)),
        )
        prompts = _prompts(11, (5, 17, 40, 9, 33, 12))
        reqs = [engine.submit(p, 14, rng_seed=i) for i, p in enumerate(prompts[:3])]
        for i, p in enumerate(prompts[3:], start=3):
            engine.step()
            reqs.append(engine.submit(p, 14, rng_seed=i))
        engine.run()
        assert engine.scheduler.preemption_count >= 1
        return [r.output_ids().tolist() for r in reqs]
    finally:
        os.environ.pop("ACCELERATE_PAGED_KERNEL", None)


@pytest.mark.parametrize("kernel_mode", ["xla", "interpret"])
def test_greedy_outputs_are_bitwise_the_parents(kernel_mode):
    """Naming the kernels and timing the step changed no arithmetic: the
    fixed workload's tokens equal those the parent commit (PR 23) produced,
    through the XLA path and through both paged kernels in interpret mode."""
    golden = json.load(open(GOLDEN_GREEDY))
    assert fixed_greedy_workload(kernel_mode) == golden[kernel_mode]


# ---------------------------------------------------------------------------
# A second kind of cache (per-sequence state, PR 35) leaves the models without
# one as they were


def _stateless_models():
    from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig, init_cohere2_moe
    from accelerate_tpu.models.mellum import MellumConfig, init_mellum

    return {"llama": (LlamaConfig.tiny(), init_llama),
            "cohere2_moe": (Cohere2MoeConfig(), init_cohere2_moe),
            "mellum": (MellumConfig(), init_mellum)}


# greedy tokens of three requests (prompts of 50, 9 and 21 from seed 0, 10 new
# tokens each) as the commit BEFORE the state rows served them, float32 on the CPU
TOKENS_BEFORE_STATE_ROWS = {
    "llama": [[201, 131, 472, 376, 20, 134, 334, 30, 114, 11],
              [276, 182, 276, 182, 220, 254, 139, 324, 182, 376],
              [294, 212, 383, 294, 212, 383, 294, 449, 294, 332]],
    "cohere2_moe": [[147, 283, 283, 283, 423, 489, 393, 254, 484, 282],
                    [481, 295, 312, 444, 60, 221, 350, 196, 434, 356],
                    [113, 97, 312, 275, 174, 68, 141, 141, 141, 141]],
    "mellum": [[252, 103, 252, 292, 209, 200, 156, 103, 182, 26],
               [378, 110, 62, 62, 62, 103, 33, 35, 378, 35],
               [70, 252, 296, 209, 209, 209, 209, 280, 122, 200]],
}


@pytest.mark.parametrize("name", list(TOKENS_BEFORE_STATE_ROWS))
def test_models_without_sequence_state_serve_the_same_programs_and_tokens(name):
    """Their step programs take no state rows and their pool has no state
    (the lowered text of all six programs was compared with the parent
    commit's by hand, PR 35: identical); a seeded run emits the parent's
    tokens bit for bit and compiles the lattice's programs and no other."""
    cfg, init = _stateless_models()[name]
    engine = ServingEngine(init(cfg, jax.random.PRNGKey(0)), cfg, num_blocks=65, block_size=8,
                           max_slots=2, cache_dtype=jnp.float32,
                           lattice=BucketLattice((2,), (16,), (16, 32)))
    assert engine.state_shape is None and set(engine.pool) == {"k", "v"}
    assert engine._state_rows([], 2) == () and engine.prefix_cache is True
    warmed = engine.warmup()
    assert warmed == {"prefill_compiles": 2, "decode_compiles": 1, "cow_compiles": 1}
    rng = np.random.default_rng(0)
    requests = [engine.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32), 10)
                for n in (50, 9, 21)]
    engine.run()
    assert [r.generated for r in requests] == TOKENS_BEFORE_STATE_ROWS[name]
    assert engine.jit_cache_sizes() == warmed
    stats = engine.stats()
    assert "state_resets" not in stats and "state_bytes" not in stats
    assert not [k for _, _, _, k in tracing.recorded("atpu.serve.state")
                if k["engine"] == engine.engine_id]
    builds = [k for _, _, _, k in tracing.recorded("atpu.serve.build")
              if k["engine"] == engine.engine_id]
    assert builds and all("state_rows" not in b for b in builds)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_route_top_k_defaults_are_bitwise_what_they_were(scoring):
    """The function as it stood before ``select_bias`` / ``weight_eps``,
    written out here, against the defaults and against None / 0.0 given."""
    from functools import partial

    from accelerate_tpu.parallel.moe import route_top_k

    def before(router_kernel, x, top_k, scoring):
        score = {"sigmoid": jax.nn.sigmoid, "softmax": partial(jax.nn.softmax, axis=-1)}[scoring]
        logits = jnp.dot(x.astype(jnp.float32), router_kernel.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores, experts = jax.lax.top_k(score(logits), top_k)
        return experts, scores / jnp.sum(scores, axis=-1, keepdims=True)

    kernel = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) / 8
    x = jax.random.normal(jax.random.PRNGKey(2), (200, 64)).astype(jnp.bfloat16)
    want_ids, want_weights = jax.jit(partial(before, top_k=4, scoring=scoring))(kernel, x)
    for kwargs in ({}, {"select_bias": None, "weight_eps": 0.0}):
        ids, weights = jax.jit(partial(route_top_k, top_k=4, scoring=scoring, **kwargs))(kernel, x)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(weights, want_weights)
    lowered = [jax.jit(partial(fn, top_k=4, scoring=scoring)).lower(kernel, x).as_text()
               for fn in (before, route_top_k)]
    assert lowered[0].replace("before", "route_top_k") == lowered[1]  # the same program


# ---------------------------------------------------------------------------
# A decode batch's inputs live a row a batch slot: each written at the event
# that changes it, read with one index over the running slots


def _churn_model(name):
    """(config, float32 params) of a tiny model of each kind the engine serves
    differently: plain llama, window layers (mellum), per-sequence state (lfm2)."""
    from accelerate_tpu.models.lfm2 import Lfm2Config, init_lfm2
    from accelerate_tpu.models.mellum import MellumConfig, init_mellum

    cfg, init = {
        "llama": (LlamaConfig.tiny(), init_llama),
        "mellum": (MellumConfig(), init_mellum),
        "lfm2": (Lfm2Config(vocab_size=256, dim=64, n_layers=6, n_heads=8, n_kv_heads=2,
                            num_dense_layers=2, conv_taps=3, dense_dim=96, expert_dim=32,
                            num_experts=16, experts_per_token=4, max_seq_len=128), init_lfm2),
    }[name]
    return cfg, init(cfg, jax.random.PRNGKey(0))


def _churn(engine, vocab_size):
    """Staggered admissions, a prompt of four whole blocks sent twice (the
    second takes the prefix cache's copy-on-write where the model has the
    cache), rows crossing block boundaries, finishes, and a pool of 11 blocks
    too small for the batch, so it preempts."""
    rng = np.random.default_rng(5)
    shared = rng.integers(0, vocab_size, (32,)).astype(np.int32)
    prompts = [rng.integers(0, vocab_size, (n,)).astype(np.int32) for n in (16, 40, 14, 9)]
    reqs = [engine.submit(prompts[0], 12, rng_seed=0), engine.submit(shared, 5, rng_seed=1)]
    engine.step()
    reqs.append(engine.submit(shared.copy(), 11, rng_seed=2))
    for i, p in enumerate(prompts[1:], start=3):
        engine.step()
        reqs.append(engine.submit(p, 12, rng_seed=i))
    engine.run()
    return reqs


# `_churn`'s greedy tokens as the engine emitted them while it rebuilt every
# decode batch row by row from the requests and the allocator (float32, CPU)
TOKENS_BEFORE_SLOT_ROWS = {
    "llama": [[172, 322, 487, 322, 344, 228, 172, 322, 158, 324, 502, 458],
              [20, 226, 43, 463, 11], [20, 226, 43, 463, 11, 507, 339, 328, 328, 328, 328],
              [323, 73, 383, 50, 323, 176, 316, 20, 50, 73, 370, 323],
              [157, 39, 50, 384, 47, 175, 88, 92, 47, 297, 47, 335],
              [258, 478, 254, 100, 328, 144, 196, 322, 294, 474, 370, 322]],
    "mellum": [[294, 85, 103, 52, 462, 296, 52, 323, 213, 323, 213, 323],
               [27, 27, 27, 27, 471], [27, 27, 27, 27, 471, 27, 471, 27, 471, 27, 159],
               [264, 74, 402, 31, 402, 264, 246, 246, 246, 402, 378, 264],
               [474, 35, 148, 13, 148, 190, 474, 474, 362, 329, 307, 35],
               [284, 346, 284, 346, 284, 284, 68, 284, 459, 68, 459, 333]],
    "lfm2": [[18, 66, 80, 56, 216, 166, 207, 55, 230, 105, 18, 126],
             [50, 190, 85, 194, 128], [50, 190, 85, 194, 128, 13, 189, 154, 1, 64, 43],
             [202, 186, 1, 148, 210, 154, 136, 239, 206, 167, 217, 177],
             [83, 197, 189, 220, 221, 121, 143, 130, 148, 210, 105, 18],
             [45, 33, 209, 232, 12, 109, 41, 82, 170, 56, 15, 48]],
}


def _rebuilt(engine, Bb, W):
    """A decode batch's arrays rebuilt row by row from the requests and the
    allocator's own tables, as the engine built them before it kept them a
    slot, and the ``atpu.serve.build`` keys such a batch carries."""
    from accelerate_tpu.serving import NULL_STATE_ROW

    running = engine.scheduler.running()
    want = dict(last=np.zeros((Bb,), np.int32), tables=np.full((Bb, W), NULL_BLOCK, np.int32),
                positions=np.zeros((Bb,), np.int32), keys=np.zeros((Bb, 2), np.uint32),
                token_idx=np.zeros((Bb,), np.int32), rows=np.full((Bb,), NULL_STATE_ROW, np.int32))
    blocks = []
    for i, req in enumerate(running):
        table = engine.allocator.block_table(req.rid)
        blocks.append(len(table))
        want["last"][i] = req.generated[-1]
        want["tables"][i, : len(table)] = table
        want["positions"][i] = req.prefix_len - 1
        want["keys"][i] = np.asarray(jax.random.PRNGKey(req.rng_seed), np.uint32)
        want["token_idx"][i] = len(req.generated)
        want["rows"][i] = req.slot + 1
    build = dict(batch=len(running), slot_bucket=Bb, block_bucket=W, live_blocks=sum(blocks))
    if engine.window is not None:
        build["window_blocks"] = sum(n - max(r.prefix_len - engine.window, 0) // engine.block_size
                                     for n, r in zip(blocks, running))
    if engine.state_shape is not None:
        build["state_rows"] = len(running)
    return want, build


class _InputsSpy:
    """Wraps the engine's decode, draft and verify programs: every call's
    arrays against :func:`_rebuilt` (a draft round after the first hands
    over the previous round's tokens, positions and fold indices, so only
    its tables and keys are held), and the build keys each step's batch
    should carry."""

    def __init__(self, engine):
        self.engine, self.calls, self.builds, self.first_draft = engine, 0, {}, True
        self.real = {name: getattr(engine, name)
                     for name in ("decode_fn", "draft_fn", "verify_fn") if hasattr(engine, name)}
        for name, fn in self.real.items():
            setattr(engine, name, self._wrap(name, fn))

    def _wrap(self, name, fn):
        def call(params, pool, first, tables, positions, keys, token_idx, *rows):
            self._check(name, first, tables, positions, keys, token_idx, rows)
            return fn(params, pool, first, tables, positions, keys, token_idx, *rows)
        return call

    def _check(self, name, first, tables, positions, keys, token_idx, rows):
        engine = self.engine
        want, build = _rebuilt(engine, *tables.shape)
        got = dict(tables=tables, keys=keys)
        if name != "draft_fn" or self.first_draft:
            got.update(last=first[:, 0] if name == "verify_fn" else first,
                       positions=positions, token_idx=token_idx)
        if rows:
            got["rows"] = rows[0]
        for key, value in got.items():
            assert value.dtype == want[key].dtype, (name, key, engine.steps)
            np.testing.assert_array_equal(value, want[key], err_msg=f"{name} {key} {engine.steps}")
        self.first_draft = name == "verify_fn"
        self.builds[engine.steps] = build
        self.calls += 1


@pytest.mark.parametrize("name", ["llama", "llama-spec", "mellum", "lfm2"])
def test_decode_inputs_equal_a_per_row_rebuild_through_churn(name):
    """On every decode, draft and verify call of a run with staggered
    admissions, block boundaries, a copy-on-write, preemptions and finishes,
    the arrays the engine hands over equal, element for element and dtype for
    dtype, those rebuilt row by row from the requests and the allocator; the
    build records carry the same keys; the tokens are the ones the row-by-row
    engine emitted (for llama, also a batch-1 ``greedy_generate``'s)."""
    model = name.split("-")[0]
    cfg, params = _churn_model(model)
    spec = dict(spec_tokens=2, draft_layers=1) if name.endswith("spec") else {}
    engine = ServingEngine(params, cfg, num_blocks=12, block_size=8, max_slots=4,
                           max_blocks_per_seq=8, cache_dtype=jnp.float32,
                           lattice=BucketLattice((2, 4), (4, 8), (16, 32)), **spec)
    warmed = engine.warmup()
    spy = _InputsSpy(engine)
    reqs = _churn(engine, cfg.vocab_size)
    assert [r.generated for r in reqs] == TOKENS_BEFORE_SLOT_ROWS[model]
    for fn_name, fn in spy.real.items():
        setattr(engine, fn_name, fn)
    assert engine.jit_cache_sizes() == warmed
    stats = engine.stats()
    assert stats["preemptions"] >= 1 and spy.calls > engine.steps // 2
    assert stats.get("cow_copies", 0) == (0 if model == "lfm2" else 1)
    builds = {k["step"]: k for _, _, _, k in _engine_records(engine, PHASE + "build")}
    assert sorted(builds) == sorted(spy.builds)
    for step, want in spy.builds.items():
        assert {k: builds[step][k] for k in want} == want, step
    if model == "llama":
        for r in reqs:
            ref = greedy_generate(params, r.prompt[None], cfg, max_new_tokens=r.max_new_tokens,
                                  cache_dtype=jnp.float32)
            assert np.array_equal(np.asarray(ref[0]), r.output_ids()), r.rid


@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["decode", "spec-decode"])
def test_build_reads_no_request_and_copies_no_block_table(params, spec_tokens, monkeypatch):
    """From the decode bucket's first read to the end of ``build`` the engine
    reads no attribute of a ``Request`` and calls no ``block_table``: a
    counted stub round both sees none there, and some elsewhere."""
    import contextlib

    kwargs = dict(spec_tokens=spec_tokens, draft_layers=1) if spec_tokens else {}
    engine = _mixed_engine(params, **kwargs)
    inside = [False]
    counts = {True: collections.Counter(), False: collections.Counter()}
    real_getattribute = Request.__getattribute__

    def getattribute(self, name):
        counts[inside[0]][name] += 1
        return real_getattribute(self, name)

    real_table, real_bucket, real_phase = (
        engine.allocator.block_table, engine._decode_bucket, engine._phase)

    def block_table(*args, **kwargs):
        counts[inside[0]]["block_table"] += 1
        return real_table(*args, **kwargs)

    def decode_bucket(*args):
        inside[0] = True
        return real_bucket(*args)

    @contextlib.contextmanager
    def phase(name, **key):
        with real_phase(name, **key) as t0_ns:
            yield t0_ns
        if name == "build":
            inside[0] = False

    engine.allocator.block_table, engine._decode_bucket, engine._phase = (
        block_table, decode_bucket, phase)
    monkeypatch.setattr(Request, "__getattribute__", getattribute)
    _run_staggered(engine, _prompts(11, (16, 40, 14, 9)), 10)
    monkeypatch.undo()
    assert engine.stats()["decode_tokens"] > 0 and not inside[0]
    assert counts[True] == collections.Counter()
    assert counts[False]["block_table"] >= 4 and counts[False]["generated"] > 0


def test_rows_written_counts_an_admissions_whole_table_and_a_block_a_boundary(params):
    """``rows_written`` on each ``atpu.serve.build`` is the table entries the
    allocator wrote since the build before: an admission's whole table (a
    resume's too) and one entry a row that crossed a block boundary;
    ``stats()["decode_table_entries_written"]`` is their sum."""
    engine = _mixed_engine(params)
    alloc = engine.allocator
    written = []  # (step, entries written, what wrote them)
    real_allocate, real_append = alloc.allocate_with_prefix, alloc.append

    def allocate(*args, **kwargs):
        out = real_allocate(*args, **kwargs)
        written.append((engine.steps, len(out.table), "admit"))
        return out

    def append(*args, **kwargs):
        new = real_append(*args, **kwargs)
        written.append((engine.steps, len(new), "append"))
        return new

    alloc.allocate_with_prefix, alloc.append = allocate, append
    _run_staggered(engine, _prompts(11, (16, 40, 14, 9)), 10)
    assert engine.scheduler.preemption_count >= 1
    builds = [k for _, _, _, k in _engine_records(engine, PHASE + "build")]
    before = -1
    for key in builds:
        assert key["rows_written"] == sum(n for s, n, _ in written if before < s <= key["step"])
        before = key["step"]
    appended = [n for _, n, what in written if what == "append"]
    assert set(appended) == {0, 1} and max(n for _, n, what in written if what == "admit") >= 5
    assert sum(k["rows_written"] == 0 for k in builds) >= 1
    assert engine.stats()["decode_table_entries_written"] == sum(k["rows_written"] for k in builds)
    assert alloc.row_entries_written == sum(n for _, n, _ in written)
