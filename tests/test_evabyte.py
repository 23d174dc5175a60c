"""The byte-level EVA decoder (``models/evabyte.py``): exact keys inside a
blockwise window, one pooled key/value pair for every chunk of the closed
windows, one softmax over both, in a paged pool whose rows hold the open
window's raw blocks (handed back when it closes) and every window's summary
blocks. Held on the CPU at a small size (hidden 64, 4 heads of 16, 2 layers,
a window of 32, chunks and blocks of 4) to the PLAIN reference of the chip
benchmark (``benchmarks/chip/evabyte_reference.py``), which shares no code
with the program. Seeded random float32 weights drawn wide enough that
attention is not flat; logits, not tokens: every logit the engine's step
programs compute for a real token is compared with the reference's full
forward at that position."""

import collections
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from accelerate_tpu.models.evabyte import EvaByteConfig, evabyte_forward, init_evabyte  # noqa: E402
from accelerate_tpu.ops.flash_attention import (  # noqa: E402
    NULL_BLOCK,
    paged_fold_gather,
    paged_fold_kernel,
    pooled_row_view,
)
from accelerate_tpu.serving import BucketLattice, PrefillEngine, ServingEngine  # noqa: E402
from accelerate_tpu.serving.engine import model_paged_forward  # noqa: E402
from accelerate_tpu.serving.kv_pager import BlockAllocator, BlockPoolExhausted  # noqa: E402
from accelerate_tpu.serving.scheduler import Request, Scheduler  # noqa: E402
from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import evabyte_reference as reference  # noqa: E402

WINDOW, CHUNK = 32, 4
BLOCK = CHUNK                   # a chunk is one block
RAW, PER_WINDOW = 8, 2          # raw slots; summary blocks a window (32 / 4 pairs, 4 a block)
WIDTH = RAW + PER_WINDOW * 8    # rows of up to 8 windows: 256 positions
SMALL = dict(vocab_size=320, dim=64, n_layers=2, n_heads=4, ffn_dim=96, window_size=WINDOW,
             chunk_size=CHUNK, max_seq_len=256, init_std=0.15)


def _reference_logits(params, ids, cfg):
    c = {"num_attention_heads": cfg.n_heads, "rms_norm_eps": cfg.norm_eps,
         "rope_theta": cfg.rope_theta, "window_size": cfg.window_size, "chunk_size": cfg.chunk_size}
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(params, jnp.asarray(ids), window=cfg.window_size,
                                           eps=cfg.norm_eps, fn=reference.layer_fn(c, jax.jit)))


@pytest.fixture(scope="module")
def model():
    cfg = EvaByteConfig(**SMALL)
    return cfg, init_evabyte(cfg, jax.random.PRNGKey(0))


class _Spy:
    """Every logit the engine's step programs compute for a real token, by
    request and position: each call of ``prefill_fn`` / ``decode_fn`` is
    preceded by the model's own paged forward on the same arguments (jitted
    apart, nothing donated)."""

    def __init__(self, engine):
        self.engine, self.seen = engine, collections.defaultdict(list)
        self.prefill_starts = collections.defaultdict(list)
        forward = jax.jit(model_paged_forward(engine.config, engine.block_size))
        prefill, decode = engine.prefill_fn, engine.decode_fn

        def spy_prefill(params, pool, ids, table, start, last_idx, key, token_idx):
            S = ids.shape[1]
            logits, _, _ = forward(params, ids, pool, table, int(start) + np.arange(S)[None],
                                   np.arange(S)[None] <= int(last_idx))
            live = [r for r in engine.scheduler.running()
                    if (engine.allocator.block_table(r.rid, table.shape[1]) == table[0]).all()]
            if table[0, 0] != NULL_BLOCK:  # not a warm-up
                (request,) = live
                self.prefill_starts[request.rid].append((int(start), S))
                for i in range(int(last_idx) + 1):
                    self.seen[request.rid].append((int(start) + i, np.asarray(logits[0, i])))
            return prefill(params, pool, ids, table, start, last_idx, key, token_idx)

        def spy_decode(params, pool, last, tables, positions, keys, token_idx):
            logits, _, _ = forward(params, last[:, None], pool, tables, positions[:, None],
                                   tables[:, :1] != NULL_BLOCK)
            for i, request in enumerate(engine.scheduler.running()):
                self.seen[request.rid].append((int(positions[i]), np.asarray(logits[i, 0])))
            return decode(params, pool, last, tables, positions, keys, token_idx)

        spy_prefill._cache_size, spy_decode._cache_size = prefill._cache_size, decode._cache_size
        engine.prefill_fn, engine.decode_fn = spy_prefill, spy_decode

    def hold_to_the_reference(self, requests, params, cfg):
        for request in requests:
            out = request.output_ids()
            want = _reference_logits(params, out, cfg)
            seen = self.seen[request.rid]
            # every position but the last token's, which was sampled and never fed
            assert {p for p, _ in seen} == set(range(out.size - 1))
            worst = max(np.abs(row - want[p]).max() for p, row in seen)
            assert worst < 1e-4 * want.std(), (request.rid, worst / want.std())


def _engine(cfg, params, *, slots=2, num_blocks=129, buckets=(8, 16), **kwargs):
    engine = ServingEngine(
        params, cfg, num_blocks=num_blocks, block_size=BLOCK, max_slots=slots,
        cache_dtype=jnp.float32, lattice=BucketLattice((slots,), (WIDTH,), buckets), **kwargs)
    return engine, _Spy(engine)


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 320, n).astype(np.int32) for n in lengths]


def _records(engine, name):
    return [k for _, _, _, k in tracing.recorded("atpu.serve." + name)
            if k["engine"] == engine.engine_id]


# ---------------------------------------------------------- program vs reference


def test_full_forward_equals_the_plain_reference(model):
    """The uncached forward over 110 positions (three windows closed, a
    ragged fourth) against the window-by-window reference."""
    cfg, params = model
    ids = _prompts(0, 110)[0]
    got = np.asarray(evabyte_forward(params, jnp.asarray(ids[None]), cfg))[0]
    want = _reference_logits(params, ids, cfg)
    assert np.abs(got - want).max() < 1e-4 * want.std()
    # the pooling weights matter: plain means of the chunks move every logit past the first window
    with_pooling_off = dict(params, layers=dict(params["layers"], pool_mu=0 * params["layers"]["pool_mu"]))
    moved = _reference_logits(with_pooling_off, ids, cfg)
    assert np.abs(moved[WINDOW:] - want[WINDOW:]).max() > 1e-2 * want.std()
    assert np.abs(moved[:WINDOW] - want[:WINDOW]).max() < 1e-5 * want.std()  # no pair before one closes


def test_decode_across_three_window_closes_equals_the_reference(model):
    cfg, params = model
    engine, spy = _engine(cfg, params)
    requests = [engine.submit(p, n) for p, n in zip(_prompts(1, 5, 20), (110, 90))]
    engine.run()
    spy.hold_to_the_reference(requests, params, cfg)
    closes = sum(k["windows_closed"] for k in _records(engine, "eva") if k["kind"] == "decode")
    assert closes == 3 + 3  # 5 -> 115 positions and 20 -> 110: three windows each
    assert engine.stats()["windows_closed"] == 6


def test_a_prompt_is_cut_at_window_boundaries_and_its_chunks_equal_the_reference(model):
    """Buckets of 8 and 24 (the largest does not divide the window): a prompt
    of 70 is prefilled as [0, 24) [24, 32) [32, 56) [56, 64) [64, 70), every
    call inside one window, the last padded to 8."""
    cfg, params = model
    engine, spy = _engine(cfg, params, buckets=(8, 24))
    (request,) = [engine.submit(p, 12) for p in _prompts(2, 70)]
    engine.run()
    spy.hold_to_the_reference([request], params, cfg)
    assert spy.prefill_starts[request.rid] == [(0, 24), (24, 8), (32, 24), (56, 8), (64, 8)]
    (prefill,) = [k for k in _records(engine, "eva") if k["kind"] == "prefill"]
    # 17 whole chunks in 70 positions; two windows closed inside the prefill, which reused
    # their raw slots, and the last window's 2 blocks of 6 positions kept: 6 slots handed back
    assert (prefill["chunks_folded"], prefill["raw_blocks_freed"]) == (17, RAW - 2)


def test_admissions_mid_run_and_a_preemption_rebuild_by_re_prefill(model):
    """Three requests behind one another in two slots (the third admitted
    when a slot frees), in a pool of 23 usable blocks: near the end of their
    second window the two rows want 8 raw and 4 summary blocks each, 24, so
    the younger is preempted and re-prefilled from position 0."""
    cfg, params = model
    engine, spy = _engine(cfg, params, num_blocks=24)
    requests = [engine.submit(p, n) for p, n in zip(_prompts(3, 28, 30, 9), (40, 40, 6))]
    engine.run()
    spy.hold_to_the_reference(requests, params, cfg)
    stats = engine.stats()
    assert stats["preemptions"] >= 1 and any(r.preemptions for r in requests)
    assert stats["prefill_calls"] == 3 + stats["preemptions"]
    assert stats["free_blocks"] == 23  # everything handed back


def test_the_paged_kernels_and_the_fold_kernel_serve_it_too(model, monkeypatch):
    """The Pallas bodies through the interpreter: paged prefill and decode
    over the composed row at one query head a key head, and ``eva_fold``."""
    monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "interpret")
    cfg, params = model
    engine, spy = _engine(cfg, params)
    requests = [engine.submit(p, n) for p, n in zip(_prompts(4, 37, 9), (32, 30))]
    engine.run()
    spy.hold_to_the_reference(requests, params, cfg)


# ---------------------------------------------------------- the fold and the row


@pytest.mark.parametrize("fold", ["gather", "kernel"])
def test_a_single_chunks_fold_equals_the_references_pooled_pair(fold):
    rng = np.random.default_rng(5)
    pool_k = jnp.asarray(rng.normal(size=(6, CHUNK, 4, 16)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(6, CHUNK, 4, 16)), jnp.float32)
    mu, phi = (jnp.asarray(rng.normal(size=(4, 16)), jnp.float32) for _ in range(2))
    blocks = jnp.asarray([3, 0, 0, 5], jnp.int32)  # two chunks, and the null block twice
    if fold == "kernel":
        kbar, vbar = paged_fold_kernel(pool_k, pool_v, blocks, mu, phi, interpret=True)
    else:
        kbar, vbar = paged_fold_gather(pool_k, pool_v, blocks, mu, phi)
    for i, b in enumerate(np.asarray(blocks)):
        want_k, want_v = reference.pooled_pairs(pool_k[b], pool_v[b], mu, phi, CHUNK)
        assert np.abs(np.asarray(kbar[i]) - want_k[0]).max() < 1e-5
        assert np.abs(np.asarray(vbar[i]) - want_v[0]).max() < 1e-5
    # a pair is a weighted mean: it lies inside its chunk's range
    assert (np.asarray(kbar[0]) <= pool_k[3].max(axis=0) + 1e-6).all()


def test_the_composed_row_puts_the_closed_windows_pairs_ahead_of_the_raw_keys():
    """A stored row [raw slots 10..17][window 0: 20 21][window 1: 22 23][window 2: 24 25]
    at a query of window 2: summary blocks 20-23 of the two closed windows,
    then the raw slots, then null; the query at position 70 stands at
    2 x 8 pairs + 6."""
    stored = np.full((1, WIDTH), NULL_BLOCK, np.int32)
    stored[0, :RAW] = np.arange(10, 18)
    stored[0, RAW:RAW + 6] = np.arange(20, 26)
    composed, row_pos = pooled_row_view(jnp.asarray(stored), jnp.asarray([[70, 71]]),
                                        WINDOW, CHUNK, BLOCK)
    assert np.asarray(composed)[0, :14].tolist() == [20, 21, 22, 23, *range(10, 18), 0, 0]
    assert (np.asarray(composed)[0, 12:] == NULL_BLOCK).all()
    assert np.asarray(row_pos).tolist() == [[2 * 8 + 6, 2 * 8 + 7]]
    composed, row_pos = pooled_row_view(jnp.asarray(stored), jnp.asarray([[5]]), WINDOW, CHUNK, BLOCK)
    assert np.asarray(composed)[0, :RAW].tolist() == list(range(10, 18)) and row_pos.tolist() == [[5]]


# ------------------------------------------------------------ the allocator


def _pooled_blocks(alloc, seq_id):
    """``(raw blocks, summary blocks)`` the sequence's pooled row holds."""
    row = alloc._row[seq_id]
    entries = alloc.row_tables[row, : alloc.row_blocks[row]]
    return int((entries[:RAW] != NULL_BLOCK).sum()), int((entries[RAW:] != NULL_BLOCK).sum())


def _held(n):
    """The formula: at n positions, c = (n - 1) // W closed windows,
    ceil((n - c W) / block) raw blocks and (c + 1) windows' summary blocks."""
    closed = (n - 1) // WINDOW
    return -(-(n - closed * WINDOW) // BLOCK), PER_WINDOW * (closed + 1)


def test_after_each_close_the_allocator_holds_the_formulas_blocks_and_reuses_the_freed():
    alloc = BlockAllocator(200, BLOCK, max_seqs=2, max_seq_blocks=WIDTH, pooled=(WINDOW, CHUNK))
    alloc.allocate("a", 5, row=0)
    assert _pooled_blocks(alloc, "a") == _held(5) == (2, 2)
    freed_before = None
    for n in range(6, 150):
        raw_before = alloc.row_tables[0, :RAW].copy()
        new = alloc.append("a")
        assert _pooled_blocks(alloc, "a") == _held(n), n
        if (n - 1) % WINDOW == 0:  # position n - 1 opens a window: the last one closed
            freed = [b for b in raw_before.tolist() if b != NULL_BLOCK]
            assert len(freed) == RAW and alloc.windows_closed == (n - 1) // WINDOW
            # LIFO: the new summary blocks and the first raw block are blocks just handed back
            assert set(new) <= set(freed) and len(new) == PER_WINDOW + 1
            freed_before = freed
        assert alloc.row_blocks[0] == RAW + _held(n)[1]
    assert freed_before and alloc.raw_blocks_freed == 4 * RAW
    assert alloc.used_blocks == sum(_held(149)) and alloc.free("a") == sum(_held(149))
    assert alloc.used_blocks == 0


def test_a_prefill_takes_its_peak_and_settles_to_the_formula():
    alloc = BlockAllocator(200, BLOCK, max_seqs=2, max_seq_blocks=WIDTH, pooled=(WINDOW, CHUNK))
    # 70 positions: two windows closed in the prefill, which reuses one window's raw slots
    assert alloc.blocks_for(70) == PER_WINDOW * 3 + RAW and alloc.row_width(70) == RAW + 6
    alloc.allocate("a", 70)
    assert _pooled_blocks(alloc, "a") == (RAW, 6)
    assert alloc.settle("a") == RAW - 2 and _pooled_blocks(alloc, "a") == _held(70) == (2, 6)
    assert alloc.settle("a") == 0
    # a prompt inside its first window takes what it holds: no settling
    assert alloc.blocks_for(9) == 3 + PER_WINDOW
    plain = BlockAllocator(10, BLOCK)
    assert plain.settle(plain.allocate("p", 9) and "p") == 0


def test_admission_refuses_what_both_kinds_together_do_not_fit():
    """18 usable blocks. A 70-position prompt takes 6 summary + 8 raw = 14 at
    its prefill's peak: beside a running 33-position row (1 raw + 4 summary
    once its prefill settled) it does not fit, though its raw blocks alone (8)
    or its summaries alone (6) would; once the row leaves it is admitted. A
    row wider than the lattice's is rejected outright."""
    alloc = BlockAllocator(19, BLOCK, max_seqs=2, max_seq_blocks=RAW + PER_WINDOW * 3,
                           pooled=(WINDOW, CHUNK))
    sched = Scheduler(alloc, 2, max_seq_blocks=RAW + PER_WINDOW * 3, max_seq_tokens=256)
    first = sched.submit(Request(prompt=np.zeros(33, np.int32), max_new_tokens=2))
    assert sched.admissions() == [first]
    alloc.settle(first.rid)
    assert _pooled_blocks(alloc, first.rid) == (1, 4) and alloc.available_blocks == 13
    second = sched.submit(Request(prompt=np.zeros(70, np.int32), max_new_tokens=2))
    # its peak is 6 summary + 8 raw = 14 > 13, though 8 raw or 6 summary alone would fit
    assert alloc.blocks_for(70) == 14 and sched.admissions() == []
    with pytest.raises(BlockPoolExhausted):
        alloc.allocate(second.rid, 70)
    sched.complete(first, 0.0)
    assert sched.admissions() == [second]
    wide = sched.submit(Request(prompt=np.zeros(90, np.int32), max_new_tokens=10))
    sched.complete(second, 0.0)
    assert sched.admissions() == [] and wide.status.value == "rejected"
    assert "exceeds the per-sequence cap" in wide.error  # 100 positions: 4 windows, a row of 16


# ------------------------------------------------------------ what it is refused


def test_a_prefix_cache_request_is_refused_with_its_reason(model):
    cfg, params = model
    engine, spy = _engine(cfg, params, prefix_cache=True)
    assert engine.prefix_cache is False and "cow_compiles" not in engine.jit_cache_sizes()
    prompt = _prompts(6, 40)[0]
    first = engine.submit(prompt, 4)
    engine.run()
    second = engine.submit(prompt, 4)
    engine.run()
    assert (first.cached_tokens, second.cached_tokens) == (0, 0)
    assert engine.stats()["prefill_tokens_saved"] == 0 and first.generated == second.generated
    spy.hold_to_the_reference([first, second], params, cfg)
    with pytest.raises(ValueError, match="closed windows would need their summaries"):
        BlockAllocator(20, BLOCK, prefix_caching=True, pooled=(WINDOW, CHUNK))
    with pytest.raises(TypeError, match="pools what lies behind its window"):
        PrefillEngine(params, cfg, num_blocks=129, block_size=BLOCK, max_slots=2,
                      cache_dtype=jnp.float32, lattice=BucketLattice((2,), (WIDTH,), (8, 16)))


def test_the_records_and_counters_say_what_the_rows_did(model):
    cfg, params = model
    engine, _ = _engine(cfg, params)
    (request,) = [engine.submit(p, 40) for p in _prompts(7, 30)]
    engine.run()
    builds = _records(engine, "build")
    eva = _records(engine, "eva")
    assert len(eva) == 1 + len(builds) and eva[0]["kind"] == "prefill"
    # decode writes positions 30..68: chunks end at 31, 35, ..., 67: 10 of them
    assert sum(k["chunks_folded"] for k in eva[1:]) == 10 and eva[0]["chunks_folded"] == 7
    first = builds[0]  # position 30: 31 raw keys of window 0, no pair
    assert (first["window_entries"], first["summary_entries"], first["live_blocks"]) == (31, 0, 8)
    last = builds[-1]  # position 68: 5 raw keys of window 2 behind 2 x 8 pairs
    assert (last["window_entries"], last["summary_entries"], last["live_blocks"]) == (5, 16, 4 + 2)
    stats = engine.stats()
    assert stats["eva_chunks_folded"] == 17 and stats["windows_closed"] == 2
    assert stats["decode_summary_entries"] == sum(b["summary_entries"] for b in builds)
    assert stats["raw_blocks_freed"] == 2 * RAW  # two closes; the prefill's 8 raw slots all held
