"""Pallas chunked-prefill paged-attention kernel (ISSUE 18): parity matrix.

``ops.flash_attention.paged_attention_prefill`` extends the S=1 decode
kernel (ISSUE 14, ``tests/test_paged_kernel.py``) to S>1 query chunks: a
query tile walks the table entries its queries can see, ``N`` blocks a grid
step, each scored against the tile with a per-query causal mask ``kv_pos <=
q_position`` (ISSUE 30: the walk, its window, its counter). The XLA
gather path (``ops.flash_attention.paged_attention_gather``) remains the reference
semantics. These tests drive the kernel through the Pallas interpreter on
CPU — identical dataflow, no TPU required — across scrambled block tables,
ragged chunk start offsets, GQA ratios, null-block rows, COW-diverged
tables, and the in-chunk causality boundary, plus the dispatch contract
and the engine end-to-end (multi-chunk prefill + k-token verify both route
through this kernel).
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import greedy_generate
from accelerate_tpu.models import LlamaConfig, init_llama
from accelerate_tpu.ops.attention import masked_attention
from accelerate_tpu.ops.flash_attention import (
    NULL_BLOCK,
    paged_attention as dispatch_paged,
    paged_attention_gather as gather_ref,
    paged_attention_prefill,
)
from accelerate_tpu.serving import BucketLattice, ServingEngine
from accelerate_tpu.telemetry import tracing

# the module, not the function ``accelerate_tpu.ops`` re-exports under its name
fa = importlib.import_module("accelerate_tpu.ops.flash_attention")

CONFIG = LlamaConfig.tiny()


def _random_prefill_case(seed, *, B, S, H, Hkv, D, bs, nb, W, starts):
    """A pool full of garbage; each row is a mid-prefill chunk: S queries at
    positions ``starts[b] + [0..S)`` whose KV (prefix + the chunk itself,
    already landed by the engine's write-before-attend order) is scattered
    over a scrambled block table. Returns (q, k_pool, v_pool, tables, qpos).
    """
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k_pool = rng.standard_normal((nb, bs, Hkv, D)).astype(np.float32)
    v_pool = rng.standard_normal((nb, bs, Hkv, D)).astype(np.float32)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.full((B, W), NULL_BLOCK, np.int32)
    qpos = np.zeros((B, S), np.int32)
    used = 0
    for b, start in enumerate(starts):
        qpos[b] = int(start) + np.arange(S)
        need = -(-(int(start) + S) // bs)
        tables[b, :need] = perm[used : used + need]
        used += need
    return q, k_pool, v_pool, tables, qpos


def _assert_parity(q, k_pool, v_pool, tables, qpos, tol=2e-6, window=None, valid=None):
    """The kernel in interpret mode against the gather reference, with the
    same ``window`` or none: every row finite, the first ``valid`` rows of the
    chunk (all of them by default) equal to ``tol``."""
    # tol is 2x the decode kernel's: S>1 rows reduce over longer contexts
    # (prefix + chunk) so accumulated f32 rounding runs slightly wider
    args = [jnp.asarray(x) for x in (q, k_pool, v_pool, tables, qpos)]
    ref = gather_ref(*args, None, window)
    kw = {} if window is None else {"window": window}
    out = paged_attention_prefill(*args, interpret=True, **kw)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    rows = slice(None) if valid is None else slice(0, valid)
    err = float(jnp.max(jnp.abs(
        ref[:, rows].astype(jnp.float32) - out[:, rows].astype(jnp.float32))))
    assert err <= tol, f"prefill kernel diverged from gather reference by {err}"


@pytest.mark.smoke
def test_kernel_parity_scrambled_tables_ragged_starts():
    case = _random_prefill_case(
        0, B=3, S=5, H=8, Hkv=2, D=32, bs=8, nb=12, W=5, starts=[0, 11, 30]
    )
    _assert_parity(*case)


@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 4), (8, 2), (8, 1)])
def test_kernel_parity_across_gqa_ratios(H, Hkv):
    case = _random_prefill_case(
        1, B=2, S=4, H=H, Hkv=Hkv, D=16, bs=4, nb=16, W=6, starts=[3, 17]
    )
    _assert_parity(*case)


def test_in_chunk_causality_boundary():
    """Query j must not see KV at positions > start+j even though the whole
    chunk's KV is already in the pool (the engine scatter-writes the chunk
    before attending): perturbing the LAST chunk token's KV may only change
    the last query's output."""
    q, k_pool, v_pool, tables, qpos = _random_prefill_case(
        2, B=1, S=4, H=4, Hkv=2, D=16, bs=8, nb=4, W=2, starts=[0]
    )
    out = paged_attention_prefill(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(qpos), interpret=True,
    )
    # position 3 lives at slot 3 of the row's first (and only live) block
    k2, v2 = k_pool.copy(), v_pool.copy()
    k2[tables[0, 0], 3] += 1.0
    v2[tables[0, 0], 3] -= 1.0
    out2 = paged_attention_prefill(
        jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2),
        jnp.asarray(tables), jnp.asarray(qpos), interpret=True,
    )
    assert np.array_equal(np.asarray(out[:, :3]), np.asarray(out2[:, :3]))
    assert not np.allclose(np.asarray(out[:, 3]), np.asarray(out2[:, 3]))


def test_kernel_parity_null_block_rows():
    """Inactive batch slots point every table entry at the null block — the
    kernel must stay finite and match the gather reference exactly as the
    decode kernel does (a NaN would poison the batched output buffer)."""
    q, k_pool, v_pool, tables, qpos = _random_prefill_case(
        3, B=3, S=4, H=4, Hkv=2, D=16, bs=4, nb=12, W=4, starts=[9, 0, 5]
    )
    tables[1, :] = NULL_BLOCK  # dead slot
    qpos[1] = np.arange(4)
    out = paged_attention_prefill(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(qpos), interpret=True,
    )
    assert bool(jnp.all(jnp.isfinite(out)))
    _assert_parity(q, k_pool, v_pool, tables, qpos)


def test_kernel_parity_at_cow_divergence_point():
    """Two rows share every block except the one their chunk lands in (the
    post-COW layout): aliased physical blocks must read identically for the
    shared prefix and independently past the divergence."""
    rng = np.random.default_rng(4)
    B, S, H, Hkv, D, bs, nb = 2, 4, 4, 2, 16, 4, 10
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k_pool = rng.standard_normal((nb, bs, Hkv, D)).astype(np.float32)
    v_pool = rng.standard_normal((nb, bs, Hkv, D)).astype(np.float32)
    tables = np.asarray([[3, 5, 7], [3, 5, 8]], np.int32)  # diverge at block 2
    qpos = np.asarray([[8, 9, 10, 11], [8, 9, 10, 11]], np.int32)
    _assert_parity(q, k_pool, v_pool, tables, qpos)


def test_kernel_parity_bf16_pools_within_one_ulp():
    """bf16 pools (the engine's cache dtype): the kernel hands the MXU bf16
    operands with f32 accumulation, keeps ``m``, ``l`` and ``acc`` in f32 and
    rounds the probabilities to bf16 for the value product, which is what the
    program's plain path computes (``ops.attention.masked_attention`` over the
    gathered keys, which normalises before it rounds where the kernel divides
    after): the two agree to one bf16 ulp of a (query, head) pair's largest
    output."""
    q, k_pool, v_pool, tables, qpos = _random_prefill_case(
        5, B=2, S=6, H=4, Hkv=2, D=32, bs=8, nb=12, W=4, starts=[14, 2]
    )
    q, k_pool, v_pool = (jnp.asarray(x, jnp.bfloat16) for x in (q, k_pool, v_pool))
    out = paged_attention_prefill(q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(qpos),
                                  interpret=True)
    assert out.dtype == jnp.bfloat16
    B, W = tables.shape
    keys = k_pool[tables].reshape(B, W * 8, 2, 32)
    values = v_pool[tables].reshape(B, W * 8, 2, 32)
    allow = (np.arange(W * 8)[None, None, :] <= qpos[:, :, None])[:, None]
    ref = masked_attention(q, keys, values, jnp.asarray(allow)).astype(jnp.float32)
    # one ulp of the largest value a (query, head) pair puts out
    ulp = 2.0 ** (np.floor(np.log2(np.abs(np.asarray(ref)).max(axis=-1, keepdims=True))) - 7)
    assert np.all(np.abs(np.asarray(out.astype(jnp.float32)) - np.asarray(ref)) <= ulp)


# ---------------------------------------------------------------------------
# the walk (ISSUE 30): a query tile visits the table entries its queries can
# see, N blocks a grid step


def _forced(monkeypatch, *, rows=None, blocks=None):
    """A small query tile and/or a given N, so that tiny shapes have several
    tiles and several steps a tile."""
    if rows is not None:
        monkeypatch.setattr(fa, "_PREFILL_TILE_ROWS", rows)
    if blocks is not None:
        monkeypatch.setattr(fa, "_prefill_group_blocks",
                            lambda bs, Hkv, D, dtype, W: max(1, min(W, blocks)))


WALK_CASES = {
    # a tile whose walk ends mid-group: 5 live blocks, N = 2 and N = 4
    "ends-mid-group-n2": dict(blocks=2, rows=None, S=8, starts=[30], W=8, window=None),
    "ends-mid-group-n4": dict(blocks=4, rows=None, S=8, starts=[30], W=8, window=None),
    # a chunk behind a cached prefix, several tiles: every tile's last differs
    "cached-prefix-several-tiles": dict(blocks=2, rows=64, S=32, starts=[40], W=12, window=None),
    # ragged starts at S = 5 (a verify step's shape), B > 1
    "ragged-b3-s5": dict(blocks=3, rows=None, S=5, starts=[0, 11, 30], W=6, window=None),
    # a window shorter than the context: first > 0 for the later tiles only
    "window-first-later-tiles": dict(blocks=2, rows=64, S=32, starts=[0], W=6, window=12),
    "window-behind-prefix": dict(blocks=2, rows=64, S=16, starts=[50], W=12, window=20),
    # N forced to 1, 2 and whatever the shapes give
    "n1": dict(blocks=1, rows=64, S=16, starts=[21, 3], W=6, window=None),
    "n2": dict(blocks=2, rows=64, S=16, starts=[21, 3], W=6, window=None),
    "n-derived": dict(blocks=None, rows=64, S=16, starts=[21, 3], W=6, window=None),
    "n-derived-window": dict(blocks=None, rows=64, S=16, starts=[21, 3], W=6, window=9),
}


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_walk_parity(monkeypatch, case):
    c = WALK_CASES[case]
    _forced(monkeypatch, rows=c["rows"], blocks=c["blocks"])
    args = _random_prefill_case(
        11, B=len(c["starts"]), S=c["S"], H=8, Hkv=2, D=16, bs=8, nb=40, W=c["W"],
        starts=c["starts"])
    _assert_parity(*args, window=c["window"])


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window24"])
def test_dead_table_entries_are_skipped_not_masked(monkeypatch, window):
    """A table far wider than the live blocks, its dead entries pointing at
    blocks of NaN: the walk never fetches them (masking would not save it: NaN
    times a zero weight is NaN), nor with a window the blocks behind it."""
    _forced(monkeypatch, rows=64, blocks=2)
    q, k_pool, v_pool, tables, qpos = _random_prefill_case(
        12, B=2, S=16, H=8, Hkv=2, D=16, bs=8, nb=48, W=24, starts=[37, 64])
    poison = next(b for b in range(1, 48) if b not in tables)  # a block no live entry uses
    k_pool[poison] = np.nan
    v_pool[poison] = np.nan
    clean = tables.copy()
    tables[tables == NULL_BLOCK] = poison
    if window is not None:  # what lies wholly behind every query's window is dead too
        for b in range(2):
            behind = (qpos[b, 0] - window + 1) // 8
            tables[b, :behind] = poison
    args = [jnp.asarray(x) for x in (q, k_pool, v_pool)]
    kw = {} if window is None else {"window": window}
    out = paged_attention_prefill(*args, jnp.asarray(tables), jnp.asarray(qpos), interpret=True, **kw)
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = gather_ref(*args, jnp.asarray(clean), jnp.asarray(qpos), None, window)
    assert float(jnp.max(jnp.abs(ref - out))) <= 2e-6


@pytest.mark.parametrize("window", [None, 10], ids=["full", "window10"])
def test_padded_chunk_tail_past_the_table_stays_finite_and_matches(monkeypatch, window):
    """The engine pads a chunk to its bucket: the tail's positions run past
    what the table holds (their keys went to the null block). The real rows
    match the reference, the padded ones stay finite (the engine drops them)."""
    _forced(monkeypatch, rows=64, blocks=2)
    q, k_pool, v_pool, tables, qpos = _random_prefill_case(
        13, B=1, S=32, H=8, Hkv=2, D=16, bs=8, nb=20, W=4, starts=[0])
    qpos[0] = 12 + np.arange(32)  # positions 12..43 against a table of 32: 20 real rows
    tables[0] = [5, 9, 3, 7]
    _assert_parity(q, k_pool, v_pool, tables, qpos, window=window, valid=20)


@pytest.mark.parametrize("window", [None, 7, 40])
@pytest.mark.parametrize("start,S,Sq,N,W", [(0, 32, 8, 2, 16), (61, 32, 32, 3, 16),
                                            (100, 64, 16, 8, 12), (5, 8, 8, 1, 4)])
def test_walk_length_is_the_brute_force_count(start, S, Sq, N, W, window):
    """``prefill_walk_blocks`` against a count over positions: a tile visits,
    in steps of N, the table entries from the first that holds a key some
    query of it sees to the last."""
    bs, want = 8, 0
    for lo in range(start, start + S, Sq):
        seen = set()
        for p in range(lo, lo + Sq):
            low = 0 if window is None else max(0, p - window + 1)
            seen |= {min(kv // bs, W - 1) for kv in range(low, p + 1)}
        want += min(N * -(-(max(seen) - min(seen) + 1) // N), W)
    assert fa.prefill_walk_blocks(start, S, Sq, N, W, bs, window) == want
    assert S // Sq <= want <= W * (S // Sq)


def test_kernel_rejects_single_token_queries():
    with pytest.raises(ValueError, match="S>1"):
        paged_attention_prefill(
            jnp.zeros((1, 1, 4, 16)), jnp.zeros((4, 4, 2, 16)),
            jnp.zeros((4, 4, 2, 16)), jnp.zeros((1, 2), jnp.int32),
            jnp.asarray([[5]], jnp.int32), interpret=True,
        )


# ---------------------------------------------------------------------------
# dispatch + kill switch


def test_kill_switch_path_is_byte_identical_to_reference(monkeypatch):
    """Off the TPU the default path (the variable unset) routes S>1 to the
    gather reference — byte-identical output, the pre-kernel engine exactly."""
    q, k_pool, v_pool, tables, qpos = _random_prefill_case(
        6, B=2, S=3, H=4, Hkv=2, D=16, bs=4, nb=8, W=3, starts=[6, 1]
    )
    args = (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(qpos))
    monkeypatch.delenv("ACCELERATE_PAGED_KERNEL", raising=False)
    out = dispatch_paged(*args)
    ref = gather_ref(*args)
    assert np.array_equal(np.asarray(out, np.float32), np.asarray(ref, np.float32))


def test_tpu_backend_dispatches_the_prefill_kernel(monkeypatch):
    """On a TPU backend with the default mode, S>1 must route to the Pallas
    prefill kernel (compiled, not interpreted) — asserted by stubbing the
    kernel entry point, since CI has no TPU to compile for."""
    import importlib

    fa = importlib.import_module("accelerate_tpu.ops.flash_attention")
    calls = []

    def fake_prefill(q, k_pool, v_pool, tables, qpos, scale=None, *, interpret=False):
        calls.append(interpret)
        return jnp.zeros_like(q)

    monkeypatch.setattr(fa, "paged_attention_prefill", fake_prefill)
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("ACCELERATE_PAGED_KERNEL", raising=False)
    q = jnp.zeros((1, 3, 4, 16))
    fa.paged_attention(
        q, jnp.zeros((4, 4, 2, 16)), jnp.zeros((4, 4, 2, 16)),
        jnp.zeros((1, 2), jnp.int32), jnp.asarray([[3, 4, 5]], jnp.int32),
    )
    assert calls == [False]  # kernel path, compiled (not interpret) mode


def test_engine_multi_chunk_prefill_through_interpreted_kernel(monkeypatch):
    """The whole serving engine with CHUNKED prefill dispatched through the
    Pallas prefill kernel (interpreter mode) must match the single-stream
    greedy reference token-for-token. Prefill buckets are capped below the
    longest prompt so every long request runs multiple S>1 chunks, each
    attending back across earlier chunks' landed KV through the kernel."""
    monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "interpret")
    params = init_llama(CONFIG, jax.random.PRNGKey(0))
    engine = ServingEngine(
        params, CONFIG, num_blocks=33, block_size=8, max_slots=4,
        cache_dtype=jnp.float32,
        lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(4,),
                              prefill_buckets=(8, 16)),
    )
    engine.warmup()
    ring_before = len(tracing.recorded("atpu.serve.prefill"))
    rng = np.random.default_rng(8)
    specs = [(21, 6), (5, 5), (17, 4)]  # 21 → chunks of 16 + 5; 17 → 16 + 1
    prompts = [rng.integers(0, CONFIG.vocab_size, (s,)).astype(np.int32)
               for s, _ in specs]
    reqs = [engine.submit(p, n, rng_seed=i)
            for i, (p, (_, n)) in enumerate(zip(prompts, specs))]
    engine.run()
    for i, ((_, n), req) in enumerate(zip(specs, reqs)):
        # the reference keeps its cache in the engine's dtype: against the
        # default bf16 cache, request 2's second token is a 6e-5 near-tie
        ref = greedy_generate(params, prompts[i][None], CONFIG, max_new_tokens=n,
                              cache_dtype=jnp.float32)
        assert np.array_equal(np.asarray(ref[0]), req.output_ids()), f"request {i}"
    # the walk's counter (ISSUE 30): each record's blocks are the kernel's own
    # arithmetic for its chunks, and stats() sums the records
    records = [key for _, _, _, key in tracing.recorded("atpu.serve.prefill")[ring_before:]]
    by_rid = {r["rid"]: r for r in records if r["engine"] == engine.engine_id}
    N = fa._prefill_group_blocks(8, CONFIG.n_kv_heads, CONFIG.head_dim, jnp.float32, 4)
    first = by_rid[reqs[0].rid]  # 21 tokens: positions 0-15 at bucket 16, 16-20 at bucket 8
    assert first["table_blocks"] == 2 * 4  # one tile a chunk, a table of 4
    assert first["walked_blocks"] == (
        fa.prefill_walk_blocks(0, 16, 16, N, 4, 8) + fa.prefill_walk_blocks(16, 8, 8, N, 4, 8))
    stats = engine.stats()
    assert stats["prefill_blocks_walked"] == sum(r["walked_blocks"] for r in by_rid.values())
    assert stats["prefill_blocks_table"] == sum(r["table_blocks"] for r in by_rid.values())
    assert 0 < stats["prefill_blocks_walked"] <= stats["prefill_blocks_table"]
