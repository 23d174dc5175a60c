"""Pallas paged-attention decode kernel (ISSUE 14): interpret-mode parity.

The kernel (``ops.flash_attention.paged_attention_decode``) walks each row's
LIVE block-table entries, ``N`` blocks a grid step (ISSUE 26), and streams
them through VMEM with online softmax, the score and value products of all
query heads against a step's blocks as two matmuls (ISSUE 34); the XLA gather path
(``ops.flash_attention.paged_attention_gather``) is the reference semantics. These
tests drive the SAME kernel through the Pallas interpreter
on CPU — identical dataflow, no TPU required — and hold the line the
acceptance criteria name: parity across scrambled non-contiguous block
tables, GQA head ratios, ragged per-slot lengths, null-block rows, and
tables aliased at a copy-on-write divergence point; plus the
``ACCELERATE_PAGED_KERNEL`` dispatch contract.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import importlib

from accelerate_tpu.generation import greedy_generate
from accelerate_tpu.models import LlamaConfig, init_llama
from accelerate_tpu.ops.flash_attention import (
    NULL_BLOCK,
    paged_attention as dispatch_paged,
    paged_attention_decode,
    paged_attention_gather as gather_ref,
    paged_kernel_mode,
)
from accelerate_tpu.serving import BucketLattice, ServingEngine

CONFIG = LlamaConfig.tiny()
# the module, not the function ``accelerate_tpu.ops`` re-exports under its name
fa = importlib.import_module("accelerate_tpu.ops.flash_attention")


def _random_paged_case(seed, *, B, H, Hkv, D, bs, nb, W, lens):
    """A pool full of garbage with each row's live tokens scattered over a
    scrambled block table; returns (q, k_pool, v_pool, tables, lens)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, 1, H, D)).astype(np.float32)
    k_pool = rng.standard_normal((nb, bs, Hkv, D)).astype(np.float32)
    v_pool = rng.standard_normal((nb, bs, Hkv, D)).astype(np.float32)
    # hand out distinct non-null physical blocks in a scrambled order
    perm = rng.permutation(np.arange(1, nb))
    tables = np.full((B, W), NULL_BLOCK, np.int32)
    used = 0
    for b, n in enumerate(lens):
        need = -(-int(n) // bs)
        tables[b, :need] = perm[used : used + need]
        used += need
    return q, k_pool, v_pool, tables, np.asarray(lens, np.int32)


def _assert_parity(q, k_pool, v_pool, tables, lens, tol=1e-6, window=None):
    qj = jnp.asarray(q)
    kj, vj = jnp.asarray(k_pool), jnp.asarray(v_pool)
    tj = jnp.asarray(tables)
    windowed = {} if window is None else {"window": window}
    ref = gather_ref(qj, kj, vj, tj, jnp.asarray(lens - 1)[:, None], **windowed)
    out = paged_attention_decode(qj, kj, vj, tj, jnp.asarray(lens), interpret=True, **windowed)
    err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - out.astype(jnp.float32))))
    assert err <= tol, f"kernel diverged from gather reference by {err}"


@pytest.mark.smoke
def test_kernel_parity_scrambled_tables_ragged_lengths():
    case = _random_paged_case(
        0, B=4, H=8, Hkv=2, D=32, bs=8, nb=24, W=5, lens=[37, 10, 40, 1]
    )
    _assert_parity(*case)


@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 4), (8, 2), (8, 1)])
def test_kernel_parity_across_gqa_ratios(H, Hkv):
    case = _random_paged_case(
        1, B=2, H=H, Hkv=Hkv, D=16, bs=4, nb=16, W=4, lens=[13, 7]
    )
    _assert_parity(*case)


def test_kernel_parity_null_block_rows():
    """Inactive batch slots point every table entry at the null block with a
    1-token length — the kernel must produce exactly what the gather
    reference produces for them (the engine discards these rows, but a NaN
    would poison the batched output buffer)."""
    q, k_pool, v_pool, tables, lens = _random_paged_case(
        2, B=3, H=4, Hkv=2, D=16, bs=4, nb=9, W=3, lens=[9, 5, 11]
    )
    tables[1, :] = NULL_BLOCK  # dead slot
    lens[1] = 1
    out = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), interpret=True,
    )
    assert bool(jnp.all(jnp.isfinite(out)))
    _assert_parity(q, k_pool, v_pool, tables, lens)


def test_kernel_parity_at_cow_divergence_point():
    """Two rows share every block except the last (the post-COW layout: a
    common cached prefix, then private diverged tails) — aliased physical
    blocks across tables must read identically for the shared part and
    independently past the divergence."""
    rng = np.random.default_rng(3)
    B, H, Hkv, D, bs, nb = 2, 4, 2, 16, 4, 10
    q = rng.standard_normal((B, 1, H, D)).astype(np.float32)
    k_pool = rng.standard_normal((nb, bs, Hkv, D)).astype(np.float32)
    v_pool = rng.standard_normal((nb, bs, Hkv, D)).astype(np.float32)
    tables = np.asarray([[3, 5, 7], [3, 5, 8]], np.int32)  # diverge at block 2
    lens = np.asarray([11, 12], np.int32)
    _assert_parity(q, k_pool, v_pool, tables, lens)


def test_kernel_parity_bf16_pools_within_one_ulp():
    """bf16 pools (the engine's cache dtype): both products are bf16 matmuls
    with f32 accumulation and the probabilities are rounded to bf16 for the
    value product, as the reference rounds them; the online softmax sums in
    another order, so agreement is to bf16 resolution, not bitwise."""
    case = _random_paged_case(
        4, B=2, H=4, Hkv=2, D=32, bs=8, nb=12, W=3, lens=[20, 9]
    )
    q, k_pool, v_pool, tables, lens = case
    _assert_parity(
        q.astype(jnp.bfloat16), k_pool.astype(jnp.bfloat16),
        v_pool.astype(jnp.bfloat16), tables, lens, tol=2e-2,
    )


# ---------------------------------------------------------------------------
# the walk: a row's live blocks, N a grid step


@pytest.fixture
def group_of(monkeypatch):
    """Pin the walk's ``N`` (clamped to ``[1, W]`` like the derived one): tiny
    pools would otherwise all fit one group."""
    def pin(n):
        monkeypatch.setattr(
            fa, "_decode_group_blocks", lambda bs, Hkv, D, dtype, G, W: max(1, min(W, n)))
    return pin


# (N, W, lens) at block_size 4
WALK_CASES = {
    "ends-inside-a-group": (3, 8, [17, 26, 5]),            # 5, 7, 2 blocks of groups of 3
    "ends-on-a-group-boundary": (3, 8, [24, 12, 21]),      # 6, 3 blocks: whole groups
    "one-token-on-an-all-null-row": (3, 8, [29, 1, 1]),
    "full-table": (3, 6, [24, 24]),
    "full-table-of-an-odd-width": (4, 7, [28, 27]),
    "W-under-N-1": (8, 1, [3, 4]),
    "W-under-N-2": (8, 2, [8, 5, 1]),
    "W-under-N-4": (8, 4, [16, 9, 1]),
    "W-not-a-multiple-of-N-5": (3, 5, [20, 13, 4]),
    "W-not-a-multiple-of-N-7": (4, 7, [25, 17, 1]),
    "N-1": (1, 4, [13, 16, 1]),
}


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_walk_parity(group_of, case):
    N, W, lens = WALK_CASES[case]
    group_of(N)
    q, k_pool, v_pool, tables, lens = _random_paged_case(
        10, B=len(lens), H=8, Hkv=2, D=16, bs=4, nb=40, W=W, lens=lens)
    for b, n in enumerate(lens):
        if n == 1:  # a padded slot: all-null table, one token
            tables[b, :] = NULL_BLOCK
    _assert_parity(q, k_pool, v_pool, tables, lens)


# (bs, G, Hkv, D, dtype, W, lens, nb) -> N: no pinning, ``N`` is what the shapes
# give and it is under ``W``. The serve cells' head ratios at their head width
# and block (4 query heads a key head at 8 key heads, 16 at 8, 8 at 4), one and
# two query heads a key head, a big block, f32 pools.
DERIVED_CASES = {
    "bs128-bf16": ((128, 2, 8, 64, jnp.bfloat16, 3, [300, 128, 1], 14), 1),  # the big-block compile shape
    "bs16-f32": ((16, 4, 8, 128, jnp.float32, 6, [90, 17, 64], 14), 6),
    "G4-Hkv8": ((16, 4, 8, 128, jnp.float32, 40, [601, 37, 256, 1], 60), 8),   # mistral-7b
    "G16-Hkv8": ((16, 16, 8, 128, jnp.float32, 40, [601, 37, 256, 1], 60), 8),  # command-a-plus
    "G8-Hkv4": ((16, 8, 4, 128, jnp.float32, 40, [601, 37, 256, 1], 60), 8),   # mellum2-12b
    "G16-Hkv8-bf16": ((16, 16, 8, 128, jnp.bfloat16, 40, [601, 37, 256, 1], 60), 8),
    "G8-Hkv4-bf16": ((16, 8, 4, 128, jnp.bfloat16, 40, [601, 37, 256, 1], 60), 8),
    "G1-Hkv8": ((16, 1, 8, 64, jnp.float32, 20, [300, 17, 160], 32), 8),
    "G2-Hkv4": ((16, 2, 4, 64, jnp.float32, 20, [300, 17, 160], 32), 8),
    "G1-Hkv3": ((16, 1, 3, 32, jnp.float32, 20, [300, 17, 160], 32), 8),      # no power of two
}


@pytest.mark.parametrize("window", [None, 100], ids=["full", "window100"])
@pytest.mark.parametrize("case", list(DERIVED_CASES))
def test_walk_parity_at_the_derived_group(case, window):
    (bs, G, Hkv, D, dtype, W, lens, nb), N = DERIVED_CASES[case]
    assert fa._decode_group_blocks(bs, Hkv, D, dtype, G, W) == N
    q, k_pool, v_pool, tables, lens = _random_paged_case(
        11, B=len(lens), H=G * Hkv, Hkv=Hkv, D=D, bs=bs, nb=nb, W=W, lens=lens)
    for b, n in enumerate(lens):
        if n == 1:  # a padded slot: all-null table, one token
            tables[b, :] = NULL_BLOCK
    bf16 = dtype == jnp.bfloat16
    _assert_parity(q.astype(dtype), k_pool.astype(dtype), v_pool.astype(dtype), tables, lens,
                   tol=2e-2 if bf16 else 2e-6, window=window)


def test_group_comes_from_shapes():
    """``N`` at the three serve configurations' shapes (128 keys a step at
    every head ratio), at a big block, and its clamps."""
    bf16 = jnp.bfloat16
    assert fa._decode_group_blocks(16, 8, 128, bf16, 4, 144) == 8     # mistral-7b.chat-sat
    assert fa._decode_group_blocks(16, 8, 128, bf16, 4, 48) == 8      # mistral-7b.chat-r80
    assert fa._decode_group_blocks(16, 8, 128, bf16, 16, 400) == 8    # command-a-plus.rag-sat
    assert fa._decode_group_blocks(16, 4, 128, bf16, 8, 1056) == 8    # mellum2-12b.code-sat
    assert fa._decode_group_blocks(16, 8, 128, jnp.float32, 16, 400) == 8
    assert fa._decode_group_blocks(16, 8, 128, bf16, 4, 2) == 2       # never over W
    assert fa._decode_group_blocks(128, 8, 64, bf16, 2, 8) == 1       # a block of 128 keys
    assert fa._decode_group_blocks(512, 8, 128, bf16, 4, 8) == 1      # never under 1
    assert fa._decode_group_blocks(4, 8, 128, bf16, 4, 64) == 32      # small blocks: 128 keys
    assert fa._decode_group_blocks(16, 32, 256, jnp.float32, 4, 64) == 1  # the bytes bind: 3 MB a block
    assert fa._decode_group_blocks(16, 16, 128, jnp.float32, 16, 64) == 4
    assert fa._decode_group_blocks(16, 8, 64, bf16, 2, 32) == \
        fa._decode_group_blocks(16, 8, 128, bf16, 2, 32)               # D pads to a lane tile


@pytest.mark.parametrize("N", [2, 3, 8])
def test_dead_table_entries_are_skipped_not_masked(group_of, N):
    """Masking multiplies a dead block's values by zero, and 0 * NaN is NaN;
    skipping never reads them. With every pool block a row does not own set to
    NaN (the null block too, but for the padded slot that owns it), the row's
    output is finite and what the clean pool gives."""
    group_of(N)
    lens = [22, 5, 1, 32]  # 6, 2, 1 (padded slot) and all 8 of 8 entries live
    q, k_pool, v_pool, tables, lens = _random_paged_case(
        12, B=4, H=4, Hkv=2, D=16, bs=4, nb=24, W=8, lens=lens)
    tables[2, :] = NULL_BLOCK
    args = (jnp.asarray(q), jnp.asarray(tables), jnp.asarray(lens))
    ref = gather_ref(args[0], jnp.asarray(k_pool), jnp.asarray(v_pool), args[1],
                     jnp.asarray(lens - 1)[:, None])
    for b, n in enumerate(lens):
        own = tables[b, : -(-int(n) // 4)]
        k_nan, v_nan = np.full_like(k_pool, np.nan), np.full_like(v_pool, np.nan)
        k_nan[own], v_nan[own] = k_pool[own], v_pool[own]
        out = paged_attention_decode(
            args[0], jnp.asarray(k_nan), jnp.asarray(v_nan), args[1], args[2], interpret=True)
        assert bool(jnp.all(jnp.isfinite(out[b]))), f"row {b} read a block it does not own"
        assert float(jnp.max(jnp.abs(out[b] - ref[b]))) <= 1e-6


# ---------------------------------------------------------------------------
# dispatch + kill switch


def test_paged_kernel_mode_parsing(monkeypatch):
    monkeypatch.delenv("ACCELERATE_PAGED_KERNEL", raising=False)
    assert paged_kernel_mode() == "on"
    # "0" was the kill switch's spelling: no value selects the gather path on a TPU now
    for raw, want in [("1", "on"), ("0", "on"), ("interpret", "interpret")]:
        monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", raw)
        assert paged_kernel_mode() == want


def test_kill_switch_path_is_byte_identical_to_reference(monkeypatch):
    """Off the TPU the default path (the variable unset) is the gather
    reference — byte-identical output, the pre-kernel engine exactly."""
    q, k_pool, v_pool, tables, lens = _random_paged_case(
        5, B=2, H=4, Hkv=2, D=16, bs=4, nb=8, W=3, lens=[9, 6]
    )
    args = (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(lens - 1)[:, None])
    monkeypatch.delenv("ACCELERATE_PAGED_KERNEL", raising=False)
    out = dispatch_paged(*args)
    ref = gather_ref(*args)
    assert np.array_equal(np.asarray(out, np.float32), np.asarray(ref, np.float32))


def test_prefill_shapes_dispatch_to_the_prefill_kernel(monkeypatch):
    """S > 1 (chunked prefill / k-verify) now routes to the Pallas
    chunked-prefill kernel under the same mode contract as decode (ISSUE 18
    extended the kernel family past S=1; ``tests/test_prefill_kernel.py``
    owns its parity matrix) — and still matches the gather reference."""
    monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "interpret")
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((1, 3, 4, 16)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((8, 4, 2, 16)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((8, 4, 2, 16)), jnp.float32)
    tables = jnp.asarray([[3, 5, 1]], jnp.int32)
    qpos = jnp.asarray([[8, 9, 10]], jnp.int32)
    calls = []
    real_prefill = fa.paged_attention_prefill

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret", False))
        return real_prefill(*args, **kwargs)

    monkeypatch.setattr(fa, "paged_attention_prefill", spy)
    out = fa.paged_attention(q, k_pool, v_pool, tables, qpos)
    ref = gather_ref(q, k_pool, v_pool, tables, qpos)
    assert calls == [True]  # S>1 hit the prefill kernel, interpreter mode
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-6


def test_tpu_backend_dispatches_the_kernel(monkeypatch):
    """On a TPU backend with the default mode, S=1 decode must route to the
    Pallas kernel (compiled, not interpreted) — asserted by stubbing the
    kernel entry point, since CI has no TPU to compile for."""
    calls = []

    def fake_decode(q, k_pool, v_pool, tables, lens, scale=None, *, interpret=False):
        calls.append(interpret)
        return jnp.zeros_like(q)

    monkeypatch.setattr(fa, "paged_attention_decode", fake_decode)
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("ACCELERATE_PAGED_KERNEL", raising=False)
    q = jnp.zeros((1, 1, 4, 16))
    fa.paged_attention(
        q, jnp.zeros((4, 4, 2, 16)), jnp.zeros((4, 4, 2, 16)),
        jnp.zeros((1, 2), jnp.int32), jnp.asarray([[3]], jnp.int32),
    )
    assert calls == [False]  # kernel path, compiled (not interpret) mode


def test_engine_through_interpreted_kernel_matches_reference(monkeypatch):
    """The whole serving engine with decode dispatched through the Pallas
    kernel (interpreter mode) must still match the single-stream greedy
    reference token-for-token — the CPU stand-in for the TPU dispatch
    acceptance line. f32 end to end: the kernel keeps softmax probabilities
    in f32 where the reference rounds them through the cache dtype, so at
    bf16 a near-tie argmax can legitimately flip (the bf16 tolerance test
    above owns that envelope) — at f32 the paths agree to ~1e-7 and greedy
    token streams are identical."""
    monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "interpret")
    params = init_llama(CONFIG, jax.random.PRNGKey(0))
    engine = ServingEngine(
        params, CONFIG, num_blocks=33, block_size=8, max_slots=4,
        cache_dtype=jnp.float32,
        lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(4,),
                              prefill_buckets=(32,)),
    )
    engine.warmup()
    rng = np.random.default_rng(7)
    specs = [(5, 7), (13, 11), (21, 5)]
    prompts = [rng.integers(0, CONFIG.vocab_size, (s,)).astype(np.int32)
               for s, _ in specs]
    reqs = [engine.submit(p, n, rng_seed=i)
            for i, (p, (_, n)) in enumerate(zip(prompts, specs))]
    engine.run()
    for i, ((_, n), req) in enumerate(zip(specs, reqs)):
        ref = greedy_generate(params, prompts[i][None], CONFIG, max_new_tokens=n)
        assert np.array_equal(np.asarray(ref[0]), req.output_ids()), f"request {i}"


def test_kernel_rejects_multi_token_queries():
    with pytest.raises(ValueError, match="S=1"):
        paged_attention_decode(
            jnp.zeros((1, 2, 4, 16)), jnp.zeros((4, 4, 2, 16)),
            jnp.zeros((4, 4, 2, 16)), jnp.zeros((1, 2), jnp.int32),
            jnp.asarray([5]), interpret=True,
        )
