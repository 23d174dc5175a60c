"""Pallas chunked-prefill paged-attention kernel (ISSUE 18): parity matrix.

``ops.flash_attention.paged_attention_prefill`` extends the S=1 decode
kernel (ISSUE 14, ``tests/test_paged_kernel.py``) to S>1 query chunks: same
grid walk over the block table, but each KV block is scored against the
whole chunk with a per-query causal mask ``kv_pos <= q_position``. The XLA
gather path (``serving.kv_pager.paged_attention``) remains the reference
semantics. These tests drive the kernel through the Pallas interpreter on
CPU — identical dataflow, no TPU required — across scrambled block tables,
ragged chunk start offsets, GQA ratios, null-block rows, COW-diverged
tables, and the in-chunk causality boundary, plus the dispatch contract
and the engine end-to-end (multi-chunk prefill + k-token verify both route
through this kernel).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import greedy_generate
from accelerate_tpu.models import LlamaConfig, init_llama
from accelerate_tpu.ops.flash_attention import (
    paged_attention as dispatch_paged,
    paged_attention_prefill,
)
from accelerate_tpu.serving import BucketLattice, ServingEngine
from accelerate_tpu.serving.kv_pager import NULL_BLOCK, paged_attention as gather_ref

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


def _assert_parity(q, k_pool, v_pool, tables, qpos, tol=2e-6):
    # tol is 2x the decode kernel's: S>1 rows reduce over longer contexts
    # (prefix + chunk) so accumulated f32 rounding runs slightly wider
    qj = jnp.asarray(q)
    kj, vj = jnp.asarray(k_pool), jnp.asarray(v_pool)
    tj, pj = jnp.asarray(tables), jnp.asarray(qpos)
    ref = gather_ref(qj, kj, vj, tj, pj)
    out = paged_attention_prefill(qj, kj, vj, tj, pj, interpret=True)
    err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - out.astype(jnp.float32))))
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
    """bf16 pools (the engine's cache dtype): the kernel keeps the whole
    softmax in f32 while the reference rounds probabilities through bf16, so
    agreement is to bf16 resolution, not bitwise."""
    q, k_pool, v_pool, tables, qpos = _random_prefill_case(
        5, B=2, S=6, H=4, Hkv=2, D=32, bs=8, nb=12, W=4, starts=[14, 2]
    )
    _assert_parity(
        q.astype(jnp.bfloat16), k_pool.astype(jnp.bfloat16),
        v_pool.astype(jnp.bfloat16), tables, qpos, tol=2e-2,
    )


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
    """``ACCELERATE_PAGED_KERNEL=0`` routes S>1 straight to the gather
    reference — byte-identical output, the pre-kernel engine exactly."""
    q, k_pool, v_pool, tables, qpos = _random_prefill_case(
        6, B=2, S=3, H=4, Hkv=2, D=16, bs=4, nb=8, W=3, starts=[6, 1]
    )
    args = (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(qpos))
    monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "0")
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
