"""The kernels the TPU dispatch can reach, handed to the chip's own compiler.

Interpret mode runs a kernel's body on the CPU and accepts what Mosaic, the
TPU's kernel compiler, refuses: a block shape off the (8, 128) tiling, a
vector read out of SMEM, more scoped VMEM than a kernel may hold. PR 21 found
three of the four kernel families refused that way while every interpret-mode
test passed. These tests compile each kernel, at real widths, for a v5e that
is DESCRIBED (``v5e:2x2``), not attached: nothing runs, so they say nothing
about results or times — only that the chip's compiler takes the program and
that a Mosaic kernel (``tpu_custom_call``) is in it.

The topology is described inside a fixture, never at import, in a ``skipif``
or in a ``parametrize`` argument: only one process may load the TPU's library,
and under pytest-xdist every worker imports every test file. Keep these tests
in this ONE file, for the same reason.
"""

import importlib
import os
import re

import pytest

import jax
import jax.numpy as jnp

# the module, not the function ``accelerate_tpu.ops`` re-exports under its name
fa = importlib.import_module("accelerate_tpu.ops.flash_attention")

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one described v5e chip; JAX's persistent compilation cache
    is off while the module runs (an entry compiled for a described chip is
    written but cannot be read back without one: the next run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# (B, S, H, Hkv, D, causal, window, segments): the widths of PR 21's step A
FLASH_CASES = {
    "dense-mha12-d64-s512": (4, 512, 12, 12, 64, False, None, False),
    "causal-gqa32x8-d128-s2048": (2, 2048, 32, 8, 128, True, None, False),
    "window+segments-mha16-d128-s4096": (1, 4096, 16, 16, 128, True, 1024, True),
}


@pytest.mark.parametrize("direction", ["fwd", "fwd+bwd"])
@pytest.mark.parametrize("case", list(FLASH_CASES))
def test_flash_attention_compiles_for_v5e(one_chip, case, direction):
    B, S, H, Hkv, D, causal, window, segments = FLASH_CASES[case]

    def fwd(q, k, v, seg):
        return fa._flash_kernel(
            q, k, v, seg if segments else None,
            causal=causal, sm_scale=D ** -0.5, window=window,
        )

    def fwd_bwd(q, k, v, seg):
        loss = lambda q, k, v: fwd(q, k, v, seg).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    _compile(
        fwd if direction == "fwd" else fwd_bwd, one_chip,
        ((B, S, H, D), BF16), ((B, S, Hkv, D), BF16), ((B, S, Hkv, D), BF16),
        ((B, S), jnp.int32),
    )


# (B, H, Hkv, D, block_size, W, window, blocks in the pool)
PAGED_DECODE_CASES = {
    "b8-16x8-d64-bs16": (8, 16, 8, 64, 16, 32, None, 512),
    "b8-32x8-d128-bs16": (8, 32, 8, 128, 16, 64, None, 512),
    "b4-16x8-d64-bs128": (4, 16, 8, 64, 128, 8, None, 512),
    # the serve cells' own decode shapes: chat-sat's and chat-r80's buckets, then
    # code-sat's (rag-sat's are further down, beside its prefill chunks)
    "b64-32x8-d128-bs16-w144": (64, 32, 8, 128, 16, 144, None, 3201),
    "b32-32x8-d128-bs16-w48": (32, 32, 8, 128, 16, 48, None, 3201),
    "b32-32x4-d128-bs16-w1056": (32, 32, 4, 128, 16, 1056, None, 14401),
    "b32-32x4-d128-bs16-w1056-window1024": (32, 32, 4, 128, 16, 1056, 1024, 14401),
}


@pytest.mark.parametrize("case", list(PAGED_DECODE_CASES))
def test_paged_decode_compiles_for_v5e(one_chip, case):
    B, H, Hkv, D, block_size, W, window, num_blocks = PAGED_DECODE_CASES[case]
    pool = ((num_blocks, block_size, Hkv, D), BF16)
    text = _compile(
        lambda q, k, v, t, n: fa.paged_attention_decode(q, k, v, t, n, window=window), one_chip,
        ((B, 1, H, D), BF16), pool, pool, ((B, W), jnp.int32), ((B,), jnp.int32),
    )
    assert ("paged_decode_win" in text) == (window is not None) and "paged_decode" in text


@pytest.mark.parametrize("block_size", [16, 128])
@pytest.mark.parametrize("chunk", [128, 512])
def test_paged_prefill_compiles_for_v5e(one_chip, chunk, block_size):
    B, H, Hkv, D = 1, 16, 8, 64
    pool = ((512, block_size, Hkv, D), BF16)
    _compile(
        fa.paged_attention_prefill, one_chip,
        ((B, chunk, H, D), BF16), pool, pool,
        ((B, 1024 // block_size), jnp.int32), ((B, chunk), jnp.int32),
    )


# (B, S, H, Hkv, D, block_size, W, window) -> (Sq, N): the serve cells' own prefill
# calls (chat-sat / chat-r80: chunks of 256 and 512 at 32/8 heads, a table of 144;
# rag-sat: 128/8 heads, a table of 400, full and window layers) and a verify step
PREFILL_CELL_CASES = {
    "mistral-chunk256": ((1, 256, 32, 8, 128, 16, 144, None), (128, 8)),
    "mistral-chunk512": ((1, 512, 32, 8, 128, 16, 144, None), (128, 8)),
    "command-a-chunk512-full": ((1, 512, 128, 8, 128, 16, 400, None), (32, 8)),
    "command-a-chunk256-window": ((1, 256, 128, 8, 128, 16, 400, 4096), (32, 8)),
    "verify-b64-s4": ((64, 4, 32, 8, 128, 16, 144, None), (4, 8)),
}


@pytest.mark.parametrize("case", list(PREFILL_CELL_CASES))
def test_paged_prefill_compiles_at_the_cells_shapes(one_chip, case):
    (B, S, H, Hkv, D, bs, W, window), tiling = PREFILL_CELL_CASES[case]
    assert fa.prefill_tiling(S, H, Hkv, D, bs, BF16, W) == tiling
    pool = ((3201, bs, Hkv, D), BF16)
    text = _compile(
        lambda q, k, v, t, p: fa.paged_attention_prefill(q, k, v, t, p, window=window),
        one_chip, ((B, S, H, D), BF16), pool, pool, ((B, W), jnp.int32), ((B, S), jnp.int32),
    )
    assert "paged_prefill" + ("_win" if window else "") in text


def test_prefill_query_tile_stays_inside_scoped_vmem():
    """A program holds its query tile for all heads (q and o tiles double
    buffered, acc in f32) beside N blocks of K and V: the tile of
    ``_PREFILL_TILE_ROWS`` rows over all heads and the N of
    ``_PREFILL_VMEM_BYTES`` are what keep it inside the v5e's 16 MB of scoped
    VMEM (the compiles above). A key head's G query heads times the tile are
    the width of its matmuls: 512 at both serve configurations."""
    assert fa._prefill_query_tile(128, 16, 64) == 128
    assert fa._prefill_query_tile(512, 16, 64) == 256
    assert fa._prefill_query_tile(512, 32, 128) == 128   # mistral-7b: 4 x 128 a key head
    assert fa._prefill_query_tile(512, 128, 128) == 32   # command-a-plus: 16 x 32
    assert fa._prefill_query_tile(4, 32, 128) == 4  # a verify step's k+1 tokens
    with pytest.raises(ValueError, match="cannot tile S=4099"):
        fa._prefill_query_tile(4099, 16, 64)  # prime: no multiple of 8 divides it
    # N: 128 keys a step at blocks of 16, one block of 128, never over the table
    assert fa._prefill_group_blocks(16, 8, 128, BF16, 144) == 8
    assert fa._prefill_group_blocks(16, 8, 128, BF16, 4) == 4
    assert fa._prefill_group_blocks(128, 8, 64, BF16, 8) == 1
    assert fa._prefill_group_blocks(16, 8, 64, BF16, 64) == fa._prefill_group_blocks(16, 8, 128, BF16, 64)


# ---- the window kernels and the grouped matmul, at the widths of the routed
# decoder's cell (128 query heads on 8 key heads of 128, blocks of 16, a table
# of 400; experts of 4096 x 4096): its decode batch and its prefill chunks


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window4096"])
def test_paged_kernels_compile_at_128_query_heads_with_and_without_a_window(one_chip, window):
    H, Hkv, D, bs, W = 128, 8, 128, 16, 400
    pool = ((6401, bs, Hkv, D), BF16)
    name = "paged_decode" + ("_win" if window else "")
    text = _compile(
        lambda q, k, v, t, n: fa.paged_attention_decode(q, k, v, t, n, window=window), one_chip,
        ((32, 1, H, D), BF16), pool, pool, ((32, W), jnp.int32), ((32,), jnp.int32),
    )
    assert name in text and (window is not None or "paged_decode_win" not in text)
    for chunk in (256, 512):
        text = _compile(
            lambda q, k, v, t, p: fa.paged_attention_prefill(q, k, v, t, p, window=window),
            one_chip, ((1, chunk, H, D), BF16), pool, pool, ((1, W), jnp.int32),
            ((1, chunk), jnp.int32),
        )
        assert "paged_prefill" + ("_win" if window else "") in text


@pytest.mark.parametrize("rows", [256, 2048, 4096], ids=["decode32x8", "chunk256x8", "chunk512x8"])
def test_grouped_matmul_compiles_for_v5e(one_chip, rows):
    gm = importlib.import_module("accelerate_tpu.ops.grouped_matmul")
    text = _compile(
        gm.grouped_matmul_kernel, one_chip,
        ((rows, 4096), BF16), ((16, 4096, 4096), BF16), ((16,), jnp.int32),
    )
    assert "moe_gmm" in text


@pytest.mark.parametrize("rows", [256, 16384], ids=["decode32x8", "chunk2048x8"])
@pytest.mark.parametrize("K,N", [(2304, 896), (896, 2304)], ids=["gate-up", "down"])
def test_grouped_matmul_compiles_at_64_narrow_experts(one_chip, rows, K, N):
    """mellum2-12b's expert layer: 64 experts of 2304 x 896, whose gate and up
    products fall to 128-column weight tiles (the only multiple of 128 that
    divides 896 and keeps ``[2304, tn]`` under 2 MB) and whose down product
    takes 1152."""
    gm = importlib.import_module("accelerate_tpu.ops.grouped_matmul")
    assert gm._tile_n(K, N, 2) == (128 if N == 896 else 1152)
    text = _compile(
        gm.grouped_matmul_kernel, one_chip,
        ((rows, K), BF16), ((64, K, N), BF16), ((64,), jnp.int32),
    )
    assert "moe_gmm" in text


# ---- the whole step programs' memory: a layer writes into the one pool.
# Decode and prefill-chunk forwards of both model kinds at the serve cells'
# widths and a reduced depth (4 layers; abstract shapes, nothing allocated),
# the pool donated, the kernels dispatched: the program aliases the whole pool
# and its temporaries stay under one layer's K + V. A forward that takes a
# layer's slice out of the stack and stacks the layers back (or feeds the stack
# to ``lax.scan`` as ``xs``) reads more than the whole pool there: the compiler
# copies it, in every program, and the chip then holds it twice.

POOL_LAYERS, POOL_BLOCK = 4, 16


def _llama_cell(unroll_layers):
    from accelerate_tpu.models import LlamaConfig, init_llama

    config = LlamaConfig(  # mistral-7b's widths (benchmarks/chip/configs/mistral-7b.json)
        vocab_size=32768, dim=4096, n_layers=POOL_LAYERS, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq_len=2304, rope_theta=1e6, unroll_layers=unroll_layers)
    return config, init_llama, 3201, 144, 64  # chat-sat's pool, table and slots


def _cohere_cell():
    from accelerate_tpu.models.cohere2_moe import Cohere2MoeConfig, init_cohere2_moe

    config = Cohere2MoeConfig(  # command-a-plus's (benchmarks/chip/configs/command-a-plus.json)
        vocab_size=32768, dim=4096, n_layers=POOL_LAYERS, n_heads=128, n_kv_heads=8,
        head_dim=128, expert_dim=4096, num_experts=128, experts_per_token=8,
        num_shared_experts=4, sliding_window=4096, experts_held=16, max_seq_len=6400)
    return config, init_cohere2_moe, 6401, 400, 32  # rag-sat's


def _mellum_cell():
    from accelerate_tpu.models.mellum import MellumConfig, init_mellum

    config = MellumConfig(  # mellum2-12b's (benchmarks/chip/configs/mellum2-12b.json), one period
        vocab_size=98304, dim=2304, n_layers=POOL_LAYERS, n_heads=32, n_kv_heads=4, head_dim=128,
        expert_dim=896, num_experts=64, experts_per_token=8, sliding_window=1024,
        max_seq_len=16896, yarn=(("factor", 16), ("original_max_seq", 8192)))
    return config, init_mellum, 14401, 1056, 32  # code-sat's


# kind -> () -> (config, init, blocks in the pool, the table's width, decode rows)
POOL_KINDS = {
    "llama-unrolled": lambda: _llama_cell(True),
    "llama-scanned": lambda: _llama_cell(False),
    "cohere2_moe": _cohere_cell,  # its layers are a Python loop
    "mellum": _mellum_cell,       # likewise, through `llama_layer`
}


def _compiled_once(build):
    """``build(one_chip, monkeypatch, *key)`` once a key: the pool's test and
    the weights' test read one compiled program (what :func:`_program_facts`
    keeps of it, not the executable)."""
    programs = {}

    def cached(one_chip, monkeypatch, *key):
        if key not in programs:
            programs[key] = build(one_chip, monkeypatch, *key)
        return programs[key]
    return cached


def _program_facts(compiled, params, pool, wq_kernel, logits_rows, vocab_size):
    from accelerate_tpu.telemetry.memory import compiled_memory_analysis

    return {
        "memory": compiled_memory_analysis(compiled), "params": params, "pool": pool,
        "entry": compiled.as_text().split("\nENTRY ")[1],  # the entry computation's text
        "wq_bytes": wq_kernel.shape[-2] * wq_kernel.shape[-1] * 2,  # one layer's, bf16
        "logits_bytes": logits_rows * vocab_size * 2,  # every row the program computes has them
    }


@_compiled_once
def _pool_step_program(one_chip, monkeypatch, kind, program):
    """The decode or prefill-chunk program of ``POOL_KINDS[kind]``, compiled
    with the pool donated: :func:`_program_facts` of it."""
    config, init, num_blocks, W, rows = POOL_KINDS[kind]()
    B, S = (rows, 1) if program == "decode" else (1, 512)
    # the described chip is not attached: the dispatch asks the backend, so say "tpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def abstract(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, BF16 if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype,
                sharding=one_chip), jax.eval_shape(make))

    params = abstract(lambda: init(config, jax.random.PRNGKey(0)))
    pool = abstract(lambda: fa.init_block_pool(config, num_blocks, POOL_BLOCK, BF16))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, pool, ids, tables, positions):
        logits, pool, _ = config.paged_forward(
            params, ids, pool, tables, positions, jnp.ones(ids.shape, bool), POOL_BLOCK)
        return pool, logits[:, -1].argmax(-1)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, ints(B, S), ints(B, W), ints(B, S)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("paged_decode" if program == "decode" else "paged_prefill") in text
    layers = params["layers"]  # one stacked tree, or a tuple of one tree a layer
    return _program_facts(
        compiled, params, pool, (layers[0] if isinstance(layers, tuple) else layers)["wq"]["kernel"],
        B * S, config.vocab_size)


@pytest.mark.parametrize("program", ["decode", "prefill512"])
@pytest.mark.parametrize("kind", list(POOL_KINDS))
def test_step_program_writes_the_donated_pool_in_place(one_chip, monkeypatch, kind, program):
    facts = _pool_step_program(one_chip, monkeypatch, kind, program)
    memory, pool = facts["memory"], facts["pool"]
    layer_kv = 2 * pool["k"].size // POOL_LAYERS * 2  # one layer's K + V, bf16
    assert memory["alias_bytes"] == POOL_LAYERS * layer_kv, memory
    assert memory["temp_bytes"] < layer_kv, (memory, layer_kv)


# ---- and no step program copies a weight. The layout that the reshape to
# heads and the rotary turn prefer used to travel back through the q/k/v dots,
# and the compiler met it by copying `wq` / `wk` / `wv` of every layer into the
# transposed layout `{0,1}`: 105 MB of temporaries at these four llama layers
# (494 MB at the cell's sixteen), 275 MB for `cohere2_moe`, under the bound
# above. The projections' outputs are pinned (`transformer.pin_qkv`), so the
# temporaries stay under ONE layer's `wq` kernel beside the program's own
# logits (every row of a chunk has them: 101 MB at `mellum`'s 98 304-wide
# head) and the entry computation holds no two-dimensional bf16 result of
# 4 MB or more in that layout. Where the compiler stages a weight through
# fast memory on its way (`mellum`, the hybrid) the copy is in no temporary
# and the layout alone shows it.

_TRANSPOSED_2D = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = bf16\[(\d+),(\d+)\]\{0,1[^}]*\} ", re.M)


def _assert_no_weight_is_copied(facts):
    bound = facts["wq_bytes"] + facts["logits_bytes"]
    assert facts["memory"]["temp_bytes"] < bound, (facts["memory"], bound)
    large = [m.group(0).strip() for m in _TRANSPOSED_2D.finditer(facts["entry"])
             if int(m.group(1)) * int(m.group(2)) * 2 >= 4 << 20]
    assert not large, large


@pytest.mark.parametrize("program", ["decode", "prefill512"])
@pytest.mark.parametrize("kind", list(POOL_KINDS))
def test_step_program_copies_no_projection_weight(one_chip, monkeypatch, kind, program):
    _assert_no_weight_is_copied(_pool_step_program(one_chip, monkeypatch, kind, program))


@_compiled_once
def _hybrid_step_program(one_chip, monkeypatch, program):
    """The hybrid model's step programs at ``lfm2-24b.agent-sat``'s widths and
    a reduced depth (conv + dense, attention + routed, conv + routed twice):
    K/V for the one attention layer only, two key heads of 64 to a 128-lane
    row, and 2 rows of state a sequence a conv layer; both donated:
    :func:`_program_facts` of the compiled program."""
    from accelerate_tpu.models.lfm2 import Lfm2Config, init_lfm2

    config = Lfm2Config(  # lfm2-24b's (benchmarks/chip/configs/lfm2-24b.json)
        vocab_size=65536, dim=2048, n_layers=4, n_heads=32, n_kv_heads=8,
        layer_types=("conv", "full_attention", "conv", "conv"), num_dense_layers=1,
        dense_dim=11776, expert_dim=1536, num_experts=64, experts_per_token=4,
        max_seq_len=4096)
    assert fa.kv_lane_pack(config.n_kv_heads, config.head_dim) == 2  # two heads of 64 to a row
    num_blocks, W, rows = 32769, 256, 128  # agent-sat's
    B, S = (rows, 1) if program == "decode" else (1, 512)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def abstract(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, BF16 if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype,
                sharding=one_chip), jax.eval_shape(make))

    params = abstract(lambda: init_lfm2(config, jax.random.PRNGKey(0)))
    pool = abstract(lambda: fa.init_block_pool(
        config, num_blocks, POOL_BLOCK, BF16, state_rows=rows + 1))
    assert pool["k"].shape == (1, num_blocks, POOL_BLOCK, 4, 128)
    assert pool["state"].shape == (3, rows + 1, 2, 2048)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, pool, ids, tables, positions, state_rows):
        logits, pool, counts = config.paged_forward(
            params, ids, pool, tables, positions, jnp.ones(ids.shape, bool), state_rows, POOL_BLOCK)
        return pool, (logits[:, -1].argmax(-1), counts)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, ints(B, S), ints(B, W), ints(B, S), ints(B)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "moe_gmm" in text
    assert ("paged_decode" if program == "decode" else "paged_prefill") in text
    return _program_facts(
        compiled, params, pool, params["layers"][1]["wq"]["kernel"], B * S, config.vocab_size)


@pytest.mark.parametrize("program", ["decode", "prefill512"])
def test_hybrid_step_program_writes_pool_and_state_in_place_with_heads_packed(
        one_chip, monkeypatch, program):
    """Both aliased whole, the temporaries under the attention layer's K + V.
    With the heads apart (rows of 64 lanes) the chip's compiler copies the
    whole pool into the kernels' padded layout and back in every program (PR
    35: 3 GB of temporaries for a 2 GB pool), which is what packing them is
    for."""
    facts = _hybrid_step_program(one_chip, monkeypatch, program)
    memory, pool = facts["memory"], facts["pool"]
    layer_kv = 2 * pool["k"].size * 2  # the one attention layer's K + V, bf16
    assert memory["alias_bytes"] == layer_kv + pool["state"].size * 2, memory
    assert memory["temp_bytes"] < layer_kv, (memory, layer_kv)


@pytest.mark.parametrize("program", ["decode", "prefill512"])
def test_hybrid_step_program_copies_no_projection_weight(one_chip, monkeypatch, program):
    _assert_no_weight_is_copied(_hybrid_step_program(one_chip, monkeypatch, program))
