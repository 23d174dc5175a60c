"""The paged pool's one owner (``ops.flash_attention.paged_write_attend``) and
the two layer loops that feed it, on the CPU: a layer writes into the WHOLE
stack ``[L, num_blocks, block_size, Hkv, D]`` at block ``layer * num_blocks +
table entry`` of its flat view, and what lands there is, layer by layer and
bit for bit, what a write into the layer's own slice gives (the form the
forwards had before ISSUE 32, restated here with ``paged_attention_gather``
as the attention). Whether a step program then holds the pool once is a
compile-time fact of the chip's compiler: ``tests/test_tpu_compile.py``."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import LlamaConfig, init_llama
from accelerate_tpu.models import cohere2_moe as cm
from accelerate_tpu.models.transformer import (
    draft_config,
    draft_params,
    llama_layer,
    llama_rope,
)

fa = importlib.import_module("accelerate_tpu.ops.flash_attention")
NULL = fa.NULL_BLOCK

BLOCK, BLOCKS = 4, 8
KINDS = ["llama-unrolled", "llama-scanned", "cohere2_moe"]


def _model(kind):
    if kind == "cohere2_moe":
        cfg = cm.Cohere2MoeConfig(
            vocab_size=128, dim=64, n_layers=4, n_heads=8, n_kv_heads=2, head_dim=16,
            expert_dim=64, num_experts=16, experts_per_token=4, num_shared_experts=2,
            sliding_window=8, max_seq_len=64)
        return cfg, cm.init_cohere2_moe(cfg, jax.random.PRNGKey(0))
    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=3, n_heads=4, n_kv_heads=2,
                      max_seq_len=64, unroll_layers=kind == "llama-unrolled")
    return cfg, init_llama(cfg, jax.random.PRNGKey(0))


def _slice_write_attend(q, k, v, k_layer, v_layer, tables, positions, window=None):
    """A layer's write into ITS OWN slice ``[num_blocks, block_size, Hkv, D]``,
    then the gather twin over it: the reference semantics."""
    W = tables.shape[1]
    logical = positions // BLOCK
    phys = jnp.take_along_axis(tables, jnp.minimum(logical, W - 1), axis=1)
    phys = jnp.where(logical < W, phys, NULL)
    k_layer = k_layer.at[phys, positions % BLOCK].set(k.astype(k_layer.dtype))
    v_layer = v_layer.at[phys, positions % BLOCK].set(v.astype(v_layer.dtype))
    attn = fa.paged_attention_gather(q, k_layer, v_layer, tables, positions, window=window)
    return attn, k_layer, v_layer


def _per_layer_forward(cfg, params, ids, pool, tables, positions, valid):
    """The model's layers in a Python loop, each against its slice of the
    pool, the slices stacked again: what both paged forwards were."""
    h = params["embed_tokens"]["embedding"][ids]
    k_new, v_new = [], []
    for layer in range(cfg.n_layers):
        k_layer, v_layer = pool["k"][layer], pool["v"][layer]

        def attend(q, k, v, window=None):
            nonlocal k_layer, v_layer
            attn, k_layer, v_layer = _slice_write_attend(
                q, k, v, k_layer, v_layer, tables, positions, window)
            return attn

        if isinstance(cfg, LlamaConfig):
            layer_params = jax.tree_util.tree_map(lambda x: x[layer], params["layers"])
            h, _ = llama_layer(layer_params, h, positions, *llama_rope(cfg), cfg, attend)
        else:
            h, _ = cm._layer(params["layers"][layer], h, positions, valid, cfg, layer, attend)
        k_new.append(k_layer)
        v_new.append(v_layer)
    # a draft's pool is deeper than the draft: the layers behind it pass through
    return {"k": jnp.concatenate([jnp.stack(k_new), pool["k"][cfg.n_layers:]]),
            "v": jnp.concatenate([jnp.stack(v_new), pool["v"][cfg.n_layers:]])}


def _seeded_pool(cfg):
    """A pool that is nowhere zero, so that a write to the wrong place shows
    whatever it writes."""
    shape = (cfg.n_layers, BLOCKS, BLOCK, cfg.n_kv_heads, cfg.head_dim)
    k, v = jax.random.split(jax.random.PRNGKey(7))
    return {"k": jax.random.normal(k, shape) + 3.0, "v": jax.random.normal(v, shape) - 3.0}


def _decode_step(cfg):
    """Three slots, the middle one idle (its table all null): one token each at
    positions 9 and 5, the table two blocks wider than either needs."""
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (3, 1)), jnp.int32)
    tables = jnp.asarray([[5, 2, 7, NULL, NULL], [NULL] * 5, [1, 6, NULL, NULL, NULL]], jnp.int32)
    positions = jnp.asarray([[9], [0], [5]], jnp.int32)
    valid = tables[:, :1] != NULL
    return ids, tables, positions, valid


def _padded_chunk(cfg):
    """One row: a chunk of 8 at positions 4-11 whose last 3 tokens are padding,
    behind 4 cached positions. On a table of 3 blocks (positions 0-11) the
    padding lies inside the table; on one of 2 its tail (8-11) lies past it."""
    rng = np.random.default_rng(4)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 8)), jnp.int32)
    positions = 4 + jnp.arange(8, dtype=jnp.int32)[None]
    valid = jnp.arange(8)[None] < 5
    return ids, positions, valid


@pytest.mark.parametrize("step", ["decode", "chunk", "chunk-past-the-table"])
@pytest.mark.parametrize("kind", KINDS)
def test_the_stack_holds_what_a_write_into_the_layers_own_slice_gives(kind, step):
    cfg, params = _model(kind)
    if step == "decode":
        ids, tables, positions, valid = _decode_step(cfg)
    else:
        ids, positions, valid = _padded_chunk(cfg)
        tables = jnp.asarray([[3, 6, 1]] if step == "chunk" else [[3, 6]], jnp.int32)
    pool = _seeded_pool(cfg)
    _, got, _ = jax.jit(cfg.paged_forward, static_argnames="block_size")(
        params, ids, pool, tables, positions, valid, block_size=BLOCK)
    want = jax.jit(_per_layer_forward, static_argnums=0)(
        cfg, params, ids, pool, tables, positions, valid)
    assert got["k"].shape == pool["k"].shape  # the boundary's format is the stack's
    for side in ("k", "v"):
        for layer in range(cfg.n_layers):
            np.testing.assert_array_equal(
                np.asarray(got[side][layer]), np.asarray(want[side][layer]),
                err_msg=f"{side} of layer {layer}")


@pytest.mark.parametrize("kind", KINDS)
def test_a_pad_write_and_an_idle_slot_land_in_their_own_layers_null_block(kind):
    """A position past the table (the padded tail of a prefill chunk: 6
    positions, a table of one block of 4) and every position of an idle slot
    (its table all null) go to the null block of THE LAYER that writes: every
    layer's null block is written, its live block holds what a forward of the
    4 real tokens alone leaves there, and no other block of any layer is
    touched."""
    cfg, params = _model(kind)
    live, real = 3, 4
    ids = jnp.asarray(np.random.default_rng(2).integers(1, 128, (2, 6)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(6)[None], (2, 6))
    tables = jnp.asarray([[live], [NULL]], jnp.int32)
    valid = jnp.asarray([[True] * real + [False] * 2, [False] * 6])
    empty = fa.init_block_pool(cfg, BLOCKS, BLOCK, jnp.float32)
    _, pool, _ = cfg.paged_forward(params, ids, empty, tables, positions, valid, BLOCK)
    _, clean, _ = cfg.paged_forward(
        params, ids[:1, :real], empty, tables[:1], positions[:1, :real], valid[:1, :real], BLOCK)
    untouched = [b for b in range(BLOCKS) if b not in (NULL, live)]
    for side in ("k", "v"):
        got, want = np.asarray(pool[side]), np.asarray(clean[side])
        np.testing.assert_allclose(got[:, live], want[:, live], rtol=1e-5, atol=1e-6)
        assert np.abs(want[:, live]).min() > 0  # all four slots of the live block written
        for layer in range(cfg.n_layers):
            assert np.abs(got[layer, NULL]).max() > 0, f"layer {layer}'s pads went elsewhere"
        assert not got[:, untouched].any() and not want[:, NULL].any()


@pytest.mark.parametrize("layer_as", ["python-int", "traced"])
def test_the_owner_writes_one_layer_of_the_stack_and_attends_over_it(layer_as):
    """``paged_write_attend`` alone, with the layer a Python int (the routed
    decoder's loop) or a traced scalar (the llama scan): layer 2 of 4 holds
    what the write into a slice gives, the attention is the slice's, and the
    other three layers are bit for bit what they were. With a window too."""
    L, H, Hkv, D = 4, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    k_pool = jax.random.normal(keys[0], (L, BLOCKS, BLOCK, Hkv, D))
    v_pool = jax.random.normal(keys[1], (L, BLOCKS, BLOCK, Hkv, D))
    q = jax.random.normal(keys[2], (2, 3, H, D))
    k = jax.random.normal(keys[3], (2, 3, Hkv, D))
    v = jax.random.normal(keys[4], (2, 3, Hkv, D))
    tables = jnp.asarray([[4, 1, NULL], [NULL, NULL, NULL]], jnp.int32)
    positions = jnp.asarray([[5, 6, 7], [0, 1, 2]], jnp.int32)
    for window in (None, 3):
        run = lambda layer: fa.paged_write_attend(
            q, k, v, k_pool, v_pool, layer, tables, positions, BLOCK, window)
        attn, k_got, v_got = run(2) if layer_as == "python-int" else jax.jit(run)(jnp.int32(2))
        want_attn, k_want, v_want = _slice_write_attend(
            q, k, v, k_pool[2], v_pool[2], tables, positions, window)
        np.testing.assert_allclose(np.asarray(attn[0]), np.asarray(want_attn[0]), rtol=1e-6, atol=1e-6)
        for got, want, before in ((k_got, k_want, k_pool), (v_got, v_want, v_pool)):
            np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want))
            others = [0, 1, 3]
            np.testing.assert_array_equal(np.asarray(got)[others], np.asarray(before)[others])


@pytest.mark.parametrize("unroll_layers", [True, False], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("n_draft", [1, 2])
def test_a_draft_of_n_layers_leaves_the_deeper_layers_of_the_pool_untouched(n_draft, unroll_layers):
    """Speculative decoding's draft is the model's first ``n_draft`` layers
    over the WHOLE pool: those layers hold what the full model's own first
    layers write there, and every layer from ``n_draft`` on is bit for bit
    what it was (null block and all)."""
    cfg, params = _model("llama-unrolled" if unroll_layers else "llama-scanned")
    ids, tables, positions, valid = _decode_step(cfg)
    pool = _seeded_pool(cfg)
    dcfg = draft_config(cfg, n_draft)
    _, got, _ = jax.jit(dcfg.paged_forward, static_argnames="block_size")(
        draft_params(params, n_draft), ids, pool, tables, positions, valid, block_size=BLOCK)
    want = jax.jit(_per_layer_forward, static_argnums=0)(
        dcfg, draft_params(params, n_draft), ids, pool, tables, positions, valid)
    for side in ("k", "v"):
        assert got[side].shape == pool[side].shape
        np.testing.assert_array_equal(
            np.asarray(got[side][n_draft:]), np.asarray(pool[side][n_draft:]))
        np.testing.assert_array_equal(np.asarray(got[side]), np.asarray(want[side]))
        assert not np.array_equal(np.asarray(got[side][:n_draft]), np.asarray(pool[side][:n_draft]))
