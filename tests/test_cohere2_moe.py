"""The parallel-block routed decoder (``models/cohere2_moe.py``), the routing
over the experts a chip holds (``parallel/moe.py``), the window predicate of
the paged kernels (``ops/flash_attention.py``) and the engine that serves a
model other than a ``LlamaConfig``, held on the CPU at a small size (hidden 64,
8/2 heads of 16, 16 experts top-4 with 4 held, 2 shared, window 32, blocks of
8, one period of the layer pattern) to the PLAIN reference of the chip
benchmark (``benchmarks/chip/cohere2_moe_reference.py``), which shares no code
with the program. Seeded random float32 weights; logits, not tokens."""

import ast
import collections
import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from accelerate_tpu.models.cohere2_moe import (  # noqa: E402
    Cohere2MoeConfig,
    _layer,
    cohere2_moe_forward,
    init_cohere2_moe,
)
from accelerate_tpu.parallel.moe import held_expert_ffn, init_held_experts  # noqa: E402
from accelerate_tpu.serving import BucketLattice, ServingEngine  # noqa: E402
from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import cohere2_moe_reference as reference  # noqa: E402

fa = importlib.import_module("accelerate_tpu.ops.flash_attention")

WINDOW, BLOCK = 32, 8
SMALL = dict(vocab_size=256, dim=64, n_layers=4, n_heads=8, n_kv_heads=2, head_dim=16,
             expert_dim=64, num_experts=16, experts_per_token=4, num_shared_experts=2,
             sliding_window=WINDOW, max_seq_len=128)
SHAPE = dict(n_heads=8, n_kv_heads=2, eps=1e-5, theta=50000.0, top_k=4)


def _config(**overrides):
    return Cohere2MoeConfig(**{**SMALL, **overrides})


def _reference_logits(params, ids, cfg):
    windows = tuple(cfg.window(layer) for layer in range(cfg.n_layers))
    return np.asarray(reference.logits(params, jnp.asarray(ids), windows=windows,
                                       first_expert=cfg.first_expert, **SHAPE))


@pytest.fixture(params=["xla", "interpret"])
def kernel_mode(request, monkeypatch):
    """The paged kernels and the grouped matmul on their XLA twins, or their
    Pallas bodies through the interpreter."""
    monkeypatch.delenv("ACCELERATE_PAGED_KERNEL", raising=False)  # xla: the default off the TPU
    if request.param == "interpret":
        monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "interpret")
    return request.param


# ------------------------------------------------------------ engine vs reference


def test_engine_prefill_in_chunks_then_decode_agrees_with_the_plain_reference(kernel_mode):
    """Contexts of 3 x the window, prefilled in chunks of at most 32 and then
    decoded through the paged cache: at every generated position the
    reference's logit of the engine's token is the reference's largest (to
    1e-4 of a logit deviation), and the program's own full forward equals the
    reference's logits to 1e-4 of a deviation. The share held: experts 4-7."""
    cfg = _config(experts_held=4, first_expert=4)
    params = init_cohere2_moe(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(params, cfg, num_blocks=65, block_size=BLOCK, max_slots=2,
                           cache_dtype=jnp.float32, lattice=BucketLattice((2,), (16,), (16, 32)))
    rng = np.random.default_rng(0)
    requests = [engine.submit(rng.integers(0, 256, n).astype(np.int32), 12) for n in (100, 9)]
    engine.run()
    for request in requests:
        out, n_prompt = request.output_ids(), request.prompt.size
        logits = _reference_logits(params, out, cfg)
        picked = np.take_along_axis(logits[n_prompt - 1:-1], out[n_prompt:, None], axis=-1)[:, 0]
        margins = (logits[n_prompt - 1:-1].max(-1) - picked) / logits[n_prompt - 1:-1].std(-1)
        assert margins.max() < 1e-4, margins
        program = np.asarray(cohere2_moe_forward(params, jnp.asarray(out)[None], cfg)[0])
        assert np.abs(program - logits).max() < 1e-4 * logits.std()
    assert requests[0].prompt.size + 12 > 3 * WINDOW  # window layers crossed their window


def test_paged_forward_logits_equal_the_references_across_the_window(kernel_mode):
    """The logits themselves: a 96-token context through ``paged_forward`` in
    three chunks, then four single-token steps, against the reference's full
    forward over the same 100 tokens."""
    cfg = _config()
    params = init_cohere2_moe(cfg, jax.random.PRNGKey(1))
    ids = np.random.default_rng(1).integers(0, 256, 100).astype(np.int32)
    pool = {k: jnp.zeros((4, 33, BLOCK, 2, 16), jnp.float32) for k in ("k", "v")}
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]  # 16 blocks of 8: 128 positions
    got = []
    for start, n in ((0, 32), (32, 32), (64, 32), (96, 1), (97, 1), (98, 1), (99, 1)):
        positions = start + jnp.arange(n)[None]
        logits, pool, counts = cfg.paged_forward(
            params, jnp.asarray(ids[start:start + n])[None], pool, table, positions,
            jnp.ones((1, n), bool), block_size=BLOCK)
        got.append(np.asarray(logits[0]))
        assert counts.shape == (4, 3)
    want = _reference_logits(params, ids, cfg)
    assert np.abs(np.concatenate(got) - want).max() < 1e-4 * want.std()


# ------------------------------------------------------------------ the share test


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """One layer, 16 experts over 4 chips of 4: the routed parts the four
    shares give, attention and the shared experts counted once (every chip
    computes them alike), add up to what the uncut reference gives for the
    whole layer. No chip drops a token and none stands in for another."""
    full = _config(n_layers=1)
    lp = init_cohere2_moe(full, jax.random.PRNGKey(2))["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64))
    positions = jnp.arange(48)[None]

    def attend(q, k, v, window):  # plain causal attention inside the window, as the full forward's
        i, j = jnp.arange(48)[:, None], jnp.arange(48)[None, :]
        seen = (j <= i) & (i - j < window)
        k, v = jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2)
        s = jnp.where(seen, jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    routed, alike = [], []
    u = (h - h.mean(-1, keepdims=True)) / jnp.sqrt(h.var(-1, keepdims=True) + 1e-5)
    for chip in range(4):
        share = _config(n_layers=1, experts_held=4, first_expert=4 * chip)
        lp_share = dict(lp, experts={
            "router": lp["experts"]["router"],
            **{w: {"kernel": lp["experts"][w]["kernel"][4 * chip:4 * chip + 4]}
               for w in ("w_gate", "w_up", "w_down")}})
        out, counts = _layer(lp_share, h, positions, None, share, 0, attend)
        part, _ = held_expert_ffn(lp_share["experts"], u, top_k=4, first_expert=4 * chip)
        routed.append(part)
        alike.append(out - part)  # h + attention + shared experts: the same on every chip
    for other in alike[1:]:
        np.testing.assert_allclose(other, alike[0], atol=1e-5)
    whole = reference.layer(h[0], lp, window=WINDOW, first_expert=0, **SHAPE)
    np.testing.assert_allclose((alike[0] + sum(routed))[0], whole, atol=2e-5)
    assert float(jnp.abs(sum(routed)).max()) > 0.1  # the routed part is no rounding error


# --------------------------------------------------------------- routing, counted


def _dense_routed(params, x, top_k, first_expert):
    """The routed part by hand: every token through every held expert, in
    numpy and float64, weighted where chosen."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    x = np.asarray(x, np.float64)
    scores = 1.0 / (1.0 + np.exp(-(x @ p["router"]["kernel"])))
    chosen = np.argsort(-scores, axis=-1, kind="stable")[:, :top_k]
    best = np.take_along_axis(scores, chosen, axis=-1)
    weights = best / best.sum(-1, keepdims=True)
    y, loads = np.zeros_like(x), []
    for e in range(p["w_gate"]["kernel"].shape[0]):
        weight = np.where(chosen == first_expert + e, weights, 0.0).sum(-1)
        gate = x @ p["w_gate"]["kernel"][e]
        y += weight[:, None] * ((gate / (1 + np.exp(-gate)) * (x @ p["w_up"]["kernel"][e]))
                                @ p["w_down"]["kernel"][e])
        loads.append(int((chosen == first_expert + e).sum()))
    return y, loads


@pytest.mark.parametrize("case", ["seeded", "every-token-to-one-expert", "none-lands-here",
                                  "padding-is-routed-nowhere"])
def test_no_token_is_dropped_and_the_counts_are_the_hand_count(case, kernel_mode):
    """``held_expert_ffn`` against the dense hand computation and its counts
    (``local_pairs``, ``experts_hit``, ``max_expert_load``) against a hand
    count, also under a skew no capacity factor would survive: a router that
    sends every token's first choice to expert 5 of the four held (4-7)."""
    params = init_held_experts(jax.random.PRNGKey(4), 64, 32, 16, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    router = np.asarray(params["router"]["kernel"]).copy()
    valid = None
    if case == "every-token-to-one-expert":
        router[:] = 0.0
        x = x.at[:, 0].set(3.0)  # a feature every token has: expert 5 first, then 4, 6, 7
        router[0, [5, 4, 6, 7]] = [4.0, 3.0, 2.0, 1.0]
    elif case == "none-lands-here":
        router[:] = 0.0
        x = x.at[:, 0].set(3.0)
        router[0, [0, 1, 2, 3]] = [4.0, 3.0, 2.0, 1.0]
    elif case == "padding-is-routed-nowhere":
        valid = jnp.arange(40) < 25
    params["router"]["kernel"] = jnp.asarray(router)
    y, counts = held_expert_ffn(params, x, top_k=4, first_expert=4, valid=valid)
    n_real = 25 if valid is not None else 40
    want, loads = _dense_routed(params, x[:n_real], 4, 4)
    np.testing.assert_allclose(np.asarray(y[:n_real]), want, atol=2e-5)
    assert np.asarray(counts).tolist() == [sum(loads), sum(n > 0 for n in loads), max(loads)]
    if valid is not None:
        assert float(jnp.abs(y[n_real:]).max()) == 0.0
    if case == "every-token-to-one-expert":
        assert loads == [40, 40, 40, 40]  # all 160 pairs here, 40 on each: nothing dropped
    if case == "none-lands-here":
        assert loads == [0, 0, 0, 0] and float(jnp.abs(y).max()) == 0.0


def test_engine_records_routing_and_window_blocks_as_counted_by_hand(monkeypatch):
    """The ring's ``atpu.serve.moe`` records (one a model call, per layer) and
    ``window_blocks`` on ``atpu.serve.build``, with ``stats()``'s totals: the
    pairs against the reference's own routing of the same tokens, the blocks
    against the rows' lengths."""
    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=4096))
    monkeypatch.setattr(fa, "_prefill_group_blocks", lambda bs, Hkv, D, dtype, W: 2)
    cfg = _config(experts_held=4, first_expert=8)
    params = init_cohere2_moe(cfg, jax.random.PRNGKey(6))
    engine = ServingEngine(params, cfg, num_blocks=65, block_size=BLOCK, max_slots=2,
                           cache_dtype=jnp.float32, lattice=BucketLattice((2,), (16,), (16, 32)))
    rng = np.random.default_rng(2)
    long, short = (engine.submit(rng.integers(0, 256, n).astype(np.int32), 5) for n in (70, 10))
    engine.run()
    moe = [key for _, _, _, key in tracing.recorded("atpu.serve.moe")]
    prefill = [r for r in moe if r["kind"] == "prefill"]
    assert [r["tokens"] for r in prefill] == [32, 32, 6, 10]  # 70 in chunks of 32; padding not counted
    assert all(len(r[k]) == 4 for r in moe for k in ("local_pairs", "experts_hit", "max_expert_load"))
    # layer 0 sees the embeddings: its routing is the reference's of the same tokens
    u = np.asarray(params["embed_tokens"]["embedding"])[long.prompt[:32]]
    u = (u - u.mean(-1, keepdims=True)) / np.sqrt(u.var(-1, keepdims=True) + 1e-5)
    _, loads = _dense_routed(params["layers"][0]["experts"], u, 4, 8)
    assert (prefill[0]["local_pairs"][0], prefill[0]["experts_hit"][0],
            prefill[0]["max_expert_load"][0]) == (sum(loads), sum(n > 0 for n in loads), max(loads))
    stats = engine.stats()
    assert stats["moe_tokens"] == sum(r["tokens"] for r in moe) == 70 + 10 + 2 * 4
    assert stats["moe_local_pairs"] == sum(sum(r["local_pairs"]) for r in moe)
    assert stats["moe_calls"] == 4 * len(moe)
    builds = [key for _, _, _, key in tracing.recorded("atpu.serve.build")]
    # first decode batch: rows of 71 and 11 tokens (prompt + the prefill's token), blocks of 8
    assert builds[0]["live_blocks"] == 9 + 2
    # the long row's window starts at token 71 - 32 = 39, in block 4: it walks blocks 4-8
    assert builds[0]["window_blocks"] == (9 - 4) + 2
    assert stats["decode_blocks_window"] == sum(b["window_blocks"] for b in builds)
    assert stats["decode_blocks_live"] == sum(b["live_blocks"] for b in builds)
    # the prefill kernel's walk, 2 blocks a step, one tile a chunk, a table of 16: the long
    # prompt's chunks at 0, 32 and 64 (a bucket of 16: positions 64-79) end in blocks 3, 7 and
    # 9; a window layer's walk of the last starts at position 64 - 31 = 33, in block 4
    walks = [key for _, _, _, key in tracing.recorded("atpu.serve.prefill")]
    assert [(w["walked_blocks"], w["window_walked_blocks"], w["table_blocks"]) for w in walks] == [
        (4 + 8 + 10, 4 + 8 + 6, 3 * 16), (2, 2, 16)]
    assert stats["prefill_blocks_walked"] == 24 and stats["prefill_blocks_table"] == 64
    assert stats["prefill_blocks_window"] == 20


# ------------------------------------------------------------ the window predicate


@pytest.mark.parametrize("window", [5, 32, 200])
def test_window_kernels_agree_with_the_gather_twin(window):
    """Both paged kernels in interpret mode against ``paged_attention_gather``
    with the same window: rows shorter than, at and far beyond the window, a
    prefill chunk behind 70 live tokens and one behind none, at a small query
    tile too (so that a tile's first block differs from the chunk's)."""
    from accelerate_tpu.ops.flash_attention import paged_attention_gather as gather_twin

    rng = np.random.default_rng(0)
    bs, Hkv, H, D, nb, W = 8, 2, 8, 16, 64, 16
    k_pool = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.float32)

    def tables_for(lengths):
        tables = np.zeros((len(lengths), W), np.int32)
        for b, n in enumerate(lengths):
            blocks = -(-int(n) // bs)
            tables[b, :blocks] = rng.permutation(np.arange(1, nb))[:blocks]
        return jnp.asarray(tables)

    lens = np.array([1, 37, 100, 128])
    tables = tables_for(lens)
    q = jnp.asarray(rng.normal(size=(4, 1, H, D)), jnp.float32)
    want = gather_twin(q, k_pool, v_pool, tables, jnp.asarray(lens - 1)[:, None], None, window)
    got = fa.paged_attention_decode(q, k_pool, v_pool, tables, jnp.asarray(lens), window=window,
                                    interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    starts = np.array([70, 0])
    positions = jnp.asarray(starts[:, None] + np.arange(32)[None])
    tables = tables_for(starts + 32)
    q = jnp.asarray(rng.normal(size=(2, 32, H, D)), jnp.float32)
    want = gather_twin(q, k_pool, v_pool, tables, positions, None, window)
    for rows in (fa._PREFILL_TILE_ROWS, 64):  # all 32 queries a tile, then 8
        fa._PREFILL_TILE_ROWS, kept = rows, fa._PREFILL_TILE_ROWS
        try:
            got = fa.paged_attention_prefill(q, k_pool, v_pool, tables, positions, window=window,
                                             interpret=True)
        finally:
            fa._PREFILL_TILE_ROWS = kept
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_window_no_row_reaches_past_is_the_windowless_decode_bit_for_bit():
    """A Mistral-shaped call (32/8 heads of 128, blocks of 16, bf16) of the
    paged decode kernel on seeded inputs: a window as long as the longest row
    starts every walk at block 0 and masks nothing, so it is ``window=None``'s
    output bit for bit. (Until ISSUE 34 this held ``window=None`` to the hash
    of the program of the PR before the window, which went with that kernel
    body, as the prefill kernel's pair went with ISSUE 30.)"""
    rng = np.random.default_rng(7)
    bs, Hkv, H, D, nb, W = 16, 8, 32, 128, 40, 12
    k_pool = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.bfloat16)
    lengths = np.array([1, 37, 100, 192])
    tables = np.zeros((len(lengths), W), np.int32)
    for b, n in enumerate(lengths):
        tables[b, :-(-int(n) // bs)] = rng.permutation(np.arange(1, nb))[:-(-int(n) // bs)]
    q = jnp.asarray(rng.normal(size=(4, 1, H, D)), jnp.bfloat16)
    args = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths))
    full = fa.paged_attention_decode(*args, interpret=True)
    wide = fa.paged_attention_decode(*args, window=int(lengths.max()), interpret=True)
    assert np.array_equal(np.asarray(full, np.float32), np.asarray(wide, np.float32))
    narrow = fa.paged_attention_decode(*args, window=64, interpret=True)
    assert not np.array_equal(np.asarray(full[2:], np.float32), np.asarray(narrow[2:], np.float32))
    assert np.array_equal(np.asarray(full[:2], np.float32), np.asarray(narrow[:2], np.float32))


# ------------------------------------------------------------------- what refuses


def test_engine_selects_by_the_configs_type_and_refuses_what_it_cannot_serve():
    cfg = _config()
    params = init_cohere2_moe(cfg, jax.random.PRNGKey(0))
    with pytest.raises(TypeError, match="speculative decoding drafts with a LlamaConfig"):
        ServingEngine(params, cfg, num_blocks=9, block_size=BLOCK, spec_tokens=2, draft_layers=1)
    with pytest.raises(TypeError, match="cannot serve a dict"):
        ServingEngine(params, {"n_layers": 1}, num_blocks=9, block_size=BLOCK)
    with pytest.raises(ValueError, match="layer_types"):
        _config(layer_types=("full_attention",))
    assert _config(n_layers=8).layer_types[3::4] == ("full_attention",) * 2  # every fourth is full


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(open(reference.__file__).read())
    imported = [alias.name for node in ast.walk(tree) if isinstance(node, ast.Import)
                for alias in node.names]
    imported += [node.module or "" for node in ast.walk(tree) if isinstance(node, ast.ImportFrom)]
    assert imported and not [name for name in imported if name.split(".")[0] == "accelerate_tpu"]


def test_single_stream_generation_refuses_the_model_by_name():
    from accelerate_tpu.generation import greedy_generate

    cfg = _config()
    with pytest.raises(TypeError, match="generation.py decodes a LlamaConfig; serve a Cohere2MoeConfig"):
        greedy_generate(init_cohere2_moe(cfg, jax.random.PRNGKey(0)), np.zeros((1, 4), np.int32),
                        cfg, max_new_tokens=2)
