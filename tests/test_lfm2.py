"""The hybrid decoder (``models/lfm2.py``): gated short convolutions whose
state lives a row a sequence beside a paged pool that only the attention
layers use, the llama layer's attention half with its query/key norm, leading
dense FFNs, bias-selected sigmoid routing (``parallel/moe.py``), and the
engine that serves it, held on the CPU at a small size (hidden 64, 8/2 heads
of 8, 6 layers: conv, conv, full_attention, conv, conv, conv, the first two
dense; 16 experts of 32 top-4; 3 taps; blocks of 8) to the PLAIN reference of
the chip benchmark (``benchmarks/chip/lfm2_reference.py``), which shares no
code with the program. Seeded random float32 weights; logits, not tokens:
every logit the engine's step programs compute for a real token is compared
with the reference's full forward at that position."""

import collections
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from accelerate_tpu.models.lfm2 import Lfm2Config, conv_operator, init_lfm2, lfm2_forward  # noqa: E402
from accelerate_tpu.parallel.moe import held_expert_ffn, init_held_experts, route_top_k  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    NULL_STATE_ROW,
    BucketLattice,
    DecodeEngine,
    PrefillEngine,
    ServingEngine,
)
from accelerate_tpu.serving.engine import model_paged_forward  # noqa: E402
from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import lfm2_reference as reference  # noqa: E402

BLOCK = 8
SMALL = dict(vocab_size=256, dim=64, n_layers=6, n_heads=8, n_kv_heads=2, num_dense_layers=2,
             conv_taps=3, dense_dim=96, expert_dim=32, num_experts=16, experts_per_token=4,
             max_seq_len=128)


def _config(**overrides):
    return Lfm2Config(**{**SMALL, **overrides})


def _published(cfg: Lfm2Config) -> dict:
    """The published keys the reference reads, for a small program config."""
    return {
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "norm_eps": cfg.norm_eps, "num_experts_per_tok": cfg.experts_per_token,
        "first_expert_held": cfg.first_expert, "layer_types": list(cfg.layer_types),
        "num_dense_layers": cfg.num_dense_layers, "routed_scaling_factor": 1,
        "rope_parameters": {"rope_type": "default", "rope_theta": cfg.rope_theta},
    }


def _reference_logits(params, ids, cfg):
    c = _published(cfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(
            params, jnp.asarray(ids), layer_types=c["layer_types"],
            num_dense_layers=c["num_dense_layers"], eps=cfg.norm_eps, fns=reference.layer_fns(c)))


@pytest.fixture(scope="module")
def model():
    cfg = _config()
    return cfg, init_lfm2(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["heads-apart", "heads-packed"])
def either(request, model):
    """``model`` (2 key heads of 8: each its own row of the pool), or the
    same at hidden 256 with 4 key heads of 32, which fill a 128-lane row
    together (``ops.flash_attention.kv_lane_pack``)."""
    if request.param == "heads-apart":
        return model
    cfg = _config(dim=256, n_kv_heads=4)
    return cfg, init_lfm2(cfg, jax.random.PRNGKey(1))


# ---------------------------------------------------------- program vs reference


def test_full_forward_equals_the_plain_reference(model):
    cfg, params = model
    ids = np.random.default_rng(0).integers(0, 256, (2, 77)).astype(np.int32)
    got = np.asarray(lfm2_forward(params, jnp.asarray(ids), cfg))
    for row in range(2):
        want = _reference_logits(params, ids[row], cfg)
        assert np.abs(got[row] - want).max() < 1e-4 * want.std()
    # the reference is causal and stateless: a prefix's logits are the whole's
    assert np.abs(_reference_logits(params, ids[1, :1], cfg) - want[:1]).max() < 1e-5


def test_the_query_key_norm_and_the_layer_pattern_are_the_published_ones(model):
    cfg, params = model
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv", "conv", "conv")
    assert dataclasses.is_dataclass(cfg) and type(cfg).__dataclass_params__.frozen  # hashable: a jit static
    assert (cfg.n_kv_layers, cfg.state_shape, cfg.head_dim) == (1, (5, 2, 64), 8)
    assert [cfg.cache_index(l) for l in range(6)] == [0, 1, 0, 2, 3, 4]
    attention, conv = params["layers"][2], params["layers"][3]
    assert {"attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "mlp_norm", "experts"} == set(attention)
    assert {"op_norm", "in_proj", "conv", "out_proj", "mlp_norm", "experts"} == set(conv)
    assert {"w1", "w2", "w3"} <= set(params["layers"][0]) and "experts" not in params["layers"][1]
    bias = attention["experts"]["expert_bias"]
    assert bias.dtype == jnp.float32 and bias.shape == (16,) and float(jnp.abs(bias).min()) > 0
    assert "lm_head" not in params  # tied
    # without the norm the logits move: it is in the path, before the rotary turn
    ids = np.random.default_rng(1).integers(0, 256, (1, 40)).astype(np.int32)
    bare = {**params, "layers": tuple(
        {k: v for k, v in lp.items() if k not in ("q_norm", "k_norm")} for lp in params["layers"])}
    with_norm = np.asarray(lfm2_forward(params, jnp.asarray(ids), cfg))
    without = np.asarray(lfm2_forward(bare, jnp.asarray(ids), cfg))
    assert np.abs(with_norm - without).max() > 1e-2 * with_norm.std()


@pytest.mark.parametrize("chunks", [[(10, 10)], [(4, 8), (6, 8)], [(1, 4), (1, 1), (5, 16), (3, 4)]],
                         ids=["whole", "padded-4+6", "single-tokens-1+1+5+3"])
def test_conv_operator_carries_its_state_behind_padding_and_across_chunks(model, chunks):
    """The operator over 10 positions at once, against the same in chunks of
    ``(real rows, bucket)``: the state a chunk leaves is ``z`` at its last two
    REAL rows, also where a chunk has fewer than two."""
    cfg, params = model
    lp = params["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 10, 64))
    zeros = jnp.zeros((2, 2, 64))
    want, state_want = conv_operator(lp, u, zeros, jnp.full((2,), 10))
    outs, state, at = [], zeros, 0
    for real, bucket in chunks:
        padded = jnp.concatenate(
            [u[:, at:at + real], 7.0 * jnp.ones((2, bucket - real, 64))], axis=1)  # padding is not zeros
        out, state = conv_operator(lp, padded, state, jnp.full((2,), real))
        outs.append(out[:, :real])
        at += real
    np.testing.assert_allclose(np.concatenate(outs, axis=1), want, atol=1e-5)
    np.testing.assert_allclose(state, state_want, atol=1e-6)


# ------------------------------------------------------- the engine, logit by logit


class _Spy:
    """Every logit the engine's step programs compute for a real token, by
    request and position: each call of ``prefill_fn`` / ``decode_fn`` is
    preceded by the model's own paged forward on the same arguments (jitted
    apart, nothing donated)."""

    def __init__(self, engine):
        self.engine, self.seen, self.decode_rows = engine, collections.defaultdict(list), []
        forward = jax.jit(model_paged_forward(engine.config, engine.block_size))
        prefill, decode = engine.prefill_fn, engine.decode_fn

        def spy_prefill(params, pool, ids, table, start, last_idx, key, token_idx, rows):
            S = ids.shape[1]
            logits, _, _ = forward(params, ids, pool, table, int(start) + np.arange(S)[None],
                                   np.arange(S)[None] <= int(last_idx), rows)
            request = engine.scheduler.slots[int(rows[0]) - 1]
            for i in range(int(last_idx) + 1 if rows[0] != NULL_STATE_ROW else 0):  # not a warm-up
                self.seen[request.rid].append((int(start) + i, np.asarray(logits[0, i])))
            return prefill(params, pool, ids, table, start, last_idx, key, token_idx, rows)

        def spy_decode(params, pool, last, tables, positions, keys, token_idx, rows):
            logits, _, _ = forward(params, last[:, None], pool, tables, positions[:, None],
                                   tables[:, :1] != 0, rows)
            running = engine.scheduler.running()
            assert [r.slot + 1 for r in running] == rows[:len(running)].tolist()
            assert (rows[len(running):] == NULL_STATE_ROW).all()
            self.decode_rows.append(rows.tolist())
            for i, request in enumerate(running):
                self.seen[request.rid].append((int(positions[i]), np.asarray(logits[i, 0])))
            return decode(params, pool, last, tables, positions, keys, token_idx, rows)

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


def _engine(cfg, params, *, slots=2, num_blocks=65, **kwargs):
    engine = ServingEngine(
        params, cfg, num_blocks=num_blocks, block_size=BLOCK, max_slots=slots,
        cache_dtype=jnp.float32, lattice=BucketLattice((slots,), (16,), (16, 32)), **kwargs)
    return engine, _Spy(engine)


def _state_records(engine):
    return [(k["rid"], k["row"], k["why"]) for _, _, _, k in tracing.recorded("atpu.serve.state")
            if k["engine"] == engine.engine_id]


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


def test_a_prompt_shorter_than_its_bucket_leaves_its_state_behind_the_padded_tail(either):
    cfg, params = either
    engine, spy = _engine(cfg, params)
    requests = [engine.submit(p, 6) for p in _prompts(10, 9, 17)]  # buckets 16 and 32
    engine.run()
    spy.hold_to_the_reference(requests, params, cfg)


def test_a_prompt_in_three_chunks_with_a_ragged_last_one_carries_state_across_them(either):
    cfg, params = either
    engine, spy = _engine(cfg, params)
    requests = [engine.submit(p, 8) for p in _prompts(11, 70)]  # 32 + 32 + 6 in a bucket of 16
    engine.run()
    spy.hold_to_the_reference(requests, params, cfg)
    assert engine.stats()["prefill_calls"] == 1 and engine.stats()["state_resets"] == 1
    starts = sorted({p for p, _ in spy.seen[requests[0].rid]})
    assert starts[:70] == list(range(70))


def test_a_state_row_handed_to_the_next_sequence_starts_from_zeros(model):
    cfg, params = model
    engine, spy = _engine(cfg, params, slots=1)
    first, second = [engine.submit(p, 5) for p in _prompts(12, 30, 11)]
    engine.run()
    spy.hold_to_the_reference([first, second], params, cfg)
    assert _state_records(engine) == [
        (first.rid, 1, "admit"), (first.rid, 1, "finish"),
        (second.rid, 1, "admit"), (second.rid, 1, "finish")]
    stats = engine.stats()
    assert stats["state_resets"] == 2 and stats["state_bytes"] == 5 * 2 * 2 * 64 * 4


def test_a_preempted_request_is_re_prefilled_from_zero_and_rebuilds_its_state(model):
    cfg, params = model
    # 9 usable blocks of 8: both prompts fit (3 + 3), their 50 tokens each (7 + 7) do not
    engine, spy = _engine(cfg, params, num_blocks=10)
    requests = [engine.submit(p, 30) for p in _prompts(13, 20, 20)]
    engine.run()
    victim = max(requests, key=lambda r: r.preemptions)
    assert engine.stats()["preemptions"] >= 1 and victim.preemptions >= 1
    spy.hold_to_the_reference(requests, params, cfg)  # the re-prefilled positions too
    records = _state_records(engine)
    assert [why for rid, _, why in records if rid == victim.rid] == (
        ["admit", "preempt"] * victim.preemptions + ["admit", "finish"])
    assert engine.stats()["state_resets"] == 2 + engine.stats()["preemptions"]


def test_the_prefix_cache_asked_for_takes_no_hit_and_the_logits_are_the_same(model):
    cfg, params = model
    engine, spy = _engine(cfg, params, prefix_cache=True)
    assert engine.prefix_cache is False and "cow_compiles" not in engine.jit_cache_sizes()
    prompt = _prompts(14, 40)[0]
    first = engine.submit(prompt, 6)
    engine.run()
    second = engine.submit(prompt, 6)  # its five full blocks are what a prefix hit would skip
    engine.run()
    assert (first.cached_tokens, second.cached_tokens) == (0, 0)
    stats = engine.stats()
    assert stats["prefill_tokens_saved"] == 0 and stats["prefill_tokens"] == 80
    spy.hold_to_the_reference([first, second], params, cfg)
    assert first.generated == second.generated


def test_a_decode_batch_with_an_idle_slot_between_live_rows_reads_the_right_rows(model):
    cfg, params = model
    engine, spy = _engine(cfg, params, slots=4)
    budgets = (12, 2, 12)  # the middle sequence leaves first: rows 1 and 3 stay live
    requests = [engine.submit(p, n) for p, n in zip(_prompts(15, 13, 22, 9), budgets)]
    engine.run()
    spy.hold_to_the_reference(requests, params, cfg)
    assert [1, 3, 0, 0] in spy.decode_rows and [1, 2, 3, 0] in spy.decode_rows
    builds = [k for name, _, _, k in tracing.recorded("atpu.serve.build")
              if k["engine"] == engine.engine_id]
    assert builds and all(b["state_rows"] == b["batch"] for b in builds)


def test_the_paged_kernels_and_the_grouped_matmul_serve_it_too(either, monkeypatch):
    """The Pallas bodies through the interpreter: paged prefill and decode at
    4 or 2 query heads a key head, the heads apart or packed, ``moe_gmm``
    under a selection bias."""
    monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "interpret")
    cfg, params = either
    engine, spy = _engine(cfg, params)
    requests = [engine.submit(p, 4) for p in _prompts(16, 37, 9)]
    engine.run()
    spy.hold_to_the_reference(requests, params, cfg)


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("pack", [2, 4])
def test_packed_heads_attend_as_the_heads_apart_do(pack, mode, monkeypatch):
    """``paged_write_attend`` on a pool whose rows hold two (four) key heads
    of 64 (32), as ``init_block_pool`` lays them out, against the same on a
    pool with a row a head: 8 query heads on 4 key heads, a chunk and then
    single tokens. The caller hands over the same ``q``, ``k``, ``v`` and
    gets the same heads back."""
    from accelerate_tpu.ops.flash_attention import init_block_pool, kv_lane_pack, paged_write_attend

    if mode == "interpret":
        monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "interpret")
    B, H, Hkv, D = 2, 8, 4, 128 // pack
    assert kv_lane_pack(Hkv, D) == pack
    shape = collections.namedtuple("shape", "n_layers n_kv_heads head_dim")(1, Hkv, D)
    packed = init_block_pool(shape, 9, BLOCK, jnp.float32)
    assert packed["k"].shape == (1, 9, BLOCK, Hkv // pack, 128)
    apart = jnp.zeros((1, 9, BLOCK, Hkv, D))
    pools = {1: [apart, apart], pack: [packed["k"], packed["v"]]}
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7]], jnp.int32)
    key = jax.random.PRNGKey(20)
    for start, S in ((0, 16), (16, 1), (17, 1)):
        key, *ks = jax.random.split(key, 4)
        q, k, v = (jax.random.normal(kk, (B, S, h, D)) for kk, h in zip(ks, (H, Hkv, Hkv)))
        positions = start + jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        want, *pools[1] = paged_write_attend(q, k, v, *pools[1], 0, table, positions, BLOCK)
        got, *pools[pack] = paged_write_attend(q, k, v, *pools[pack], 0, table, positions, BLOCK)
        np.testing.assert_allclose(got, want, atol=2e-5)
    # the packed pool holds the same numbers, two (four) heads to a row
    np.testing.assert_array_equal(pools[pack][0].reshape(pools[1][0].shape), pools[1][0])


@pytest.mark.parametrize("heads, width, pack", [
    (8, 128, 1), (8, 256, 1), (8, 64, 2), (4, 32, 4), (2, 32, 1), (3, 64, 1), (2, 8, 1), (8, 96, 1)])
def test_key_heads_share_a_row_only_where_they_fill_its_lanes(heads, width, pack):
    """Heads of 128 and wider lie apart (the three other models: their pool
    and programs are the parent's); narrower ones share a row where they fill
    its 128 lanes exactly, and packed heads pack no further."""
    from accelerate_tpu.ops.flash_attention import kv_lane_pack

    assert kv_lane_pack(heads, width) == pack
    assert kv_lane_pack(heads // pack, width * pack) == 1


# ------------------------------------------------------------ what the engine keeps


def test_the_pool_is_what_the_model_says_it_needs_and_the_counters_say_so(model):
    cfg, params = model
    engine, _ = _engine(cfg, params, slots=3)
    pool = engine.pool
    assert pool["k"].shape == pool["v"].shape == (1, 65, BLOCK, 2, 8)  # the one attention layer's
    assert pool["state"].shape == (5, 3 + 1, 2, 64)                  # five conv layers, a null row
    warmed = engine.warmup()
    assert warmed == {"prefill_compiles": 2, "decode_compiles": 1}
    requests = [engine.submit(p, 5) for p in _prompts(17, 9, 33, 12, 20)]
    engine.run()
    assert engine.jit_cache_sizes() == warmed  # rows are data: no program beyond the lattice
    moe = [k for name, _, _, k in tracing.recorded("atpu.serve.moe") if k["engine"] == engine.engine_id]
    # one entry a ROUTED layer (4 of 6), every expert held: top-k pairs a token on each
    assert moe and all(len(r["local_pairs"]) == 4 for r in moe)
    assert all(r["local_pairs"] == [4 * r["tokens"]] * 4 and r["held"] == 16 for r in moe)
    records = _state_records(engine)
    assert sorted(r for r in records if r[2] == "admit") == sorted(
        (q.rid, row, "admit") for q, row in zip(requests, (1, 2, 3, records[-1][1])))
    assert len(records) == 8 and engine.stats()["state_resets"] == 4


def test_a_model_with_per_sequence_state_is_refused_what_would_skip_or_split_it(model):
    cfg, params = model
    with pytest.raises(TypeError, match="no draft"):
        ServingEngine(params, cfg, spec_tokens=2, draft_layers=1)
    for role in (PrefillEngine, DecodeEngine):
        with pytest.raises(TypeError, match="per-sequence state rows"):
            role(params, cfg, num_blocks=17, block_size=BLOCK, max_slots=2)
    with pytest.raises(TypeError, match="per-sequence state"):
        ServingEngine(params, cfg, mesh=object())
    engine = ServingEngine(params, cfg, num_blocks=17, block_size=BLOCK, max_slots=2)
    with pytest.raises(TypeError, match="no copy of a sequence's state"):
        engine.cow_fn(engine.pool, np.int32(0), np.int32(0))
    from accelerate_tpu.generation import init_kv_cache

    with pytest.raises(TypeError, match="decodes a LlamaConfig"):
        init_kv_cache(cfg, 1, 16)


# ------------------------------------------------------------------- the routing


def test_selection_sees_the_bias_and_the_weights_do_not(model):
    """A bias large enough to change who is chosen: the chosen are the top-k
    of ``score + bias``, their weights the bare scores over ``their sum +
    eps``. With the biased scores as weights, or the bare scores as
    selection, program and reference part."""
    cfg, params = model
    bias = jnp.where(jnp.arange(16) % 4 == 0, 2.0, 0.0)  # four experts win every selection
    kernel = params["layers"][2]["experts"]["router"]["kernel"]
    x = jax.random.normal(jax.random.PRNGKey(5), (50, 64))
    score = jax.nn.sigmoid(jnp.dot(x, kernel, precision="highest"))
    ids, weights = route_top_k(kernel, x, 4, select_bias=bias, weight_eps=1e-6)
    assert (np.sort(np.asarray(ids), axis=-1) == [0, 4, 8, 12]).all()
    plain_ids, _ = route_top_k(kernel, x, 4)
    assert (np.sort(np.asarray(plain_ids), axis=-1) != [0, 4, 8, 12]).any(axis=-1).mean() > 0.9
    picked = jnp.take_along_axis(score, ids, axis=-1)
    np.testing.assert_allclose(weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    biased = picked + bias[ids]
    assert np.abs(np.asarray(weights - biased / biased.sum(-1, keepdims=True))).max() > 0.02

    heavy = {**params, "layers": tuple(
        {**lp, "experts": {**lp["experts"], "expert_bias": bias}} if "experts" in lp else lp
        for lp in params["layers"])}
    tokens = np.random.default_rng(6).integers(0, 256, 48).astype(np.int32)
    got = np.asarray(lfm2_forward(heavy, jnp.asarray(tokens)[None], cfg)[0])
    want = _reference_logits(heavy, tokens, cfg)
    assert np.abs(got - want).max() < 1e-4 * want.std()
    unbiased = _reference_logits(params, tokens, cfg)  # the model's own small bias: other experts
    assert np.abs(got - unbiased).max() > 1e-2 * want.std()


@pytest.mark.parametrize("masked", [False, True], ids=["all-real", "padded"])
@pytest.mark.parametrize("top_k", [2, 4])
def test_four_shares_of_sixteen_experts_add_up_to_the_uncut_layer(top_k, masked):
    """``held_expert_ffn`` under a selection bias: the shares of four chips
    holding 16 of 64 experts each add up to the layer held whole, and that is
    the reference's routed FFN."""
    whole = init_held_experts(jax.random.PRNGKey(7), 64, 32, 64, 64)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(8), (64,))
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 11, 64))
    valid = jnp.arange(11)[None] < jnp.array([11, 4, 7])[:, None] if masked else None
    kwargs = dict(top_k=top_k, valid=valid, select_bias=bias, weight_eps=1e-6)
    uncut, counts = held_expert_ffn(whole, x, **kwargs)
    total, pairs = 0.0, 0
    for chip in range(4):
        share = {"router": whole["router"], **{
            name: {"kernel": whole[name]["kernel"][16 * chip:16 * chip + 16]}
            for name in ("w_gate", "w_up", "w_down")}}
        y, share_counts = held_expert_ffn(share, x, first_expert=16 * chip, **kwargs)
        total, pairs = total + y, pairs + int(share_counts[0])
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    real = int(valid.sum()) if masked else 33
    assert pairs == int(counts[0]) == top_k * real
    with jax.default_matmul_precision("highest"):
        want = reference.routed_ffn(x.reshape(-1, 64), {**whole, "expert_bias": bias}, top_k=top_k)
    keep = np.asarray(valid).reshape(-1) if masked else slice(None)
    np.testing.assert_allclose(np.asarray(uncut).reshape(-1, 64)[keep], np.asarray(want)[keep],
                               atol=2e-5)
