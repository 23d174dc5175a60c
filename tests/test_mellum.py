"""The sequential routed decoder (``models/mellum.py``): softmax top-k routing
over experts all held here (``parallel/moe.py``), YaRN's rotary table beside
the plain one (``models/transformer.py``), the llama layer's attention half
shared, and the engine that serves it, held on the CPU at a small size (hidden
64, 8/2 heads of 16, 16 experts of 32 top-4, window 32, blocks of 8, one
period of the layer pattern, YaRN over an original context of 32 so that the
tests' positions pass it) to the PLAIN reference of the chip benchmark
(``benchmarks/chip/mellum_reference.py``), which shares no code with the
program. Seeded random float32 weights; logits, not tokens."""

import ast
import collections
import dataclasses
import hashlib
import inspect
import math
import os
import sys
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from accelerate_tpu.models import mellum  # noqa: E402
from accelerate_tpu.models.mellum import MellumConfig, init_mellum, mellum_forward  # noqa: E402
from accelerate_tpu.models.transformer import (  # noqa: E402
    apply_rope,
    llama_layer,
    rope_frequencies,
    yarn_rope_frequencies,
)
from accelerate_tpu.parallel.moe import held_expert_ffn, init_held_experts, route_top_k  # noqa: E402
from accelerate_tpu.serving import BucketLattice, ServingEngine  # noqa: E402
from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import mellum_reference as reference  # noqa: E402

WINDOW, BLOCK, THETA = 32, 8, 500000.0
YARN = dict(factor=16.0, original_max_seq=32, beta_fast=32.0, beta_slow=1.0,
            attention_factor=0.1 * math.log(16.0) + 1.0)
SMALL = dict(vocab_size=256, dim=64, n_layers=4, n_heads=8, n_kv_heads=2, head_dim=16,
             expert_dim=32, num_experts=16, experts_per_token=4, sliding_window=WINDOW,
             max_seq_len=128, rope_theta=THETA, yarn=tuple(YARN.items()))


def _config(**overrides):
    return MellumConfig(**{**SMALL, **overrides})


def _published(cfg: MellumConfig) -> dict:
    """The published keys the reference reads, for a small program config."""
    yarn = dict(cfg.yarn)
    return {
        "head_dim": cfg.head_dim, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "rms_norm_eps": cfg.norm_eps,
        "num_experts_per_tok": cfg.experts_per_token, "sliding_window": cfg.sliding_window,
        "first_expert_held": cfg.first_expert, "layer_types": list(cfg.layer_types),
        "rope_parameters": {
            "sliding_attention": {"rope_type": "default", "rope_theta": cfg.rope_theta},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta, "factor": yarn["factor"],
                "original_max_position_embeddings": yarn["original_max_seq"],
                "beta_fast": yarn["beta_fast"], "beta_slow": yarn["beta_slow"],
                "attention_factor": yarn["attention_factor"]}},
    }


def _reference_logits(params, ids, cfg):
    c = _published(cfg)
    return np.asarray(reference.logits(params, jnp.asarray(ids), layer_types=c["layer_types"],
                                       eps=cfg.norm_eps, fns=reference.layer_fns(c)))


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
    """Contexts of 3 x the window (and of YaRN's original 32), prefilled in
    chunks of at most 32 and then decoded through the paged cache: at every
    generated position the reference's logit of the engine's token is the
    reference's largest (margin under 1e-4 of a logit deviation: float32 on
    both sides, so only the order of summation differs), and the program's own
    full forward equals the reference's logits to 1e-4 of a deviation."""
    cfg = _config()
    params = init_mellum(cfg, jax.random.PRNGKey(0))
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
        program = np.asarray(mellum_forward(params, jnp.asarray(out)[None], cfg)[0])
        assert np.abs(program - logits).max() < 1e-4 * logits.std()
    assert requests[0].prompt.size + 12 > 3 * WINDOW  # window layers crossed their window


def test_paged_forward_logits_equal_the_references_across_the_window(kernel_mode):
    """The logits themselves, which is what holds the mask: a 96-token context
    through ``paged_forward`` in three chunks, then four single-token steps,
    against the reference's full forward over the same 100 tokens; and with
    the window layers' mask off in the reference the two differ."""
    cfg = _config()
    params = init_mellum(cfg, jax.random.PRNGKey(1))
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
        assert counts.shape == (4, 3) and np.asarray(counts)[:, 0].tolist() == [4 * n] * 4
    got = np.concatenate(got)
    want = _reference_logits(params, ids, cfg)
    assert np.abs(got - want).max() < 1e-4 * want.std()
    unmasked = _reference_logits(params, ids, _config(sliding_window=1000))
    assert np.abs(got - unmasked)[WINDOW:].max() > 1e-2 * want.std()


# ------------------------------------------------------------------ the rotary tables


def _yarn_by_hand(head_dim, theta, factor, original, beta_fast, beta_slow):
    """The inverse frequencies, pair by pair, as the configuration file's
    ``assumed_why`` states them."""
    def corr(r):
        return head_dim * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), head_dim - 1)
    inv = []
    for i in range(head_dim // 2):
        ramp = min(max((i - low) / ((high + 0.001 if high == low else high) - low), 0.0), 1.0)
        plain = theta ** (-2 * i / head_dim)
        inv.append((1 - ramp) * plain + ramp * plain / factor)
    return low, high, inv


@pytest.mark.parametrize("head_dim,original", [(128, 8192), (16, 32)], ids=["published", "small"])
def test_yarn_table_is_the_formula_written_out(head_dim, original):
    low, high, inv = _yarn_by_hand(head_dim, THETA, 16.0, original, 32.0, 1.0)
    if head_dim == 128:  # the published model's: corr(32) = 18.08, corr(1) = 34.98
        assert (low, high) == (18, 35)
        assert inv[18] == THETA ** (-36 / 128) and inv[35] == pytest.approx(THETA ** (-70 / 128) / 16)
    cos, sin = yarn_rope_frequencies(head_dim, 100, THETA, factor=16.0, original_max_seq=original)
    factor = 0.1 * math.log(16.0) + 1.0
    assert factor == pytest.approx(1.2772588722239782, rel=1e-15)
    positions = np.arange(100)[:, None]
    np.testing.assert_allclose(cos, factor * np.cos(positions * np.asarray(inv)), atol=2e-6)
    np.testing.assert_allclose(sin, factor * np.sin(positions * np.asarray(inv)), atol=2e-6)
    assert cos.shape == rope_frequencies(head_dim, 100, THETA)[0].shape == (100, head_dim // 2)
    # the reference's own table, written apart, agrees
    np.testing.assert_allclose(
        reference.yarn_inv_freq(head_dim, THETA, factor=16.0, original_max=original), inv, rtol=1e-12)
    # a given factor is taken as given; the fast pairs are the plain table's
    cos2, _ = yarn_rope_frequencies(head_dim, 100, THETA, factor=16.0, original_max_seq=original,
                                    attention_factor=2.0)
    np.testing.assert_allclose(cos2[:, :max(low, 1)],
                               2.0 * rope_frequencies(head_dim, 100, THETA)[0][:, :max(low, 1)], atol=2e-6)


def test_full_layers_turn_by_yarn_and_window_layers_by_the_plain_table():
    """Positions past the original context (32): a full layer's keys turn by
    the interpolated frequencies and are ``attention_factor`` times as long; a
    window layer's are the plain rotary's. Without ``yarn`` both are plain."""
    cfg = _config()
    assert [cfg.layer_types[i] for i in (0, 3)] == [mellum.SLIDING, mellum.FULL]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 100, 2, 16))
    plain = apply_rope(x, *map(jnp.asarray, cfg.rope(mellum.SLIDING)))
    np.testing.assert_allclose(plain, apply_rope(x, *map(jnp.asarray, rope_frequencies(16, 128, THETA))))
    turned = apply_rope(x, *map(jnp.asarray, cfg.rope(mellum.FULL)))
    np.testing.assert_allclose(jnp.linalg.norm(turned, axis=-1),
                               YARN["attention_factor"] * jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert float(jnp.abs(turned / YARN["attention_factor"] - plain)[0, 40:].max()) > 0.1
    np.testing.assert_array_equal(_config(yarn=None).rope(mellum.FULL)[0], cfg.rope(mellum.SLIDING)[0])


# --------------------------------------------------------------- routing, counted


def _dense_routed(params, x, top_k, first_expert):
    """The routed part by hand: softmax over every expert, every token through
    every held expert, in numpy and float64, weighted where chosen."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    x = np.asarray(x, np.float64)
    logits = x @ p["router"]["kernel"]
    scores = np.exp(logits - logits.max(-1, keepdims=True))
    scores /= scores.sum(-1, keepdims=True)
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


def test_softmax_top_k_weights_against_a_hand_count():
    """Router logits (3, 1, 2, 0, -1) for one token, top 2: softmax over all
    five, the two largest (experts 0 and 2), divided by their sum, which is a
    softmax over the two chosen logits: e^3 / (e^3 + e^2) and e^2 / (e^3 + e^2)."""
    router = jnp.asarray([[3.0, 1.0, 2.0, 0.0, -1.0]])
    experts, weights = route_top_k(router, jnp.ones((1, 1)), 2, scoring="softmax")
    assert np.asarray(experts).tolist() == [[0, 2]]
    np.testing.assert_allclose(weights, [[1 / (1 + math.exp(-1)), 1 / (1 + math.exp(1))]], rtol=1e-6)
    # sigmoid scores of the same logits weigh them otherwise
    _, sigmoid = route_top_k(router, jnp.ones((1, 1)), 2)
    s3, s2 = 1 / (1 + math.exp(-3)), 1 / (1 + math.exp(-2))
    np.testing.assert_allclose(sigmoid, [[s3 / (s3 + s2), s2 / (s3 + s2)]], rtol=1e-6)
    with pytest.raises(KeyError):
        route_top_k(router, jnp.ones((1, 1)), 2, scoring="tanh")


# sha256 of the float32 bytes of `held_expert_ffn`'s output and of `route_top_k`'s weights on
# the seeded inputs below, computed on the PARENT of the PR that brought `scoring` (commit
# 39bb24d) by this same function: the default and `scoring="sigmoid"` are that arithmetic
SIGMOID_PARENT = ("3a08ed0f7a80dd5891221b60dea511f60b19e2515b185f9335942cc3b185f00d",
                  "332de6a517138063698a859ef39795324abfbe03fd955ab27d43a2de46fe4853")


def _sigmoid_path(**scoring):
    params = init_held_experts(jax.random.PRNGKey(4), 64, 32, 16, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    y, _ = held_expert_ffn(params, x, top_k=4, first_expert=4, **scoring)
    _, weights = route_top_k(params["router"]["kernel"], x, 4, **scoring)
    return tuple(hashlib.sha256(np.asarray(a, np.float32).tobytes()).hexdigest()
                 for a in (y, weights))


@pytest.mark.parametrize("scoring", [{}, {"scoring": "sigmoid"}], ids=["default", "named"])
def test_the_sigmoid_path_is_the_parents_bit_for_bit(scoring):
    assert _sigmoid_path(**scoring) == SIGMOID_PARENT
    router = jax.random.normal(jax.random.PRNGKey(6), (64, 16))
    x = jax.random.normal(jax.random.PRNGKey(7), (40, 64))

    def parents(router, x):  # the parent's `route_top_k`, word for word
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores, experts = jax.lax.top_k(jax.nn.sigmoid(logits), 4)
        return experts, scores / jnp.sum(scores, axis=-1, keepdims=True)

    assert str(jax.make_jaxpr(lambda r, x: route_top_k(r, x, 4, **scoring))(router, x)) == str(
        jax.make_jaxpr(parents)(router, x))


@pytest.mark.parametrize("case", ["seeded", "every-token-to-one-expert", "padding-is-routed-nowhere"])
def test_no_token_is_dropped_and_every_pair_is_local_with_every_expert_held(case, kernel_mode):
    """``held_expert_ffn`` with all 16 experts held against the dense hand
    computation: ``local_pairs`` is ``top_k x tokens`` whatever the router
    does, also under a skew no capacity factor would survive (every token's
    first choice is expert 5)."""
    params = init_held_experts(jax.random.PRNGKey(4), 64, 32, 16, 16)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    router = np.asarray(params["router"]["kernel"]).copy()
    valid = None
    if case == "every-token-to-one-expert":
        router[:] = 0.0
        x = x.at[:, 0].set(3.0)  # a feature every token has: expert 5 first, then 4, 6, 7
        router[0, [5, 4, 6, 7]] = [4.0, 3.0, 2.0, 1.0]
    elif case == "padding-is-routed-nowhere":
        valid = jnp.arange(40) < 25
    params["router"]["kernel"] = jnp.asarray(router)
    y, counts = held_expert_ffn(params, x, top_k=4, valid=valid, scoring="softmax")
    n_real = 25 if valid is not None else 40
    want, loads = _dense_routed(params, x[:n_real], 4, 0)
    np.testing.assert_allclose(np.asarray(y[:n_real]), want, atol=2e-5)
    assert np.asarray(counts).tolist() == [4 * n_real, sum(n > 0 for n in loads), max(loads)]
    assert sum(loads) == 4 * n_real
    if valid is not None:
        assert float(jnp.abs(y[n_real:]).max()) == 0.0
    if case == "every-token-to-one-expert":
        assert [loads[e] for e in (4, 5, 6, 7)] == [40, 40, 40, 40] and max(loads) == 40


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """One layer, 16 experts over 4 chips of 4 (``first_expert`` 0, 4, 8, 12;
    the published model's 64 would be 0, 16, 32, 48): the routed parts the four
    shares give, with the attention half counted once (every chip computes it
    alike), add up to what the uncut reference gives for the whole layer."""
    full = _config(n_layers=1)
    lp = init_mellum(full, jax.random.PRNGKey(2))["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64))

    def attend(q, k, v):  # plain causal attention inside the window, as the full forward's
        i, j = jnp.arange(48)[:, None], jnp.arange(48)[None, :]
        k, v = jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2)
        s = jnp.where((j <= i) & (i - j < WINDOW), jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0,
                      -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    def layer_out(cfg, lp, ffn):  # the program's layer with the FFN half it is given
        cos, sin = map(jnp.asarray, cfg.rope(mellum.SLIDING))
        return llama_layer(lp, h, None, cos, sin, cfg, attend, ffn=ffn)[0]

    routed, alike = [], []
    for chip in range(4):
        share = _config(n_layers=1, experts_held=4, first_expert=4 * chip)
        lp_share = dict(lp, experts={
            "router": lp["experts"]["router"],
            **{w: {"kernel": lp["experts"][w]["kernel"][4 * chip:4 * chip + 4]}
               for w in ("w_gate", "w_up", "w_down")}})
        alike.append(layer_out(share, lp_share, lambda lp, y: (jnp.zeros_like(y), None)))
        routed.append(layer_out(share, lp_share, partial(mellum._routed_ffn, config=share, valid=None))
                      - alike[-1])  # what this chip's experts added to h + attention
    for other in alike[1:]:
        np.testing.assert_array_equal(other, alike[0])  # the same on every chip
    c = _published(full)
    whole = reference.layer_fns(c)["sliding_attention"](h[0], lp)
    np.testing.assert_allclose((alike[0] + sum(routed))[0], whole, atol=2e-5)
    held_whole = layer_out(full, lp, partial(mellum._routed_ffn, config=full, valid=None))
    np.testing.assert_allclose(held_whole[0], whole, atol=2e-5)
    assert float(jnp.abs(sum(routed)).max()) > 0.1  # the routed part is no rounding error


def test_engine_records_routing_and_window_blocks_as_counted_by_hand(monkeypatch):
    """The ring's ``atpu.serve.moe`` records (one a model call, per layer),
    with ``held`` and ``top_k``, and ``window_blocks`` on ``atpu.serve.build``:
    every pair is local, the blocks follow from the rows' lengths."""
    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=4096))
    cfg = _config()
    params = init_mellum(cfg, jax.random.PRNGKey(6))
    engine = ServingEngine(params, cfg, num_blocks=65, block_size=BLOCK, max_slots=2,
                           cache_dtype=jnp.float32, lattice=BucketLattice((2,), (16,), (16, 32)))
    assert engine.window == WINDOW
    rng = np.random.default_rng(2)
    long, short = (engine.submit(rng.integers(0, 256, n).astype(np.int32), 5) for n in (70, 10))
    engine.run()
    moe = [key for _, _, _, key in tracing.recorded("atpu.serve.moe")]
    prefill = [r for r in moe if r["kind"] == "prefill"]
    assert [r["tokens"] for r in prefill] == [32, 32, 6, 10]  # 70 in chunks of 32; padding not counted
    assert all((r["held"], r["top_k"]) == (16, 4) for r in moe)
    assert all(r["local_pairs"] == [4 * r["tokens"]] * 4 for r in moe)  # every pair lands here
    # layer 0's FFN sees the embeddings after its attention half: the most-loaded expert of
    # the first chunk is the hand count's
    stats = engine.stats()
    assert stats["moe_tokens"] == sum(r["tokens"] for r in moe) == 70 + 10 + 2 * 4
    assert stats["moe_local_pairs"] == 4 * 4 * stats["moe_tokens"]
    assert stats["moe_calls"] == 4 * len(moe)
    assert all(1 <= hit <= 16 and load * hit >= 4 * r["tokens"] for r in moe
               for hit, load in zip(r["experts_hit"], r["max_expert_load"]))
    builds = [key for _, _, _, key in tracing.recorded("atpu.serve.build")]
    # first decode batch: rows of 71 and 11 tokens (prompt + the prefill's token), blocks of 8
    assert builds[0]["live_blocks"] == 9 + 2
    # the long row's window starts at token 71 - 32 = 39, in block 4: it walks blocks 4-8
    assert builds[0]["window_blocks"] == (9 - 4) + 2
    assert stats["decode_blocks_window"] == sum(b["window_blocks"] for b in builds)


# ------------------------------------------------------------------- what is shared, what refuses


def test_the_attention_half_is_the_llama_layers_and_the_engine_knows_no_model():
    from accelerate_tpu.models import transformer
    from accelerate_tpu.serving import engine, kv_pager, scheduler

    source = inspect.getsource(mellum)
    assert "llama_layer(" in source and "apply_rope" not in source and "rms_norm" not in source
    assert inspect.signature(transformer.llama_layer).parameters["ffn"].default is None
    for module in (engine, kv_pager, scheduler):
        assert "mellum" not in inspect.getsource(module).lower()


def test_what_is_written_for_a_llama_config_refuses_the_model_by_name():
    from accelerate_tpu.generation import greedy_generate
    from accelerate_tpu.serving.replica import ReplicaSpec

    cfg = _config()
    params = init_mellum(cfg, jax.random.PRNGKey(0))
    with pytest.raises(TypeError, match="generation.py decodes a LlamaConfig; serve a MellumConfig"):
        greedy_generate(params, np.zeros((1, 4), np.int32), cfg, max_new_tokens=2)
    with pytest.raises(TypeError, match="a MellumConfig has no draft"):
        ServingEngine(params, cfg, num_blocks=9, block_size=BLOCK, spec_tokens=2, draft_layers=1)
    # disaggregated serving (`disagg.KVHandoff` travels between replicas) builds every replica's
    # model from a `ReplicaSpec`, whose `model` holds a LlamaConfig's fields and no other's
    with pytest.raises(TypeError, match="LlamaConfig.* unexpected keyword argument 'head_dim'"):
        ReplicaSpec(model=dataclasses.asdict(cfg)).config()
    with pytest.raises(ValueError, match="layer_types"):
        _config(layer_types=("full_attention",))
    with pytest.raises(ValueError, match="outside the router's range"):
        _config(experts_held=8, first_expert=12)
    assert _config(n_layers=8).layer_types[3::4] == ("full_attention",) * 2  # every fourth is full


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(open(reference.__file__).read())
    imported = [alias.name for node in ast.walk(tree) if isinstance(node, ast.Import)
                for alias in node.names]
    imported += [node.module or "" for node in ast.walk(tree) if isinstance(node, ast.ImportFrom)]
    assert imported and not [name for name in imported if name.split(".")[0] == "accelerate_tpu"]
