"""KV-cache autoregressive decoding: the runnable counterpart of the
reference's big-model-inference benchmark (BASELINE config #5, reference
``benchmarks/big_model_inference/README.md:27-37`` — model load time +
seconds/token with device_map dispatch).

Two paths, matching the two ways params can live:

- :func:`greedy_generate` — resident params (replicated or GSPMD-sharded):
  one jitted decode step; the cache is a stacked ``[L, B, max_len, Hkv, D]``
  pytree threaded functionally (donated each step), the layer loop is the same
  ``lax.scan`` as training so TP/FSDP shardings apply unchanged.
- :func:`generate_dispatched` — offloaded params (:class:`DispatchedParams`
  from ``device_map``-style dispatch): params are re-staged PER LAYER
  (``unstack_layer_params``) so paging granularity matches the reference's
  per-module hooks (``hooks.py:331-407``); each token pages layers through the
  execution device with one-stage-ahead prefetch while a jitted single-layer
  step computes.

Static shapes throughout: the cache is pre-sized to ``max_len`` and positions
mask the unwritten tail — no dynamic shapes reach XLA.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .models.transformer import LlamaConfig, llama_head, llama_layer, llama_rope
from .ops.attention import masked_attention
from .ops.flash_attention import kv_lane_pack

__all__ = [
    "init_kv_cache",
    "generation_shardings",
    "serving_shardings",
    "greedy_generate",
    "sample_generate",
    "beam_generate",
    "sample_token_logits",
    "generate_dispatched",
    "unstack_layer_params",
]


def init_kv_cache(config: LlamaConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked cache: {"k","v"}: [L, B, max_len, Hkv, D]. The single-stream
    decode of this module runs the llama layer (``models.transformer.
    llama_layer``) over a contiguous cache: another model is refused here,
    where every generate path starts, and is served through
    ``serving.ServingEngine``."""
    if not isinstance(config, LlamaConfig):
        raise TypeError(
            f"generation.py decodes a LlamaConfig; serve a {type(config).__name__} "
            "through serving.ServingEngine")
    shape = (config.n_layers, batch_size, max_len, config.n_kv_heads, config.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def generation_shardings(mesh, batch_size: int, config: LlamaConfig):
    """(prompt_sharding, cache_sharding) for decoding over ``mesh`` — the
    multi-chip leg of BASELINE config #5 ("dispatch_model generate, multi-chip
    sharding"; reference shards generate via ``device_map`` across GPUs,
    ``big_modeling.py:309``; here the TPU-native form is GSPMD over the mesh).

    Placement policy (an axis is used only where it divides evenly; anything
    else stays replicated over that axis):

    - batch over the data axes (``dp_replicate``/``dp_shard``/``dp``), claimed
      greedily one axis at a time while the joint shard count still divides the
      batch — batched serving parallelism;
    - KV heads over ``tp`` — with the params TP-sharded by
      ``models.transformer.llama_shard_rules`` this reproduces the Megatron
      decode dataflow: column-parallel QKV writes head-sharded cache entries,
      attention runs per-head-shard, row-parallel ``wo`` psums the output.

    Single-controller view: callers pass the GLOBAL batch (the test CPU mesh
    and a one-host TPU are both fully addressable; multihost
    serving would hand each process its slice via
    ``jax.make_array_from_process_local_data`` before calling decode).
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    axes = dict(mesh.shape)
    # greedy per-axis: claim each data axis whose size still divides the batch
    used: list = []
    used_size = 1
    for a in ("dp_replicate", "dp_shard", "dp"):
        size = axes.get(a, 1)
        if size > 1 and batch_size % (used_size * size) == 0:
            used.append(a)
            used_size *= size
    batch: Any = None if not used else (used[0] if len(used) == 1 else tuple(used))
    tp = "tp" if axes.get("tp", 1) > 1 and config.n_kv_heads % axes["tp"] == 0 else None
    prompt_sharding = NamedSharding(mesh, P(batch, None))
    # cache leaves: [L, B, max_len, Hkv, D]
    cache_sharding = NamedSharding(mesh, P(None, batch, None, tp, None))
    return prompt_sharding, cache_sharding


def serving_shardings(mesh, config: LlamaConfig):
    """NamedSharding for the serving engine's paged block pool
    ``[L, num_blocks, block_size, Hkv, D]`` — the paged-cache leg of the same
    placement policy as :func:`generation_shardings`: KV heads over ``tp``
    (where divisible) so the Megatron decode dataflow carries over unchanged;
    the block axis stays replicated because block tables address the WHOLE
    pool (any sequence may hold any block, so there is no batch axis to
    shard — batch parallelism for serving is a scheduler concern: run one
    engine per data-parallel replica).

    The spec is CANONICALIZED (PR 9's ``canonicalize_spec``: trailing
    ``None`` dims trimmed) so the placed pool's sharding compares equal to
    the canonical form GSPMD hands back on every step OUTPUT. The
    non-canonical ``P(None, None, None, tp, None)`` made the first warmed
    prefill bucket — the only one compiled against the freshly
    ``device_put`` pool — re-specialize on its first steady-state call on a
    multi-device mesh (the "4x2 recompile" noted in PR 14)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from .parallel.sharding import canonicalize_spec

    axes = dict(mesh.shape)
    heads = config.n_kv_heads // kv_lane_pack(config.n_kv_heads, config.head_dim)  # a row's
    tp = "tp" if axes.get("tp", 1) > 1 and heads % axes["tp"] == 0 else None
    return NamedSharding(mesh, canonicalize_spec(P(None, None, None, tp, None), axes))


def _place_for_mesh(mesh, prompt_ids, cache, config):
    """device_put prompt + cache per :func:`generation_shardings`."""
    prompt_sharding, cache_sharding = generation_shardings(mesh, prompt_ids.shape[0], config)
    prompt_ids = jax.device_put(prompt_ids, prompt_sharding)
    cache = jax.tree_util.tree_map(lambda c: jax.device_put(c, cache_sharding), cache)
    return prompt_ids, cache


def _cached_attention(q, k_cache, v_cache, q_positions, scale=None):
    """q: [B, S, H, D]; caches [B, max_len, Hkv, D]; q_positions [S] — attend
    causally over all cache slots with position <= the query's position."""
    max_len = k_cache.shape[1]
    kv_pos = jnp.arange(max_len)
    allow = kv_pos[None, :] <= q_positions[:, None]  # [S, max_len]
    return masked_attention(q, k_cache, v_cache, allow[None, None], scale)


def _cached_layer(layer_params, h, k_cache, v_cache, positions, cos, sin, config, mesh=None):
    """One decoder layer over S tokens at ``positions [S]``: ``llama_layer``
    whose attention writes the [B,max,·,·] caches in place
    (dynamic_update_slice along the sequence axis), then attends over them."""

    def attend(q, k, v):
        nonlocal k_cache, v_cache
        at = (0, positions[0], 0, 0)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), at)
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), at)
        return _cached_attention(q, k_cache, v_cache, positions)

    h, _ = llama_layer(
        layer_params, h, jnp.broadcast_to(positions[None], h.shape[:2]), cos, sin, config,
        attend, mesh=mesh)
    return h, k_cache, v_cache


def _forward_cached(params, ids, cache, start_pos, config: LlamaConfig, mesh=None):
    """Forward S tokens starting at ``start_pos`` against the cache.
    Returns (logits [B, S, vocab], new_cache)."""
    cos, sin = llama_rope(config)
    positions = start_pos + jnp.arange(ids.shape[1])

    def layer(h, xs):
        layer_params, k_c, v_c = xs
        h, k_c, v_c = _cached_layer(
            layer_params, h, k_c, v_c, positions, cos, sin, config, mesh=mesh)
        return h, (k_c, v_c)

    h, (k_new, v_new) = jax.lax.scan(
        layer, params["embed_tokens"]["embedding"][ids],
        (params["layers"], cache["k"], cache["v"]), unroll=config.unroll_layers,
    )
    return llama_head(params, h, config), {"k": k_new, "v": v_new}


def sample_token_logits(logits, key, *, temperature: float = 1.0, top_k: int = 0,
                        top_p: float = 1.0):
    """One sampling step over ``logits [B, V]`` (jit-friendly; knobs are
    Python-static): temperature scaling, then top-k truncation, then nucleus
    (top-p) — the standard HF sampler composition. ``temperature == 0`` is
    greedy argmax."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    logits = logits.astype(jnp.float32) / temperature
    if top_k and top_k > 0:
        k = min(top_k, logits.shape[-1])  # HF clamps oversize top_k
        kth = jax.lax.top_k(logits, k)[0][..., -1:]  # [B, 1]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        cum = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
        # smallest prefix reaching mass >= top_p (always keeps >= 1 token)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def _cached_generate(
    params,
    prompt_ids,  # [B, S_prompt] (non-ragged; pad+mask upstream if needed)
    config: LlamaConfig,
    max_new_tokens: int,
    eos_token_id: Optional[int],
    cache_dtype,
    return_stats: bool,
    warmup: bool,
    select,  # (logits [B, V], key) -> next token [B]
    rng_key,
    mesh=None,
):
    """Shared KV-cache decode core: prefill once, then the ENTIRE decode loop
    in one compiled ``lax.scan`` (a single host round-trip — per-token fetches
    would serialize on host/ICI latency). Sequences that hit ``eos_token_id``
    keep emitting it; there is no data-dependent early exit under jit.

    With ``mesh``, the prompt and KV cache are placed per
    :func:`generation_shardings` (batch over data axes, KV heads over ``tp``)
    and GSPMD propagates the params' shardings through the compiled scan —
    params should already be on the mesh (``parallel.sharding.shard_params``
    with ``models.transformer.llama_shard_rules``)."""
    prompt_ids = jnp.asarray(prompt_ids)
    B, S = prompt_ids.shape
    max_len = S + max_new_tokens
    cache = init_kv_cache(config, B, max_len, cache_dtype)
    if mesh is not None:
        prompt_ids, cache = _place_for_mesh(mesh, prompt_ids, cache, config)
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)

    prefill = jax.jit(partial(_forward_cached, config=config, mesh=mesh))

    @partial(jax.jit, donate_argnums=(1,))
    def decode_all(params, cache, first_tok, key):
        def body(carry, i):
            tok, finished, cache = carry
            logits, cache = _forward_cached(params, tok[:, None], cache, S + i - 1, config, mesh=mesh)
            nxt = select(logits[:, -1], jax.random.fold_in(key, i)).astype(tok.dtype)
            if eos_token_id is not None:
                nxt = jnp.where(finished, eos_token_id, nxt)
                finished = jnp.logical_or(finished, nxt == eos_token_id)
            return (nxt, finished, cache), nxt

        finished = (
            first_tok == eos_token_id if eos_token_id is not None else jnp.zeros((B,), bool)
        )
        (_, _, cache), toks = jax.lax.scan(
            body, (first_tok, finished, cache), jnp.arange(1, max_new_tokens)
        )
        return toks.T  # [B, max_new_tokens-1]

    def _first(logits):
        return select(logits[:, -1], jax.random.fold_in(rng_key, 0)).astype(prompt_ids.dtype)

    if warmup and max_new_tokens > 1:
        cache_w = init_kv_cache(config, B, max_len, cache_dtype)
        if mesh is not None:
            _, cache_w = _place_for_mesh(mesh, prompt_ids, cache_w, config)
        logits_w, cache_w = prefill(params, prompt_ids, cache_w, jnp.int32(0))
        jax.device_get(decode_all(params, cache_w, _first(logits_w), rng_key))

    t0 = time.time()
    logits, cache = prefill(params, prompt_ids, cache, jnp.int32(0))
    first_tok = _first(logits)
    first_host = np.asarray(jax.device_get(first_tok))  # forces prefill for timing
    prefill_s = time.time() - t0

    t0 = time.time()
    if max_new_tokens > 1:
        rest = np.asarray(jax.device_get(decode_all(params, cache, first_tok, rng_key)))
    else:
        rest = np.zeros((B, 0), first_host.dtype)
    decode_s = time.time() - t0
    generated = np.concatenate(
        [np.asarray(jax.device_get(prompt_ids)), first_host[:, None], rest], axis=1
    )
    if return_stats:
        n_decoded = max(max_new_tokens - 1, 1)
        return generated, {
            "prefill_seconds": prefill_s,
            "decode_tokens_per_sec": n_decoded * B / max(decode_s, 1e-9),
            "seconds_per_token": decode_s / n_decoded,
        }
    return generated


def greedy_generate(
    params,
    prompt_ids,
    config: LlamaConfig,
    max_new_tokens: int = 32,
    eos_token_id: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
    return_stats: bool = False,
    warmup: bool = False,
    mesh=None,
):
    """Jitted KV-cache greedy decoding for resident (replicated/sharded)
    params. Returns ids [B, S_prompt + max_new_tokens] (with a stats dict —
    prefill seconds, decode tokens/sec — when ``return_stats``); ``warmup``
    runs the decode once before timing so stats exclude compilation. Pass
    ``mesh`` (params already mesh-sharded) for multi-chip TP/DP decode — see
    :func:`generation_shardings`."""
    return _cached_generate(
        params, prompt_ids, config, max_new_tokens, eos_token_id, cache_dtype,
        return_stats, warmup,
        select=lambda logits, key: jnp.argmax(logits, axis=-1),
        rng_key=None,
        mesh=mesh,
    )


def sample_generate(
    params,
    prompt_ids,
    config: LlamaConfig,
    max_new_tokens: int = 32,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng_key=None,
    eos_token_id: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
    return_stats: bool = False,
    warmup: bool = False,
    mesh=None,
):
    """Jitted KV-cache SAMPLED decoding (temperature / top-k / nucleus), the
    counterpart of HF ``generate(do_sample=True)``. The PRNG key is folded per
    step inside the compiled scan, so a given (key, prompt, knobs) triple is
    fully deterministic; ``temperature=0`` degrades to greedy. ``mesh`` as in
    :func:`greedy_generate`."""
    return _cached_generate(
        params, prompt_ids, config, max_new_tokens, eos_token_id, cache_dtype,
        return_stats, warmup,
        select=partial(sample_token_logits, temperature=temperature,
                       top_k=top_k, top_p=top_p),
        rng_key=rng_key,
        mesh=mesh,
    )


def beam_generate(
    params,
    prompt_ids,  # [B, S_prompt]
    config: LlamaConfig,
    num_beams: int = 4,
    max_new_tokens: int = 32,
    eos_token_id: Optional[int] = None,
    length_penalty: float = 1.0,
    cache_dtype=jnp.bfloat16,
    return_scores: bool = False,
    mesh=None,
):
    """Jitted KV-cache beam search (deterministic highest-probability decode).

    Standard beam algorithm: prefill once at batch B, tile the cache to
    ``B * num_beams``, then each scanned step expands every live beam over the
    vocab, keeps the top ``num_beams`` of ``num_beams * V`` candidates, and
    REORDERS the KV cache with the surviving beams' parent indices (a gather
    on the cache batch axis — the whole loop stays one compiled scan, like the
    greedy/sampled paths). Finished beams (hit ``eos_token_id``) are frozen:
    their only continuation is another eos at zero log-prob, so their score is
    carried unchanged. Final ranking divides by
    ``generated_length^length_penalty`` (modern HF >= 4.35 semantics;
    1.0 = average log-prob over the generated tokens).

    Returns ids ``[B, S_prompt + max_new_tokens]`` for the best beam
    (``return_scores=True`` adds the [B] length-normalized scores).
    """
    prompt_ids = jnp.asarray(prompt_ids)
    B, S = prompt_ids.shape
    K = num_beams
    max_len = S + max_new_tokens
    V = config.vocab_size

    cache = init_kv_cache(config, B, max_len, cache_dtype)
    if mesh is not None:
        # beams tile the batch axis inside jit (B -> B*K), which preserves the
        # batch-axis divisibility, so the same placement policy applies
        prompt_ids, cache = _place_for_mesh(mesh, prompt_ids, cache, config)
    prefill = jax.jit(partial(_forward_cached, config=config, mesh=mesh))
    logits, cache = prefill(params, prompt_ids, cache, jnp.int32(0))

    @jax.jit
    def beam_all(params, cache, last_logits):
        # tile the cache over beams: [L, B, ...] -> [L, B*K, ...]
        cache = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c, K, axis=1), cache
        )
        logp0 = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)  # [B, V]
        scores0, tok0 = jax.lax.top_k(logp0, K)  # [B, K]
        finished0 = (
            tok0 == eos_token_id if eos_token_id is not None else jnp.zeros((B, K), bool)
        )
        # modern HF (>= 4.35) normalizes by GENERATED length only
        # (GenerationMixin._update_finished_beams: cur_len+1-decoder_prompt_len);
        # the pre-4.35 full-sequence divisor is legacy
        lengths0 = jnp.ones((B, K), jnp.int32)
        tokens0 = jnp.zeros((B, K, max_new_tokens), jnp.int32)
        tokens0 = tokens0.at[:, :, 0].set(tok0)

        def body(carry, i):
            tokens, scores, finished, lengths, cache = carry
            last = jax.lax.dynamic_index_in_dim(tokens, i - 1, axis=2)  # [B, K, 1]
            logits, cache = _forward_cached(
                params, last.reshape(B * K, 1), cache, S + i - 1, config, mesh=mesh
            )
            logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
            logp = logp.reshape(B, K, V)
            if eos_token_id is not None:
                # frozen beams may only emit eos again, at no score cost
                frozen = jnp.full((V,), -jnp.inf).at[eos_token_id].set(0.0)
                logp = jnp.where(finished[:, :, None], frozen[None, None], logp)
            cand = scores[:, :, None] + logp  # [B, K, V]
            new_scores, flat_idx = jax.lax.top_k(cand.reshape(B, K * V), K)
            parent = flat_idx // V  # [B, K]
            tok = (flat_idx % V).astype(jnp.int32)

            tokens = jnp.take_along_axis(tokens, parent[:, :, None], axis=1)
            tokens = tokens.at[:, :, i].set(tok)
            finished = jnp.take_along_axis(finished, parent, axis=1)
            lengths = jnp.take_along_axis(lengths, parent, axis=1)
            lengths = jnp.where(finished, lengths, lengths + 1)
            if eos_token_id is not None:
                finished = jnp.logical_or(finished, tok == eos_token_id)
            # reorder the cache: [L, B*K, ...] -> group beams -> gather parents
            def cache_reorder(c):
                shaped = c.reshape((c.shape[0], B, K) + c.shape[2:])
                idx = parent.reshape((1, B, K) + (1,) * (shaped.ndim - 3))
                return jnp.take_along_axis(shaped, idx, axis=2).reshape(c.shape)

            cache = jax.tree_util.tree_map(cache_reorder, cache)
            return (tokens, new_scores, finished, lengths, cache), None

        (tokens, scores, finished, lengths, cache), _ = jax.lax.scan(
            body,
            (tokens0, scores0, finished0, lengths0, cache),
            jnp.arange(1, max_new_tokens),
        )
        norm = scores / jnp.power(lengths.astype(jnp.float32), length_penalty)
        best = jnp.argmax(norm, axis=1)  # [B]
        best_tokens = jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0]
        best_score = jnp.take_along_axis(norm, best[:, None], axis=1)[:, 0]
        return best_tokens, best_score

    best_tokens, best_score = beam_all(params, cache, logits[:, -1])
    out = np.concatenate(
        [np.asarray(jax.device_get(prompt_ids)), np.asarray(jax.device_get(best_tokens))],
        axis=1,
    )
    if return_scores:
        return out, np.asarray(jax.device_get(best_score))
    return out


# ---------------------------------------------------------------------------
# dispatched (offloaded) decoding


def unstack_layer_params(params, config: LlamaConfig) -> dict:
    """Re-stage stacked-layer params into per-layer subtrees so device-map
    dispatch pages ONE layer at a time (the reference's per-module hook
    granularity). ``layer_07`` etc. sort correctly for stage ordering."""
    stages = {"embed_tokens": params["embed_tokens"]}
    for i in range(config.n_layers):
        stages[f"layer_{i:03d}"] = jax.tree_util.tree_map(lambda x: x[i], params["layers"])
    stages["final_norm"] = params["final_norm"]
    if not config.tie_embeddings:
        stages["lm_head"] = params["lm_head"]
    return stages


def generate_dispatched(
    dispatched,  # DispatchedParams over unstack_layer_params(...) stages
    prompt_ids,
    config: LlamaConfig,
    max_new_tokens: int = 32,
    eos_token_id: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
    return_stats: bool = False,
    warmup: bool = False,
):
    """Greedy decoding with per-layer paged params (cpu/disk offload).

    Each forward pages layer stages through the execution device with
    one-ahead prefetch (reference ``AlignDevicesHook`` hot loop, §3.4); the
    jitted single-layer step is shared across layers so there is exactly one
    compile per (S, position-signature)."""
    prompt_ids = jnp.asarray(prompt_ids)
    B, S = prompt_ids.shape
    max_len = S + max_new_tokens
    cos, sin = llama_rope(config)

    per_layer_cache = [
        {
            "k": jnp.zeros((B, max_len, config.n_kv_heads, config.head_dim), cache_dtype),
            "v": jnp.zeros((B, max_len, config.n_kv_heads, config.head_dim), cache_dtype),
        }
        for _ in range(config.n_layers)
    ]

    layer_fn = jax.jit(
        lambda lp, h, kc, vc, positions: _cached_layer(lp, h, kc, vc, positions, cos, sin, config)
    )
    embed_fn = jax.jit(lambda emb, ids: emb["embedding"][ids])
    head_fn = jax.jit(lambda stages, h: llama_head(stages, h, config))

    layer_names = [f"layer_{i:03d}" for i in range(config.n_layers)]
    head_names = ("final_norm", "embed_tokens" if config.tie_embeddings else "lm_head")

    def forward(ids, start_pos):
        positions = start_pos + jnp.arange(ids.shape[1])
        dispatched.prefetch("embed_tokens")
        h = embed_fn(dispatched["embed_tokens"], ids)
        for i, name in enumerate(layer_names):
            if i + 1 < len(layer_names):
                dispatched.prefetch(layer_names[i + 1])
            lp = dispatched[name]
            cache_i = per_layer_cache[i]
            h, cache_i["k"], cache_i["v"] = layer_fn(
                lp, h, cache_i["k"], cache_i["v"], positions
            )
            dispatched.release(name)
        return head_fn({name: dispatched[name] for name in head_names}, h)

    t0 = time.time()
    logits = forward(prompt_ids, jnp.int32(0))
    next_tok = np.asarray(jax.device_get(jnp.argmax(logits[:, -1], axis=-1)))
    prefill_s = time.time() - t0

    tokens = [next_tok]
    finished = np.zeros((B,), bool)
    if eos_token_id is not None:
        finished |= next_tok == eos_token_id
    if warmup and max_new_tokens > 1:
        # the first seq-len-1 forward carries layer_fn's decode-signature
        # compile; greedy decode is deterministic, so repeating step 1 writes
        # the SAME cache values — the timed loop below re-runs it identically
        # with the compile excluded (same contract as greedy_generate warmup)
        logits = forward(jnp.asarray(tokens[-1])[:, None], jnp.int32(S))
        np.asarray(jax.device_get(logits[:, -1, 0]))  # force completion
    t0 = time.time()
    for i in range(1, max_new_tokens):
        logits = forward(jnp.asarray(tokens[-1])[:, None], jnp.int32(S + i - 1))
        tok = np.asarray(jax.device_get(jnp.argmax(logits[:, -1], axis=-1)))
        if eos_token_id is not None:
            tok = np.where(finished, eos_token_id, tok)
            finished |= tok == eos_token_id
        tokens.append(tok)
        if eos_token_id is not None and finished.all():
            break
    decode_s = time.time() - t0
    generated = np.concatenate(
        [np.asarray(jax.device_get(prompt_ids))] + [t[:, None] for t in tokens], axis=1
    )
    if return_stats:
        n_decoded = max(len(tokens) - 1, 1)
        return generated, {
            "prefill_seconds": prefill_s,
            "decode_tokens_per_sec": n_decoded * B / max(decode_s, 1e-9),
            "seconds_per_token": decode_s / n_decoded,
        }
    return generated
