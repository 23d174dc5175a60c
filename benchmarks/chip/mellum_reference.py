"""Plain reference of the ``mellum`` kind (JetBrains Mellum2-12B-A2.5B-Instruct
is of this shape): the forward pass of one sequence in straightforward
``jax.numpy`` and float32, no kernels, no cache, no batching, no sorting of
tokens by expert; nothing is imported from ``accelerate_tpu``. Run it under
``jax.default_matmul_precision("highest")`` (the serve check does): on a TPU a
float32 matrix multiplication otherwise runs in bf16 passes.

Written from the model's public ``config.json``. One layer, input ``h [T, D]``:

    x      = RMSNorm(h)                           weight, no bias
    q,k,v  = x Wq, x Wk, x Wv                     n_heads / n_kv_heads heads of head_dim, no
                                                  bias, no query/key norm
    q, k   rotated over all of head_dim, half-split: (x1, x2) -> (x1 cos - x2 sin,
           x2 cos + x1 sin) with angle pos * inv_freq_i, i = 0 .. head_dim / 2 - 1
      sliding_attention layer:  inv_freq_i = theta^(-2i / head_dim); key j is seen by
                                query i iff j <= i and i - j < sliding_window
      full_attention layer:     YaRN (see `yarn_inv_freq`), cos and sin both times
                                attention_factor; key j seen iff j <= i
    h1     = h + softmax(q k^T / sqrt(head_dim)) v Wo      8 query heads share a key head
    y      = RMSNorm(h1)
    p      = softmax(y Wr)                        over all num_experts router outputs
    routed = sum over the experts_per_token largest p_i, of (p_i / their sum) E_i(y)
    E(y)   = (silu(y Wg) * (y Wu)) Wd
    h2     = h1 + routed                          no shared expert, nothing dropped

and after the last layer ``logits = RMSNorm(h) W_head`` with an untied head.

Departures from the published model, each also in the configuration's file:
no query/key norm and no multi-token-prediction head (the config has no key
for either); the window counts the query; the router's arithmetic is float32
(everything here is); YaRN's ``truncate`` takes its default (the correction
range is floored and ceiled). Where the weights hold ``held`` of the
``num_experts`` routed experts (``first_expert .. first_expert + held``, a
chip's share in the CPU tests; the benchmark's cell holds all 64) a chosen
expert that is not held adds nothing.

So that it fits beside the served weights at the cell's size: a layer's
parameters arrive in the program's dtype and are upcast here, the routed
experts one at a time into a running sum (``lax.scan``), and attention runs a
block of queries at a time (32 heads x 4096 x 4096 scores would be 2.1 GB).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def plain_inv_freq(head_dim: int, theta: float):
    return theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)


def yarn_inv_freq(head_dim: int, theta: float, *, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's inverse frequencies, ``[head_dim / 2]``. Pair ``i`` makes ``r``
    turns over ``original_max`` positions at ``i = corr(r)``; pairs faster than
    ``beta_fast`` turns keep their frequency, pairs slower than ``beta_slow``
    are divided by ``factor``, and a linear ramp blends those between."""
    def corr(r):
        return head_dim * math.log(original_max / (2 * math.pi * r)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    plain = plain_inv_freq(head_dim, theta)
    return (1.0 - ramp) * plain + ramp * plain / factor


def _rope(x, inv_freq, scale: float):
    """``x [T, H, D]`` turned half-split by ``pos * inv_freq`` (float32), cos
    and sin both times ``scale``."""
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv_freq  # [T, 1, D/2]
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window):
    """``q [T, H, D]`` against ``k, v [T, Hkv, D]``, causal, within ``window``
    if there is one; a block of queries at a time."""
    T, H, D = q.shape
    group = H // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    j = jnp.arange(T)

    def one_block(start):
        i = start + jnp.arange(block)
        seen = j[None, :] <= i[:, None]
        if window is not None:
            seen = seen & (i[:, None] - j[None, :] < window)
        scores = jnp.einsum("qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, start, block), k)
        scores = jnp.where(seen[None], scores / np.sqrt(D), -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    return jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, H * D)


def layer(h, lp, *, window, inv_freq, rope_scale: float, n_heads: int, n_kv_heads: int,
          eps: float, top_k: int, first_expert: int = 0):
    """One layer over one sequence ``h [T, D]`` (float32). ``window`` None is
    a full-attention layer; ``inv_freq`` (a tuple of floats) and ``rope_scale``
    are its kind's rotary table. ``lp`` may be in any float type: it is upcast
    here, the routed experts one at a time."""
    T = h.shape[0]
    experts, lp = lp["experts"], _f32({k: v for k, v in lp.items() if k != "experts"})
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    x = _rms_norm(h, lp["attn_norm"]["scale"], eps)
    q = _rope((x @ lp["wq"]["kernel"]).reshape(T, n_heads, -1), inv_freq, rope_scale)
    k = _rope((x @ lp["wk"]["kernel"]).reshape(T, n_kv_heads, -1), inv_freq, rope_scale)
    v = (x @ lp["wv"]["kernel"]).reshape(T, n_kv_heads, -1)
    h = h + _attention(q, k, v, window) @ lp["wo"]["kernel"]

    y = _rms_norm(h, lp["mlp_norm"]["scale"], eps)
    scores = jax.nn.softmax(y @ experts["router"]["kernel"].astype(jnp.float32), axis=-1)  # [T, E]
    best, chosen = jax.lax.top_k(scores, top_k)
    weights = best / jnp.sum(best, axis=-1, keepdims=True)
    held = experts["w_gate"]["kernel"].shape[0]

    def add_expert(routed, args):  # every token through the expert, weight 0 where not chosen
        e, *matrices = args
        w_gate, w_up, w_down = _f32(matrices)
        weight = jnp.sum(jnp.where(chosen == first_expert + e, weights, 0.0), axis=-1)
        return routed + weight[:, None] * ((jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        jnp.arange(held), experts["w_gate"]["kernel"], experts["w_up"]["kernel"],
        experts["w_down"]["kernel"]))
    return h + routed


def rope_tables(c: dict) -> dict:
    """``{layer kind: (inv_freq as a tuple, scale)}`` from the published
    ``rope_parameters`` and ``head_dim``."""
    tables = {}
    for kind, p in c["rope_parameters"].items():
        if p["rope_type"] == "yarn":
            inv = yarn_inv_freq(
                c["head_dim"], p["rope_theta"], factor=p["factor"],
                original_max=p["original_max_position_embeddings"],
                beta_fast=p.get("beta_fast", 32.0), beta_slow=p.get("beta_slow", 1.0))
            scale = p.get("attention_factor") or 0.1 * math.log(p["factor"]) + 1.0
        elif p["rope_type"] == "default":
            inv, scale = plain_inv_freq(c["head_dim"], p["rope_theta"]), 1.0
        else:
            raise ValueError(f"rope_type {p['rope_type']!r}: this reference knows default and yarn")
        tables[kind] = (tuple(float(f) for f in inv), float(scale))
    return tables


def layer_fns(c: dict, jit=lambda fn: fn) -> dict:
    """``{layer kind: fn(h, lp)}`` from the published keys ``c``: the layer
    with its kind's window and rotary table bound (``jit`` wraps each once)."""
    tables = rope_tables(c)
    fns = {}
    for kind, (inv_freq, scale) in tables.items():
        window = c["sliding_window"] if kind == "sliding_attention" else None
        fns[kind] = jit(functools.partial(
            layer, window=window, inv_freq=inv_freq, rope_scale=scale,
            n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
            eps=c["rms_norm_eps"], top_k=c["num_experts_per_tok"],
            first_expert=c.get("first_expert_held", 0)))
    return fns


def logits(params, ids, *, layer_types, eps: float, fns: dict):
    """Logits ``[T, V]`` of one sequence ``ids [T]``. ``layer_types`` has one
    entry a layer of ``params["layers"]`` (one tree a layer); ``fns`` is
    :func:`layer_fns`'."""
    h = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    for kind, lp in zip(layer_types, params["layers"]):
        h = fns[kind](h, lp)
    h = _rms_norm(h, params["final_norm"]["scale"].astype(jnp.float32), eps)
    return h @ params["lm_head"]["kernel"].astype(jnp.float32)
