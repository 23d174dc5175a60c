"""Plain reference of the ``cohere2_moe`` kind (CohereLabs command-a-plus-05-2026
is of this shape): the forward pass of one sequence in straightforward
``jax.numpy`` and float32, no kernels, no cache, no batching, no sorting of
tokens by expert; nothing is imported from ``accelerate_tpu``. Run it under
``jax.default_matmul_precision("highest")`` (the serve check does): on a TPU a
float32 matrix multiplication otherwise runs in bf16 passes.

Written from the model's public ``config.json`` and its description. One layer,
input ``h [T, D]``:

    u      = LayerNorm(h)            mean subtracted, variance normalised, weight, no bias
    q,k,v  = u Wq, u Wk, u Wv        n_heads / n_kv_heads heads of head_dim, no bias, no q/k norm
    sliding_attention layer:  q, k rotated over all of head_dim in interleaved pairs
                              (x[2i], x[2i+1]) by pos * theta^(-2i/head_dim); key j is
                              seen by query i iff j <= i and i - j < sliding_window
    full_attention layer:     no rotation, no positions at all; key j seen iff j <= i
    attn   = softmax(q k^T / sqrt(head_dim)) v Wo     16 query heads share a key head
    s      = sigmoid(u Wr)           over all num_experts router outputs
    routed = sum over the experts_per_token largest s_i, of (s_i / their sum) E_i(u)
    shared = (1 / num_shared_experts) sum of S_j(u)
    E(u)   = (silu(u Wg) * (u Wu)) Wd   (the same form for S)
    h'     = h + attn + routed + shared     one norm, both branches read u

and after the last layer ``logits = LayerNorm(h) E^T * logit_scale`` with the
tied embedding ``E``.

Departures from the published model, each also in the configuration's file:

- **The chip's share.** The weights hold ``held`` of the ``num_experts``
  routed experts (``first_expert .. first_expert + held``) and a slice of the
  vocabulary. The router keeps its width and its experts per token; a chosen
  expert that is not held adds nothing, here as in the program, and that
  partial result goes on to the next layer. Logits are over the slice.
- ``intermediate_size`` is read as the width of ONE expert, routed or shared.
- ``shared_expert_combination_strategy: "average"`` is read as the mean of the
  shared experts' outputs.
- The window counts the query: ``i - j < sliding_window``.
- The router's arithmetic is float32 (everything here is).
- The vision tower is left out: the configuration holds the language model.

So that it fits beside the served weights at the cell's size: a layer's
parameters arrive in the program's dtype and are upcast here, the routed
experts one at a time (``lax.map``), and attention runs a block of queries at a
time (128 heads x 6400 x 6400 scores would be 21 GB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _layer_norm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale


def _rope_interleaved(x, positions, theta: float):
    """``x [T, H, D]``: the pair ``(x[2i], x[2i+1])`` turns by ``pos * theta^(-2i/D)``."""
    D = x.shape[-1]
    angle = positions[:, None, None] * (1.0 / theta ** (jnp.arange(0, D, 2) / D))  # [T, 1, D/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _gated_mlp(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


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


def layer(h, lp, *, window, n_heads: int, n_kv_heads: int, eps: float, theta: float,
          top_k: int, first_expert: int):
    """One layer over one sequence ``h [T, D]`` (float32). ``window`` None is a
    full-attention layer: no rotation and no window. ``lp`` may be in any float
    type: it is upcast here, the routed experts one at a time."""
    T = h.shape[0]
    experts, lp = lp["experts"], _f32({k: v for k, v in lp.items() if k != "experts"})
    u = _layer_norm(h, lp["norm"]["scale"], eps)
    q = (u @ lp["wq"]["kernel"]).reshape(T, n_heads, -1)
    k = (u @ lp["wk"]["kernel"]).reshape(T, n_kv_heads, -1)
    v = (u @ lp["wv"]["kernel"]).reshape(T, n_kv_heads, -1)
    if window is not None:
        pos = jnp.arange(T, dtype=jnp.float32)
        q, k = _rope_interleaved(q, pos, theta), _rope_interleaved(k, pos, theta)
    attn = _attention(q, k, v, window) @ lp["wo"]["kernel"]

    scores = jax.nn.sigmoid(u @ experts["router"]["kernel"].astype(jnp.float32))  # [T, E]
    best, chosen = jax.lax.top_k(scores, top_k)
    weights = best / jnp.sum(best, axis=-1, keepdims=True)
    held = experts["w_gate"]["kernel"].shape[0]

    def one_expert(args):  # every token through the expert, weighted by 0 where not chosen
        e, w_gate, w_up, w_down = args
        weight = jnp.sum(jnp.where(chosen == first_expert + e, weights, 0.0), axis=-1)
        return weight[:, None] * _gated_mlp(u, *_f32((w_gate, w_up, w_down)))

    routed = jnp.sum(jax.lax.map(one_expert, (
        jnp.arange(held), experts["w_gate"]["kernel"], experts["w_up"]["kernel"],
        experts["w_down"]["kernel"])), axis=0)
    s = lp["shared"]
    n_shared = s["w_gate"]["kernel"].shape[0]
    shared = sum(_gated_mlp(u, s["w_gate"]["kernel"][i], s["w_up"]["kernel"][i],
                            s["w_down"]["kernel"][i]) for i in range(n_shared)) / n_shared
    return h + attn + routed + shared


def logits(params, ids, *, windows, eps: float, logit_scale: float = 1.0, layer_fns=None,
           **shape):
    """Logits ``[T, V]`` of one sequence ``ids [T]``. ``windows`` has one entry
    a layer: its window, or None for a full-attention layer.
    ``params["layers"]`` holds one tree a layer; ``layer_fns`` maps a window
    to a jitted :func:`layer` (built once a run by the kind file)."""
    layer_fns = layer_fns or {w: functools.partial(layer, window=w, eps=eps, **shape)
                              for w in set(windows)}
    embedding = params["embed_tokens"]["embedding"]
    h = embedding[ids].astype(jnp.float32)
    for i, window in enumerate(windows):
        h = layer_fns[window](h, params["layers"][i])
    h = _layer_norm(h, params["final_norm"]["scale"].astype(jnp.float32), eps)
    return (h @ embedding.astype(jnp.float32).T) * logit_scale
