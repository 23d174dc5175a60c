"""Plain reference of the ``lfm2`` kind (LiquidAI LFM2-24B-A2B is of this
shape): the forward pass of one sequence in straightforward ``jax.numpy`` and
float32, no kernels, no cache, no state carried from call to call, no
batching, no sorting of tokens by expert; nothing is imported from
``accelerate_tpu``. Run it under ``jax.default_matmul_precision("highest")``
(the serve check does): on a TPU a float32 matrix multiplication otherwise
runs in bf16 passes.

Written from the model's public ``config.json``. One layer, input ``h [T, D]``:

    h1 = h  + op(RMSNorm_operator(h))             by layer_types[l]
    h2 = h1 + ffn(RMSNorm_ffn(h1))                dense for l < num_dense_layers, routed after

    op, `conv` layer, input u:
      [B, C, x] = split3(u W_in)                  W_in [D, 3D], in that order, no bias
      z   = B * x                                 elementwise
      c_t = sum_{j=0..K-1} w[:, j] z_{t-(K-1)+j}  K = conv_L_cache taps a channel, w [D, K];
                                                  z is zero before position 0; no positions
      out = (C * c) W_out
    op, `full_attention` layer, input u:
      q, k, v = u Wq, u Wk, u Wv                  n_heads / n_kv_heads heads of D / n_heads
      q, k    each head through RMSNorm over the head size (q_layernorm, k_layernorm),
              THEN rotated half-split: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin),
              angle pos * theta^(-2i / head size)
      out     = softmax(q k^T / sqrt(head size), causal) v Wo      4 query heads share a key head
    ffn, dense:   (silu(y W1) * (y W3)) W2
    ffn, routed:  s = sigmoid(y Wr)               over all num_experts router outputs
                  chosen  = the num_experts_per_tok largest of s + expert_bias
                  weights = s[chosen] / (sum of s[chosen] + 1e-6) * routed_scaling_factor
                  sum over chosen of weight_i E_i(y),  E(y) = (silu(y Wg) * (y Wu)) Wd

and after the last layer ``logits = RMSNorm(h) W_emb^T`` (tied) or ``W_head``.

Departures from the published model, each also in the configuration's file:
the embeddings are tied (``config.json`` does not say); the ``1e-6`` in the
weights' sum is the family's public modelling code's, not a key; the router,
its bias and the weights are float32 (everything here is); the published
name of the last norm is ``embedding_norm``, the tree's is ``final_norm``.
Where the weights hold ``held`` of the ``num_experts`` routed experts
(``first_expert .. first_expert + held``, a chip's share in the CPU tests;
the benchmark's cell holds all 64) a chosen expert that is not held adds
nothing.

So that it fits beside the served weights at the cell's size: a layer's
parameters arrive in the program's dtype and are upcast here, the routed
experts one at a time into a running sum (``lax.scan``), and attention runs a
block of queries at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
WEIGHT_EPS = 1e-6


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def conv_operator(u, lp):
    """The gated short convolution over one sequence ``u [T, D]``: the sum
    over taps of ``z`` shifted down by ``K - 1 - j`` rows, zeros shifted in."""
    T, D = u.shape
    gate_in, gate_out, x = jnp.split(u @ lp["in_proj"]["kernel"], 3, axis=-1)
    z = gate_in * x
    w = lp["conv"]["kernel"]  # [D, K]
    taps = w.shape[1]
    c = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads z at t - back
        shifted = jnp.concatenate([jnp.zeros((back, D), z.dtype), z], axis=0)[:T]
        c = c + w[:, j] * shifted
    return (gate_out * c) @ lp["out_proj"]["kernel"]


def _rope(x, theta: float):
    """``x [T, H, D]`` turned half-split by ``pos * theta^(-2i / D)``."""
    D = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, D, 2, dtype=np.float64) / D), jnp.float32)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_operator(u, lp, *, n_heads: int, n_kv_heads: int, theta: float, eps: float):
    """Grouped-query causal attention over one sequence ``u [T, D]``, the
    query/key norm before the rotary turn; a block of queries at a time."""
    T = u.shape[0]
    q = (u @ lp["wq"]["kernel"]).reshape(T, n_heads, -1)
    k = (u @ lp["wk"]["kernel"]).reshape(T, n_kv_heads, -1)
    v = (u @ lp["wv"]["kernel"]).reshape(T, n_kv_heads, -1)
    q = _rope(_rms_norm(q, lp["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, lp["k_norm"]["scale"], eps), theta)
    D = q.shape[-1]
    k, v = jnp.repeat(k, n_heads // n_kv_heads, axis=1), jnp.repeat(v, n_heads // n_kv_heads, axis=1)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    j = jnp.arange(T)

    def one_block(start):
        i = start + jnp.arange(block)
        scores = jnp.einsum("qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, start, block), k)
        scores = jnp.where((j[None, :] <= i[:, None])[None], scores / np.sqrt(D), -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    attn = jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, n_heads * D)
    return attn @ lp["wo"]["kernel"]


def dense_ffn(y, lp):
    return (jax.nn.silu(y @ lp["w1"]["kernel"]) * (y @ lp["w3"]["kernel"])) @ lp["w2"]["kernel"]


def routed_ffn(y, experts, *, top_k: int, scaling: float = 1.0, first_expert: int = 0):
    """The routed experts over ``y [T, D]``; ``experts`` may be in any float
    type and is upcast here, an expert's matrices one expert at a time."""
    s = jax.nn.sigmoid(y @ experts["router"]["kernel"].astype(jnp.float32))  # [T, E]
    biased = s + experts["expert_bias"].astype(jnp.float32)
    chosen = jnp.argsort(-biased, axis=-1)[:, :top_k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)  # the weights do not see the bias
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + WEIGHT_EPS) * scaling
    held = experts["w_gate"]["kernel"].shape[0]

    def add_expert(routed, args):  # every token through the expert, weight 0 where not chosen
        e, *matrices = args
        w_gate, w_up, w_down = _f32(matrices)
        weight = jnp.sum(jnp.where(chosen == first_expert + e, weights, 0.0), axis=-1)
        return routed + weight[:, None] * ((jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), (
        jnp.arange(held), experts["w_gate"]["kernel"], experts["w_up"]["kernel"],
        experts["w_down"]["kernel"]))
    return routed


def layer(h, lp, *, kind: str, routed: bool, n_heads: int, n_kv_heads: int, theta: float,
          eps: float, top_k: int, scaling: float = 1.0, first_expert: int = 0):
    """One layer over one sequence ``h [T, D]`` (float32). ``kind`` is its
    ``layer_types`` entry, ``routed`` whether its FFN is (``l >=
    num_dense_layers``)."""
    experts = lp.get("experts")
    lp = _f32({k: v for k, v in lp.items() if k != "experts"})
    if kind == "conv":
        h = h + conv_operator(_rms_norm(h, lp["op_norm"]["scale"], eps), lp)
    elif kind == "full_attention":
        h = h + attention_operator(_rms_norm(h, lp["attn_norm"]["scale"], eps), lp,
                                   n_heads=n_heads, n_kv_heads=n_kv_heads, theta=theta, eps=eps)
    else:
        raise ValueError(f"layer type {kind!r}: this reference knows conv and full_attention")
    y = _rms_norm(h, lp["mlp_norm"]["scale"], eps)
    if routed:
        return h + routed_ffn(y, experts, top_k=top_k, scaling=scaling, first_expert=first_expert)
    return h + dense_ffn(y, lp)


def layer_fns(c: dict, jit=lambda fn: fn) -> dict:
    """``{(layer kind, routed): fn(h, lp)}`` from the published keys ``c``
    (``jit`` wraps each once)."""
    return {
        (kind, routed): jit(functools.partial(
            layer, kind=kind, routed=routed, n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], theta=float(c["rope_parameters"]["rope_theta"]),
            eps=c["norm_eps"], top_k=c["num_experts_per_tok"],
            scaling=float(c.get("routed_scaling_factor", 1.0)),
            first_expert=c.get("first_expert_held", 0)))
        for kind in dict.fromkeys(c["layer_types"]) for routed in (False, True)}


def logits(params, ids, *, layer_types, num_dense_layers: int, eps: float, fns: dict):
    """Logits ``[T, V]`` of one sequence ``ids [T]``. ``layer_types`` has one
    entry a layer of ``params["layers"]`` (one tree a layer); ``fns`` is
    :func:`layer_fns`'."""
    h = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    for l, (kind, lp) in enumerate(zip(layer_types, params["layers"])):
        h = fns[kind, l >= num_dense_layers](h, lp)
    h = _rms_norm(h, params["final_norm"]["scale"].astype(jnp.float32), eps)
    if "lm_head" in params:
        return h @ params["lm_head"]["kernel"].astype(jnp.float32)
    return h @ params["embed_tokens"]["embedding"].astype(jnp.float32).T
