"""Plain reference of the ``linear/evabyte`` kind (EvaByte 6.5B is of this
shape): the forward pass of one sequence in straightforward ``jax.numpy`` and
float32, no kernels, no cache, no batching; nothing is imported from
``accelerate_tpu``. Run it under ``jax.default_matmul_precision("highest")``
(the serve check does): on a TPU a float32 matrix multiplication otherwise
runs in bf16 passes.

Written from the model's public ``config.json`` and EVA (Zheng et al.,
arXiv:2302.04542, sections 4-5). ``W = window_size``, ``C = chunk_size``,
head size ``d``, ``n(x) = x / sqrt(mean(x^2) + eps) * (1 + g)``
(``norm_add_unit_offset``). One layer, input ``h [T, D]`` (float32, the
residual stream of ``fp32_skip_add``), position ``i``:

    x = n(h)
    q, k, v = x Wq, x Wk, x Wv                 n_heads heads of d, as many key heads
    q, k    rotated half-split: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin),
            angle i * theta^(-2m / d)
    chunk c = positions C c .. C c + C - 1, head h, learned mu_h, phi_h [d]:
      kbar_c = sum_j softmax_j(POOL_SCALE k_j . mu_h) k_j      (the rotated keys)
      vbar_c = sum_j softmax_j(POOL_SCALE k_j . phi_h) v_j
    w = i // W:
      o_i = softmax over { q_i . k_j / sqrt d : wW <= j <= i }
                       u { q_i . kbar_c / sqrt d : C c < wW }  applied to v_j, vbar_c
    h1 = h + o Wo
    h2 = h1 + (silu(n(h1) W1) * (n(h1) W3)) W2

and after the last layer ``logits = n(h) W_head[0]`` (the next byte's head
of ``num_pred_heads``; ``fp32_logits``).

Departures and readings, each also in the configuration's file: the pooling
logits are unscaled (``POOL_SCALE``, not a key of ``config.json``); the window
is blockwise, a query's window the ``W`` positions of ``i // W``
(``WINDOW_ALIGNMENT``), not the ``W`` positions before it; ``mixedp_attn`` is
read as float32 scores and softmax, which is all there is here.

So that a 32 768-position check fits beside the served weights: a layer's
parameters arrive in the program's dtype and are upcast here one layer at a
time, attention runs ``QUERY_BLOCK`` queries at a time (each block inside one
window) and the feed-forward a window of rows at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

POOL_SCALE = 1.0
WINDOW_ALIGNMENT = "blockwise"
QUERY_BLOCK = 512


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _norm(x, offset, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + offset)


def _rotary(T: int, d: int, theta: float):
    angles = np.outer(np.arange(T), 1.0 / theta ** (np.arange(0, d, 2) / d))
    return jnp.asarray(np.cos(angles), jnp.float32), jnp.asarray(np.sin(angles), jnp.float32)


def _turn(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def pooled_pairs(k, v, mu, phi, chunk: int):
    """``(kbar, vbar) [T / C, H, d]`` of keys and values ``[T, H, d]``: each
    chunk's keys and values weighted by the softmax over its ``C`` positions
    of ``POOL_SCALE k . mu`` and ``POOL_SCALE k . phi``, a head at a time."""
    T, H, d = k.shape
    kc, vc = k.reshape(T // chunk, chunk, H, d), v.reshape(T // chunk, chunk, H, d)
    a = jax.nn.softmax(POOL_SCALE * jnp.einsum("njhd,hd->njh", kc, mu), axis=1)
    b = jax.nn.softmax(POOL_SCALE * jnp.einsum("njhd,hd->njh", kc, phi), axis=1)
    return jnp.einsum("njh,njhd->nhd", a, kc), jnp.einsum("njh,njhd->nhd", b, vc)


def layer(lp, h, *, n_heads: int, eps: float, theta: float, window: int, chunk: int):
    """One layer over one sequence ``h [T, D]``, ``T`` a whole number of windows."""
    lp = _f32(lp)
    T, D = h.shape
    d = D // n_heads
    x = _norm(h, lp["attn_norm"]["offset"], eps)
    q, k, v = (x @ lp[name]["kernel"] for name in ("wq", "wk", "wv"))
    cos, sin = _rotary(T, d, theta)
    q = _turn(q.reshape(T, n_heads, d), cos, sin)
    k = _turn(k.reshape(T, n_heads, d), cos, sin)
    v = v.reshape(T, n_heads, d)

    n = T // chunk
    kbar, vbar = pooled_pairs(k, v, lp["pool_mu"], lp["pool_phi"], chunk)

    block = min(QUERY_BLOCK, window)

    def attend(start):
        w = start // window
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)            # [block, H, d]
        kw = jax.lax.dynamic_slice_in_dim(k, w * window, window)      # the window's keys
        vw = jax.lax.dynamic_slice_in_dim(v, w * window, window)
        position = start + jnp.arange(block)[:, None]
        raw_seen = w * window + jnp.arange(window)[None] <= position  # [block, window]
        pooled_seen = jnp.arange(n)[None] * chunk < w * window         # [1, n]: closed windows
        s_raw = jnp.einsum("qhd,khd->hqk", qb, kw) / np.sqrt(d)
        s_pooled = jnp.einsum("qhd,nhd->hqn", qb, kbar) / np.sqrt(d)
        s = jnp.concatenate([jnp.where(pooled_seen, s_pooled, -jnp.inf),
                             jnp.where(raw_seen, s_raw, -jnp.inf)], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("hqn,nhd->qhd", p[..., :n], vbar)
                + jnp.einsum("hqk,khd->qhd", p[..., n:], vw))

    o = jax.lax.map(attend, jnp.arange(0, T, block)).reshape(T, D)
    h = h + o @ lp["wo"]["kernel"]

    def ffn(rows):
        x = _norm(rows, lp["mlp_norm"]["offset"], eps)
        return rows + (jax.nn.silu(x @ lp["w1"]["kernel"]) * (x @ lp["w3"]["kernel"])) @ lp["w2"]["kernel"]

    return jax.lax.map(ffn, h.reshape(T // window, window, D)).reshape(T, D)


def layer_fn(c: dict, jit=lambda f: f):
    """One layer as a function of ``(layer params, h)`` for the published keys ``c``."""
    return jit(functools.partial(
        layer, n_heads=c["num_attention_heads"], eps=c["rms_norm_eps"],
        theta=float(c["rope_theta"]), window=c["window_size"], chunk=c["chunk_size"]))


def logits(params, ids, *, window: int, eps: float, fn):
    """``ids [T] -> float32 logits [T, vocab]``: the teacher-forced full
    forward, ``fn`` (:func:`layer_fn`) a layer at a time over the stacked
    tree, the sequence padded behind to a whole number of windows (causal:
    what lies behind changes nothing before it)."""
    T = ids.shape[0]
    padded = jnp.zeros((-(-T // window) * window,), ids.dtype).at[:T].set(ids)
    h = params["embed_tokens"]["embedding"].astype(jnp.float32)[padded]
    n_layers = params["layers"]["wq"]["kernel"].shape[0]
    for l in range(n_layers):
        h = fn(jax.tree_util.tree_map(lambda x: x[l], params["layers"]), h)
    x = _norm(h, params["final_norm"]["offset"].astype(jnp.float32), eps)
    return (x @ params["lm_head"]["kernel"][0].astype(jnp.float32))[:T]
