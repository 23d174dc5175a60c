"""Plain references: each architecture's forward pass (and, for training, its
loss and gradient norm) in straightforward ``jax.numpy`` and float32, with no
kernels, no cache and no batching tricks, under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matrix
multiplication otherwise runs in bf16 passes). Written from the published
equations; nothing is imported from ``accelerate_tpu``. What the references
share with the program is the layout of the parameter tree they are handed
(layers stacked on a leading axis), because they are given the program's own
weights.

bert: Devlin et al. 2018 as in ``google-bert/bert-base-uncased``. Departures:
no dropout (the program has none), and GELU in its tanh approximation, which
is what the program computes (the published model uses the erf form; the two
differ by under 1e-3 in the activation).

llama (Mistral-7B-v0.3 is of this shape): pre-norm decoder with RMSNorm,
rotary embeddings in the half-split convention, grouped-query attention,
SwiGLU, untied head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x**3)))


# ---------------------------------------------------------------------- bert


def bert_logits(params, batch, *, n_heads: int, eps: float):
    """Classification logits ``[B, num_labels]``; ``params`` in float32."""
    ids = batch["input_ids"]
    B, S = ids.shape
    emb = params["embeddings"]
    h = (emb["word"]["embedding"][ids] + emb["position"]["embedding"][:S][None]
         + emb["token_type"]["embedding"][batch["token_type_ids"]])
    h = _layer_norm(h, emb["norm"]["scale"], emb["norm"]["bias"], eps)
    keep = batch["attention_mask"].astype(bool)[:, None, None, :]  # keys that are real tokens
    n_layers = params["layers"]["wq"]["kernel"].shape[0]
    for i in range(n_layers):
        lp = jax.tree_util.tree_map(lambda x: x[i], params["layers"])

        def heads(name):
            y = h @ lp[name]["kernel"] + lp[name]["bias"]
            return y.reshape(B, S, n_heads, -1).transpose(0, 2, 1, 3)  # [B, H, S, dh]

        q, k, v = heads("wq"), heads("wk"), heads("wv")
        scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(q.shape[-1])
        scores = jnp.where(keep, scores, -jnp.inf)
        ctx = (jax.nn.softmax(scores, axis=-1) @ v).transpose(0, 2, 1, 3).reshape(B, S, -1)
        h = _layer_norm(h + ctx @ lp["wo"]["kernel"] + lp["wo"]["bias"],
                        lp["attn_norm"]["scale"], lp["attn_norm"]["bias"], eps)
        x = _gelu_tanh(h @ lp["fc1"]["kernel"] + lp["fc1"]["bias"])
        h = _layer_norm(h + x @ lp["fc2"]["kernel"] + lp["fc2"]["bias"],
                        lp["mlp_norm"]["scale"], lp["mlp_norm"]["bias"], eps)
    pooled = jnp.tanh(h[:, 0] @ params["pooler"]["kernel"] + params["pooler"]["bias"])
    return pooled @ params["classifier"]["kernel"] + params["classifier"]["bias"]


def bert_loss(params, batch, *, n_heads: int, eps: float):
    """Mean cross entropy of the labels."""
    logp = jax.nn.log_softmax(bert_logits(params, batch, n_heads=n_heads, eps=eps), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1))


# --------------------------------------------------------------------- llama


def _rope(x, positions, theta: float):
    """``x [T, H, dh]``: pairs (i, i + dh/2) rotated by ``pos * theta^(-2i/dh)``."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2) / dh))
    angle = positions[:, None].astype(jnp.float32) * inv[None]  # [T, dh/2]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def llama_layer(h, lp, *, n_heads: int, n_kv_heads: int, eps: float, theta: float):
    """One decoder layer over one sequence ``h [T, D]``, causal. ``lp`` may be
    in any float type: it is upcast here, so a caller can hand over one bf16
    layer at a time."""
    lp = _f32(lp)
    T = h.shape[0]
    x = _rms_norm(h, lp["attn_norm"]["scale"], eps)
    q = (x @ lp["wq"]["kernel"]).reshape(T, n_heads, -1)
    k = (x @ lp["wk"]["kernel"]).reshape(T, n_kv_heads, -1)
    v = (x @ lp["wv"]["kernel"]).reshape(T, n_kv_heads, -1)
    pos = jnp.arange(T)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = n_heads // n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v).reshape(T, -1)
    h = h + ctx @ lp["wo"]["kernel"]
    x = _rms_norm(h, lp["mlp_norm"]["scale"], eps)
    return h + (jax.nn.silu(x @ lp["w1"]["kernel"]) * (x @ lp["w3"]["kernel"])) @ lp["w2"]["kernel"]


def llama_logits(params, ids, *, n_heads: int, n_kv_heads: int, eps: float, theta: float,
                 layer_fn=None):
    """Logits ``[T, V]`` of one sequence ``ids [T]``, every position attending
    to itself and what came before. The layers are taken one at a time from
    the stacked tree (``layer_fn``: a jitted :func:`llama_layer`, so that only
    one layer is ever held in float32)."""
    layer_fn = layer_fn or functools.partial(
        llama_layer, n_heads=n_heads, n_kv_heads=n_kv_heads, eps=eps, theta=theta)
    h = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    n_layers = params["layers"]["wq"]["kernel"].shape[0]
    for i in range(n_layers):
        h = layer_fn(h, jax.tree_util.tree_map(lambda x: x[i], params["layers"]))
    h = _rms_norm(h, params["final_norm"]["scale"].astype(jnp.float32), eps)
    return h @ params["lm_head"]["kernel"].astype(jnp.float32)


def llama_loss(params, batch, *, n_heads: int, n_kv_heads: int, eps: float, theta: float):
    """Mean next-token cross entropy over ``batch["input_ids"] [B, S]``."""
    def one(ids):
        logits = llama_logits(params, ids, n_heads=n_heads, n_kv_heads=n_kv_heads,
                              eps=eps, theta=theta)
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.take_along_axis(logp, ids[1:, None], axis=-1)[:, 0]

    return jnp.mean(jax.vmap(one)(batch["input_ids"]))


# ------------------------------------------------------- what the cells check


def loss_and_grad_norm(loss_fn, params, batch, *, rows_at_a_time: int):
    """``loss_fn(params, batch)`` (a mean over rows) and the global norm of its
    gradient, in float32 at ``highest`` matmul precision, over all rows of
    ``batch`` taken ``rows_at_a_time`` so that the float32 activations fit."""
    params = _f32(params)
    n = len(next(iter(batch.values())))
    assert n % rows_at_a_time == 0, (n, rows_at_a_time)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    loss_sum, grad_sum = 0.0, None
    with jax.default_matmul_precision("highest"):
        for start in range(0, n, rows_at_a_time):
            part = {k: jnp.asarray(v[start:start + rows_at_a_time]) for k, v in batch.items()}
            loss, grads = grad_fn(params, part)
            loss_sum += float(loss)
            grad_sum = grads if grad_sum is None else jax.tree_util.tree_map(
                jnp.add, grad_sum, grads)
    parts = n // rows_at_a_time
    squares = sum(float(jnp.sum(jnp.square(g / parts)))
                  for g in jax.tree_util.tree_leaves(grad_sum))
    return loss_sum / parts, float(np.sqrt(squares))


def greedy_margins(logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """For each position, how far the reference's logit of the token the system
    chose lies under the reference's largest logit, in units of that
    position's logit standard deviation. 0 where the system chose the
    reference's own argmax; a wrong cache or mask gives several deviations."""
    logits = np.asarray(logits, np.float64)
    picked = np.take_along_axis(logits, np.asarray(chosen)[:, None], axis=-1)[:, 0]
    return (logits.max(axis=-1) - picked) / logits.std(axis=-1)
