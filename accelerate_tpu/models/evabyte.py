"""A byte-level decoder whose attention reads exact keys inside a window and
one pooled key/value pair for every chunk behind it (EVA attention as EvaByte
uses it, ``model_type`` ``evabyte``), pure JAX.

One layer at position ``i``, with ``W = window_size``, ``C = chunk_size``,
head size ``d`` and the unit-offset RMSNorm ``n(x) = x / rms(x) * (1 + g)``:

- ``x = n(h)``; ``q, k, v = x W_q, x W_k, x W_v`` in ``n_heads`` heads, as
  many key heads as query heads; ``q`` and ``k`` turned by the half-split
  rotary at position ``i``.
- For chunk ``c`` (positions ``C c .. C c + C - 1``) and head ``h``, with
  the layer's learned vectors ``mu_h`` and ``phi_h``:
  ``kbar_c = sum_j softmax_j(k_j . mu_h) k_j`` and ``vbar_c = sum_j
  softmax_j(k_j . phi_h) v_j`` over the chunk's rotated keys.
- With ``w = i // W``: ``o_i = [sum_{wW <= j <= i} e^{q_i . k_j / sqrt d} v_j
  + sum_{c < wW / C} e^{q_i . kbar_c / sqrt d} vbar_c] / (the same weights
  summed)``: one softmax over the window's exact keys and the pooled pairs of
  every closed window.
- ``h1 = h + o W_o`` and ``h2 = h1 + SwiGLU(n(h1))``, the residual stream in
  float32 (``fp32_skip_add``); the norms are taken in float32 and their
  outputs rounded to the weights' dtype for the matmuls.

After the last layer ``logits = n(h) W_head`` in float32 (``fp32_logits``).
The published model has ``num_pred_heads`` heads, the next byte's and the
draft of the bytes after it for self-speculative decoding: every head's
weights are made, and the served path samples from the first
(``heads_served`` 1: a step that yields more than one token is ROADMAP B-m5's).

These equations follow EVA (Zheng et al., arXiv:2302.04542, sections 4-5) with
its random proposal replaced by the learned ``mu`` / ``phi``; the scale of the
pooling logits (1) and a window that is blockwise, not sliding, are
assumptions the configuration file lists.

The layers are parameterised by their cache: none (:func:`evabyte_forward`)
or the serving engine's paged pool (:meth:`EvaByteConfig.paged_forward`),
whose rows hold the open window's raw blocks and every window's summary
blocks (``ops.flash_attention.paged_write_fold_attend``: the write, the fold
of each chunk completed, and the attention over the composed row).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import masked_attention
from ..ops.flash_attention import paged_write_fold_attend
from .transformer import _proj, apply_rope, pin_qkv, rms_norm, rope_frequencies


@dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320
    dim: int = 64
    n_layers: int = 2
    n_heads: int = 4
    ffn_dim: int = 128
    window_size: int = 32     # W: the positions a query reads exactly, a window at a time
    chunk_size: int = 4       # C: the positions one pooled pair stands for
    num_pred_heads: int = 8   # heads made; the first `heads_served` are served
    heads_served: int = 1
    max_seq_len: int = 256
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    init_std: float = 0.01275

    def __post_init__(self):
        if self.heads_served != 1:
            raise ValueError("a step yields one token: the next byte's head alone is served")
        if self.window_size % self.chunk_size:
            raise ValueError(f"window {self.window_size} is not whole chunks of {self.chunk_size}")

    @property
    def n_kv_heads(self) -> int:
        return self.n_heads

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def paged_forward(self, params, ids, pool, block_tables, positions, valid, block_size: int):
        """What ``ServingEngine`` calls: ``(logits, pool, None)``."""
        return _paged_forward(params, ids, pool, block_tables, positions, valid, self, block_size)


def init_evabyte(config: EvaByteConfig, key) -> dict:
    """The parameter tree, float32. Every matrix is drawn at ``init_std``;
    the norms' offsets ``g`` and the pooling vectors ``mu`` / ``phi`` near 0
    (0.1 deviations: a pooling logit ``k . mu`` then has about one deviation
    over a head of 128 at these keys, so the pooled pairs are neither a plain
    mean nor one key). Layers are stacked on a leading axis (every layer is
    alike); ``lm_head`` is ``[num_pred_heads, dim, vocab]``."""
    c = config
    L, D, F, H, d = c.n_layers, c.dim, c.ffn_dim, c.n_heads, c.head_dim
    ks = jax.random.split(key, 14)

    def normal(k, shape, std=c.init_std):
        return (std * jax.random.normal(k, shape)).astype(jnp.float32)

    return {
        "embed_tokens": {"embedding": normal(ks[0], (c.vocab_size, D))},
        "layers": {
            "attn_norm": {"offset": normal(ks[1], (L, D), 0.1)},
            "wq": {"kernel": normal(ks[2], (L, D, D))},
            "wk": {"kernel": normal(ks[3], (L, D, D))},
            "wv": {"kernel": normal(ks[4], (L, D, D))},
            "wo": {"kernel": normal(ks[5], (L, D, D))},
            "pool_mu": normal(ks[6], (L, H, d), 0.1),
            "pool_phi": normal(ks[7], (L, H, d), 0.1),
            "mlp_norm": {"offset": normal(ks[8], (L, D), 0.1)},
            "w1": {"kernel": normal(ks[9], (L, D, F))},
            "w3": {"kernel": normal(ks[10], (L, D, F))},
            "w2": {"kernel": normal(ks[11], (L, F, D))},
        },
        "final_norm": {"offset": normal(ks[12], (D,), 0.1)},
        "lm_head": {"kernel": normal(ks[13], (c.num_pred_heads, D, c.vocab_size))},
    }


def unit_offset_norm(h, offset, eps):
    """``n(h) = h / rms(h) * (1 + g)`` in float32 (``norm_add_unit_offset``)."""
    return rms_norm(h.astype(jnp.float32), 1.0 + offset.astype(jnp.float32), eps)


def _layer(lp, h, positions, cos, sin, config: EvaByteConfig, attend):
    """One layer over the float32 residual ``h [B, S, D]``; ``attend(q, k, v,
    mu, phi)`` is how attention reaches earlier positions."""
    B, S, _ = h.shape
    dtype = lp["wq"]["kernel"].dtype
    x = unit_offset_norm(h, lp["attn_norm"]["offset"], config.norm_eps).astype(dtype)
    q, k, v = pin_qkv(_proj(lp["wq"], x), _proj(lp["wk"], x), _proj(lp["wv"], x))
    shape = (B, S, config.n_heads, config.head_dim)
    q = apply_rope(q.reshape(shape), cos, sin, positions=positions)
    k = apply_rope(k.reshape(shape), cos, sin, positions=positions)
    o = attend(q, k, v.reshape(shape), lp["pool_mu"], lp["pool_phi"])
    h = h + _proj(lp["wo"], o.reshape(B, S, -1)).astype(jnp.float32)
    x = unit_offset_norm(h, lp["mlp_norm"]["offset"], config.norm_eps).astype(dtype)
    y = _proj(lp["w2"], jax.nn.silu(_proj(lp["w1"], x)) * _proj(lp["w3"], x))
    return h + y.astype(jnp.float32)


def _run(params, ids, positions, config: EvaByteConfig, attend, carry):
    """Embedding, the layers (one ``lax.scan`` over the stack, ``carry`` the
    cache riding it, ``attend(carry, index, q, k, v, mu, phi) -> (o,
    carry)``) and the served head: ``(logits [B, S, vocab] float32, carry)``."""
    cos, sin = map(jnp.asarray, rope_frequencies(
        config.head_dim, config.max_seq_len, config.rope_theta))

    def layer(state, xs):
        h, carry = state
        lp, index = xs

        def step(q, k, v, mu, phi):
            nonlocal carry
            o, carry = attend(carry, index, q, k, v, mu, phi)
            return o

        h = _layer(lp, h, positions, cos, sin, config, step)
        return (h, carry), None

    h = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    (h, carry), _ = jax.lax.scan(layer, (h, carry), (params["layers"], jnp.arange(config.n_layers)),
                                 unroll=True)
    x = unit_offset_norm(h, params["final_norm"]["offset"], config.norm_eps)
    return x @ params["lm_head"]["kernel"][0].astype(jnp.float32), carry


def pooled_pairs(k, v, mu, phi, chunk: int):
    """``(kbar, vbar) [B, S // C, H, d]`` of every whole chunk of ``k``, ``v``
    ``[B, S, H, d]`` (float32 softmax pooling, as the fold kernel)."""
    B, S, H, d = k.shape
    n = S // chunk
    kc = k[:, : n * chunk].reshape(B, n, chunk, H, d).astype(jnp.float32)
    vc = v[:, : n * chunk].reshape(B, n, chunk, H, d).astype(jnp.float32)

    def pool(weights, x):
        p = jax.nn.softmax(jnp.einsum("bnjhd,hd->bnjh", kc, weights.astype(jnp.float32)), axis=2)
        return jnp.einsum("bnjh,bnjhd->bnhd", p, x)

    return pool(mu, kc).astype(k.dtype), pool(phi, vc).astype(v.dtype)


def evabyte_forward(params, ids, config: EvaByteConfig):
    """``ids [B, S] -> logits [B, S, vocab]``, the whole sequence at once and
    no cache: every query against all raw keys and all pooled pairs under
    the EVA mask (raw key ``j`` where ``j // W == i // W`` and ``j <= i``,
    pair ``c`` where ``C c < W (i // W)``). For the CPU tests."""
    W, C = config.window_size, config.chunk_size
    S = ids.shape[1]
    i = np.arange(S)[:, None]
    raw = (np.arange(S)[None] // W == i // W) & (np.arange(S)[None] <= i)
    pooled = np.arange(S // C)[None] * C < W * (i // W)
    allow = jnp.asarray(np.concatenate([raw, pooled], axis=1))[None, None]

    def attend(carry, index, q, k, v, mu, phi):
        kbar, vbar = pooled_pairs(k, v, mu, phi, C)
        keys, values = jnp.concatenate([k, kbar], axis=1), jnp.concatenate([v, vbar], axis=1)
        return masked_attention(q, keys, values, allow), carry

    return _run(params, ids, None, config, attend, ())[0]


def _paged_forward(params, ids, pool, block_tables, positions, valid, config, block_size):
    """Forward ``ids [B, S]`` at per-row ``positions [B, S]`` against the
    engine's pool ``{"k", "v"}: [L, num_blocks, block_size, H, d]`` through
    stored pooled rows ``block_tables [B, W]``: each layer writes its keys
    and values into the open window's raw slots, attends the composed row and
    folds every chunk this call completes (``valid`` marks the real tokens;
    ``ops.flash_attention.paged_write_fold_attend``). The stack rides the
    layer loop as carry, so a program that donates the pool writes in place.
    Returns ``(logits, new pool, None)``."""
    def attend(carry, index, q, k, v, mu, phi):
        k_pool, v_pool = carry
        o, k_pool, v_pool = paged_write_fold_attend(
            q, k, v, k_pool, v_pool, index, block_tables, positions, valid, mu, phi,
            window=config.window_size, chunk=config.chunk_size, block_size=block_size)
        return o, (k_pool, v_pool)

    logits, (k_pool, v_pool) = _run(params, ids, positions, config, attend, (pool["k"], pool["v"]))
    return logits, {"k": k_pool, "v": v_pool}, None
