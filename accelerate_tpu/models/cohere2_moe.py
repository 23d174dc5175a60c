"""A parallel-block decoder with routed and shared experts and mixed window /
full attention layers (``model_type`` ``cohere2_moe``), pure JAX.

One layer, input ``h``, as the model's public configuration describes it:

- ``u = LayerNorm(h)``: mean subtracted, variance normalised, a weight and no
  bias, statistics in float32. It is the layer's ONE norm: attention and the
  expert layer both read ``u`` (``use_parallel_block``).
- ``q = u Wq`` as ``n_heads`` heads of ``head_dim`` (their product need not be
  the hidden size), ``k = u Wk``, ``v = u Wv`` as ``n_kv_heads`` heads; no
  bias, no query/key norm.
- a ``sliding_attention`` layer rotates ``q`` and ``k`` over all of
  ``head_dim`` in interleaved pairs ``(x[2i], x[2i+1])`` (GPT-J rotary) and a
  query at position ``i`` sees the keys ``i - sliding_window < j <= i``; a
  ``full_attention`` layer uses no positions at all and sees ``j <= i``.
- the expert layer: ``routed`` from :func:`accelerate_tpu.parallel.moe.
  held_expert_ffn` (sigmoid scores over all ``num_experts``, the
  ``experts_per_token`` largest, weights normalised, the experts held HERE
  computed and nothing dropped) plus ``shared``, the MEAN of
  ``num_shared_experts`` gated MLPs of the same width that every token takes.
- ``h' = h + attn + routed + shared``.

After the last layer ``LayerNorm`` and ``logits = h E^T * logit_scale`` with
the tied embedding ``E``. ``experts_held`` / ``first_expert`` and
``vocab_size`` say what this chip holds of a wider deployment: the router
keeps its published width, and what the experts held elsewhere would have
added is left out (nothing stands in for the other chips).

The layer is written once (:func:`_layer`) and parameterised by how attention
reads and writes its cache: none (:func:`cohere2_moe_forward`, the whole
sequence at once) or the serving engine's paged pool
(:meth:`Cohere2MoeConfig.paged_forward`, which ``ServingEngine`` calls). The
engine serves it greedily or sampled; speculative decoding (``spec_tokens``), ``serving.disagg.KVHandoff`` and the
single-stream ``generation.py`` are written for ``LlamaConfig`` and refuse it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import paged_write_attend
from ..parallel.moe import held_expert_ffn, init_held_experts
from .transformer import _dense_init, layer_norm, pin_qkv

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 512
    dim: int = 64
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    expert_dim: int = 64          # width of one expert, routed or shared
    num_experts: int = 16         # the router's width
    experts_per_token: int = 4
    num_shared_experts: int = 2
    #: one of ``sliding_attention`` / ``full_attention`` a layer; None is the
    #: published pattern, every fourth layer full
    layer_types: Optional[tuple] = None
    sliding_window: int = 32
    #: the routed experts this chip holds, a contiguous range; None: all
    experts_held: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 256
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                FULL if l % 4 == 3 else SLIDING for l in range(self.n_layers)))
        if len(self.layer_types) != self.n_layers or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} for {self.n_layers} layers")
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_experts)
        if self.first_expert + self.experts_held > self.num_experts:
            raise ValueError("the experts held lie outside the router's range")

    def window(self, layer: int) -> Optional[int]:
        """The window of layer ``layer``; None for a full-attention layer."""
        return self.sliding_window if self.layer_types[layer] == SLIDING else None

    def paged_forward(self, params, ids, pool, block_tables, positions, valid, block_size: int):
        """What ``ServingEngine`` calls: ``(logits, pool, counts [n_layers, 3])``."""
        return _paged_forward(params, ids, pool, block_tables, positions, valid, self, block_size)


def init_cohere2_moe(config: Cohere2MoeConfig, key) -> dict:
    """The parameter tree; ``params["layers"]`` is a tuple of one tree a layer
    (the layers are unrolled, not scanned, and a Pallas call wants a layer's
    expert stack as a buffer of its own: cut out of a stack over the layers it
    would be copied, 1.6 GB a layer at the published widths)."""
    c = config
    Dq, Dkv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim

    def layer(k):
        ks = jax.random.split(k, 8)

        def stack(k, n, in_dim, out_dim):
            return jnp.stack([_dense_init(kk, in_dim, out_dim) for kk in jax.random.split(k, n)])

        return {
            "norm": {"scale": jnp.ones((c.dim,))},
            "wq": {"kernel": _dense_init(ks[0], c.dim, Dq)},
            "wk": {"kernel": _dense_init(ks[1], c.dim, Dkv)},
            "wv": {"kernel": _dense_init(ks[2], c.dim, Dkv)},
            "wo": {"kernel": _dense_init(ks[3], Dq, c.dim)},
            "experts": init_held_experts(ks[4], c.dim, c.expert_dim, c.num_experts, c.experts_held),
            "shared": {
                "w_gate": {"kernel": stack(ks[5], c.num_shared_experts, c.dim, c.expert_dim)},
                "w_up": {"kernel": stack(ks[6], c.num_shared_experts, c.dim, c.expert_dim)},
                "w_down": {"kernel": stack(ks[7], c.num_shared_experts, c.expert_dim, c.dim)},
            },
        }

    k_embed, k_layers = jax.random.split(key)
    return {
        "embed_tokens": {"embedding": _dense_init(k_embed, c.vocab_size, c.dim, scale=0.02)},
        "layers": tuple(layer(k) for k in jax.random.split(k_layers, c.n_layers)),
        "final_norm": {"scale": jnp.ones((c.dim,))},
    }


def rope_interleaved(x, positions, theta: float):
    """GPT-J rotary over all of the last axis: the pair ``(x[2i], x[2i+1])``
    turns by ``positions * theta ** (-2i / D)``. ``x [B, S, H, D]``,
    ``positions [B, S]``; the angles are float32."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float32) / D))
    angle = positions[..., None, None].astype(jnp.float32) * inv  # [B, S, 1, D/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], D // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape).astype(x.dtype)


def shared_experts(params, u, n: int):
    """The mean of the ``n`` shared experts' outputs, each ``(silu(u Wg) *
    (u Wu)) Wd``: two batched matmuls, the second summing over the experts."""
    gate = jnp.einsum("...d,sdf->...sf", u, params["w_gate"]["kernel"])
    up = jnp.einsum("...d,sdf->...sf", u, params["w_up"]["kernel"])
    out = jnp.einsum("...sf,sfd->...d", jax.nn.silu(gate) * up, params["w_down"]["kernel"],
                     preferred_element_type=jnp.float32)
    return (out / n).astype(u.dtype)


def _layer(lp, h, positions, valid, config: Cohere2MoeConfig, layer: int, attend):
    """One layer; ``attend(q, k, v, window) -> [B, S, H, D]`` is how attention
    reaches the keys and values of earlier positions (and stores these).
    Returns ``(h', counts [3])``, the counts of :func:`held_expert_ffn`. The
    three projections' outputs are pinned before the reshape to heads, so that
    no step program copies their weights into another layout
    (:func:`transformer.pin_qkv`; ``tests/test_tpu_compile.py`` holds it)."""
    c = config
    B, S, _ = h.shape
    u = layer_norm(h, lp["norm"]["scale"], 0.0, c.norm_eps)
    q, k, v = pin_qkv(u @ lp["wq"]["kernel"], u @ lp["wk"]["kernel"], u @ lp["wv"]["kernel"])
    q = q.reshape(B, S, c.n_heads, c.head_dim)
    k = k.reshape(B, S, c.n_kv_heads, c.head_dim)
    v = v.reshape(B, S, c.n_kv_heads, c.head_dim)
    window = c.window(layer)
    if window is not None:  # a full layer takes no positions at all
        q, k = rope_interleaved(q, positions, c.rope_theta), rope_interleaved(k, positions, c.rope_theta)
    attn = attend(q, k, v, window).reshape(B, S, -1) @ lp["wo"]["kernel"]
    routed, counts = held_expert_ffn(
        lp["experts"], u, top_k=c.experts_per_token, first_expert=c.first_expert, valid=valid)
    return h + attn + routed + shared_experts(lp["shared"], u, c.num_shared_experts), counts


def _logits(params, h, config):
    h = layer_norm(h, params["final_norm"]["scale"], 0.0, config.norm_eps)
    return (h @ params["embed_tokens"]["embedding"].T) * config.logit_scale


def cohere2_moe_forward(params, ids, config: Cohere2MoeConfig):
    """``ids [B, S] -> logits [B, S, vocab]``, the whole sequence at once
    (no cache): plain masked attention, the layers unrolled."""
    B, S = ids.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    rep = config.n_heads // config.n_kv_heads

    def attend(q, k, v, window):
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = j <= i if window is None else (j <= i) & (i - j < window)
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        s = jnp.where(seen, s / np.sqrt(config.head_dim), jnp.finfo(jnp.float32).min)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1).astype(q.dtype), v)

    h = params["embed_tokens"]["embedding"][ids]
    for layer in range(config.n_layers):
        h, _ = _layer(params["layers"][layer], h, positions, None, config, layer, attend)
    return _logits(params, h, config)


def _paged_forward(params, ids, pool, block_tables, positions, valid, config, block_size):
    """Forward ``ids [B, S]`` at per-row ``positions [B, S]`` against the
    engine's paged pool ``{"k", "v"}: [L, num_blocks, block_size, Hkv, D]``:
    each layer writes its keys and values through the block tables into its
    part of the whole stack and attends over the row's blocks, with its window
    or none (``ops.flash_attention.paged_write_attend``). The stack goes from
    layer to layer whole, never sliced or restacked, so a program that donates
    the pool writes in place and holds no second pool among its temporaries.
    ``valid [B, S]`` marks the real tokens of the padded batch (the others are
    routed to no expert). Returns ``(logits, new pool, counts [L, 3])``. One
    block table and one pool serve all layer kinds: a window layer keeps (and
    never reads) what lies behind its window."""
    h = params["embed_tokens"]["embedding"][ids]
    k_pool, v_pool = pool["k"], pool["v"]
    counts = []
    for layer in range(config.n_layers):

        def attend(q, k, v, window):
            nonlocal k_pool, v_pool
            attn, k_pool, v_pool = paged_write_attend(
                q, k, v, k_pool, v_pool, layer, block_tables, positions, block_size, window)
            return attn

        h, layer_counts = _layer(
            params["layers"][layer], h, positions, valid, config, layer, attend)
        counts.append(layer_counts)
    return _logits(params, h, config), {"k": k_pool, "v": v_pool}, jnp.stack(counts)
