"""A sequential-block decoder whose every FFN is a routed expert layer, with
mixed window / full attention layers and a rotary table a layer kind
(``model_type`` ``mellum``), pure JAX.

One layer, input ``h``, as the model's public configuration describes it:

- the attention half is the llama layer's (:func:`.transformer.llama_layer`,
  shared, not copied): ``x = RMSNorm(h)``, ``q = x Wq`` as ``n_heads`` heads
  of ``head_dim`` (their product need not be the hidden size), ``k``, ``v`` as
  ``n_kv_heads`` heads, no bias, no query/key norm; half-split rotary over all
  of ``head_dim``; ``h1 = h + attn Wo``.
- what differs by layer kind is the table and the mask. A
  ``sliding_attention`` layer turns by the plain frequencies ``theta ** (-2i /
  head_dim)`` and a query at ``i`` sees keys ``i - sliding_window < j <= i``;
  a ``full_attention`` layer turns by YaRN's blended frequencies with the
  attention factor on cos and sin (:func:`.transformer.yarn_rope_frequencies`)
  and sees ``j <= i``.
- ``y = RMSNorm(h1)``; ``h2 = h1 + routed(y)`` from
  :func:`accelerate_tpu.parallel.moe.held_expert_ffn`: softmax scores over all
  ``num_experts``, the ``experts_per_token`` largest, weights normalised,
  nothing dropped, no shared expert. With every expert held (the default)
  that is the whole layer; ``experts_held`` / ``first_expert`` give a chip's
  share of a wider deployment, and the shares add up to the whole.

After the last layer ``RMSNorm`` and an untied head. The layer is
parameterised by how attention reads and writes its cache: none
(:func:`mellum_forward`) or the serving engine's paged pool
(:meth:`MellumConfig.paged_forward`, which ``ServingEngine`` calls).
Speculative decoding, disaggregated serving's ``ReplicaSpec`` and the
single-stream ``generation.py`` are written for ``LlamaConfig`` and refuse it.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from ..ops.flash_attention import paged_write_attend
from ..parallel.moe import held_expert_ffn, init_held_experts
from .transformer import (
    _dense_init,
    llama_head,
    llama_layer,
    rope_frequencies,
    yarn_rope_frequencies,
)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 512
    dim: int = 64
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    expert_dim: int = 32          # width of one routed expert
    num_experts: int = 16         # the router's width
    experts_per_token: int = 4
    #: one of ``sliding_attention`` / ``full_attention`` a layer; None is the
    #: published pattern, every fourth layer full
    layer_types: Optional[tuple] = None
    sliding_window: int = 32
    #: the routed experts this chip holds, a contiguous range; None: all
    experts_held: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 256
    rope_theta: float = 500000.0
    #: YaRN on the full layers: ``factor``, ``original_max_seq`` and optionally
    #: ``beta_fast``, ``beta_slow``, ``attention_factor``, as a tuple of pairs
    #: (hashable); None turns them by the plain table too
    yarn: Optional[tuple] = None
    norm_eps: float = 1e-6
    tie_embeddings: bool = False

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

    def rope(self, kind: str):
        """The ``(cos, sin)`` tables ``[max_seq_len, head_dim / 2]`` of a layer
        kind (numpy: constants of the program that uses them)."""
        if kind == FULL and self.yarn is not None:
            return yarn_rope_frequencies(
                self.head_dim, self.max_seq_len, self.rope_theta, **dict(self.yarn))
        return rope_frequencies(self.head_dim, self.max_seq_len, self.rope_theta)

    def paged_forward(self, params, ids, pool, block_tables, positions, valid, block_size: int):
        """What ``ServingEngine`` calls: ``(logits, pool, counts [n_layers, 3])``."""
        return _paged_forward(params, ids, pool, block_tables, positions, valid, self, block_size)


def init_mellum(config: MellumConfig, key) -> dict:
    """The parameter tree; ``params["layers"]`` is a tuple of one tree a layer
    (unrolled as ``cohere2_moe``'s and for its reasons: the window is static in
    the kernels, and a layer's expert stack must be a buffer of its own for
    the grouped matmul). A layer's attention half has ``llama_layer``'s names."""
    c = config
    Dq, Dkv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim

    def layer(k):
        ks = jax.random.split(k, 5)
        return {
            "attn_norm": {"scale": jnp.ones((c.dim,))},
            "wq": {"kernel": _dense_init(ks[0], c.dim, Dq)},
            "wk": {"kernel": _dense_init(ks[1], c.dim, Dkv)},
            "wv": {"kernel": _dense_init(ks[2], c.dim, Dkv)},
            "wo": {"kernel": _dense_init(ks[3], Dq, c.dim)},
            "mlp_norm": {"scale": jnp.ones((c.dim,))},
            "experts": init_held_experts(ks[4], c.dim, c.expert_dim, c.num_experts, c.experts_held),
        }

    k_embed, k_layers, k_head = jax.random.split(key, 3)
    params = {
        "embed_tokens": {"embedding": _dense_init(k_embed, c.vocab_size, c.dim, scale=0.02)},
        "layers": tuple(layer(k) for k in jax.random.split(k_layers, c.n_layers)),
        "final_norm": {"scale": jnp.ones((c.dim,))},
    }
    if not c.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(k_head, c.dim, c.vocab_size, scale=0.02)}
    return params


def _layers(params, h, positions, valid, config: MellumConfig, attend):
    """All layers over ``h [B, S, D]``: ``(h, counts [n_layers, 3])``.
    ``attend(layer, q, k, v, window)`` is how layer ``layer``'s attention
    reaches earlier keys and values; ``valid [B, S]`` (or None) marks the real
    tokens, the others are routed to no expert."""
    ffn = partial(_routed_ffn, config=config, valid=valid)
    tables = {kind: tuple(map(jnp.asarray, config.rope(kind)))
              for kind in dict.fromkeys(config.layer_types)}  # in order: the same program every run
    counts = []
    for layer in range(config.n_layers):
        cos, sin = tables[config.layer_types[layer]]
        h, layer_counts = llama_layer(
            params["layers"][layer], h, positions, cos, sin, config,
            partial(attend, layer, window=config.window(layer)), ffn=ffn)
        counts.append(layer_counts)
    return h, jnp.stack(counts)


def _routed_ffn(layer_params, y, *, config: MellumConfig, valid):
    return held_expert_ffn(
        layer_params["experts"], y, top_k=config.experts_per_token, scoring="softmax",
        first_expert=config.first_expert, valid=valid)


def mellum_forward(params, ids, config: MellumConfig):
    """``ids [B, S] -> logits [B, S, vocab]``, the whole sequence at once
    (no cache): causal attention, inside the window on a window layer."""

    def attend(layer, q, k, v, window):
        return dot_product_attention(q, k, v, causal=True, window=window)

    h, _ = _layers(params, params["embed_tokens"]["embedding"][ids], None, None, config, attend)
    return llama_head(params, h, config)


def _paged_forward(params, ids, pool, block_tables, positions, valid, config, block_size):
    """Forward ``ids [B, S]`` at per-row ``positions [B, S]`` against the
    engine's paged pool ``{"k", "v"}: [L, num_blocks, block_size, Hkv, D]``:
    each layer writes its keys and values through the block tables into its
    part of the whole stack and attends over the row's blocks, with its window
    or none (``ops.flash_attention.paged_write_attend``). The stack goes from
    layer to layer whole, never sliced or restacked. Returns ``(logits, new
    pool, counts [L, 3])``. One block table and one pool serve both layer
    kinds: a window layer keeps (and never reads) what lies behind its window."""
    k_pool, v_pool = pool["k"], pool["v"]

    def attend(layer, q, k, v, window):
        nonlocal k_pool, v_pool
        attn, k_pool, v_pool = paged_write_attend(
            q, k, v, k_pool, v_pool, layer, block_tables, positions, block_size, window)
        return attn

    h, counts = _layers(
        params, params["embed_tokens"]["embedding"][ids], positions, valid, config, attend)
    return llama_head(params, h, config), {"k": k_pool, "v": v_pool}, counts
