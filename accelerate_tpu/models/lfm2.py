"""A decoder most of whose layers do not attend: gated short convolutions with
a few rows of state a sequence, grouped-query attention on the others, leading
dense FFNs and routed experts after (``model_type`` ``lfm2_moe``), pure JAX.

One layer, input ``h``, as the model's public configuration describes it:
``h1 = h + op(RMSNorm(h))``, ``h2 = h1 + ffn(RMSNorm(h1))``, no bias anywhere.

- ``op`` on a ``conv`` layer (:func:`conv_operator`): ``[B, C, x] = split3(u
  W_in)``, ``z = B * x``, ``c_t = sum_j w[:, j] z_{t-(K-1)+j}`` (a depthwise
  causal convolution over time with ``K = conv_taps`` taps a channel, zeros
  before position 0), ``out = (C * c) W_out``. No positions. What a later
  token needs of the earlier ones is ``z`` at the ``K - 1`` positions before
  it: the layer's STATE, ``[K - 1, dim]`` a sequence, whatever the context.
- ``op`` on a ``full_attention`` layer is the llama layer's attention half
  (:func:`.transformer.llama_layer`, shared, not copied) with its query/key
  norm: every query and key head through an RMSNorm over ``head_dim`` before
  the half-split rotary turn; causal, no window.
- ``ffn`` is a dense SwiGLU of ``dense_dim`` on the first ``num_dense_layers``
  layers and the routed experts after
  (:func:`accelerate_tpu.parallel.moe.held_expert_ffn`): sigmoid scores, the
  ``experts_per_token`` largest of ``score + expert_bias`` (a float32 buffer
  of the model that moves who is chosen and never a weight), the chosen
  experts' scores over ``their sum + route_weight_eps``, nothing shared,
  nothing dropped. ``experts_held`` / ``first_expert`` give a chip's share of
  a wider deployment, and the shares add up to the whole.

After the last layer ``RMSNorm`` (``final_norm``: the published
``embedding_norm``) and the head, the embedding transposed by default.

The layers are parameterised by their cache: none (:func:`lfm2_forward`) or
the serving engine's (:meth:`Lfm2Config.paged_forward`). The model says what
cache it needs and the engine allocates that: keys and values on
``n_kv_layers`` layers (the attention layers only: an attention layer's index
into the paged pool is its rank among them) and ``state_shape`` a sequence,
which the engine keeps as ``pool["state"] [conv layers, rows, K - 1, dim]`` and
addresses by the row it gives each sequence (``ops.flash_attention``: row 0
is the null row, as block 0 is the null block). A call that starts a sequence
(its first position is 0) starts from zeros whatever the row held, so a row
handed from one sequence to the next needs no clearing. Speculative decoding,
disaggregated serving, the prefix cache and ``generation.py`` refuse a model
with such state (``serving/engine.py`` says why).
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
from .transformer import _dense_init, _proj, llama_head, llama_layer, rms_norm, rope_frequencies

CONV, FULL = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 512
    dim: int = 64
    n_layers: int = 6
    n_heads: int = 8
    n_kv_heads: int = 2
    #: one of ``conv`` / ``full_attention`` a layer; None is the published
    #: pattern: ``conv, conv`` and then ``full_attention, conv, conv, conv``
    layer_types: Optional[tuple] = None
    num_dense_layers: int = 2     # these leading layers have a dense FFN
    conv_taps: int = 3            # K: a conv layer's state is K - 1 rows
    dense_dim: int = 128          # width of the dense FFN
    expert_dim: int = 32          # width of one routed expert
    num_experts: int = 16         # the router's width
    experts_per_token: int = 4
    #: the routed experts this chip holds, a contiguous range; None: all
    experts_held: Optional[int] = None
    first_expert: int = 0
    route_weight_eps: float = 1e-6
    max_seq_len: int = 256
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = True

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                FULL if l % 4 == 2 else CONV for l in range(self.n_layers)))
        if len(self.layer_types) != self.n_layers or set(self.layer_types) - {CONV, FULL}:
            raise ValueError(f"layer_types {self.layer_types} for {self.n_layers} layers")
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_experts)
        if self.first_expert + self.experts_held > self.num_experts:
            raise ValueError("the experts held lie outside the router's range")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_kv_layers(self) -> int:
        """The layers the paged pool holds keys and values for: those that attend."""
        return self.layer_types.count(FULL)

    @property
    def state_shape(self) -> tuple:
        """What a sequence keeps beside its blocks: ``(conv layers, K - 1, dim)``."""
        return (self.layer_types.count(CONV), self.conv_taps - 1, self.dim)

    def cache_index(self, layer: int) -> int:
        """Layer ``layer``'s rank among the layers of its kind: its index
        into the K/V pool or into the state."""
        return self.layer_types[:layer].count(self.layer_types[layer])

    def routed(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    def paged_forward(self, params, ids, pool, block_tables, positions, valid, rows,
                      block_size: int):
        """What ``ServingEngine`` calls: ``(logits, pool, counts [routed
        layers, 3])``. ``rows [B]`` is each sequence's row of ``pool["state"]``."""
        return _paged_forward(params, ids, pool, block_tables, positions, valid, rows, self,
                              block_size)


def init_lfm2(config: Lfm2Config, key) -> dict:
    """The parameter tree; ``params["layers"]`` is a tuple of one tree a layer
    (the layers differ in kind). An attention layer has ``llama_layer``'s names
    (with ``q_norm`` / ``k_norm``), a conv layer ``op_norm``, ``in_proj``
    ``[D, 3D]``, ``conv`` ``[D, K]`` and ``out_proj``; a dense FFN ``w1``,
    ``w3``, ``w2``, a routed one ``experts`` with its float32 ``expert_bias``."""
    c = config
    Dkv = c.n_kv_heads * c.head_dim

    def layer(l, k):
        ks = jax.random.split(k, 8)
        if c.layer_types[l] == FULL:
            tree = {
                "attn_norm": {"scale": jnp.ones((c.dim,))},
                "wq": {"kernel": _dense_init(ks[0], c.dim, c.dim)},
                "wk": {"kernel": _dense_init(ks[1], c.dim, Dkv)},
                "wv": {"kernel": _dense_init(ks[2], c.dim, Dkv)},
                "wo": {"kernel": _dense_init(ks[3], c.dim, c.dim)},
                # seeded, not ones: a norm left out would otherwise change nothing but a scale
                "q_norm": {"scale": 1.0 + 0.1 * jax.random.normal(ks[4], (c.head_dim,))},
                "k_norm": {"scale": 1.0 + 0.1 * jax.random.normal(ks[5], (c.head_dim,))},
            }
        else:
            tree = {
                "op_norm": {"scale": jnp.ones((c.dim,))},
                "in_proj": {"kernel": _dense_init(ks[0], c.dim, 3 * c.dim)},
                "conv": {"kernel": jax.random.normal(ks[1], (c.dim, c.conv_taps)) / c.conv_taps ** 0.5},
                "out_proj": {"kernel": _dense_init(ks[2], c.dim, c.dim)},
            }
        tree["mlp_norm"] = {"scale": jnp.ones((c.dim,))}
        if c.routed(l):
            tree["experts"] = init_held_experts(
                ks[6], c.dim, c.expert_dim, c.num_experts, c.experts_held)
            # a trained bias evens the experts' load; a seeded one can only skew it, so it is
            # small: 0.01 moves the k-th / next boundary for about a fifth of the tokens
            tree["experts"]["expert_bias"] = 0.01 * jax.random.normal(ks[7], (c.num_experts,))
        else:
            tree.update(
                w1={"kernel": _dense_init(ks[6], c.dim, c.dense_dim)},
                w3={"kernel": _dense_init(ks[7], c.dim, c.dense_dim)},
                w2={"kernel": _dense_init(jax.random.fold_in(ks[7], 1), c.dense_dim, c.dim)})
        return tree

    k_embed, k_layers, k_head = jax.random.split(key, 3)
    params = {
        "embed_tokens": {"embedding": _dense_init(k_embed, c.vocab_size, c.dim, scale=0.02)},
        "layers": tuple(layer(l, k) for l, k in enumerate(jax.random.split(k_layers, c.n_layers))),
        "final_norm": {"scale": jnp.ones((c.dim,))},
    }
    if not c.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(k_head, c.dim, c.vocab_size, scale=0.02)}
    return params


def conv_operator(layer_params, u, prev, n_real):
    """The gated short convolution over ``u [B, S, D]`` (normed), plain
    ``jax.numpy`` between its two matmuls. ``prev [B, K - 1, D]`` is ``z`` at
    the ``K - 1`` positions before ``u``'s first (zeros at a sequence's start)
    and ``n_real [B]`` how many of the ``S`` rows are real tokens (the rest is
    a bucket's padding, behind them). Returns ``(out [B, S, D], state [B, K -
    1, D])``: the state is ``z`` at the last ``K - 1`` REAL positions, which
    reach back into ``prev`` where fewer than ``K - 1`` rows are real."""
    S = u.shape[1]
    # the operator has no kernel of its own: the scope names its fused operations in a profile
    with jax.named_scope("conv_operator"):
        gate_in, gate_out, x = jnp.split(_proj(layer_params["in_proj"], u), 3, axis=-1)
        z = jnp.concatenate([prev.astype(u.dtype), gate_in * x], axis=1)  # position t: row t + K - 1
        w = layer_params["conv"]["kernel"].astype(jnp.float32)
        taps = w.shape[-1]
        c = sum(w[:, j] * z[:, j:j + S].astype(jnp.float32) for j in range(taps))
        out = _proj(layer_params["out_proj"], gate_out * c.astype(u.dtype))
        last = n_real[:, None] + jnp.arange(taps - 1)[None]  # rows n_real .. n_real + K - 2 of z
        return out, jnp.take_along_axis(z, last[:, :, None], axis=1)


def _dense_ffn(layer_params, y):
    hidden = jax.nn.silu(_proj(layer_params["w1"], y)) * _proj(layer_params["w3"], y)
    return _proj(layer_params["w2"], hidden), None


def _layers(params, h, positions, valid, config: Lfm2Config, attend, convolve):
    """All layers over ``h [B, S, D]``: ``(h, counts [routed layers, 3] or
    None)``. ``attend(index, q, k, v)`` is how attention layer ``index`` (its
    rank among the attention layers) reaches earlier keys and values;
    ``convolve(index, layer_params, u)`` is conv layer ``index``'s operator
    with that layer's state behind it. ``valid [B, S]`` (or None) marks the
    real tokens, the others are routed to no expert."""
    cos, sin = map(jnp.asarray, rope_frequencies(
        config.head_dim, config.max_seq_len, config.rope_theta))

    def routed_ffn(layer_params, y):
        experts = layer_params["experts"]
        return held_expert_ffn(
            experts, y, top_k=config.experts_per_token, scoring="sigmoid",
            first_expert=config.first_expert, valid=valid,
            select_bias=experts["expert_bias"], weight_eps=config.route_weight_eps)

    counts = []
    for layer, layer_params in enumerate(params["layers"]):
        ffn = routed_ffn if config.routed(layer) else _dense_ffn
        index = config.cache_index(layer)
        if config.layer_types[layer] == FULL:
            h, layer_counts = llama_layer(
                layer_params, h, positions, cos, sin, config,
                partial(attend, index), ffn=ffn)
        else:
            u = rms_norm(h, layer_params["op_norm"]["scale"], config.norm_eps)
            h = h + convolve(index, layer_params, u)
            y, layer_counts = ffn(
                layer_params, rms_norm(h, layer_params["mlp_norm"]["scale"], config.norm_eps))
            h = h + y
        if layer_counts is not None:
            counts.append(layer_counts)
    return h, jnp.stack(counts) if counts else None


def lfm2_forward(params, ids, config: Lfm2Config):
    """``ids [B, S] -> logits [B, S, vocab]``, the whole sequence at once (no
    cache): causal attention, and every conv layer from zeros."""
    B = ids.shape[0]
    none_before = jnp.zeros((B, config.conv_taps - 1, config.dim))

    def attend(index, q, k, v):
        return dot_product_attention(q, k, v, causal=True)

    def convolve(index, layer_params, u):
        return conv_operator(layer_params, u, none_before, jnp.zeros((B,), jnp.int32))[0]

    h, _ = _layers(params, params["embed_tokens"]["embedding"][ids], None, None, config,
                   attend, convolve)
    return llama_head(params, h, config)


def _paged_forward(params, ids, pool, block_tables, positions, valid, rows, config, block_size):
    """Forward ``ids [B, S]`` at per-row ``positions [B, S]`` against the
    engine's cache ``{"k", "v": [attention layers, num_blocks, block_size, ...],
    "state": [conv layers, state rows, K - 1, dim]}``. An attention layer
    writes its keys and values through the block tables into its part of the
    K/V stack and attends over the row's blocks
    (``ops.flash_attention.paged_write_attend``); a conv layer reads row
    ``rows[b]`` of its part of the state, or zeros where ``positions[b, 0]``
    is 0 (a sequence's start, whatever the row's last owner left), and
    writes ``z`` at the last real positions of ``valid`` back. An idle
    slot's row is the null row, which nobody reads for a result. Returns
    ``(logits, new pool, counts)``."""
    k_pool, v_pool, state = pool["k"], pool["v"], pool["state"]
    starts = (positions[:, 0] == 0)[:, None, None]
    n_real = jnp.sum(valid, axis=1, dtype=jnp.int32)

    def attend(index, q, k, v):
        nonlocal k_pool, v_pool
        attn, k_pool, v_pool = paged_write_attend(
            q, k, v, k_pool, v_pool, index, block_tables, positions, block_size)
        return attn

    def convolve(index, layer_params, u):
        nonlocal state
        with jax.named_scope("conv_state"):  # the gather and the scatter of the rows, in a profile
            prev = jnp.where(starts, 0, state[index, rows])
        out, new = conv_operator(layer_params, u, prev, n_real)
        with jax.named_scope("conv_state"):
            state = state.at[index, rows].set(new.astype(state.dtype))
        return out

    h, counts = _layers(params, params["embed_tokens"]["embedding"][ids], positions, valid,
                        config, attend, convolve)
    return llama_head(params, h, config), {"k": k_pool, "v": v_pool, "state": state}, counts
