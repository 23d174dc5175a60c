"""The ``moe/lfm2`` kind: a pre-norm decoder (two RMSNorms a layer) most of
whose layers are gated short convolutions with a few rows of state a
sequence, the others grouped-query attention with a query/key norm before a
half-split rotary turn; leading dense SwiGLU layers and then routed experts
chosen by the largest sigmoid scores plus a bias and weighted by the scores
without it, all held on the chip, no shared expert; tied embeddings
(LiquidAI LFM2-24B-A2B is of this shape). The program's side is
``accelerate_tpu.models.lfm2``; its plain reference is ``lfm2_reference.py``
and its operation count is here. It serves (``reference_logits``) and does
not train: the routed layer's grouped matmul has no backward.

It lies under ``kinds/moe/`` for the reason ``cohere2_moe.py`` beside it gives."""

from __future__ import annotations

import jax

from accelerate_tpu.models import lfm2 as m
from benchmarks.chip import lfm2_reference as reference

init = m.init_lfm2


def program_config(c: dict, *, n_layers: int, max_seq_len: int):
    rope, assumed = c["rope_parameters"], c["assumed"]
    if not (c["model_type"] == "lfm2_moe" and not c["conv_bias"] and c["norm_topk_prob"]
            and c["use_expert_bias"] and c["routed_scaling_factor"] == 1
            and rope["rope_type"] == "default"
            and set(c["layer_types"]) <= {"conv", "full_attention"}
            and c["hidden_size"] % c["num_attention_heads"] == 0
            and reference.WEIGHT_EPS == assumed["route_weight_eps"]):
        raise ValueError("the program's Lfm2Config is this published shape and no other")
    return m.Lfm2Config(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        layer_types=tuple(c["layer_types"][:n_layers]), num_dense_layers=c["num_dense_layers"],
        conv_taps=c["conv_L_cache"], dense_dim=c["intermediate_size"],
        expert_dim=c["moe_intermediate_size"], num_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        experts_held=c.get("num_experts_held"), first_expert=c.get("first_expert_held", 0),
        route_weight_eps=assumed["route_weight_eps"], max_seq_len=max_seq_len,
        rope_theta=float(rope["rope_theta"]), norm_eps=c["norm_eps"],
        tie_embeddings=assumed["tie_word_embeddings"],
    )


def forward_flops_per_token(c: dict, seq_len: float, n_layers: int) -> float:
    """Matmul operations of one token's forward pass. A conv layer: its two
    projections, ``2 x (3 D^2 + D^2)``, and the taps, ``2 K D``; no score term.
    An attention layer: the four projections and the scores, a token at
    position p scoring ``p + 1`` keys, ``(seq_len + 1) / 2`` on average. The
    FFN of a layer under ``num_dense_layers`` is ``3 x D x intermediate_size``;
    after, the router's ``num_experts`` outputs and ``num_experts_per_tok``
    experts of ``3 x D x moe_intermediate_size`` (every expert is held here,
    so every chosen pair is computed). The head."""
    d = c["hidden_size"]
    dq = d  # num_attention_heads heads of hidden_size / num_attention_heads
    dkv = c["num_key_value_heads"] * (d // c["num_attention_heads"])
    held = c.get("num_experts_held", c["num_experts"]) / c["num_experts"]
    operator = {
        "conv": 2 * (3 * d * d + d * d) + 2 * c["conv_L_cache"] * d,
        "full_attention": 2 * (d * dq + 2 * d * dkv + dq * d) + 4 * dq * (seq_len + 1) / 2,
    }
    dense = 2 * 3 * d * c["intermediate_size"]
    routed = 2 * (d * c["num_experts"]
                  + c["num_experts_per_tok"] * held * 3 * d * c["moe_intermediate_size"])
    layers = sum(operator[kind] + (routed if l >= c["num_dense_layers"] else dense)
                 for l, kind in enumerate(c["layer_types"][:n_layers]))
    return layers + 2 * d * c["vocab_size"]


def reference_logits(c: dict):
    """``fn(params, ids [T]) -> float32 logits [T, vocab]``: the teacher-forced
    full forward. One layer function a layer kind and FFN kind is jitted, once
    a run, and the layers go through them one at a time, so only one layer's
    attention weights, and one routed expert, are ever held in float32."""
    fns = reference.layer_fns(c, jit=jax.jit)

    def logits(params, ids):
        return reference.logits(
            params, ids, layer_types=c["layer_types"][:len(params["layers"])],
            num_dense_layers=c["num_dense_layers"], eps=c["norm_eps"], fns=fns)

    return logits
