"""The ``moe/mellum`` kind: a sequential pre-norm decoder (two RMSNorms a
layer) whose every FFN is a routed expert layer, softmax top-k routing over
experts all held on the chip, no shared expert, sliding-window layers with the
plain rotary table and full layers with YaRN's, half-split rotary on both, an
untied head (JetBrains Mellum2-12B-A2.5B-Instruct is of this shape). The
program's side is ``accelerate_tpu.models.mellum``; its plain reference is
``mellum_reference.py`` and its operation count is here. It serves
(``reference_logits``) and does not train: the routed layer's grouped matmul
has no backward.

It lies under ``kinds/moe/`` for the reason ``cohere2_moe.py`` beside it gives."""

from __future__ import annotations

import jax

from accelerate_tpu.models import mellum as m
from benchmarks.chip import mellum_reference as reference

init = m.init_mellum


def program_config(c: dict, *, n_layers: int, max_seq_len: int):
    rope = c["rope_parameters"]
    yarn, plain = rope["full_attention"], rope["sliding_attention"]
    if not (c["model_type"] == "mellum" and c["norm_topk_prob"] and c["hidden_act"] == "silu"
            and not c["attention_bias"] and not c["tie_word_embeddings"]
            and set(c["mlp_layer_types"]) == {"sparse"} and c["use_sliding_window"]
            and yarn["rope_type"] == "yarn" and plain["rope_type"] == "default"
            and yarn["rope_theta"] == plain["rope_theta"]):
        raise ValueError("the program's MellumConfig is this published shape and no other")
    return m.MellumConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], expert_dim=c["moe_intermediate_size"],
        num_experts=c["num_experts"], experts_per_token=c["num_experts_per_tok"],
        layer_types=tuple(c["layer_types"][:n_layers]), sliding_window=c["sliding_window"],
        experts_held=c.get("num_experts_held"), first_expert=c.get("first_expert_held", 0),
        max_seq_len=max_seq_len, rope_theta=plain["rope_theta"],
        yarn=(("factor", yarn["factor"]),
              ("original_max_seq", yarn["original_max_position_embeddings"]),
              ("beta_fast", yarn["beta_fast"]), ("beta_slow", yarn["beta_slow"]),
              ("attention_factor", yarn["attention_factor"])),
        norm_eps=c["rms_norm_eps"],
    )


def forward_flops_per_token(c: dict, seq_len: float, n_layers: int) -> float:
    """Matmul operations of one token's forward pass: the attention
    projections, the router's ``num_experts`` outputs, ``num_experts_per_tok``
    experts of ``3 x hidden_size x moe_intermediate_size`` (every expert is
    held here, so every chosen pair is computed), the head. Attention scores:
    a token at position p scores ``p + 1`` keys on a full layer and ``min(p +
    1, sliding_window)`` on a window layer, averaged over a sequence of
    ``seq_len``."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    dq = c["num_attention_heads"] * c["head_dim"]
    dkv = c["num_key_value_heads"] * c["head_dim"]
    held = c.get("num_experts_held", c["num_experts"]) / c["num_experts"]
    dense = 2 * (d * dq + 2 * d * dkv + dq * d + d * c["num_experts"]
                 + c["num_experts_per_tok"] * held * 3 * d * f)

    def mean_keys(kind):
        window = c["sliding_window"]
        if kind != "sliding_attention" or seq_len <= window:
            return (seq_len + 1) / 2
        return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len

    scores = sum(4 * dq * mean_keys(kind) for kind in c["layer_types"][:n_layers])
    return n_layers * dense + scores + 2 * d * c["vocab_size"]


def reference_logits(c: dict):
    """``fn(params, ids [T]) -> float32 logits [T, vocab]``: the teacher-forced
    full forward. One layer function a layer kind is jitted, once a run, and
    the layers go through them one at a time, so only one layer's attention
    weights, and one routed expert, are ever held in float32."""
    fns = reference.layer_fns(c, jit=jax.jit)

    def logits(params, ids):
        return reference.logits(params, ids, layer_types=c["layer_types"][:len(params["layers"])],
                                eps=c["rms_norm_eps"], fns=fns)

    return logits
