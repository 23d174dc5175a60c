"""The ``moe/cohere2_moe`` kind: a parallel-block decoder (one LayerNorm
feeding attention and the expert layer), sigmoid top-k routing over the experts
a chip holds of a wider router, shared experts averaged, sliding-window layers
with interleaved rotary pairs and full layers with no positions, a tied
embedding (CohereLabs command-a-plus-05-2026 is of this shape). The program's
side is ``accelerate_tpu.models.cohere2_moe``; its plain reference is
``cohere2_moe_reference.py`` and its operation count is here. It serves
(``reference_logits``) and does not train: at 16 bytes a parameter no cut of
it inside the guide's floors fits a chip.

It lies under ``kinds/moe/`` and not beside the other two because
``tests/chip_benchmark/test_kinds_and_rooflines.py`` holds ``kinds/*.py`` to
``['bert', 'llama']``, and a PR that adds a configuration may not edit a file
the benchmark has."""

from __future__ import annotations

import functools

from accelerate_tpu.models import cohere2_moe as m
from benchmarks.chip import cohere2_moe_reference as reference

init = m.init_cohere2_moe


def _windows(c: dict, n_layers: int) -> tuple:
    """A layer's window, None for a full-attention layer, for the first
    ``n_layers`` of the published pattern."""
    return tuple(c["sliding_window"] if kind == m.SLIDING else None
                 for kind in c["layer_types"][:n_layers])


def program_config(c: dict, *, n_layers: int, max_seq_len: int):
    if not (c["use_parallel_block"] and c["tie_word_embeddings"] and c["norm_topk_prob"]
            and c["expert_selection_fn"] == "sigmoid" and not c["use_qk_norm"]
            and c["position_embedding_type"] == "rope_gptj" and c["rotary_pct"] == 1
            and c["shared_expert_combination_strategy"] == "average"
            and c["first_k_dense_replace"] == 0 and c["hidden_act"] == "silu"):
        raise ValueError("the program's Cohere2MoeConfig is this published shape and no other")
    return m.Cohere2MoeConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], expert_dim=c["intermediate_size"],
        num_experts=c["num_experts"], experts_per_token=c["num_experts_per_tok"],
        num_shared_experts=c["num_shared_experts"],
        layer_types=tuple(c["layer_types"][:n_layers]), sliding_window=c["sliding_window"],
        experts_held=c["num_experts_held"], first_expert=c.get("first_expert_held", 0),
        max_seq_len=max_seq_len, rope_theta=c["rope_theta"], norm_eps=c["layer_norm_eps"],
        logit_scale=c["logit_scale"],
    )


def forward_flops_per_token(c: dict, seq_len: float, n_layers: int) -> float:
    """Matmul operations of one token's forward pass on this chip's share:
    the attention projections, the shared experts, the router, and the routed
    experts a token meets HERE in expectation (``experts per token x held /
    num_experts`` of them: 1 at 8 x 16 / 128; the engine's counters say what
    was routed). Attention scores: a token at position p scores ``p + 1`` keys
    on a full layer and ``min(p + 1, window)`` on a window layer, averaged
    over a sequence of ``seq_len``."""
    d, f = c["hidden_size"], c["intermediate_size"]
    dq = c["num_attention_heads"] * c["head_dim"]
    dkv = c["num_key_value_heads"] * c["head_dim"]
    routed_here = c["num_experts_per_tok"] * c["num_experts_held"] / c["num_experts"]
    dense = 2 * (d * dq + 2 * d * dkv + dq * d + d * c["num_experts"]
                 + (c["num_shared_experts"] + routed_here) * 3 * d * f)

    def mean_keys(window):
        if window is None or seq_len <= window:
            return (seq_len + 1) / 2
        return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len

    scores = sum(4 * dq * mean_keys(w) for w in _windows(c, n_layers))
    return n_layers * dense + scores + 2 * d * c["vocab_size"]


def reference_logits(c: dict):
    """``fn(params, ids [T]) -> float32 logits [T, vocab]``: the teacher-forced
    full forward. One layer function a layer kind is jitted, once a run, and
    the layers go through them one at a time, so only one layer's attention
    and shared weights, and one routed expert, are ever held in float32."""
    import jax

    shape = dict(n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
                 eps=c["layer_norm_eps"], theta=c["rope_theta"], top_k=c["num_experts_per_tok"],
                 first_expert=c.get("first_expert_held", 0))
    layer_fns = {w: jax.jit(functools.partial(reference.layer, window=w, **shape))
                 for w in (c["sliding_window"], None)}

    def logits(params, ids):
        n_layers = len(params["layers"])
        return reference.logits(params, ids, windows=_windows(c, n_layers),
                                eps=c["layer_norm_eps"], logit_scale=c["logit_scale"],
                                layer_fns=layer_fns)

    return logits
