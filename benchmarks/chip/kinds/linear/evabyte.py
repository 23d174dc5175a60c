"""The ``linear/evabyte`` kind: a byte-level pre-norm decoder whose every
layer is EVA attention (exact keys inside a blockwise window, one pooled
key/value pair for every chunk of the closed windows, one softmax over both)
and a SwiGLU, unit-offset RMSNorms, a float32 residual stream and float32
logits, untied next-byte head among ``num_pred_heads`` (EvaByte 6.5B is of
this shape). The program's side is ``accelerate_tpu.models.evabyte``; its
plain reference is ``evabyte_reference.py`` and its operation count is here.
It serves (``reference_logits``) and does not train: at 16 bytes a parameter a
layer is 3.2 GB.

It lies under ``kinds/linear/`` for the reason ``kinds/moe/cohere2_moe.py``
gives: ``tests/chip_benchmark/test_kinds_and_rooflines.py`` holds
``kinds/*.py`` to ``['bert', 'llama']``."""

from __future__ import annotations

import jax

from accelerate_tpu.models import evabyte as m
from benchmarks.chip import evabyte_reference as reference

init = m.init_evabyte


def program_config(c: dict, *, n_layers: int, max_seq_len: int):
    assumed = c["assumed"]
    if not (c["model_type"] == "evabyte" and c["attention_class"] == "eva"
            and c["hidden_act"] == "silu" and not c["attention_bias"]
            and not c["tie_word_embeddings"] and c["fp32_skip_add"] and c["fp32_logits"]
            and c["norm_add_unit_offset"] and c["rope_scaling"] is None
            and c["num_key_value_heads"] == c["num_attention_heads"]
            and assumed["pooling_scale"] == reference.POOL_SCALE
            and assumed["window_alignment"] == reference.WINDOW_ALIGNMENT):
        raise ValueError("the program's EvaByteConfig is this published shape and no other")
    return m.EvaByteConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], ffn_dim=c["intermediate_size"],
        window_size=c["window_size"], chunk_size=c["chunk_size"],
        num_pred_heads=c["num_pred_heads"], heads_served=assumed["heads_served"],
        max_seq_len=max_seq_len, rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        init_std=c["init_std"],
    )


def mean_keys(seq_len: float, window: int, chunk: int) -> float:
    """The keys a token scores, averaged over the positions ``0..seq_len-1``:
    at position ``i``, ``(i mod W) + 1`` of its window and ``(i // W) x W / C``
    pooled pairs of the closed windows."""
    full, rest = divmod(seq_len, window)
    raw = full * window * (window + 1) / 2 + rest * (rest + 1) / 2
    pooled = window / chunk * (window * full * (full - 1) / 2 + full * rest)
    return (raw + pooled) / seq_len


def forward_flops_per_token(c: dict, seq_len: float, n_layers: int) -> float:
    """Matmul operations of one token's forward pass: the four projections
    and the SwiGLU of every layer, the scores and the weighted sum over
    :func:`mean_keys` keys (``4 x hidden_size`` each), and the one head
    served. The pooling (a chunk's 16 keys against ``mu`` and ``phi`` and
    their weighted sums, ``8 x hidden_size`` a token) is elementwise work
    and counts nothing here."""
    d, f = c["hidden_size"], c["intermediate_size"]
    dense = 2 * (4 * d * d + 3 * d * f)
    scores = 4 * d * mean_keys(seq_len, c["window_size"], c["chunk_size"])
    return n_layers * (dense + scores) + 2 * d * c["vocab_size"]


def reference_logits(c: dict):
    """``fn(params, ids [T]) -> float32 logits [T, vocab]``: the teacher-forced
    full forward. One layer is jitted, once a run, and the stacked tree's
    layers go through it one at a time, so only one is ever held in float32."""
    fn = reference.layer_fn(c, jit=jax.jit)

    def logits(params, ids):
        return reference.logits(params, ids, window=c["window_size"], eps=c["rms_norm_eps"], fn=fn)

    return logits
