"""The ``llama`` kind: a pre-norm decoder with RMSNorm, half-split rotary
embeddings, grouped-query attention and SwiGLU (Mistral-7B-v0.3 is of this
shape). The program's side is ``accelerate_tpu.models``; its plain reference
and its operation count are in ``reference.py`` and ``flops.py``. It trains
(``reference_loss``) and serves (``reference_logits``)."""

from __future__ import annotations

import functools

from accelerate_tpu import models as m
from benchmarks.chip import flops, reference

init = m.init_llama
shard_rules = m.llama_shard_rules
forward_flops_per_token = flops.llama_forward_flops_per_token


def program_config(c: dict, *, n_layers: int, max_seq_len: int):
    if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"]:
        raise ValueError("the program's LlamaConfig derives head_dim as dim / n_heads")
    if c.get("sliding_window") is not None:
        raise ValueError("the paged kernels cannot express a sliding window")
    return m.LlamaConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        ffn_dim=c["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
    )


def loss(cfg, remat=False):
    # remat as a cell asks for it: at long sequences the activations of every
    # layer do not fit beside 16 bytes a parameter
    return lambda p, b: m.llama_loss(p, b, cfg, remat=remat)


def _shape(c: dict) -> dict:
    return dict(n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
                eps=c["rms_norm_eps"], theta=c["rope_theta"])


def reference_loss(c: dict):
    return functools.partial(reference.llama_loss, **_shape(c))


def reference_logits(c: dict):
    """``fn(params, ids [T]) -> float32 logits [T, vocab]``: the teacher-forced
    full forward. One layer is jitted, once a run, and the stacked tree's
    layers go through it one at a time, so only one is ever held in float32."""
    import jax

    shape = _shape(c)
    layer_fn = jax.jit(functools.partial(reference.llama_layer, **shape))
    return lambda params, ids: reference.llama_logits(params, ids, layer_fn=layer_fn, **shape)
