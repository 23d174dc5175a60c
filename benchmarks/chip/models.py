"""The small registry behind a configuration's ``kind``: how to turn the
published keys into the program's config, and where its init, loss, sharding
rules, operation count and plain reference are. A configuration of a kind that
is here is a data file; a new kind is a new entry."""

from __future__ import annotations

import functools

from benchmarks.chip import flops, reference


def _bert_config(c: dict, *, n_layers: int, max_seq_len: int):
    from accelerate_tpu.models import BertConfig

    return BertConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], ffn_dim=c["intermediate_size"],
        max_seq_len=max_seq_len, type_vocab_size=c["type_vocab_size"],
        num_labels=c["assumed"]["num_labels"], norm_eps=c["layer_norm_eps"],
    )


def _llama_config(c: dict, *, n_layers: int, max_seq_len: int):
    from accelerate_tpu.models import LlamaConfig

    if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"]:
        raise ValueError("the program's LlamaConfig derives head_dim as dim / n_heads")
    if c.get("sliding_window") is not None:
        raise ValueError("the paged kernels cannot express a sliding window")
    return LlamaConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        ffn_dim=c["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
    )


def _bert_kind():
    from accelerate_tpu import models as m

    return {
        "program_config": _bert_config,
        "init": m.init_bert,
        "loss": lambda cfg, **kw: (lambda p, b: m.bert_loss(p, b, cfg)),
        "shard_rules": m.bert_shard_rules,
        "forward_flops_per_token": flops.bert_forward_flops_per_token,
        "reference_loss": lambda c: functools.partial(
            reference.bert_loss, n_heads=c["num_attention_heads"], eps=c["layer_norm_eps"]),
    }


def _llama_kind():
    from accelerate_tpu import models as m

    return {
        "program_config": _llama_config,
        "init": m.init_llama,
        # remat as a cell asks for it: at long sequences the activations of
        # every layer do not fit beside 16 bytes a parameter
        "loss": lambda cfg, remat=False: (lambda p, b: m.llama_loss(p, b, cfg, remat=remat)),
        "shard_rules": m.llama_shard_rules,
        "forward_flops_per_token": flops.llama_forward_flops_per_token,
        "reference_loss": lambda c: functools.partial(
            reference.llama_loss, n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], eps=c["rms_norm_eps"], theta=c["rope_theta"]),
    }


_KINDS = {"bert": _bert_kind, "llama": _llama_kind}


def kind_of(config: dict) -> dict:
    if config["kind"] not in _KINDS:
        raise KeyError(f"no model kind {config['kind']!r}; the registry has {sorted(_KINDS)}")
    return _KINDS[config["kind"]]()


def depth(cell) -> int:
    """Depth is the one key a cell may set over its configuration."""
    return int(cell.spec.get("n_layers", cell.config["num_hidden_layers"]))
