"""The ``bert`` kind: a post-norm encoder with a pooled classification head
(``google-bert/bert-base-uncased`` is of this shape). The program's side is
``accelerate_tpu.models``; its plain reference and its operation count are in
``reference.py`` and ``flops.py``. It trains only: there is no
``reference_logits`` hook, so the serve runner refuses it by name."""

from __future__ import annotations

import functools

from accelerate_tpu import models as m
from benchmarks.chip import flops, reference  # noqa: F401  (`reference` names the module for the tests)

init = m.init_bert
shard_rules = m.bert_shard_rules
forward_flops_per_token = flops.bert_forward_flops_per_token


def program_config(c: dict, *, n_layers: int, max_seq_len: int):
    return m.BertConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], ffn_dim=c["intermediate_size"],
        max_seq_len=max_seq_len, type_vocab_size=c["type_vocab_size"],
        num_labels=c["assumed"]["num_labels"], norm_eps=c["layer_norm_eps"],
    )


def loss(cfg, **kw):
    return lambda p, b: m.bert_loss(p, b, cfg)


def reference_loss(c: dict):
    return functools.partial(
        reference.bert_loss, n_heads=c["num_attention_heads"], eps=c["layer_norm_eps"])
