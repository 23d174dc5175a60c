"""The ``moe/cohere2_moe`` kind and the cell ``command-a-plus.rag-sat``,
rehearsed on the CPU: the configuration keeps every published width, the serve
runner takes the kind at a tiny size, and each of the cell's five per-layer
metrics is held to a hand count on a made-up slice (the times are invented:
nothing here is a device number)."""

import collections
import copy
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import harness, models  # noqa: E402
from benchmarks.chip.runners import serve  # noqa: E402

CELL = "command-a-plus.rag-sat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(vocab_size=384, hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
            head_dim=16, intermediate_size=64, num_experts=16, num_experts_per_tok=4,
            num_experts_held=4, num_shared_experts=2, sliding_window=32)
TINY_ENGINE = dict(max_slots=4, num_blocks=129, block_size=8, max_seq_len=128, slot_buckets=[4],
                   block_buckets=[16], prefill_buckets=[16, 32], admit_watermark_blocks=4)


def test_the_configuration_keeps_every_published_width_and_says_what_it_cut():
    c = harness.load_cell(CELL).config
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]) == (
        4096, 128, 8, 128)
    assert (c["intermediate_size"], c["num_experts"], c["num_experts_per_tok"],
            c["num_shared_experts"], c["sliding_window"], c["rope_theta"]) == (4096, 128, 8, 4, 4096, 50000)
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_experts_held"], c["vocab_size"], c["vocab_size_published"]) == (16, 32768, 262144)
    assert c["vocab_size"] * 8 >= c["vocab_size_published"] and c["num_experts_held"] >= 8  # the floors
    assert set(c["assumed"]) == set(c["assumed_why"]) and "8 chips share each layer" in c["deployment"]
    assert set(c["reduced_why"]) == set(c["reduced"])
    entry = next(e for e in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["configs"]
                 if e["name"] == "command-a-plus")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    cell = harness.load_cell(CELL)
    assert models.depth(cell) == 4 and c["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    if os.path.exists(CATALOG):  # every number of the catalog's config under the same key, but the reduced
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "command-a-plus-05-2026")
        assert c["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if c.get(k) != v}
        assert differs == {"vocab_size"} and differs <= set(c["reduced"])


def test_forward_flops_count_one_routed_expert_a_token_and_the_window():
    cell = harness.load_cell(CELL)
    count = models.kind_of(cell.config, cell.root)["forward_flops_per_token"]
    d = f = 4096
    dense = 2 * (d * 16384 + 2 * d * 1024 + 16384 * d + d * 128 + (4 + 8 * 16 / 128) * 3 * d * f)
    # a sequence of 1000: every layer scores (1000 + 1) / 2 keys a token on average
    assert count(cell.config, 1000, 4) == pytest.approx(
        4 * dense + 4 * 4 * 16384 * 500.5 + 2 * d * 32768)
    # 6000 tokens: a window layer's mean is (4096 x 4097 / 2 + 1904 x 4096) / 6000 = 2698.3 keys
    window_mean = (4096 * 4097 / 2 + 1904 * 4096) / 6000
    assert count(cell.config, 6000, 4) == pytest.approx(
        4 * dense + 4 * 16384 * (3 * window_mean + 3000.5) + 2 * d * 32768)


def test_serve_runner_takes_the_kind_at_a_tiny_size():
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell.config.update(TINY)
    cell.spec["dtype"] = "f32"
    cell.spec["engine"].update(TINY_ENGINE)
    # float32 on both sides: the engine's token is the reference's argmax at every position
    cell.spec["check"].update(max_tokens=128, margin=1e-3, agreement=0.99, margin_quantile=100)
    cell.traffic.update(prompt_len=[8, 100], output_len=[4, 16])
    cell.traffic["arrival"]["n_requests"] = 60
    record = serve.run(cell, seed=2147483659, seconds=1.0, trace=False, process_t0=0.0,
                       allow_cpu=True)
    assert record.correct and record.facts["check"]["ok"], record.facts
    assert record.facts["check"]["requests"] == 6 and record.failed == 0
    line = harness.result_line(cell, record, traced=False)
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"} and line["correct"] is True
    per_layer = {entry["name"] for entry in cell.per_layer}
    assert {"moe_gmm_share.serve", "moe_gmm_roofline.serve", "paged_decode_win_roofline.serve",
            "paged_prefill_win_roofline.serve", "moe_local_pairs_per_token.serve", "mfu.serve",
            "occupancy.serve", "paged_decode_share.serve"} <= per_layer
    assert not {"paged_decode_roofline.serve", "paged_prefill_roofline.serve"} & per_layer
    record = harness.dataclasses.replace(record, cell=cell)
    pairs = harness.layer_metric_reader("moe_local_pairs_per_token.serve")(record)
    assert 0.6 < pairs < 1.6  # 4 x 4 / 16 = 1 with even routing
    for name in per_layer:  # no trace: the device metrics read nothing and raise nothing
        if "roofline" in name or "share" in name:
            assert harness.layer_metric_reader(name)(record) is None


# ------------------------------------------------------- the readers, by hand


def _ring(step=5):
    """Step 5 of engine 7, made up: one prefill of 5000 tokens behind nothing
    (ten chunks of 512: 9 x 512 + 392) whose chunks each landed 500 pairs on
    14 experts a layer, then a decode batch of 30 rows holding 3000 blocks of
    which a window layer walks 2500, landing 28, 30, 31, 33 pairs on 12, 13,
    14, 15 experts."""
    ring = collections.deque(maxlen=64)
    key = dict(engine=7, step=step)
    ring.append(("atpu.serve.prefill", 0, 1, dict(key, rid=1, tokens=5000, cached=0)))
    for i in range(10):
        ring.append(("atpu.serve.moe", 0, 0, dict(
            key, kind="prefill", rid=1, tokens=512 if i < 9 else 392, local_pairs=[500] * 4,
            experts_hit=[14] * 4, max_expert_load=[50] * 4)))
    ring.append(("atpu.serve.build", 0, 1, dict(key, batch=30, slot_bucket=32, block_bucket=400,
                                                live_blocks=3000, window_blocks=2500)))
    ring.append(("atpu.serve.moe", 0, 0, dict(
        key, kind="decode", tokens=30, local_pairs=[28, 30, 31, 33], experts_hit=[12, 13, 14, 15],
        max_expert_load=[5, 6, 7, 8])))
    ring.append(("atpu.serve.step", 0, 2, key))
    return ring


def _record(ops, calls, steps=5):
    trace = {"window_s": 2.0, "busy_s": 1.6, "kernel_s": 1.0, "device_ops": ops,
             "device_op_calls": calls, "idle_gaps": []}
    clocks = {"steps": steps, "slice_steps": [5, 6], "device_kind": "TPU v5 lite", "chips": 1}
    return harness.Record(True, 0, 0, {}, clocks, {}, trace=trace, cell=harness.load_cell(CELL))


OPS = [["moe_gmm.3", 0.10], ["moe_gmm.4", 0.06], ["paged_prefill_win.2", 0.6],
       ["paged_prefill.7", 0.2], ["paged_decode_win.5", 0.012], ["paged_decode.6", 0.005],
       ["fusion.1 kLoop", 0.3]]
# 11 model calls x 4 layers x 3 matmuls; 10 chunks and 1 decode batch x 3 window layers / 1 full
CALLS = {"moe_gmm.3": 66, "moe_gmm.4": 66, "paged_prefill_win.2": 30, "paged_prefill.7": 10,
         "paged_decode_win.5": 3, "paged_decode.6": 1, "fusion.1 kLoop": 11}
PAIRS = 10 * 4 * 500 + 122  # local pairs of the slice
EXPERTS = 10 * 4 * 14 + 54  # expert stacks of three matrices that had to be read
HAND = {
    # bytes: experts x 3 x 4096 x 4096 x 2 + pairs x 2 x 4096 x 2 = 62.14 GB -> 75.87 ms at
    # 819 GB/s; operations: 6 x 4096 x 4096 x 20122 pairs = 2.026 T -> 10.28 ms: the bytes bind
    "moe_gmm_roofline.serve": 100 * ((EXPERTS * 3 * 4096 * 4096 * 2 + PAIRS * 2 * 4096 * 2)
                                     / 819e9) / 0.16,
    # 3 window layers x 2 B x (2500 blocks x 16 x 1024 x 2 (K, V) + 30 rows x 16384 x 2) =
    # 497.4 MB -> 0.6073 ms; operations 3 x 4 x 40000 tokens x 16384 = 7.86 G -> 0.04 ms
    "paged_decode_win_roofline.serve": 100 * (3 * 2 * (2500 * 16 * 1024 * 2 + 30 * 16384 * 2)
                                              / 819e9) / 0.012,
    "moe_gmm_share.serve": 100 * 0.16 / 1.6,
    "paged_decode_share.serve": 100 * 0.017 / 1.6,  # both names hold `paged_decode`
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_reader_against_a_hand_count(monkeypatch, name):
    monkeypatch.setattr(tracing, "_RING", _ring())
    value = harness.layer_metric_reader(name)(_record(OPS, CALLS))
    assert value == pytest.approx(HAND[name], rel=1e-9) and 0.0 < value < 100.0


def test_windowed_prefill_roofline_against_a_hand_count(monkeypatch):
    """Ten chunks of a 5000-token prompt, window 4096. Pairs inside the
    window: positions 0-4095 see p + 1 keys (4096 x 4097 / 2 = 8 390 656), the
    other 904 see 4096 each (3 702 784): 12 093 440 pairs x 4 x 16384 x 3
    layers = 2.378 T operations -> 12.07 ms at 197 T/s. Bytes: a chunk at s
    reads keys and values from max(0, s - 4095) on: the first eight chunks
    (s + n <= 4096) read s + n, the ninth (s = 4096) 4607, the last (s = 4608,
    n = 392) 4487; the operations bind."""
    monkeypatch.setattr(tracing, "_RING", _ring())
    value = harness.layer_metric_reader("paged_prefill_win_roofline.serve")(_record(OPS, CALLS))
    pairs = 4096 * 4097 // 2 + 904 * 4096
    kv_tokens = sum(512 * (i + 1) for i in range(8)) + 4607 + 4487
    bytes_moved = 3 * 2 * (kv_tokens * 1024 * 2 + 5000 * 16384 * 2)
    least_s = max(3 * 4 * 16384 * pairs / 197e12, bytes_moved / 819e9)
    assert least_s == pytest.approx(3 * 4 * 16384 * pairs / 197e12)  # the operations bind
    assert value == pytest.approx(100 * least_s / 0.6, rel=1e-9) and 0.0 < value < 100.0


def test_local_pairs_per_token_counts_real_tokens_and_layers(monkeypatch):
    monkeypatch.setattr(tracing, "_RING", _ring(step=0))  # the window's steps, not the slice's
    read = harness.layer_metric_reader("moe_local_pairs_per_token.serve")
    assert read(_record(OPS, CALLS, steps=1)) == pytest.approx(PAIRS / (4 * (5000 + 30)))
    monkeypatch.setattr(tracing, "_RING", collections.deque(
        r for r in _ring(step=0) if r[0] != "atpu.serve.moe"))
    assert read(_record(OPS, CALLS, steps=1)) is None  # a program that counts no routing


@pytest.mark.parametrize("name", ["moe_gmm_roofline.serve", "paged_decode_win_roofline.serve",
                                  "paged_prefill_win_roofline.serve"])
def test_new_rooflines_read_nothing_where_records_and_trace_do_not_match(monkeypatch, name):
    """None, never 0: no trace, a trace without the kernel's name (the parent's
    program has none of the three), a ring without the counters (the parent
    writes no ``atpu.serve.moe`` and no ``window_blocks``), and calls that are
    not what the records account for."""
    read = harness.layer_metric_reader(name)
    monkeypatch.setattr(tracing, "_RING", _ring())
    assert read(_record(OPS, CALLS)) is not None
    record = _record(OPS, CALLS)
    record.trace = None
    assert read(record) is None
    parents = [op for op in OPS if "moe_gmm" not in op[0] and "_win" not in op[0]]
    assert read(_record(parents, CALLS)) is None
    assert read(_record(OPS, {**CALLS, "moe_gmm.3": 65, "paged_decode_win.5": 4,
                              "paged_prefill_win.2": 31})) is None
    bare = collections.deque(maxlen=64)
    for name_, t0, t1, key in _ring():
        if name_ != "atpu.serve.moe":
            bare.append((name_, t0, t1, {k: v for k, v in key.items() if k != "window_blocks"}))
    monkeypatch.setattr(tracing, "_RING", bare)
    if "prefill" not in name:  # the prefill reader needs only what the parent's ring has
        assert read(_record(OPS, CALLS)) is None


@pytest.mark.parametrize("s,n,window", [(5000, 512, 4096), (0, 512, 4096), (3900, 512, 4096),
                                        (4095, 2, 4096), (10, 5, 8), (3, 2, 8)])
def test_pairs_inside_the_window_against_the_sum_written_out(s, n, window):
    from benchmarks.chip import windowed

    assert windowed.pairs_in_window(s, n, window) == sum(min(p + 1, window) for p in range(s, s + n))
    assert list(windowed.chunks(1200, 64, 512)) == [(64, 512), (576, 512), (1088, 176)]
