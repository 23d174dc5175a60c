"""The ``moe/mellum`` kind and the cell ``mellum2-12b.code-sat``, rehearsed on
the CPU: the configuration keeps every published key, the operation count is
held to a hand count, the serve runner takes the kind at a tiny size, each of
the cell's three new per-layer metrics is held to a hand count on a made-up
slice (the times are invented: nothing here is a device number), and the
shape of the four-chip causal-LM cell that PERF.md keeps for later (FSDP over
four devices and remat together) runs through the train runner at a tiny size."""

import collections
import copy
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import harness, models  # noqa: E402
from benchmarks.chip.runners import serve, train  # noqa: E402

CELL = "mellum2-12b.code-sat"
CHIP = os.path.join(REPO, "benchmarks", "chip")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
TINY = dict(vocab_size=384, hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
            head_dim=16, moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
            sliding_window=32)
TINY_ENGINE = dict(max_slots=4, num_blocks=129, block_size=8, max_seq_len=128, slot_buckets=[4],
                   block_buckets=[16], prefill_buckets=[16, 32], admit_watermark_blocks=4)
NEW_READERS = ["moe_gmm_narrow_roofline.serve", "moe_max_load_ratio.serve",
               "kv_behind_window_share.serve"]


def test_the_configuration_keeps_every_published_key_and_cuts_only_depth():
    cell = harness.load_cell(CELL)
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]) == (
        2304, 32, 4, 128)
    assert (c["moe_intermediate_size"], c["intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["sliding_window"], c["vocab_size"]) == (896, 7168, 64, 8, 1024, 98304)
    yarn = c["rope_parameters"]["full_attention"]
    assert (yarn["rope_type"], yarn["factor"], yarn["original_max_position_embeddings"],
            yarn["attention_factor"]) == ("yarn", 16, 8192, 1.2772588722239782)
    assert c["reduced"] == ["num_hidden_layers"] == list(c["reduced_why"])
    assert "num_experts_held" not in c and "vocab_size_published" not in c  # nothing else is a share
    assert set(c["assumed"]) == set(c["assumed_why"]) >= {
        "qk_norm", "mtp_head", "window_counts_the_query", "router_dtype", "yarn_truncate", "max_seq_len"}
    assert "four-stage pipeline" in c["deployment"] and "7.59 GB" in c["size_arithmetic"]
    entry = next(e for e in MANIFEST["configs"] if e["name"] == "mellum2-12b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    # two whole periods of the pattern, and no leading dense layer
    assert models.depth(cell) == 8 and set(c["mlp_layer_types"]) == {"sparse"}
    assert c["layer_types"][:8] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    if os.path.exists(CATALOG):  # every key of the catalog's config, unchanged: depth is set by the cell
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert c["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if c.get(k) != v} == set()
    # the arithmetic the file states, from the keys
    layer = (2304 * 4096 * 2 + 2 * 2304 * 512) + 2304 * 64 + 64 * 3 * 2304 * 896
    assert round(layer / 1e6, 1) == 417.7 and round((8 * layer + 2 * 98304 * 2304) * 2 / 1e9, 2) == 7.59
    assert round((28 * layer + 2 * 98304 * 2304) / 1e9, 2) == 12.15  # the published count


def test_forward_flops_count_eight_narrow_experts_a_token_the_router_and_the_window():
    cell = harness.load_cell(CELL)
    count = models.kind_of(cell.config, cell.root)["forward_flops_per_token"]
    d = 2304
    dense = 2 * (d * 4096 + 2 * d * 512 + 4096 * d + d * 64 + 8 * 3 * d * 896)
    assert dense == pytest.approx(2 * 70.9e6, rel=2e-3)  # ISSUE 33: 70.9 M multiply-adds a layer
    # a sequence of 1000 (under the window): every layer scores (1000 + 1) / 2 keys a token
    assert count(cell.config, 1000, 8) == pytest.approx(
        8 * dense + 8 * 4 * 4096 * 500.5 + 2 * d * 98304)
    # 4753 tokens: a window layer's mean is (1024 x 1025 / 2 + 3729 x 1024) / 4753 = 913.8 keys
    window_mean = (1024 * 1025 / 2 + 3729 * 1024) / 4753
    assert count(cell.config, 4753, 8) == pytest.approx(
        8 * dense + 4 * 4096 * (6 * window_mean + 2 * 2377.0) + 2 * d * 98304)
    # one period: three window layers and one full
    assert count(cell.config, 4753, 4) == pytest.approx(
        4 * dense + 4 * 4096 * (3 * window_mean + 2377.0) + 2 * d * 98304)


def test_the_kind_refuses_another_published_shape():
    cell = harness.load_cell(CELL)
    program_config = models.kind_of(cell.config, cell.root)["program_config"]
    cfg = program_config(cell.config, n_layers=8, max_seq_len=16896)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.expert_dim, cfg.num_experts,
            cfg.experts_held, cfg.experts_per_token, cfg.sliding_window, cfg.vocab_size) == (
        2304, 32, 4, 128, 896, 64, 64, 8, 1024, 98304)
    assert dict(cfg.yarn)["original_max_seq"] == 8192 and not cfg.tie_embeddings
    for key, other in (("tie_word_embeddings", True), ("norm_topk_prob", False),
                       ("mlp_layer_types", ["dense"] + ["sparse"] * 27), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="this published shape and no other"):
            program_config({**cell.config, key: other}, n_layers=8, max_seq_len=128)


def _tiny_cell():
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell.config.update(TINY)
    cell.config["rope_parameters"]["full_attention"]["original_max_position_embeddings"] = 32
    cell.spec["dtype"] = "f32"
    cell.spec["engine"].update(TINY_ENGINE)
    # float32 on both sides: the engine's token is the reference's argmax at every position
    cell.spec["check"].update(max_tokens=128, margin=1e-3, agreement=0.99, margin_quantile=100)
    cell.traffic.update(prompt_len=[8, 100], output_len=[4, 16])
    cell.traffic["arrival"]["n_requests"] = 60
    return cell


def test_serve_runner_takes_the_kind_at_a_tiny_size():
    cell = _tiny_cell()
    record = serve.run(cell, seed=2147483659, seconds=1.0, trace=False, process_t0=0.0,
                       allow_cpu=True)
    assert record.correct and record.facts["check"]["ok"], record.facts
    assert record.facts["check"]["requests"] == 6 and record.failed == 0
    line = harness.result_line(cell, record, traced=False)
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"} and line["correct"] is True
    per_layer = {entry["name"] for entry in cell.per_layer}
    assert per_layer == {
        "occupancy.serve", "kernel_share.serve", "device_idle_share.serve",
        "paged_decode_share.serve", "host_gap_ms.serve", "mfu.serve", "moe_gmm_share.serve",
        "moe_local_pairs_per_token.serve", "paged_decode_win_roofline.serve",
        "paged_prefill_win_roofline.serve", *NEW_READERS}
    # its reader would take an expert's width from `intermediate_size`, the unused dense width
    assert "moe_gmm_roofline.serve" not in per_layer
    record = harness.dataclasses.replace(record, cell=cell)
    read = {name: harness.layer_metric_reader(name)(record) for name in per_layer - {"mfu.serve"}}
    assert read["moe_local_pairs_per_token.serve"] == 4.0  # every expert is held: top_k pairs a token
    assert 1.0 <= read["moe_max_load_ratio.serve"] <= 16.0
    # prompts of 8-100 against a window of 32: some of what three layers of four keep is behind it
    assert 0.0 < read["kv_behind_window_share.serve"] < 75.0
    for name, value in read.items():  # no trace: the device metrics read nothing and raise nothing
        if "roofline" in name or name.endswith("_share.serve") and "kv_" not in name:
            assert value is None, name


# ------------------------------------------------------- the readers, by hand


def _ring(step=5, with_held=True):
    """Step 5 of engine 7, made up: one prefill of 5000 tokens behind nothing
    in three chunks (2048, 2048, 904), each landing 8 pairs a token on all 64
    experts of each of 8 layers, the fullest expert getting 1.25 x its share;
    then a decode batch of 30 rows holding 9000 blocks of which a window layer
    walks 1950, its 240 pairs a layer on 57, 58, ... 64 experts, 10 on the fullest."""
    ring = collections.deque(maxlen=64)
    key = dict(engine=7, step=step)
    extra = dict(held=64, top_k=8) if with_held else {}
    ring.append(("atpu.serve.prefill", 0, 1, dict(key, rid=1, tokens=5000, cached=0)))
    for tokens in (2048, 2048, 904):
        ring.append(("atpu.serve.moe", 0, 0, dict(
            key, kind="prefill", rid=1, tokens=tokens, local_pairs=[8 * tokens] * 8,
            experts_hit=[64] * 8, max_expert_load=[tokens * 8 // 64 * 5 // 4] * 8, **extra)))
    ring.append(("atpu.serve.build", 0, 1, dict(key, batch=30, slot_bucket=32, block_bucket=1056,
                                                live_blocks=9000, window_blocks=1950)))
    ring.append(("atpu.serve.moe", 0, 0, dict(
        key, kind="decode", tokens=30, local_pairs=[240] * 8, experts_hit=list(range(57, 65)),
        max_expert_load=[10] * 8, **extra)))
    ring.append(("atpu.serve.step", 0, 2, key))
    return ring


def _record(ops, calls, steps=5):
    trace = {"window_s": 2.0, "busy_s": 1.6, "kernel_s": 1.0, "device_ops": ops,
             "device_op_calls": calls, "idle_gaps": []}
    clocks = {"steps": steps, "slice_steps": [5, 6], "device_kind": "TPU v5 lite", "chips": 1}
    return harness.Record(True, 0, 0, {}, clocks, {}, trace=trace, cell=harness.load_cell(CELL))


OPS = [["moe_gmm.3", 0.05], ["moe_gmm.4", 0.03], ["paged_prefill_win.2", 0.1],
       ["paged_decode_win.5", 0.012], ["fusion.1 kLoop", 0.3]]
CALLS = {"moe_gmm.3": 48, "moe_gmm.4": 48, "paged_prefill_win.2": 18, "paged_decode_win.5": 6,
         "fusion.1 kLoop": 4}  # 4 model calls x 8 layers x 3 matmuls = 96
PAIRS = 8 * 8 * 5000 + 8 * 240                  # local pairs of the slice
EXPERTS = 3 * 8 * 64 + sum(range(57, 65))       # expert stacks of three matrices that had to be read


def test_narrow_roofline_against_a_hand_count(monkeypatch):
    """Operations: 6 x 2304 x 896 x 321 920 pairs = 3.987 T -> 20.24 ms at 197
    T/s; bytes: 2020 experts x 3 x 2304 x 896 x 2 B + pairs x 2 x 2304 x 2 B =
    27.99 GB -> 34.17 ms at 819 GB/s: the bytes bind, over 0.08 s of kernel."""
    monkeypatch.setattr(tracing, "_RING", _ring())
    value = harness.layer_metric_reader("moe_gmm_narrow_roofline.serve")(_record(OPS, CALLS))
    bytes_moved = 2 * (EXPERTS * 3 * 2304 * 896 + PAIRS * 2 * 2304)
    assert bytes_moved / 819e9 > 6 * 2304 * 896 * PAIRS / 197e12  # the bytes bind
    assert value == pytest.approx(100 * (bytes_moved / 819e9) / 0.08, rel=1e-9) and 0 < value < 100
    # the accepted reader, on this configuration, would count the unused dense width: 8 x the bytes
    wide = harness.layer_metric_reader("moe_gmm_roofline.serve")(_record(OPS, CALLS))
    assert wide > 100.0 > value


def test_max_load_ratio_and_kv_behind_window_against_a_hand_count(monkeypatch):
    monkeypatch.setattr(tracing, "_RING", _ring(step=0))  # the window's steps, not the slice's
    record = _record(OPS, CALLS, steps=1)
    # 24 chunk-layers at 1.25 (904 tokens: 113 x 5 // 4 = 141 of a share of 113) and 8
    # decode-layers at 10 x 64 / 240
    ratios = [1.25] * 16 + [141 / 113] * 8 + [10 * 64 / 240] * 8
    assert harness.layer_metric_reader("moe_max_load_ratio.serve")(record) == pytest.approx(
        sum(ratios) / 32, rel=1e-12)
    # 6 of 8 layers keep 9000 - 1950 blocks that they never read again, of 8 x 9000 held
    assert harness.layer_metric_reader("kv_behind_window_share.serve")(record) == pytest.approx(
        100 * 6 * 7050 / (8 * 9000), rel=1e-12)
    # a record that does not say what is held: nothing to divide by
    monkeypatch.setattr(tracing, "_RING", _ring(step=0, with_held=False))
    assert harness.layer_metric_reader("moe_max_load_ratio.serve")(record) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_where_records_and_trace_do_not_match(monkeypatch, name):
    """None, never 0 and never an exception: a ring without the records (a
    program without the counters, as the parent of the PR that brought the
    kind), and for the roofline no trace, a trace without the kernel's name,
    or calls that are not three a layer for each record."""
    read = harness.layer_metric_reader(name)
    in_slice = "roofline" in name
    monkeypatch.setattr(tracing, "_RING", _ring(step=5 if in_slice else 0))
    record = _record(OPS, CALLS, steps=5 if in_slice else 1)
    assert read(record) is not None
    bare = collections.deque(maxlen=64)
    for name_, t0, t1, key in _ring(step=5 if in_slice else 0):
        if name_ != "atpu.serve.moe":
            bare.append((name_, t0, t1, {k: v for k, v in key.items() if k != "window_blocks"}))
    monkeypatch.setattr(tracing, "_RING", bare)
    assert read(record) is None
    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=4))
    assert read(record) is None  # no ring at all
    if in_slice:
        monkeypatch.setattr(tracing, "_RING", _ring())
        assert read(_record(OPS, {**CALLS, "moe_gmm.3": 47})) is None
        assert read(_record([op for op in OPS if "moe_gmm" not in op[0]], CALLS)) is None
        no_trace = _record(OPS, CALLS)
        no_trace.trace = None
        assert read(no_trace) is None
        other = _record(OPS, CALLS)
        other.cell = harness.load_cell("command-a-plus.rag-sat")  # no `moe_intermediate_size`
        assert read(other) is None


# ----------------------------------------------- the cell's files and the manifest


def test_the_cells_files_say_what_the_issue_asks():
    cell = harness.load_cell(CELL)
    mix, eng, check = cell.traffic, cell.spec["engine"], cell.spec["check"]
    assert (mix["prompt_len"], mix["output_len"], mix["block"], mix["sizes_seed"]) == (
        [512, 16384], [32, 512], 16, 0)
    assert mix["arrival"] == {"kind": "at_zero", "n_requests": 800} and "shared_prefix" not in mix
    assert (eng["max_slots"], eng["block_size"], eng["num_blocks"], eng["max_seq_len"]) == (
        32, 16, 14401, 16896)
    assert eng["slot_buckets"] == [32] and eng["block_buckets"] == [16896 // 16]
    assert max(eng["prefill_buckets"]) in (512, 1024, 2048)
    assert (cell.chips, cell.spec["n_layers"], cell.spec["dtype"]) == (1, 8, "bf16")
    assert check["max_tokens"] == 4096 and check["margin_quantile"] == 99
    for key in ("engine_why", "check_why", "why", "reduced"):
        assert len(cell.spec[key]) > 40 and "TBD" not in cell.spec[key], key
    # every request fits a row, and what the check may sample is over half of the mix
    from benchmarks.chip import traffic

    prompt, output = traffic.request_sizes(mix, 800)
    assert (prompt + output).max() <= eng["max_seq_len"]
    assert 0.5 < ((prompt + output) <= check["max_tokens"]).mean() < 0.65
    assert 4400 < prompt.mean() < 4800 and 160 < output.mean() < 185


def test_every_new_manifest_string_is_short_and_the_cell_stands_where_the_issue_says():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    config = next(c for c in MANIFEST["configs"] if c["name"] == "mellum2-12b")
    for text in (entry["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    metrics = {m["name"]: m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for name in NEW_READERS:
        assert len(name) <= 64 and metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
    assert CELL in metrics["serve_tokens_per_s"]["workloads"]
    assert CELL not in metrics["moe_gmm_roofline.serve"]["workloads"]
    assert (metrics["moe_max_load_ratio.serve"]["layer"], metrics["kv_behind_window_share.serve"]["layer"],
            metrics["moe_gmm_narrow_roofline.serve"]["layer"]) == ("model code", "scheduler / pager", "kernels")
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])  # no cell across chips yet: PERF.md 7


# ------------------------------------ the four-chip causal-LM cell's shape, for a later PR


def test_train_runner_takes_fsdp_over_four_devices_and_remat_together():
    """``mistral-7b.fsdp4-clm-s4096`` (PERF.md section 7) is a llama causal-LM
    cell with ``dp_shard_size`` 4 AND remat; the existing tests run the two
    apart. At a tiny size the train runner takes both from the cell's data."""
    cell = copy.deepcopy(harness.load_cell("bert-base.seqcls-s128"))
    cell.chips = 4
    cell.spec.update({
        "check_rows_at_a_time": 4, "parallelism": {"dp_shard_size": 4},
        "loss_kwargs": {"remat": True},
        "tolerances": {"loss": 1e-2, "grad_norm": 5e-2, "grad_norm_abs": 5e-2}})
    cell.traffic.update({"task": "clm", "seq_len": 64, "global_batch": 8, "n_batches": 2})
    cell.config = {**json.load(open(os.path.join(CHIP, "configs", "mistral-7b.json"))),
                   **dict(vocab_size=512, hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, head_dim=32, intermediate_size=512)}
    record = train.run(cell, seed=3, seconds=0.5, trace=False, process_t0=0.0, allow_cpu=True)
    assert record.correct, record.facts
    assert record.facts["mesh"] == {"dp_shard": 4} and record.facts["tokens_per_step"] == 8 * 64
