"""The ``moe/lfm2`` kind and the cell ``lfm2-24b.agent-sat``, rehearsed on the
CPU: the configuration keeps every published key, the operation count is held
to a hand count a layer kind, the serve runner takes the kind at a tiny size
(state rows, a packed pool and all), and each of the cell's two new per-layer
metrics is held to a hand count on a made-up slice (the times are invented:
nothing here is a device number)."""

import collections
import copy
import dataclasses
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import harness, models, traffic  # noqa: E402
from benchmarks.chip.runners import serve  # noqa: E402

CELL = "lfm2-24b.agent-sat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
TINY = dict(vocab_size=384, hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
            intermediate_size=96, moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4)
TINY_ENGINE = dict(max_slots=4, num_blocks=129, block_size=8, max_seq_len=128, slot_buckets=[4],
                   block_buckets=[16], prefill_buckets=[16, 32])
NEW_READERS = ["paged_decode_attn_layers_roofline.serve", "state_cache_share.serve"]
JOINED = ["occupancy.serve", "kernel_share.serve", "device_idle_share.serve",
          "paged_decode_share.serve", "host_gap_ms.serve", "mfu.serve", "moe_gmm_share.serve",
          "moe_local_pairs_per_token.serve"]
# the issue also names `moe_gmm_narrow_roofline.serve` and `moe_max_load_ratio.serve`:
# `test_mellum_cell.py` pins their `workloads` to its own cell alone, and a PR of this kind may
# edit no file the benchmark has, so they wait for a benchmark PR (PERF.md section 7)
D = 2048


def test_the_configuration_keeps_every_published_key_and_cuts_only_depth():
    cell = harness.load_cell(CELL)
    c = cell.config
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["num_attention_heads"], c["num_key_value_heads"],
            c["conv_L_cache"], c["vocab_size"], c["num_dense_layers"]) == (
        2048, 11776, 1536, 64, 4, 32, 8, 3, 65536, 2)
    assert c["reduced"] == ["num_hidden_layers"] == list(c["reduced_why"])
    assert "num_experts_held" not in c  # nothing is a share: every layer is whole
    assert set(c["assumed"]) == set(c["assumed_why"]) >= {
        "tie_word_embeddings", "route_weight_eps", "router_dtype", "conv_state_dtype", "max_seq_len"}
    assert c["assumed"]["tie_word_embeddings"] is True and c["assumed"]["route_weight_eps"] == 1e-6
    assert "four-stage pipeline" in c["deployment"] and "10.53 GB" in c["size_arithmetic"]
    entry = next(e for e in MANIFEST["configs"] if e["name"] == "lfm2-24b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    # both leading dense layers, then two whole periods
    assert models.depth(cell) == 10 and len(c["layer_types"]) == c["num_hidden_layers"] == 40
    assert c["layer_types"][:10] == ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    if os.path.exists(CATALOG):  # every key of the catalog's config, unchanged: depth is set by the cell
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "LFM2-24B-A2B")
        assert c["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if c.get(k) != v} == set()
    # the arithmetic the file states, from the keys
    conv, attention = 3 * D * D + 3 * D + D * D, 2 * D * D + 2 * D * 512
    dense, routed, embedding = 3 * D * 11776, 64 * 3 * D * 1536 + 64 * D, 65536 * D
    whole = 2 * (conv + dense) + 28 * conv + 10 * attention + 38 * routed + embedding
    cut = 2 * (conv + dense) + 6 * conv + 2 * attention + 8 * routed + embedding
    assert round(whole / 1e9, 1) == 23.8 and round(2 * cut / 1e9, 2) == 10.53
    active = whole - 38 * 60 * 3 * D * 1536
    assert round(active / 1e9, 2) == 2.33 and round((active + embedding) / 1e9, 2) == 2.46


def test_forward_flops_count_each_layer_kind_as_written_out_by_hand():
    cell = harness.load_cell(CELL)
    count = models.kind_of(cell.config, cell.root)["forward_flops_per_token"]
    conv = 2 * (3 * D * D + D * D) + 2 * 3 * D          # two projections and three taps
    assert conv == 33_566_720
    attention = 2 * (D * D + 2 * D * 512 + D * D)       # q, k, v, o at 32 / 8 heads of 64
    assert attention == 20_971_520
    dense = 2 * 3 * D * 11776
    routed = 2 * (D * 64 + 4 * 3 * D * 1536)            # the router and 4 experts a token
    assert (dense, routed) == (144_703_488, 75_759_616)
    head = 2 * D * 65536
    c = cell.config
    # one conv layer with the dense FFN: no score term whatever the length
    assert count(c, 1000, 1) == count(c, 4000, 1) == conv + dense + head
    # the first three: conv + dense twice, then attention + routed, scoring (1000 + 1) / 2 keys
    assert count(c, 1000, 3) == pytest.approx(
        2 * (conv + dense) + attention + 4 * D * 500.5 + routed + head)
    # the fourth is a conv layer with routed experts
    assert count(c, 1000, 4) - count(c, 1000, 3) == pytest.approx(conv + routed)
    # the cell's ten: 2 dense conv, 6 routed conv, 2 routed attention
    assert count(c, 1384, 10) == pytest.approx(
        8 * conv + 2 * dense + 8 * routed + 2 * (attention + 4 * D * 692.5) + head)
    assert count(c, 1384, 10) == pytest.approx(1.486e9, rel=2e-3)


def test_the_kind_refuses_another_published_shape():
    cell = harness.load_cell(CELL)
    program_config = models.kind_of(cell.config, cell.root)["program_config"]
    cfg = program_config(cell.config, n_layers=10, max_seq_len=4096)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dense_dim, cfg.expert_dim,
            cfg.num_experts, cfg.experts_held, cfg.experts_per_token, cfg.conv_taps,
            cfg.vocab_size, cfg.num_dense_layers) == (2048, 32, 8, 64, 11776, 1536, 64, 64, 4, 3, 65536, 2)
    assert cfg.tie_embeddings and cfg.norm_eps == 1e-5 and cfg.rope_theta == 1e6
    # 2 of 10 layers keep keys and values, two heads of 64 to a 128-lane row; 8 keep 2 rows a sequence
    assert cfg.n_kv_layers == 2 and cfg.state_shape == (8, 2, 2048)
    for key, other in (("conv_bias", True), ("norm_topk_prob", False), ("use_expert_bias", False),
                       ("routed_scaling_factor", 2.5), ("model_type", "lfm2"),
                       ("layer_types", ["sliding_attention"] * 40)):
        with pytest.raises(ValueError, match="this published shape and no other"):
            program_config({**cell.config, key: other}, n_layers=10, max_seq_len=128)


def _tiny_cell():
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell.config.update(TINY)
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
    # K/V for the 2 attention layers only (129 blocks of 8 x 2 heads x 8, K and V) and 8 conv
    # layers' 2 rows of 64 for 4 slots and the null row, float32
    assert record.facts["pool_bytes"] == 4 * (2 * 2 * 129 * 8 * 16 + 8 * 5 * 2 * 64)
    assert record.facts["engine"]["preemptions"] == 0
    assert record.facts["engine"]["prefill_tokens_saved"] == 0  # no prefix hit is taken
    line = harness.result_line(cell, record, traced=False)
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"} and line["correct"] is True
    per_layer = {entry["name"] for entry in cell.per_layer}
    assert per_layer >= {*JOINED, *NEW_READERS}  # at least: a later PR may hand it more readers
    record = harness.dataclasses.replace(record, cell=cell)
    read = {name: harness.layer_metric_reader(name)(record)
            for name in {*JOINED, *NEW_READERS} - {"mfu.serve"}}
    assert read["moe_local_pairs_per_token.serve"] == 4.0  # every expert is held: top_k pairs a token
    # not the cell's yet, but their readers find what they read in its records
    assert harness.layer_metric_reader("moe_gmm_narrow_roofline.serve")(record) is None  # no trace
    assert 1.0 <= harness.layer_metric_reader("moe_max_load_ratio.serve")(record) <= 16.0
    # 8 conv layers x 2 x 64 a row against 2 layers x 2 x 16 a token: a row is worth 16 tokens,
    # and the tiny contexts are 12-116 tokens in blocks of 8
    assert 10.0 < read["state_cache_share.serve"] < 60.0
    moe = [k for _, _, _, k in tracing.recorded("atpu.serve.moe")][-1]
    assert len(moe["local_pairs"]) == 8  # one entry a ROUTED layer, not one a layer
    for name, value in read.items():  # no trace: the device metrics read nothing and raise nothing
        if "roofline" in name or name in ("kernel_share.serve", "device_idle_share.serve",
                                          "paged_decode_share.serve", "moe_gmm_share.serve"):
            assert value is None, name


# ------------------------------------------------------- the readers, by hand


def _ring(step=5, with_state=True):
    """Step 5 of engine 7, made up: one prefill of 700 tokens in one chunk,
    then a decode batch of 120 rows (a 128-slot bucket) holding 8000 blocks
    of 16, every row with its state row; 4 pairs a token on each of the 8
    routed layers."""
    ring = collections.deque(maxlen=64)
    key = dict(engine=7, step=step)
    state = dict(state_rows=120) if with_state else {}
    ring.append(("atpu.serve.state", 0, 0, dict(key, rid=1, row=9, why="admit")))
    ring.append(("atpu.serve.prefill", 0, 1, dict(key, rid=1, tokens=700, cached=0)))
    ring.append(("atpu.serve.moe", 0, 0, dict(
        key, kind="prefill", rid=1, tokens=700, local_pairs=[2800] * 8, experts_hit=[64] * 8,
        max_expert_load=[60] * 8, held=64, top_k=4)))
    ring.append(("atpu.serve.build", 0, 1, dict(key, batch=120, slot_bucket=128, block_bucket=256,
                                                live_blocks=8000, **state)))
    ring.append(("atpu.serve.moe", 0, 0, dict(
        key, kind="decode", tokens=120, local_pairs=[480] * 8, experts_hit=[64] * 8,
        max_expert_load=[14] * 8, held=64, top_k=4)))
    ring.append(("atpu.serve.step", 0, 2, key))
    return ring


def _record(ops, calls, steps=5):
    trace = {"window_s": 2.0, "busy_s": 1.6, "kernel_s": 1.0, "device_ops": ops,
             "device_op_calls": calls, "idle_gaps": []}
    clocks = {"steps": steps, "slice_steps": [5, 6], "device_kind": "TPU v5 lite", "chips": 1}
    return harness.Record(True, 0, 0, {}, clocks, {}, trace=trace, cell=harness.load_cell(CELL))


OPS = [["moe_gmm.3", 0.03], ["paged_decode.5", 0.002], ["paged_prefill.2", 0.004],
       ["fusion.1 kLoop", 0.3]]
CALLS = {"moe_gmm.3": 48, "paged_decode.5": 2, "paged_prefill.2": 2, "fusion.1 kLoop": 4}


def test_attention_layers_roofline_against_a_hand_count(monkeypatch):
    """2 attention layers of the cell's 10. Bytes: 8000 blocks x 16 tokens x
    (8 heads x 64) x 2 (K, V) x 2 B = 262.1 MB a layer and 120 rows x 2048 x 2
    (q, out) x 2 B = 0.98 MB: 526.3 MB, 0.6426 ms at 819 GB/s; operations 4 x
    2048 x 128 000 tokens x 2 layers = 2.1 G, 0.011 ms: the bytes bind, over
    0.002 s of kernel."""
    monkeypatch.setattr(tracing, "_RING", _ring())
    read = harness.layer_metric_reader("paged_decode_attn_layers_roofline.serve")
    value = read(_record(OPS, CALLS))
    bytes_moved = 2 * 2 * (8000 * 16 * 512 * 2 + 120 * 2048 * 2)
    assert bytes_moved == 526_254_080 and bytes_moved / 819e9 > 2 * 4 * 2048 * 128_000 / 197e12
    assert value == pytest.approx(100 * (bytes_moved / 819e9) / 0.002, rel=1e-9)
    assert 0 < value < 100 and value == pytest.approx(32.13, rel=1e-3)
    # the accepted reader would hold the trace to 10 calls a build, and read nothing
    assert harness.layer_metric_reader("paged_decode_roofline.serve")(_record(OPS, CALLS)) is None


def test_state_cache_share_against_a_hand_count(monkeypatch):
    monkeypatch.setattr(tracing, "_RING", _ring(step=0))  # the window's steps, not the slice's
    record = _record(OPS, CALLS, steps=1)
    state = 120 * 8 * 2 * 2048          # rows x conv layers x (K - 1) x hidden
    kv = 8000 * 16 * 2 * 2 * 8 * 64     # blocks x tokens x attention layers x (K, V) x heads x 64
    assert (state, kv) == (3_932_160, 262_144_000)
    value = harness.layer_metric_reader("state_cache_share.serve")(record)
    assert value == pytest.approx(100 * state / (state + kv), rel=1e-12)
    assert value == pytest.approx(1.478, rel=1e-3)  # a row is the K/V of 16 tokens; 1067 live a row


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_where_records_and_trace_do_not_match(monkeypatch, name):
    """None, never 0 and never an exception: a ring whose builds carry no
    ``state_rows`` (the parent's program), no ring at all, and for the
    roofline no trace, a trace without the kernel's name, calls that are not
    one an attention layer for each record, or a configuration without
    ``layer_types``."""
    read = harness.layer_metric_reader(name)
    in_slice = "roofline" in name
    monkeypatch.setattr(tracing, "_RING", _ring(step=5 if in_slice else 0))
    record = _record(OPS, CALLS, steps=5 if in_slice else 1)
    assert read(record) is not None
    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=4))
    assert read(record) is None  # no ring at all
    if in_slice:
        monkeypatch.setattr(tracing, "_RING", _ring())
        assert read(_record(OPS, {**CALLS, "paged_decode.5": 10})) is None  # one a layer of depth
        assert read(_record(OPS, {**CALLS, "paged_decode.5": 3})) is None
        assert read(_record([op for op in OPS if "paged_decode" not in op[0]], CALLS)) is None
        no_trace = _record(OPS, CALLS)
        no_trace.trace = None
        assert read(no_trace) is None
        other = _record(OPS, CALLS)
        other.cell = harness.load_cell("mistral-7b.chat-sat")  # no `layer_types`
        assert read(other) is None
    else:
        monkeypatch.setattr(tracing, "_RING", _ring(step=0, with_state=False))
        assert read(record) is None


# ------------------------------------------------------- the controls of the check


@pytest.mark.parametrize("control", ["none", "weights_f8", "router_f8", "cache_f8"])
def test_a_control_comes_out_not_correct_by_the_runners_own_comparison(control):
    """``lfm2_control.py``: the tiny cell with one part of the engine rounded
    to float8's mantissa inside its step programs. The runner's own check,
    against the reference on the true weights, says not correct; with nothing
    rounded the same run is correct."""
    from benchmarks.chip import lfm2_control

    cell = _tiny_cell()
    cell.traffic["arrival"]["n_requests"] = 24
    kind_of = models.kind_of
    record = lfm2_control.run(cell, control, seed=2147483693, seconds=0.5, allow_cpu=True)
    assert models.kind_of is kind_of  # the runner is as it was
    check = record.facts["check"]
    assert check["requests"] == 6 and record.failed == 0
    assert record.correct == check["ok"] == (control == "none"), check
    if control != "none":
        assert check["argmax_agreement"] < check["agreement_required"]
    assert record.facts["engine"]["preemptions"] == 0 and record.facts["late_compiles"] == 0


def test_a_controlled_config_is_the_config_but_for_its_forward():
    from accelerate_tpu.models.lfm2 import Lfm2Config
    from benchmarks.chip import lfm2_control

    cfg = Lfm2Config(n_layers=3, dim=64, n_heads=8, n_kv_heads=2)
    under = lfm2_control.degraded(cfg, "cache_f8")
    assert isinstance(under, Lfm2Config) and dataclasses.asdict(under) == dataclasses.asdict(cfg)
    assert (under.state_shape, under.n_kv_layers, hash(under)) == (
        cfg.state_shape, cfg.n_kv_layers, hash(cfg))
    assert under != cfg and type(under).__name__ == "Lfm2Config_cache_f8"
    with pytest.raises(KeyError):
        lfm2_control.degraded(cfg, "weights_f4")


# ----------------------------------------------- the cell's files and the manifest


def test_the_cells_files_say_what_the_issue_asks():
    cell = harness.load_cell(CELL)
    mix, eng, check = cell.traffic, cell.spec["engine"], cell.spec["check"]
    assert (mix["prompt_len"], mix["output_len"], mix["block"], mix["sizes_seed"]) == (
        [128, 2048], [128, 2048], 16, 0)
    assert mix["arrival"] == {"kind": "at_zero", "n_requests": 2000} and "shared_prefix" not in mix
    assert (eng["block_size"], eng["num_blocks"], eng["max_seq_len"]) == (16, 32769, 4096)
    assert eng["max_slots"] in (64, 128, 256) and eng["slot_buckets"] == [eng["max_slots"]]
    assert eng["block_buckets"] == [4096 // 16] and eng["prefill_buckets"] == [256, 512, 1024, 2048]
    assert (cell.chips, cell.spec["n_layers"], cell.spec["dtype"], cell.spec["runner"]) == (
        1, 10, "bf16", "serve")
    assert check["max_tokens"] == 4096 and check["margin_quantile"] == 99
    for key in ("engine_why", "check_why", "why", "reduced"):
        assert len(cell.spec[key]) > 40 and "TBD" not in cell.spec[key], key
    # every request fits a row and the check may sample every one; the pool never preempts
    prompt, output = traffic.request_sizes(mix, 2000)
    assert (prompt + output).max() <= check["max_tokens"] <= eng["max_seq_len"]
    assert 685 < prompt.mean() < 700 and 685 < output.mean() < 700
    assert eng["num_blocks"] - 1 >= min(eng["max_slots"], 128) * eng["block_buckets"][0]


def test_every_new_manifest_string_is_short_and_the_cell_stands_where_the_issue_says():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    config = next(c for c in MANIFEST["configs"] if c["name"] == "lfm2-24b")
    for text in (entry["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    metrics = {m["name"]: m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for name in NEW_READERS:
        assert len(name) <= 64 and CELL in metrics[name]["workloads"]
        assert metrics[name]["moves"] == "serve_tokens_per_s" and metrics[name]["unit"] == "%"
    for name in JOINED + ["serve_tokens_per_s"]:  # membership only: later cells join these lists too
        assert CELL in metrics[name]["workloads"], name
    assert (metrics["paged_decode_attn_layers_roofline.serve"]["layer"],
            metrics["state_cache_share.serve"]["layer"]) == ("kernels", "scheduler / pager")
    assert entry["chips"] == 1
    # the parent cannot run the cell: `load_cell` leaves at once on a name it does not have
    with pytest.raises(SystemExit, match="no cell"):
        harness.load_cell("lfm2-24b.agent-rare")
