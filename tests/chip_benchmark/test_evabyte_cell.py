"""The ``linear/evabyte`` kind and the cell ``evabyte-6.5b.bytedoc-sat``,
rehearsed on the CPU: the configuration keeps every published key and cuts
only depth, the operation count is held to a hand count, the serve runner
takes the kind at a tiny size and comes out correct, and not correct where
the fold is skipped or the window is misaligned, and each of the cell's new
per-layer metrics is held to a hand count on a made-up slice (the times are
invented: nothing here is a device number)."""

import collections
import contextlib
import copy
import dataclasses
import importlib
import json
import os
import sys

import pytest

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import harness, models, traffic  # noqa: E402
from benchmarks.chip.runners import serve  # noqa: E402

# the module, not the function ``accelerate_tpu.ops`` re-exports under its name
fa = importlib.import_module("accelerate_tpu.ops.flash_attention")
CELL = "evabyte-6.5b.bytedoc-sat"
# the model catalog (JSON lines of name, source_url and config), where one is given
CATALOG = os.environ.get("MODEL_CATALOG", "")
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
# the tiny shape: a window of 32 in chunks (and blocks) of 4; weights drawn wide
# enough that attention and pooling are not flat
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
            window_size=32, chunk_size=4, init_std=0.15)
TINY_ENGINE = dict(max_slots=4, num_blocks=257, block_size=4, max_seq_len=256, slot_buckets=[4],
                   block_buckets=[8 + 2 * 8], prefill_buckets=[8, 16])
NEW_READERS = ["eva_decode_roofline.serve", "eva_summary_share.serve", "eva_fold_share.serve",
               "eva_fold_roofline.serve"]
JOINED = ["occupancy.serve", "kernel_share.serve", "device_idle_share.serve",
          "paged_decode_share.serve", "host_gap_ms.serve", "mfu.serve"]
D, F = 4096, 11008


def test_the_configuration_keeps_every_published_key_and_cuts_only_depth():
    cell = harness.load_cell(CELL)
    c = cell.config
    assert (c["hidden_size"], c["intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["vocab_size"], c["window_size"], c["chunk_size"],
            c["num_pred_heads"], c["rope_theta"]) == (4096, 11008, 32, 32, 320, 2048, 16, 8, 100000)
    assert c["reduced"] == ["num_hidden_layers"] == list(c["reduced_why"])
    assert set(c["assumed"]) == set(c["assumed_why"]) >= {
        "pooling_scale", "window_alignment", "heads_served", "init", "mixedp_attn"}
    assert "four-stage pipeline" in c["deployment"] and "3.262 GB" in c["size_arithmetic"]
    entry = next(e for e in MANIFEST["configs"] if e["name"] == "evabyte-6.5b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert models.depth(cell) == 8 and c["num_hidden_layers"] == 32
    if os.path.exists(CATALOG):  # every key of the catalog's config, unchanged: depth is set by the cell
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "EvaByte")
        assert c["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if c.get(k, "absent") != v} == set()
    # the arithmetic the file states, from the keys
    layer = 4 * D * D + 3 * D * F + 2 * 32 * 128 + 2 * D
    assert layer == 202_391_552
    whole = 32 * layer + 320 * D + 8 * D * 320 + D
    cut = 8 * layer + 320 * D + 8 * D * 320 + D
    assert round(whole / 1e9, 2) == 6.49 and round(2 * cut / 1e9, 3) == 3.262
    pool = (cell.spec["engine"]["num_blocks"] - 1) * 8 * 16 * 32 * 128 * 2 * 2
    assert pool == 4600 * 2 ** 21 and round((2 * cut + pool) / 16e9, 2) == 0.81


def test_forward_flops_count_the_window_and_the_pooled_pairs_by_hand():
    cell = harness.load_cell(CELL)
    kind = models.kind_of(cell.config, cell.root)
    count, mean_keys = kind["forward_flops_per_token"], kind.module.mean_keys
    # inside the first window a token scores (i + 1) keys: (L + 1) / 2 on average
    assert mean_keys(1000, 2048, 16) == pytest.approx(500.5)
    # 4096 positions: window 0 as above; in window 1 a token scores i - 2048 + 1 raw keys
    # and 128 pairs: (2048.5 + 128) on average over the second half
    assert mean_keys(4096, 2048, 16) == pytest.approx((1024.5 + 1024.5 + 128) / 2)
    assert mean_keys(2048 * 3 + 100, 2048, 16) == pytest.approx(
        (3 * 2048 * 2049 / 2 + 100 * 101 / 2 + 128 * (2048 + 2 * 2048 + 3 * 100)) / 6244)
    dense = 2 * (4 * D * D + 3 * D * F)
    assert dense == 404_750_336
    c = cell.config
    assert count(c, 1000, 1) == pytest.approx(dense + 4 * D * 500.5 + 2 * D * 320)
    assert count(c, 13_000, 8) == pytest.approx(
        8 * (dense + 4 * D * mean_keys(13_000, 2048, 16)) + 2 * D * 320)


def test_the_kind_refuses_another_published_shape():
    cell = harness.load_cell(CELL)
    program_config = models.kind_of(cell.config, cell.root)["program_config"]
    cfg = program_config(cell.config, n_layers=8, max_seq_len=32768)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim, cfg.vocab_size,
            cfg.window_size, cfg.chunk_size, cfg.num_pred_heads, cfg.heads_served) == (
        4096, 32, 32, 128, 11008, 320, 2048, 16, 8, 1)
    assert cfg.norm_eps == 1e-5 and cfg.rope_theta == 1e5 and cfg.init_std == 0.01275
    for key, other in (("attention_class", "mha"), ("tie_word_embeddings", True),
                       ("fp32_skip_add", False), ("norm_add_unit_offset", False),
                       ("num_key_value_heads", 8), ("model_type", "llama"),
                       ("assumed", {**cell.config["assumed"], "pooling_scale": 0.5}),
                       ("assumed", {**cell.config["assumed"], "window_alignment": "sliding"})):
        with pytest.raises(ValueError, match="this published shape and no other"):
            program_config({**cell.config, key: other}, n_layers=8, max_seq_len=128)


def _tiny_cell():
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell.config.update(TINY)
    cell.spec["dtype"] = "f32"
    cell.spec["engine"].update(TINY_ENGINE)
    # float32 on both sides: the engine's token is the reference's argmax at every position
    cell.spec["check"].update(max_tokens=256, margin=1e-3, agreement=0.99, margin_quantile=100)
    cell.traffic.update(prompt_len=[8, 100], output_len=[4, 40])
    cell.traffic["arrival"]["n_requests"] = 40
    return cell


def _run(cell):
    return serve.run(cell, seed=2147483659, seconds=1.0, trace=False, process_t0=0.0,
                     allow_cpu=True)


def test_serve_runner_takes_the_kind_at_a_tiny_size():
    cell = _tiny_cell()
    record = _run(cell)
    assert record.correct and record.facts["check"]["ok"], record.facts
    assert record.facts["check"]["requests"] == 6 and record.failed == 0
    # the cell's 8 layers of 257 blocks of 4 positions x 4 heads x 16, K and V, float32
    assert record.facts["pool_bytes"] == 4 * 2 * 8 * 257 * 4 * 64
    assert record.facts["engine"]["preemptions"] == 0
    assert record.facts["engine"]["prefill_tokens_saved"] == 0  # no prefix hit is taken
    line = harness.result_line(cell, record, traced=False)
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"} and line["correct"] is True
    assert {entry["name"] for entry in cell.per_layer} >= {*JOINED, *NEW_READERS}
    record = dataclasses.replace(record, cell=cell)
    for name in {*JOINED, *NEW_READERS} - {"mfu.serve"}:
        value = harness.layer_metric_reader(name)(record)
        if name in ("kernel_share.serve", "device_idle_share.serve", "paged_decode_share.serve",
                    *[n for n in NEW_READERS if n != "eva_summary_share.serve"]):
            assert value is None, name  # no trace: the device metrics read nothing and raise nothing
        else:
            assert value is not None, name
    # requests of 8-140 positions in windows of 32: some rows attend pooled pairs
    assert 0 < harness.layer_metric_reader("eva_summary_share.serve")(record) < 60


@contextlib.contextmanager
def _program_window(scale):
    """The kind's program config with its window times ``scale``: the
    program's windows no longer lie where the reference's do."""
    kind_of = models.kind_of

    def shifted(config, root):
        kind = kind_of(config, root)
        program_config = kind["program_config"]
        kind["program_config"] = lambda *a, **k: dataclasses.replace(
            program_config(*a, **k), window_size=int(config["window_size"] * scale))
        return kind

    models.kind_of = shifted
    try:
        yield
    finally:
        models.kind_of = kind_of


def test_a_run_whose_fold_is_skipped_comes_out_not_correct(monkeypatch):
    """The pooled pairs never written (the summary blocks keep the pool's
    zeros): the closed windows' context is gone, and the check sees it."""
    def skipped(k_flat, v_flat, blocks, mu, phi):
        zeros = jnp.zeros((blocks.shape[0], *k_flat.shape[2:]), k_flat.dtype)
        return zeros, zeros

    monkeypatch.setattr(fa, "paged_fold_gather", skipped)
    record = _run(_tiny_cell())
    assert not record.correct and not record.facts["check"]["ok"], record.facts["check"]
    assert record.facts["check"]["max_margin_deviations"] > 1e-3


@pytest.mark.parametrize("scale", [0.5, 2])
def test_a_run_whose_window_is_misaligned_comes_out_not_correct(scale):
    with _program_window(scale):
        record = _run(_tiny_cell())
    assert not record.correct and not record.facts["check"]["ok"], record.facts["check"]


def test_a_control_comes_out_not_correct_by_the_runners_own_comparison():
    """``evabyte_control.py`` runs ``lfm2_control``'s controls on this cell:
    the engine's weights rounded to float8's mantissa come out not correct,
    the same run with nothing rounded correct."""
    from benchmarks.chip import lfm2_control

    for control in ("none", "weights_f8"):
        record = lfm2_control.run(_tiny_cell(), control, seed=2147483693, seconds=0.5,
                                  allow_cpu=True)
        assert record.correct == record.facts["check"]["ok"] == (control == "none"), control


# ------------------------------------------------------- the readers, by hand


def _ring(step=5, pooled=True):
    """Step 5 of engine 7, made up: one prefill of 2048 positions in one call
    (128 chunks folded), then a decode batch of 32 rows attending 1000 raw
    keys and 640 pooled pairs a row on average, 2 of them closing a chunk."""
    ring = collections.deque(maxlen=64)
    key = dict(engine=7, step=step)
    counts = dict(window_entries=32_000, summary_entries=20_480) if pooled else {}
    ring.append(("atpu.serve.prefill", 0, 1, dict(key, rid=1, tokens=2048, cached=0)))
    ring.append(("atpu.serve.prefill_call", 0, 1, dict(key, bucket=2048, start=0)))
    if pooled:
        ring.append(("atpu.serve.eva", 1, 1, dict(key, kind="prefill", rid=1, chunks_folded=128,
                                                  windows_closed=0, raw_blocks_freed=0)))
    ring.append(("atpu.serve.build", 1, 2, dict(key, batch=32, slot_bucket=32, block_bucket=256,
                                                live_blocks=3264, **counts)))
    if pooled:
        ring.append(("atpu.serve.eva", 2, 2, dict(key, kind="decode", chunks_folded=2,
                                                  windows_closed=1, raw_blocks_freed=128)))
    ring.append(("atpu.serve.step", 0, 3, key))
    return ring


def _record(ops, calls, steps=5):
    trace = {"window_s": 2.0, "busy_s": 1.6, "kernel_s": 1.0, "device_ops": ops,
             "device_op_calls": calls, "idle_gaps": []}
    clocks = {"steps": steps, "slice_steps": [5, 6], "device_kind": "TPU v5 lite", "chips": 1}
    return harness.Record(True, 0, 0, {}, clocks, {}, trace=trace, cell=harness.load_cell(CELL))


OPS = [["paged_decode.5", 0.012], ["paged_prefill.2", 0.03], ["eva_fold.7", 0.0006],
       ["fusion.1 kLoop", 0.3]]
CALLS = {"paged_decode.5": 8, "paged_prefill.2": 8, "eva_fold.7": 16, "fusion.1 kLoop": 4}


def test_decode_roofline_against_a_hand_count(monkeypatch):
    """8 layers. Bytes: 52 480 entries x 32 heads x 128 x 2 (K, V) x 2 B =
    429.9 MB a layer, and 32 rows x 4096 x 2 (q, out) x 2 B = 0.52 MB: 3.44 GB
    over the 8, 4.20 ms at 819 GB/s; operations 4 x 4096 x 52 480 x 8 = 6.9 G,
    0.035 ms: the bytes bind, over 0.012 s of kernel."""
    monkeypatch.setattr(tracing, "_RING", _ring())
    value = harness.layer_metric_reader("eva_decode_roofline.serve")(_record(OPS, CALLS))
    bytes_moved = 8 * 2 * (52_480 * 4096 * 2 + 32 * 4096 * 2)
    assert bytes_moved == 6_882_852_864 and bytes_moved / 819e9 > 8 * 4 * 4096 * 52_480 / 197e12
    assert value == pytest.approx(100 * (bytes_moved / 819e9) / 0.012, rel=1e-9)
    assert value == pytest.approx(70.03, rel=1e-3)


def test_fold_roofline_against_a_hand_count(monkeypatch):
    """130 chunks (128 of the prefill, 2 of the decode step) on each of 8
    layers, 16 calls (a prefill call and a decode batch): 1040 x (16 + 1) x
    4096 x 2 x 2 B = 289.7 MB, 0.354 ms at 819 GB/s, over 0.6 ms."""
    monkeypatch.setattr(tracing, "_RING", _ring())
    value = harness.layer_metric_reader("eva_fold_roofline.serve")(_record(OPS, CALLS))
    bytes_moved = 1040 * 17 * 4096 * 2 * 2
    assert bytes_moved == 289_669_120 and bytes_moved / 819e9 > 1040 * 8 * 16 * 4096 / 197e12
    assert value == pytest.approx(100 * (bytes_moved / 819e9) / 0.0006, rel=1e-9)
    assert value == pytest.approx(58.95, rel=1e-3)


def test_summary_and_fold_shares_against_a_hand_count(monkeypatch):
    monkeypatch.setattr(tracing, "_RING", _ring(step=0))  # the window's steps
    value = harness.layer_metric_reader("eva_summary_share.serve")(_record(OPS, CALLS, steps=1))
    assert value == pytest.approx(100 * 20_480 / 52_480, rel=1e-12)
    fold = harness.layer_metric_reader("eva_fold_share.serve")(_record(OPS, CALLS))
    assert fold == pytest.approx(100 * 0.0006 / 1.6, rel=1e-12)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_where_records_and_trace_do_not_match(monkeypatch, name):
    """None, never 0 and never an exception: no ring at all, builds without
    ``summary_entries`` and no ``atpu.serve.eva`` record (the parent's
    program), and where the reader needs the trace: no trace, a trace
    without the kernel's name, calls that are not one a layer for each
    program of the slice."""
    read = harness.layer_metric_reader(name)
    in_slice = "roofline" in name
    monkeypatch.setattr(tracing, "_RING", _ring(step=5 if in_slice else 0))
    record = _record(OPS, CALLS, steps=5 if in_slice else 1)
    assert read(record) is not None
    if name == "eva_fold_share.serve":  # reads the trace alone
        assert read(_record([op for op in OPS if "eva_fold" not in op[0]], CALLS)) is None
        no_trace = _record(OPS, CALLS)
        no_trace.trace = None
        assert read(no_trace) is None
        return
    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=4))
    assert read(record) is None  # no ring at all
    monkeypatch.setattr(tracing, "_RING", _ring(step=5 if in_slice else 0, pooled=False))
    assert read(record) is None
    if in_slice:
        monkeypatch.setattr(tracing, "_RING", _ring())
        mark = "paged_decode.5" if "decode" in name else "eva_fold.7"
        assert read(_record(OPS, {**CALLS, mark: CALLS[mark] + 1})) is None
        assert read(_record([op for op in OPS if op[0] != mark], CALLS)) is None
        no_trace = _record(OPS, CALLS)
        no_trace.trace = None
        assert read(no_trace) is None


# ----------------------------------------------- the cell's files and the manifest


def test_the_cells_files_say_what_the_issue_asks():
    cell = harness.load_cell(CELL)
    mix, eng, check = cell.traffic, cell.spec["engine"], cell.spec["check"]
    assert (mix["prompt_len"], mix["output_len"], mix["block"], mix["sizes_seed"]) == (
        [4096, 28672], [256, 4096], 16, 0)
    assert mix["arrival"] == {"kind": "at_zero", "n_requests": 600} and "shared_prefix" not in mix
    assert (eng["max_slots"], eng["block_size"], eng["max_seq_len"]) == (32, 16, 32768)
    assert eng["slot_buckets"] == [32] and eng["block_buckets"] == [256]
    assert all(2048 % b == 0 for b in eng["prefill_buckets"]) and eng["prefill_buckets"][-1] == 2048
    assert 4400 <= eng["num_blocks"] - 1 <= 4600
    assert (cell.chips, cell.spec["n_layers"], cell.spec["dtype"], cell.spec["runner"]) == (
        1, 8, "bf16", "serve")
    assert check["requests"] == 6 and check["max_tokens"] == 32768
    for key in ("engine_why", "check_why", "why", "reduced"):
        assert len(cell.spec[key]) > 40 and "PENDING" not in cell.spec[key], key
    prompt, output = traffic.request_sizes(mix, 600)
    assert (prompt + output).max() <= check["max_tokens"] == eng["max_seq_len"]
    assert 12_600 < prompt.mean() < 12_700 and 1_370 < output.mean() < 1_400
    # the widest row the mix reaches fits the table: 128 raw slots and 16 windows' 8 summary blocks
    assert 2048 // 16 + 8 * ((int((prompt + output).max()) - 1) // 2048 + 1) <= eng["block_buckets"][0]


def test_every_new_manifest_string_is_short_and_the_cell_stands_where_the_issue_says():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    config = next(c for c in MANIFEST["configs"] if c["name"] == "evabyte-6.5b")
    for text in (entry["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    metrics = {m["name"]: m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for name in NEW_READERS:
        assert len(name) <= 64 and CELL in metrics[name]["workloads"]
        assert metrics[name]["moves"] == "serve_tokens_per_s" and metrics[name]["unit"] == "%"
    for name in JOINED + ["serve_tokens_per_s"]:  # membership only: later cells join these lists too
        assert CELL in metrics[name]["workloads"], name
    assert metrics["eva_summary_share.serve"]["layer"] == "scheduler / pager"
    assert {metrics[n]["layer"] for n in NEW_READERS if n != "eva_summary_share.serve"} == {"kernels"}
    assert entry["chips"] == 1
