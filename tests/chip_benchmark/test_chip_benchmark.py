"""The chip benchmark (``BENCHMARK.json``, ``benchmarks/chip/``) rehearsed on
the CPU: the manifest names files that exist, the traffic generator repeats
from its seed, each runner runs at a tiny size and prints the contract's keys,
the plain references agree with the program in float32, and the trace
reduction is held to a sample cut from a recorded v5e trace.

Nothing here is a device number: the runners' CPU path is the explicit
``allow_cpu`` argument of the runner functions, which the command never passes.
"""

import copy
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.chip import flops, harness, reference, trace_reduce, traffic  # noqa: E402
from benchmarks.chip.runners import serve, train  # noqa: E402

CHIP = os.path.join(REPO, "benchmarks", "chip")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TINY_BERT = dict(vocab_size=1024, hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
                 intermediate_size=256)
TINY_LLAMA = dict(vocab_size=512, hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=32, intermediate_size=512)


def _cell(name, *, config=None, spec=None, mix=None, engine=None, chips=None):
    """A committed cell with its sizes overridden down to something the CPU
    runs in a second."""
    cell = copy.deepcopy(harness.load_cell(name))
    cell.config.update(config or {})
    cell.spec.update(spec or {})
    cell.traffic.update(mix or {})
    if engine:
        cell.spec["engine"].update(engine)
    if chips:
        cell.chips = chips
    return cell


# ------------------------------------------------------------- the manifest


def test_manifest_names_files_that_exist():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for c in configs.values():
        assert NAME.match(c["name"]) and os.path.exists(os.path.join(REPO, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
    used = set()
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        cell = harness.load_cell(w["name"])  # config, cell file and traffic mix, by name
        assert cell.spec["config"] == w["config"] and cell.spec["traffic"] == w["traffic"]
        assert cell.spec["chips"] == w["chips"]
        assert os.path.exists(os.path.join(CHIP, "runners", cell.runner + ".py"))
        used.add(w["config"])
    assert used == set(configs), "every configuration is used by some cell"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(MANIFEST["workloads"]) // 4)


def test_manifest_metrics_are_well_formed_and_moves_is_reported():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in end_to_end and "workloads" not in end_to_end["setup_s"]
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(CHIP, "layer_metrics", m["name"] + ".py"))
        assert callable(harness.layer_metric_reader(m["name"]))
        moved = end_to_end[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells)), m["name"]
    for cell in cells:  # setup_s, one more end-to-end metric and one per-layer metric at least
        loaded = harness.load_cell(cell)
        assert len(loaded.end_to_end) >= 2 and len(loaded.per_layer) >= 1


# --------------------------------------------------------------- the traffic


def test_traffic_repeats_from_its_seed_and_keeps_its_sizes_and_times_across_seeds():
    mix = json.load(open(os.path.join(CHIP, "traffic", "chat-r80.json")))
    a = traffic.requests(mix, 1000, seed=2147483659, horizon_s=20.0)
    b = traffic.requests(mix, 1000, seed=2147483659, horizon_s=20.0)
    c = traffic.requests(mix, 1000, seed=7, horizon_s=20.0)
    assert [(r.due_s, r.max_new_tokens) for r in a] == [(r.due_s, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed: other tokens, the same lengths due at the same times
    assert not np.array_equal(a[0].prompt[:8], c[0].prompt[:8])
    shape = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens) for r in rs]  # noqa: E731
    assert shape(a) == shape(c)
    lo, hi = mix["prompt_len"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    # an open loop on the wall clock: due times count from the window's start at the mix's rate
    due = np.array([r.due_s for r in a])
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0)
    gaps = traffic.arrival_gaps(mix, 500)
    assert gaps.mean() == pytest.approx(1.0 / mix["arrival"]["rate_per_s"], rel=1e-9)
    assert gaps.std() / gaps.mean() == pytest.approx(mix["arrival"]["cv"], rel=0.15)  # Poisson
    # every run of `block` requests holds every stratum of the prompt lengths
    prompt_len, _ = traffic.request_sizes(mix, 64)
    strata = np.floor(np.log(prompt_len / lo) / np.log(hi / lo) * 16 - 1e-9).clip(0, 15)
    assert all(len(set(strata[i:i + 16])) >= 15 for i in range(0, 64, 16))


def test_saturating_mix_is_all_due_at_zero_and_train_rows_repeat():
    mix = json.load(open(os.path.join(CHIP, "traffic", "chat-sat.json")))
    rs = traffic.requests(mix, 1000, seed=3, horizon_s=5.0)
    assert len(rs) == mix["arrival"]["n_requests"] and all(r.due_s == 0.0 for r in rs)
    shared = traffic.requests({**mix, "shared_prefix": {"tokens": 32, "groups": 2}, "repeats": 2},
                              1000, seed=3, horizon_s=5.0)
    assert np.array_equal(shared[0].prompt, shared[1].prompt)  # asked twice in a row
    assert np.array_equal(shared[0].prompt[:32], shared[4].prompt[:32])  # group 0 again
    rows = json.load(open(os.path.join(CHIP, "traffic", "seqcls-s128.json")))
    x, y = traffic.train_rows(rows, 30522, seed=5), traffic.train_rows(rows, 30522, seed=5)
    assert all(np.array_equal(x[k], y[k]) for k in x)
    assert x["input_ids"].shape == (rows["global_batch"] * rows["n_batches"], rows["seq_len"])


def test_first_token_is_timed_from_when_the_request_was_due():
    spec = types.SimpleNamespace(due_s=1.0)
    late = types.SimpleNamespace(spec=spec, submit_s=1.5, stamps=[2.0, 2.1, 2.4])
    ttft, gaps = serve.latencies_ms([late])
    assert ttft == pytest.approx([1000.0]) and gaps == pytest.approx([100.0, 300.0])
    assert harness.nearest_rank(range(1, 101), 95) == 95 and harness.nearest_rank([3.0], 95) == 3.0


# --------------------------------------------------------------- the runners


def _assert_contract(line, cell, *, traced=False):
    assert set(line) == set(harness.RESULT_KEYS) | ({"breakdown"} if "breakdown" in line else set())
    assert list(line)[-1] == "compared" and line["compared"]  # each number beside its limit, last
    assert all(set(c) == {"value", "limit"} for c in line["compared"].values())
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    names = cell.per_layer if traced else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in names}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])


def test_train_runner_at_a_tiny_size_prints_the_contracts_keys():
    cell = _cell("bert-base.seqcls-s128", config=TINY_BERT, spec={"check_rows_at_a_time": 8},
                 mix={"seq_len": 32, "global_batch": 16, "n_batches": 4})
    record = train.run(cell, seed=2147483659, seconds=0.5, trace=False, process_t0=0.0,
                       allow_cpu=True)
    assert record.facts["late_compiles"] == 0 and record.facts["check"]["ok"]
    line = harness.result_line(cell, record, traced=False)
    _assert_contract(line, cell)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    with pytest.raises(SystemExit):  # without the tests' argument the CPU is refused
        train.run(cell, seed=0, seconds=0.5, trace=False, process_t0=0.0)


@pytest.mark.parametrize("case", ["llama-clm", "fsdp4"])
def test_train_runner_takes_the_data_only_cells(case):
    """The two cells PERF.md keeps for later (a causal-LM Mistral cell, and the
    same across four chips as FSDP) need files only: the runner reads the model
    kind and the mesh from data."""
    if case == "llama-clm":
        cell = _cell("bert-base.seqcls-s128", spec={
            "check_rows_at_a_time": 4, "loss_kwargs": {"remat": True},
            "tolerances": {"loss": 1e-2, "grad_norm": 5e-2, "grad_norm_abs": 5e-2}},
            mix={"task": "clm", "seq_len": 64, "global_batch": 8, "n_batches": 2})
        cell.config = {**json.load(open(os.path.join(CHIP, "configs", "mistral-7b.json"))),
                       **TINY_LLAMA}
    else:
        cell = _cell("bert-base.seqcls-s128", config=TINY_BERT, chips=4,
                     spec={"check_rows_at_a_time": 8, "parallelism": {"dp_shard_size": 4}},
                     mix={"seq_len": 32, "global_batch": 16, "n_batches": 4})
    record = train.run(cell, seed=3, seconds=0.5, trace=False, process_t0=0.0, allow_cpu=True)
    assert record.correct, record.facts
    assert record.facts["mesh"] == ({"dp_shard": 4} if case == "fsdp4" else {})
    _assert_contract(harness.result_line(cell, record, traced=False), cell)


TINY_ENGINE = dict(max_slots=4, num_blocks=65, max_seq_len=96, slot_buckets=[2, 4],
                   block_buckets=[3, 6], prefill_buckets=[16, 32], admit_watermark_blocks=2)


@pytest.mark.parametrize("name", ["mistral-7b.chat-sat", "mistral-7b.chat-r80"])
def test_serve_runner_at_a_tiny_size_prints_the_contracts_keys(name):
    if name not in {w["name"] for w in MANIFEST["workloads"]}:
        pytest.skip(f"{name} is not in the manifest")
    cell = _cell(name, config=TINY_LLAMA, engine=TINY_ENGINE,
                 spec={"n_layers": 2, "dtype": "f32"},
                 mix={"prompt_len": [8, 64], "output_len": [4, 16]})
    # float32 on both sides: the engine's token is the reference's argmax
    cell.spec["check"].update(max_tokens=96, margin=1e-3, agreement=0.99)
    if cell.traffic["arrival"]["kind"] == "at_zero":
        cell.traffic["arrival"]["n_requests"] = 200
    else:
        cell.traffic["arrival"]["rate_per_s"] = 20.0
    record = serve.run(cell, seed=2147483659, seconds=1.0, trace=False, process_t0=0.0,
                       allow_cpu=True)
    assert record.facts["check"]["ok"] and not record.facts["jit_cache_grew"], record.facts
    _assert_contract(harness.result_line(cell, record, traced=False), cell)
    for entry in cell.per_layer:  # the readers of what needs no trace find their numbers
        read = harness.layer_metric_reader(entry["name"])
        if "mfu" in entry["name"]:  # a share of a peak: the CPU has none, and that is an error
            with pytest.raises(KeyError, match="no published peaks"):
                read(record)
            continue
        value = read(record)
        assert value is None if entry["source"] == "device_trace" else np.isfinite(value)


def test_command_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode != 0 and "no CPU fallback" in res.stderr
    assert not any(line.startswith("{") and "metrics" in line for line in res.stdout.splitlines())


# ------------------------------------------------------------ the references


def test_bert_reference_agrees_with_the_program_in_float32():
    """The comparison the train cells make on the chip, here with float32 on
    both sides: only the order of summation differs, so 1e-5 (relative) holds;
    on the chip the program computes in bf16 and the cell's file states the
    wider tolerance."""
    import jax

    from accelerate_tpu.models import BertConfig, bert_loss, init_bert

    cfg = BertConfig(vocab_size=1024, dim=128, n_layers=2, n_heads=4, ffn_dim=256, max_seq_len=32)
    params = init_bert(cfg, jax.random.PRNGKey(0))
    rows = traffic.train_rows({"task": "seqcls", "seq_len": 32, "global_batch": 8, "n_batches": 1},
                              cfg.vocab_size, seed=1)
    loss, grads = jax.value_and_grad(lambda p: bert_loss(p, rows, cfg))(params)
    norm = float(np.sqrt(sum(float((g ** 2).sum()) for g in jax.tree_util.tree_leaves(grads))))
    ref_loss, ref_norm = reference.loss_and_grad_norm(
        lambda p, b: reference.bert_loss(p, b, n_heads=4, eps=cfg.norm_eps), params, rows,
        rows_at_a_time=4)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-5)
    assert norm == pytest.approx(ref_norm, rel=1e-4)


def test_llama_reference_agrees_with_prefill_then_decode_through_the_engine():
    """Float32 weights and cache: the engine's logits path (chunked prefill,
    then decode through the paged cache) picks the reference's argmax at every
    position, and the reference's logits equal the program's full forward to
    1e-4 of a logit deviation (summation order)."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, init_llama, llama_forward
    from accelerate_tpu.serving import BucketLattice, ServingEngine

    cfg = LlamaConfig(vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=512,
                      max_seq_len=96, rope_theta=1e6, norm_eps=1e-5)
    params = init_llama(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(params, cfg, num_blocks=33, block_size=16, max_slots=2,
                           cache_dtype=jnp.float32,
                           lattice=BucketLattice((2,), (6,), (16, 32)))
    rng = np.random.default_rng(0)
    requests = [engine.submit(rng.integers(0, 512, n).astype(np.int32), 12) for n in (40, 9)]
    engine.run()
    shape = dict(n_heads=4, n_kv_heads=2, eps=1e-5, theta=1e6)
    for request in requests:
        out, n_prompt = request.output_ids(), request.prompt.size  # 40 > 32: two prefill chunks
        logits = np.asarray(reference.llama_logits(params, jnp.asarray(out), **shape))
        margins = reference.greedy_margins(logits[n_prompt - 1:-1], out[n_prompt:])
        assert margins.max() < 1e-4, margins
        program = np.asarray(llama_forward(params, jnp.asarray(out)[None], cfg, attention_impl="xla")[0])
        assert np.abs(program - logits).max() < 1e-4 * logits.std()
    # a wrong token is several deviations under the maximum
    assert reference.greedy_margins(logits[-2:-1], np.array([int(np.argmin(logits[-2]))]))[0] > 2.0


# ------------------------------------------------------- the trace reduction


def test_short_name_keeps_the_head_and_the_fusion_kind():
    op = ("%fusion.468 = (f32[12,768,3072]{2,1,0:T(8,128)}, f32[12,768,3072]{2,1,0:T(8,128)}) "
          "fusion(f32[12,768,3072]{2,1,0:T(8,128)} %params.1), kind=kLoop, calls=%fused_computation.648")
    assert trace_reduce.short_name(op) == "fusion.468 kLoop"
    assert trace_reduce.short_name("%custom-call.12 = bf16[8] custom-call(...)") == "custom-call.12"
    assert trace_reduce.short_name("copy-start.3") == "copy-start.3"


def test_trace_reduction_on_a_sample_of_a_recorded_v5e_trace():
    """``fixtures/v5e_trace_sample.json``: the device operations and the host's
    ``cb.*`` spans of a slice of a real traced run on one ``TPU v5 lite``,
    cut by ``fixtures/cut_sample.py``. The expected numbers were worked out
    from the sample by hand (sums of durations), not by the code under test."""
    sample = json.load(open(os.path.join(FIXTURES, "v5e_trace_sample.json")))
    reduced = trace_reduce.reduce(
        [[tuple(op) for op in sample["device_ops"]]], [tuple(a) for a in sample["annotations"]])
    expect = sample["expect"]
    assert reduced["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert reduced["busy_s"] / reduced["window_s"] == pytest.approx(expect["busy_share"], rel=1e-6)
    assert reduced["device_ops"][0][0] == expect["top_op"]
    assert len(reduced["device_ops"][0][0]) < 64  # a short name, never the instruction
    gaps = dict(reduced["idle_gaps"])
    assert gaps[expect["gap_name"]] == pytest.approx(expect["gap_s"], rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_result_line_leaves_out_what_a_reader_cannot_find_and_keeps_breakdown_short():
    cell = harness.load_cell(MANIFEST["workloads"][0]["name"])
    ops = [[f"fusion.{i} kLoop", 0.001 * (20 - i)] for i in range(20)]
    record = harness.Record(
        correct=True, attempted=3, failed=0, end_to_end={}, facts={},
        clocks={"window_s": 10.0, "data_wait_s": 0.1, "dispatch_median_s": 0.001,
                "tokens_per_s": 2e5, "train_flops_per_token": 5e8, "device_kind": "TPU v5 lite",
                "chips": 1},
        trace={"window_s": 1.0, "busy_s": 0.99, "kernel_s": 0.0, "device_ops": ops,
               "idle_gaps": [["cb.fetch_loss", 0.01]]})
    line = harness.result_line(cell, record, traced=True)
    assert len(line["breakdown"]["device_ops"]) == 10 and line["device"]["busy_s"] == 0.99
    assert line["metrics"]["device_idle_share.train"]["value"] == pytest.approx(1.0)
    assert line["metrics"]["mfu.train"]["value"] == pytest.approx(100 * 2e5 * 5e8 / 197e12)
    record.trace = None  # no trace: the device metric is left out, the others stay
    line = harness.result_line(cell, record, traced=True)
    assert "device_idle_share.train" not in line["metrics"] and "mfu.train" in line["metrics"]
    assert "breakdown" not in line and "busy_s" not in line["device"]


def test_operation_counts_and_peaks():
    bert = json.load(open(os.path.join(CHIP, "configs", "bert-base.json")))
    per_token = flops.bert_forward_flops_per_token(bert, 128, 12)
    matmul_params = 12 * (4 * 768 * 768 + 2 * 768 * 3072)  # 85 M, embeddings not among them
    assert per_token == pytest.approx(2 * matmul_params + 12 * 4 * 128 * 768, rel=1e-3)
    mistral = json.load(open(os.path.join(CHIP, "configs", "mistral-7b.json")))
    per_token = flops.llama_forward_flops_per_token(mistral, 1, 32)
    assert per_token == pytest.approx(2 * 7.11e9, rel=0.01)  # 7.25 B less the embedding table
    assert flops.mfu_percent(1e5, 1e9, "TPU v5 lite", 1) == pytest.approx(100 * 1e14 / 197e12)
    with pytest.raises(KeyError, match="no published peaks"):
        flops.peaks("cpu")
