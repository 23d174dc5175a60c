"""The user-runnable benchmarks/ scripts stay runnable and emit parseable
JSON (reference ships standalone benchmark dirs; ours must not rot)."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def run_script(rel, *args, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(REPO / rel), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    last = res.stdout.strip().splitlines()[-1]
    return json.loads(last)


@pytest.mark.slow
def test_fp8_benchmark_emits_parity_json():
    out = run_script("benchmarks/fp8/run.py", "--steps", "5")
    assert {"bf16_final_loss", "fp8_final_loss", "bf16_step_ms", "fp8_step_ms"} <= set(out)


@pytest.mark.slow
def test_long_context_benchmark_honors_seq_knob():
    out = run_script("benchmarks/long_context/run.py", "--seq", "512")
    assert out["unit"] == "tokens/sec/chip" and out["value"] > 0
    assert out["seq_len"] == 512  # the CLI knob actually reached the workload


def test_input_pipeline_benchmark_smoke():
    """Fast tier-1 smoke: the sync-vs-prefetch microbench runs and emits the
    contract keys (overlap correctness itself is asserted by
    test_data_loader's acceptance test; a loaded CI box makes speedup-margin
    assertions here flaky)."""
    out = run_script(
        "benchmarks/input_pipeline/run.py",
        "--steps", "6", "--item-delay-ms", "1", "--compute-ms", "5",
    )
    assert out["bench"] == "input_pipeline"
    assert out["unit"] == "speedup(prefetch/sync)" and out["value"] > 0
    assert out["sync"]["samples_per_s"] > 0
    assert out["prefetch"]["samples_per_s"] > 0
    assert out["prefetch_depth"] == 2


def test_checkpoint_benchmark_smoke():
    """Fast tier-1 smoke: the sync-vs-async checkpoint microbench runs and
    emits the contract keys (the zero-stall margin itself is asserted by
    test_async_checkpoint's timing tests; wall-clock ratio assertions here
    would be flaky on a loaded CI box)."""
    out = run_script(
        "benchmarks/checkpoint/run.py",
        "--steps", "9", "--compute-ms", "10", "--every", "3", "--mb", "2",
    )
    assert out["bench"] == "checkpoint"
    assert out["unit"] == "exposed_stall_ratio(async/sync)"
    assert out["value"] >= 0
    for variant in ("baseline", "sync", "async"):
        assert out[variant]["p95_step_ms"] > 0
    assert out["sync"]["saves"] == out["async"]["saves"] == 3
    assert out["baseline"]["saves"] == 0


def test_perf_benchmark_smoke():
    """Fast tier-1 smoke: the performance-observatory microbench (ISSUE 7)
    runs the bench train step under telemetry + a trace window and emits the
    contract keys. On the CPU there is no peak, so MFU and the roofline bucket
    are null (not nominal); the cost capture itself still lands."""
    out = run_script("benchmarks/perf/run.py", "--steps", "5", "--trace-every", "2")
    assert out["bench"] == "perf"
    assert out["unit"] == "mfu(p50)" and out["value"] is None and out["mfu"] == {}
    assert out["roofline"] is None
    assert out["arithmetic_intensity"] > 0 and out["flops_per_step"] > 0
    assert out["trace_windows"] >= 1
    assert out["top_ops"] and all(op["total_s"] > 0 for op in out["top_ops"])
    # single-process CPU run traces no collectives: the ratio must be an
    # honest null, not a fake 1.0
    assert out["overlap_ratio"] is None


def test_weight_update_benchmark_smoke():
    """Fast tier-1 smoke: the fused-vs-annotation ZeRO-1 microbench (ISSUE 9)
    runs on the 8-virtual-device CPU mesh and emits the contract keys. CPU
    step-time ratios are emulation artifacts (see the README), so only
    structure + the memory/parity facts are asserted; the step-time and
    overlap numbers become meaningful on TPU hardware runs."""
    out = run_script(
        "benchmarks/weight_update/run.py",
        "--steps", "5", "--dim", "64", "--layers", "2", "--trace-every", "3",
    )
    assert out["bench"] == "weight_update"
    assert out["unit"] == "step_time_ratio(fused/unfused)" and out["value"] > 0
    assert out["n_devices"] == 8
    assert out["fused"]["fused"] is True  # the fused path actually engaged
    assert out["unfused"]["fused"] is False
    for leg in ("fused", "unfused"):
        assert out[leg]["step_ms"] > 0
        assert out[leg]["opt_state_bytes_per_replica"] > 0
    # one replica holds ~1/8 of the state (scalar count leaves ride on top)
    assert out["fused"]["opt_state_fraction"] < 0.2
    # both legs compute the same training: loss parity to float32 print width
    assert out["fused"]["final_loss"] == pytest.approx(
        out["unfused"]["final_loss"], rel=1e-6
    )
    # compiled-collective accounting flowed through telemetry
    assert out["collective_bytes_per_step"] > 0


def test_serving_benchmark_smoke():
    """Fast tier-1 smoke: the continuous-vs-static serving microbench
    (ISSUE 11) runs at a reduced workload and emits the contract keys with a
    continuous win. The win is held to what a CPU run can say: continuous
    batching serves the same tokens in fewer engine steps at a higher
    occupancy. Its wall-clock ratio (`value`) is a CPU timing — it read 0.46
    on a loaded box and 1.9 on an idle one — and is only required to exist;
    what it is on a chip is PERF.md's to record."""
    out = run_script(
        "benchmarks/serving/run.py",
        "--requests", "12", "--rate", "2.0", "--max-slots", "4",
        "--replicated-requests", "8", "--prefix-requests", "10",
        "--disagg-requests", "8", "--spec-requests", "8",
        timeout=600,
    )
    assert out["bench"] == "serving"
    assert out["unit"] == "throughput_ratio(continuous/static)"
    assert out["value"] > 0
    assert out["continuous"]["engine_steps"] < out["static"]["engine_steps"]
    for leg in ("continuous", "static"):
        assert out[leg]["completed"] == 12
        assert out[leg]["rejected"] == 0  # whole workload actually measured
        assert out[leg]["tokens_per_s"] > 0
        assert out[leg]["p99_latency_ms"] >= out[leg]["p50_latency_ms"] > 0
    # same workload -> same useful tokens; only the schedule differs
    assert out["continuous"]["tokens"] == out["static"]["tokens"]
    assert out["continuous"]["mean_occupancy"] > out["static"]["mean_occupancy"]
    assert out["p99_latency_ms"] == out["continuous"]["p99_latency_ms"]
    # replicated router leg (ISSUE 12): no scaling-margin bar at reduced
    # scale, but the robustness invariants are absolute — nothing lost, the
    # kill run's outputs bitwise-equal to the unkilled run, failover fired
    rep = out["replicated"]
    assert rep["bench"] == "serving_replicated" and rep["value"] > 0
    for leg in ("one_replica", "replicated", "replica_kill"):
        assert rep[leg]["completed"] == 8
        assert rep[leg]["lost"] == 0
        assert rep[leg]["tokens_per_s"] > 0
    assert rep["replica_kill"]["failovers"] >= 1
    assert rep["kill_outputs_match_unkilled"] is True
    assert rep["replica_kill"]["p99_latency_ms"] >= rep["replica_kill"]["p50_latency_ms"]
    # observability leg (ISSUE 15): tracing ON over the same kill workload —
    # outputs still bitwise-identical, and 100% of completions carry a
    # gap-free span tree (failover hops included)
    traced = rep["replica_kill_traced"]
    assert traced["completed"] == 8 and traced["lost"] == 0
    assert traced["span_trees_complete"] is True
    assert traced["broken_span_trees"] == 0
    assert rep["traced_outputs_match_unkilled"] is True
    assert rep["tracing_tokens_per_s_ratio"] > 0
    # shared-prefix leg (ISSUE 14): the deterministic invariants hold even at
    # reduced scale — prefill-token reduction is a token COUNT, not a wall
    # clock, so the ≥40% acceptance bar is assertable here; the wall-clock
    # tok/s and ttft improvements are asserted by `make bench-serve` at full
    # scale and only sanity-checked (> 0) under CI load
    pc = out["prefix_cache"]
    assert pc["bench"] == "serving_prefix_cache"
    assert pc["value"] >= 0.4  # prefill tokens cut by at least 40%
    assert pc["prefix_hit_rate"] > 0
    assert pc["prefill_tokens_saved"] > 0
    assert pc["outputs_match"] is True  # bitwise parity between the legs
    assert pc["zero_recompiles"] is True
    assert pc["cached"]["completed"] == pc["uncached"]["completed"] == 10
    assert pc["cached"]["rejected"] == pc["uncached"]["rejected"] == 0
    assert pc["tokens_per_s_ratio"] > 0 and pc["ttft_p50_ratio"] > 0
    # disaggregated leg (ISSUE 16): no throughput bar at reduced scale on a
    # loaded box, but the correctness invariants are absolute — bitwise
    # parity with the monolith, zero lost requests, ≥1 autoscaler scale-up
    # under the tight ttft objective, and a WARM join (every warmup point
    # pre-shipped: zero compiles on the joiner)
    dg = out["disagg"]
    assert dg["bench"] == "serving_disagg" and dg["value"] > 0
    assert dg["outputs_match_monolith"] is True
    assert dg["zero_lost"] is True
    assert dg["monolith"]["completed"] == dg["disagg"]["completed"] == 8
    assert dg["disagg"]["handoffs"] >= 8
    assert dg["scale_up_fired"] is True
    assert dg["join_compiles"] == 0 and dg["warm_join"] is True
    tr = dg["disagg"]["transition"]
    # the burn trigger fires on ttft OBSERVATIONS (first tokens), not
    # completions, so neither phase has a guaranteed minimum on a loaded
    # box — but the cut must partition every completion, and whichever
    # phase is populated must carry real percentiles
    assert tr["pre_scale"]["completed"] + tr["post_scale"]["completed"] == 8
    assert any(
        tr[ph]["completed"] > 0 and tr[ph]["p99_ttft_ms"] > 0
        for ph in ("pre_scale", "post_scale")
    )
    # speculative-decoding leg (ISSUE 18): no latency bar on CPU (the
    # truncated-layer draft only pays on TPU, where draft+verify beat k+1
    # sequential decode steps), but bitwise-accept makes the correctness
    # invariants absolute — outputs identical to the plain decode loop,
    # zero post-warmup recompiles with draft+verify watched, and the step
    # count must not grow (accepted drafts can only shorten the run)
    sd = out["spec_decode"]
    assert sd["bench"] == "serving_spec_decode"
    assert sd["outputs_match"] is True
    assert sd["zero_recompiles"] is True
    assert sd["speculative"]["completed"] == sd["baseline"]["completed"] == 8
    assert sd["speculative"]["rejected"] == sd["baseline"]["rejected"] == 0
    assert sd["speculative"]["tokens"] == sd["baseline"]["tokens"]
    assert sd["speculative"]["engine_steps"] <= sd["baseline"]["engine_steps"]
    assert sd["speculative"]["draft_proposed_tokens"] > 0
    assert 0.0 <= sd["spec_accept_rate"] <= 1.0
    assert sum(sd["speculative"]["spec_accept_hist"]) > 0
    # prefill-kernel chunk microbench rode along: gather column is always
    # compiled; the kernel column is compiled on TPU, interpreted on CPU
    pk = sd["prefill_kernel"]
    assert pk["gather_us_per_token"] > 0 and pk["kernel_us_per_token"] > 0
    assert pk["kernel_mode"] == ("compiled" if sd["on_tpu"] else "interpret")


def test_attention_benchmark_smoke():
    """Fast tier-1 smoke for `make bench-attn` (ISSUE 20): the kernel grid
    runs on CPU shapes (xla path — interpret mode is a correctness tool, not
    a perf signal), every cell lands without error, and the payload carries
    the roofline numbers plus the regression-guarded block. The fp8 leg's
    loss parity is absolute even at CPU scale; step-time margins are TPU
    facts and asserted nowhere here."""
    out = run_script("benchmarks/attention/run.py", "--steps", "2", timeout=600)
    assert out["unit"] == "us/token" and out["value"] > 0
    assert out["grid"] and all("error" not in g for g in out["grid"])
    for g in out["grid"]:
        assert g["us_per_token"] > 0
        assert g["achieved_tflops"] > 0
        assert "fraction_of_peak" not in g  # a CPU has no peak: absent, not nominal
    # every sparsity leg actually ran (the block-skip comparison needs all 3)
    assert {g["sparsity"] for g in out["grid"]} == {"dense", "causal", "window"}
    fp8 = out["fp8_train_step"]
    assert fp8["bf16_step_ms"] > 0 and fp8["fp8_step_ms"] > 0
    assert fp8["loss_rel_delta"] < 0.05  # fp8 recipe parity envelope
    g = out["guarded"]
    assert g["attn_kernel_us_per_token"] == out["value"]
    assert g["fp8_step_ms"] == fp8["fp8_step_ms"]
    assert g["attn_mfu_best_fraction"] is None and out["peak_flops"] is None


def test_compile_time_restart_benchmark_smoke():
    """Fast tier-1 smoke for `make bench-compile` (ISSUE 13): the train leg
    only (two subprocess generations against one cache) — the payload must
    carry cold/warm seconds plus the cache-event counts, and the warm
    generation must actually HIT (miss>0 there would be a silent recompile
    masquerading as a warm start). Speedup-margin assertions live in the
    chaos/compile-cache suites; wall-clock ratios here would flake on a
    loaded CI box."""
    out = run_script("benchmarks/compile_time/run.py", "--modes", "train", timeout=360)
    assert out["bench"] == "compile_time_restart"
    assert out["unit"].startswith("speedup")
    leg = out["train"]
    assert leg["metric"] == "restart_to_first_step_s"
    assert leg["cold_s"] > 0 and leg["warm_s"] > 0 and leg["speedup"] > 0
    assert leg["cold_cache_events"].get("store", 0) >= 1
    assert leg["warm_cache_events"].get("hit", 0) >= 1
    assert leg["warm_cache_events"].get("miss", 0) == 0


def _regress_cli(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.telemetry", "regress", *args],
        capture_output=True, text=True, timeout=120, env=env, cwd=str(REPO),
    )


def _bench_payload(value):
    return {
        "metric": "tok_per_sec", "value": value, "mfu": 0.4,
        "env": {"device_kind": "cpu", "device_count": 1, "jaxlib": "x"},
    }


def test_bench_check_flags_synthetic_regression(tmp_path):
    """The `make bench-check` gate, tier-1: a synthetic 20% tok/s regression
    must exit nonzero and NAME the regressed metric."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(_bench_payload(100.0)))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(_bench_payload(80.0)))
    res = _regress_cli(tmp_path, "--scan", str(tmp_path))
    assert res.returncode == 1, res.stdout + res.stderr
    assert "REGRESSION" in res.stdout and "tok_per_sec" in res.stdout


def test_bench_check_waiver_buys_exit_code_not_silence(tmp_path):
    """`--waive` flips the exit code for a known regression, but the
    REGRESSION row still prints, the WAIVED marker carries the reason, and
    the verdict line names the waiver again — silence is the one thing a
    waiver must never buy."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(_bench_payload(100.0)))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(_bench_payload(80.0)))
    res = _regress_cli(tmp_path, "--scan", str(tmp_path),
                       "--waive", "*tok_per_sec*=cpu runner flake")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "REGRESSION" in res.stdout          # the row survives the waiver
    assert "^ WAIVED" in res.stdout and "cpu runner flake" in res.stdout
    assert "regress verdict: OK with 1 regression(s) WAIVED" in res.stdout


def test_bench_check_waiver_file_autoloads_in_scan_mode(tmp_path):
    """Scan mode picks up BENCH_WAIVERS next to the payloads (the committed
    path `make bench-check` uses) and announces the load."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(_bench_payload(100.0)))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(_bench_payload(80.0)))
    (tmp_path / "BENCH_WAIVERS").write_text(
        "# known CPU variance\n*tok_per_sec*  # runner variance at boundary\n"
    )
    res = _regress_cli(tmp_path, "--scan", str(tmp_path))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "regress: loaded 1 waiver(s)" in res.stdout
    assert "runner variance at boundary" in res.stdout


def test_bench_check_unmatched_waiver_does_not_apply(tmp_path):
    """A waiver that names some OTHER metric must not buy this regression's
    exit code."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(_bench_payload(100.0)))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(_bench_payload(80.0)))
    res = _regress_cli(tmp_path, "--scan", str(tmp_path),
                       "--waive", "configs.some_other_bench=nope")
    assert res.returncode == 1, res.stdout + res.stderr
    assert "REGRESSION" in res.stdout and "^ WAIVED" not in res.stdout


def _attn_guarded_payload(us=100.0, fp8_ms=30.0, mfu=0.4):
    p = _bench_payload(100.0)
    p["configs"] = {
        "attention": {
            "metric": "attention fwd+bwd µs/token", "value": us,
            "guarded": {
                "attn_kernel_us_per_token": us,
                "fp8_step_ms": fp8_ms,
                "attn_mfu_best_fraction": mfu,
            },
        }
    }
    return p


@pytest.mark.parametrize(
    "kwargs, name",
    [
        ({"us": 130.0}, "attn_kernel_us_per_token"),      # 30% slower kernel
        ({"fp8_ms": 39.0}, "fp8_step_ms"),                # 30% slower fp8 step
        ({"mfu": 0.28}, "attn_mfu_best_fraction"),        # 30% roofline drop
    ],
)
def test_bench_check_flags_attention_guarded_regressions(tmp_path, kwargs, name):
    """ISSUE 20 acceptance: a synthetic regression on each guarded attention
    metric (30% — past the 10% spec band even after the 2x CPU-fingerprint
    widening) must fail `make bench-check` and NAME the metric — the specs
    give the kernel time and fp8 step ms lower-is-better direction and the
    mfu fraction higher-is-better (a generic catch-all would read a slower
    kernel as an 'improvement')."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(_attn_guarded_payload()))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(_attn_guarded_payload(**kwargs)))
    res = _regress_cli(tmp_path, "--scan", str(tmp_path))
    assert res.returncode == 1, res.stdout + res.stderr
    assert "REGRESSION" in res.stdout and name in res.stdout


def test_bench_check_accepts_unchanged_attention_guarded_payload(tmp_path):
    for fname in ("BENCH_r01.json", "BENCH_r02.json"):
        (tmp_path / fname).write_text(json.dumps(_attn_guarded_payload()))
    res = _regress_cli(tmp_path, "--scan", str(tmp_path))
    assert res.returncode == 0, res.stdout + res.stderr


def test_bench_check_accepts_identical_payloads(tmp_path):
    for name in ("BENCH_r01.json", "BENCH_r02.json"):
        (tmp_path / name).write_text(json.dumps(_bench_payload(100.0)))
    res = _regress_cli(tmp_path, "--scan", str(tmp_path))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "regress verdict: OK" in res.stdout


def test_bench_check_refuses_cross_fingerprint(tmp_path):
    a = _bench_payload(100.0)
    b = _bench_payload(100.0)
    b["env"] = {"device_kind": "TPU v5 lite", "device_count": 8, "jaxlib": "x"}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(a))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(b))
    res = _regress_cli(tmp_path, "--scan", str(tmp_path))
    assert res.returncode == 2, res.stdout + res.stderr
    assert "REFUSING" in res.stdout


def test_hub_dashboard_render_stays_under_overhead_budget(tmp_path):
    """Tier-1 guard for the live plane (ISSUE 19): tailing + folding a
    ~2000-record stream and rendering one `top` frame — detectors armed —
    must finish well inside a fixed budget. The dashboard watches the
    fleet; it must never cost like one."""
    import time

    from accelerate_tpu.telemetry.anomaly import AnomalyEngine
    from accelerate_tpu.telemetry.hub import EventHub, render_top

    path = tmp_path / "events-rank0.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "schema": 1, "run_id": "bench",
                            "process_index": 0, "num_processes": 1}) + "\n")
        for i in range(2000):
            f.write(json.dumps({"kind": "step", "step": i, "t": float(i),
                                "dur_s": 0.01 + 0.0001 * (i % 7),
                                "execute_s": 0.01}) + "\n")
    hub = EventHub([str(tmp_path)], anomaly=AnomalyEngine(emit_records=False))
    t0 = time.perf_counter()
    hub.poll()
    frame = render_top(hub.model)
    elapsed = time.perf_counter() - t0
    assert len(hub.model.records) >= 2001
    assert "steps: 2000" in frame
    # generous for a loaded single-core CI box; a regression that makes the
    # live plane quadratic or per-record-expensive blows straight past it
    assert elapsed < 3.0, f"hub poll+fold+render took {elapsed:.2f}s"


def test_benchmark_dirs_are_documented():
    dirs = [p for p in (REPO / "benchmarks").iterdir() if p.is_dir() and p.name != "__pycache__"]
    assert len(dirs) >= 5
    for d in dirs:
        assert (d / "README.md").exists(), f"{d.name} lacks a README"
        assert (d / "run.py").exists(), f"{d.name} lacks run.py"
