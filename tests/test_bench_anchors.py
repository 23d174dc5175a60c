"""The bench's baseline-anchoring must never lose the driver's number: these
pin the pure bookkeeping (``bench.apply_baseline_anchors``) that runs between
measurement and the final JSON line."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import apply_baseline_anchors, sanitize_json


def _result(per_chip=1000.0):
    return {"per_chip": per_chip, "model": "bert-base", "backend": "tpu"}


class TestBaselineAnchors:
    def test_first_run_seeds_all_anchors(self, tmp_path):
        path = str(tmp_path / "b.json")
        configs = {"resnet_dp": {"value": 500.0}, "inference": {"value": 0.0}}
        ratio = apply_baseline_anchors(_result(), configs, path)
        assert ratio == 1.0
        saved = json.load(open(path))
        assert saved["per_chip"] == 1000.0
        assert saved["configs"] == {"resnet_dp": 500.0}  # zero values never anchor
        assert "vs_baseline" not in configs["resnet_dp"]

    def test_second_run_reports_ratios(self, tmp_path):
        path = str(tmp_path / "b.json")
        apply_baseline_anchors(_result(1000.0), {"resnet_dp": {"value": 500.0}}, path)
        configs = {"resnet_dp": {"value": 600.0}, "fsdp_lm": {"value": 70.0}}
        ratio = apply_baseline_anchors(_result(1500.0), configs, path)
        assert ratio == 1.5
        assert configs["resnet_dp"]["vs_baseline"] == 1.2
        # new config on a later run: anchored now, ratio next time
        saved = json.load(open(path))
        assert saved["configs"]["fsdp_lm"] == 70.0
        assert "vs_baseline" not in configs["fsdp_lm"]

    def test_remat_policy_mismatch_noted(self, tmp_path):
        """Self-tuning configs: anchor remembers the policy; a run that fell
        back to a different policy flags its ratio as non-comparable."""
        path = str(tmp_path / "b.json")
        apply_baseline_anchors(
            _result(), {"fsdp_lm": {"value": 100.0, "remat": "dots_no_batch"}}, path
        )
        saved = json.load(open(path))
        assert saved["configs_meta"]["fsdp_lm"] == {"remat": "dots_no_batch"}
        configs = {"fsdp_lm": {"value": 80.0, "remat": "True"}}
        apply_baseline_anchors(_result(), configs, path)
        assert configs["fsdp_lm"]["vs_baseline"] == 0.8
        assert "dots_no_batch" in configs["fsdp_lm"]["vs_baseline_note"]
        # same policy → no note
        configs = {"fsdp_lm": {"value": 110.0, "remat": "dots_no_batch"}}
        apply_baseline_anchors(_result(), configs, path)
        assert "vs_baseline_note" not in configs["fsdp_lm"]

    def test_legacy_headline_only_baseline(self, tmp_path):
        """Round-2's file has only per_chip; configs get added without
        touching the headline anchor."""
        path = str(tmp_path / "b.json")
        json.dump({"per_chip": 852.4, "model": "bert-base"}, open(path, "w"))
        configs = {"long_context": {"value": 22586.0}}
        ratio = apply_baseline_anchors(_result(1796.7), configs, path)
        assert round(ratio, 3) == round(1796.7 / 852.4, 3)
        saved = json.load(open(path))
        assert saved["per_chip"] == 852.4
        assert saved["configs"]["long_context"] == 22586.0

    def test_corrupt_baseline_reanchors_instead_of_crashing(self, tmp_path):
        path = str(tmp_path / "b.json")
        with open(path, "w") as f:
            f.write('{"per_chip": 10')  # truncated by a killed writer
        ratio = apply_baseline_anchors(_result(), {"resnet_dp": {"value": 5.0}}, path)
        assert ratio == 1.0
        assert json.load(open(path))["per_chip"] == 1000.0

    def test_sanitize_strips_non_finite(self):
        configs = {"a": {"final_loss": float("nan"), "value": 1.0,
                         "list": [float("inf"), 2.0]}}
        out = json.dumps(sanitize_json(configs), allow_nan=False)  # must not raise
        assert json.loads(out) == {"a": {"final_loss": None, "value": 1.0,
                                         "list": [None, 2.0]}}

    def test_nan_values_never_anchor_or_divide(self, tmp_path):
        path = str(tmp_path / "b.json")
        nan = float("nan")
        configs = {"fsdp_lm": {"value": nan}}
        ratio = apply_baseline_anchors(_result(nan), configs, path)
        assert ratio == 1.0
        saved = json.load(open(path)) if os.path.exists(path) else {}
        assert "per_chip" not in saved and saved.get("configs", {}) == {}
        # nan against an existing finite anchor: ratio 0, anchor untouched
        json.dump({"per_chip": 1000.0, "configs": {"fsdp_lm": 50.0}}, open(path, "w"))
        configs = {"fsdp_lm": {"value": nan}}
        apply_baseline_anchors(_result(), configs, path)
        assert configs["fsdp_lm"]["vs_baseline"] == 0.0
        assert json.load(open(path))["configs"]["fsdp_lm"] == 50.0

    def test_nan_headline_vs_real_anchor_is_failure_sentinel(self, tmp_path):
        path = str(tmp_path / "b.json")
        json.dump({"per_chip": 1000.0}, open(path, "w"))
        ratio = apply_baseline_anchors(_result(float("nan")), {}, path)
        assert ratio == 0.0  # failed run must not read as "at baseline"

    def test_malformed_env_knobs_fall_back(self, monkeypatch):
        from bench import _env_int

        monkeypatch.setenv("ACCELERATE_BENCH_BUDGET", "three")
        assert _env_int("ACCELERATE_BENCH_BUDGET", 4) == 4
        monkeypatch.setenv("ACCELERATE_BENCH_BUDGET", "")
        assert _env_int("ACCELERATE_BENCH_BUDGET", 4) == 4
        monkeypatch.setenv("ACCELERATE_BENCH_BUDGET", "2")
        assert _env_int("ACCELERATE_BENCH_BUDGET", 4) == 2

    def test_wrong_shaped_baseline_reanchors(self, tmp_path):
        path = str(tmp_path / "b.json")
        json.dump([1, 2, 3], open(path, "w"))  # valid JSON, wrong shape
        ratio = apply_baseline_anchors(_result(), {"resnet_dp": {"value": 5.0}}, path)
        assert ratio == 1.0
        assert json.load(open(path))["per_chip"] == 1000.0
        json.dump({"per_chip": 1000.0, "configs": "oops"}, open(path, "w"))
        configs = {"resnet_dp": {"value": 5.0}}
        apply_baseline_anchors(_result(), configs, path)
        assert json.load(open(path))["configs"] == {"resnet_dp": 5.0}

    def test_errored_config_entries_are_harmless(self, tmp_path):
        path = str(tmp_path / "b.json")
        configs = {"inference": {"metric": "inference", "value": 0.0, "error": "boom"}}
        apply_baseline_anchors(_result(), configs, path)
        saved = json.load(open(path))
        assert saved["configs"] == {}
        # and an errored run against an existing anchor reports ratio 0, not a crash
        json.dump({"per_chip": 1000.0, "configs": {"inference": 50.0}}, open(path, "w"))
        configs = {"inference": {"value": 0.0, "error": "boom"}}
        apply_baseline_anchors(_result(), configs, path)
        assert configs["inference"]["vs_baseline"] == 0.0


class TestAnchorNotes:
    def test_headline_batch_size_mismatch_noted(self, tmp_path):
        path = str(tmp_path / "b.json")
        json.dump({"per_chip": 800.0, "model": "bert-base", "batch_size": 64}, open(path, "w"))
        result = _result()
        result["batch_size"] = 256
        apply_baseline_anchors(result, {}, path)
        assert "batch size differs" in result.get("vs_baseline_note", "")

    def test_headline_anchor_seeds_batch_size(self, tmp_path):
        path = str(tmp_path / "b.json")
        result = _result()
        result["batch_size"] = 128
        apply_baseline_anchors(result, {}, path)
        assert json.load(open(path))["batch_size"] == 128

    def test_null_config_value_gives_null_ratio(self, tmp_path):
        path = str(tmp_path / "b.json")
        json.dump({"per_chip": 800.0, "configs": {"compile_time_llama1b": 5.0}}, open(path, "w"))
        configs = {"compile_time_llama1b": {"value": None, "note": "budget blown"}}
        apply_baseline_anchors(_result(), configs, path)
        assert configs["compile_time_llama1b"]["vs_baseline"] is None


class TestNoFallback:
    """PR 21: nothing on the measurement path can pretend to be the chip. No
    TPU and no ``JAX_PLATFORMS=cpu`` is a failure, a config that raises makes
    the run exit non-zero after the others have printed, and every record
    names the device it ran on."""

    @pytest.fixture(autouse=True)
    def _leave_jax_cache_alone(self, monkeypatch):
        # detect_backend() places JAX's compile cache; the suite's own compiles
        # must not start landing in the checkout's .jax_cache
        import benchmarks._common as common

        monkeypatch.setattr(common, "enable_jax_cache", lambda: "unused")

    def test_cpu_only_when_asked_for(self, monkeypatch):
        from benchmarks._common import detect_backend, device_record

        assert device_record() == {"platform": "cpu", "kind": "cpu", "count": 8}
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert detect_backend() is False
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(RuntimeError, match="no TPU"):
            detect_backend()

    def test_bench_without_a_chip_fails(self, monkeypatch, capsys):
        import bench

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(SystemExit) as exit_info:
            bench.main()
        assert exit_info.value.code == 1
        record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert record["value"] == 0.0 and "no TPU" in record["error"]

    def _fake_bench(self, monkeypatch, tmp_path, failing=()):
        import bench

        monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))  # BENCH_BASELINE.json
        monkeypatch.setattr(bench, "run_bench", lambda: {
            "per_chip": 10.0, "samples_per_sec": 10.0, "backend": "cpu", "n_chips": 8,
            "model": "bert-tiny", "batch_size": 16, "final_loss": 0.5, "mfu": None,
            "n_params": 1, "device_kind": "cpu",
        })

        def config(name):
            def run(on_tpu):
                if name in failing:
                    raise ValueError(f"{name} broke")
                return {"metric": name, "value": 1.0}
            return run

        for name in ("resnet", "grad_accum", "fsdp_lm", "inference", "longcontext",
                     "compile_time", "checkpoint_stall", "weight_update", "serving",
                     "attention"):
            monkeypatch.setattr(bench, f"run_bench_{name}", config(name))
        return bench

    def test_failed_config_exits_nonzero_after_the_others_print(
        self, monkeypatch, tmp_path, capsys
    ):
        bench = self._fake_bench(monkeypatch, tmp_path, failing=("inference",))
        with pytest.raises(SystemExit) as exit_info:
            bench.main()
        assert exit_info.value.code == 1
        final = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert "partial" not in final and len(final["configs"]) == 10
        assert final["configs"]["inference"]["error"] == "ValueError: inference broke"
        assert final["configs"]["attention"]["value"] == 1.0  # ran after the failure

    def test_every_record_names_the_device(self, monkeypatch, tmp_path, capsys):
        bench = self._fake_bench(monkeypatch, tmp_path)
        bench.main()  # no failure: returns, exit code 0
        device = {"platform": "cpu", "kind": "cpu", "count": 8}
        for line in capsys.readouterr().out.strip().splitlines():
            record = json.loads(line)
            assert (record["platform"], record["device_kind"], record["n_chips"]) == (
                "cpu", "cpu", 8)
            assert record["env"]["platform"] == "cpu" and record["env"]["device_count"] == 8
            assert all(entry["device"] == device for entry in record["configs"].values())


class TestPerConfigMfu:
    """VERDICT r04 item 2: every config must report utilization on TPU. The
    arithmetic is exercised here by faking the peak-FLOPs lookup (CPU reports
    no peak, so the fields gate on it). Since ISSUE 7 the lookup lives in the
    shared telemetry perf registry — bench-local call sites patch through
    ``bench.device_peak_flops``, the LM configs go through
    ``telemetry.perf.lm_train_mfu`` whose module global is patched instead."""

    def test_resnet_reports_mfu_when_peak_known(self, monkeypatch):
        import bench
        from accelerate_tpu.telemetry import perf

        monkeypatch.setattr(bench, "device_peak_flops", lambda d: 1e12)
        # the roofline placement reads the chip's ridge: a CPU has no peak
        monkeypatch.setattr(perf, "peaks_for_device",
                            lambda device=None: perf.HardwarePeaks("TPU v5 lite", 197e12, 819e9))
        out = bench.run_bench_resnet(on_tpu=False)
        assert out.get("mfu") is not None and out["mfu"] > 0
        # XLA reports bytes too: the conv step gets a roofline placement
        assert out.get("roofline") in ("compute-bound", "hbm-bound")
        assert out.get("arithmetic_intensity", 0) > 0

    def test_grad_accum_reports_mfu_when_peak_known(self, monkeypatch):
        import bench
        from accelerate_tpu.telemetry import perf

        monkeypatch.setattr(perf, "device_peak_flops", lambda d: 1e12)
        out = bench.run_bench_grad_accum(on_tpu=False)
        assert out.get("mfu") is not None and out["mfu"] > 0

    def test_inference_reports_mfu_and_roofline(self, monkeypatch):
        import bench

        monkeypatch.setattr(bench, "device_peak_flops", lambda d: 1e12)
        monkeypatch.setattr(bench, "device_hbm_bandwidth", lambda d: 819e9)
        out = bench.run_bench_inference(on_tpu=False)
        assert out.get("mfu") is not None and out["mfu"] > 0
        assert out.get("hbm_roofline_frac") is not None and out["hbm_roofline_frac"] > 0

    def test_bench_has_no_private_peak_table(self):
        """ISSUE 7 ratchet: bench.py must consume the shared telemetry/perf
        registry — a reintroduced private table could silently diverge."""
        import bench

        assert not hasattr(bench, "_PEAK_FLOPS")
        assert not hasattr(bench, "_HBM_BW")
        assert not hasattr(bench, "_lm_train_mfu")
        assert not hasattr(bench, "_peak_flops")
        assert not hasattr(bench, "_train_flops_per_sample")
