"""``chip_smoke.py`` rehearsed on the CPU: each phase function at a tiny size
(8 virtual devices for the four-chip phase, the Pallas kernels in interpret
mode, set here and not by the script), and the script itself, which must
refuse to run anywhere but on the chip."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from accelerate_tpu.models import BertConfig, LlamaConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# head_dim 64: the narrowest head impl="auto" hands to the flash kernel
TINY_LLAMA = LlamaConfig(vocab_size=512, dim=128, n_layers=2, n_heads=2, n_kv_heads=1,
                         max_seq_len=512)


def _count_calls(monkeypatch, module_name, attr):
    module = importlib.import_module(module_name)
    real, calls = getattr(module, attr), []

    def counted(*args, **kwargs):
        calls.append(attr)
        return real(*args, **kwargs)

    monkeypatch.setattr(module, attr, counted)
    return calls


def test_train_phase_at_tiny_size():
    out = chip_smoke.phase_train(BertConfig.tiny(), batch_size=16, seq_len=32, steps=4)
    assert out["loss_last"] < out["loss_first"]
    assert out["compiles_after_first_step"] == 0 and out["checkpoint_roundtrip_bitwise"]
    assert out["peak_bytes_in_use"] is None  # the CPU reports no memory stats


def test_train_long_phase_takes_the_flash_kernels(monkeypatch):
    monkeypatch.setenv("ACCELERATE_FLASH_KERNEL", "interpret")
    calls = _count_calls(monkeypatch, "accelerate_tpu.ops.flash_attention", "_flash_kernel")
    out = chip_smoke.phase_train_long(
        TINY_LLAMA, batch=2, seq_len=512, steps=2, expect_kernel=None
    )
    assert calls, "impl='auto' past the crossover did not reach the kernel"
    # the kernel is a different program from the einsum path, and close to it
    assert 0 < out["step0_loss_rel_vs_xla"] < 1e-4
    assert 0 < out["step0_grad_norm_rel_vs_xla"] < 1e-2


def test_train_long_phase_fails_where_no_kernel_is_lowered():
    """On the CPU without interpret mode ``auto`` takes the einsum path: the
    phase must say so, not pass."""
    with pytest.raises(AssertionError, match="did not lower to tpu_custom_call"):
        chip_smoke.phase_train_long(TINY_LLAMA, batch=2, seq_len=512, steps=1)


def test_serve_phase_at_tiny_size(monkeypatch):
    monkeypatch.setenv("ACCELERATE_PAGED_KERNEL", "interpret")
    prefill = _count_calls(
        monkeypatch, "accelerate_tpu.ops.flash_attention", "paged_attention_prefill")
    decode = _count_calls(
        monkeypatch, "accelerate_tpu.ops.flash_attention", "paged_attention_decode")
    config = LlamaConfig(vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                         max_seq_len=256)
    out = chip_smoke.phase_serve(
        config, max_new=6, block_size=8, max_slots=4, prefill_buckets=(8, 16),
        blocks_per_seq=8, prompt_lens=(5, 12, 16, 40, 9, 21), shared_prefix=8,
        expect_kernel=None,
    )
    assert prefill and decode
    assert out["requests"] == 8 and out["outputs_equal_greedy_generate"]
    assert out["prompt_lens"][3] > 16  # a multi-chunk prefill
    assert out["prefill_tokens_saved"] >= 8 and out["cow_copies"] >= 1
    assert out["min_top2_gap_deviations"] >= chip_smoke.SERVE_TIE_MARGIN
    assert out["tokens_generated"] == sum(out["new_tokens"])


def test_multichip_phase_on_four_virtual_devices():
    out = chip_smoke.phase_multichip(
        BertConfig.tiny(), n_devices=4, batch_size=16, seq_len=32, steps=8
    )
    assert out["one_device"]["placed"] == {"params": (1, 1.0), "opt_state": (1, 1.0)}
    for name in ("fsdp", "dp_zero1"):
        leg = out[name]
        assert leg["placed"]["opt_state"][0] == 4 and 0.25 <= leg["placed"]["opt_state"][1] < 0.3
        assert leg["collectives"] and leg["max_loss_rel_vs_one_device"] < 1e-3
    assert out["dp_zero1"]["fused_zero1"] and not out["fsdp"]["fused_zero1"]
    assert out["fsdp"]["placed"]["params"][1] < 0.3 and out["dp_zero1"]["placed"]["params"][1] == 1.0


@pytest.mark.parametrize("args,chips", [((), 1), (("--chips", "4"), 4)])
def test_script_refuses_to_run_off_the_chip(args, chips):
    """Under JAX_PLATFORMS=cpu: a non-zero exit and ``"ok": false`` on the
    last line, before any phase has run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert res.returncode == 1, res.stderr[-1500:]
    lines = res.stdout.strip().splitlines()
    assert len(lines) == 1  # no phase printed anything
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert f"needs {chips} TPU chip(s)" in last["error"]


def test_main_fails_when_a_phase_fails(monkeypatch, capsys):
    """On the chip, a phase that raises is reported and fails the run; the
    other phases still say what they find."""
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_record", lambda: tpu)
    monkeypatch.setattr(chip_smoke, "enable_jax_cache", lambda: "unused")
    monkeypatch.setattr(chip_smoke, "phase_train", lambda seed: {"steps": 8})

    def broken(seed):
        raise AssertionError("loss did not fall")

    monkeypatch.setattr(chip_smoke, "phase_train_long", broken)
    monkeypatch.setattr(chip_smoke, "phase_serve", lambda seed: {"requests": 8})
    assert chip_smoke.main([]) == 1
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["host_data_path"] in ("native", "numpy")
    assert [(r["phase"], r["ok"]) for r in lines[1:4]] == [
        ("train", True), ("train_long", False), ("serve", True)]
    assert lines[2]["error"] == "AssertionError: loss did not fall"
    assert all(r["device"] == tpu for r in lines[1:])
    assert lines[-1] == {"ok": False, "device": tpu}

    monkeypatch.setattr(chip_smoke, "phase_train_long", lambda seed: {"steps": 4})
    assert chip_smoke.main([]) == 0
    assert capsys.readouterr().out.strip().splitlines()[-1] == json.dumps(
        {"ok": True, "device": tpu})
