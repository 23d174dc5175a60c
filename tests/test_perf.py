"""Performance observatory (ISSUE 7): peak registry + MFU/roofline math
goldens, CPU-backend cost-analysis capture on a real jitted fn, xplane
fixture + real-trace parsing, overlap-ratio computation, automatic trace
windows, the report CLI's performance section, and the disabled-path
zero-cost smoke (mirrors test_forensics.py style)."""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator, telemetry as tel
from accelerate_tpu.telemetry import perf, xplane
from accelerate_tpu.telemetry.report import build_report, format_report
from accelerate_tpu.utils.dataclasses import ProfileConfig


@pytest.fixture(autouse=True)
def _telemetry_clean(monkeypatch):
    for var in ("ACCELERATE_TELEMETRY", "ACCELERATE_TELEMETRY_DIR",
                "ACCELERATE_PERF_CAPTURE", "ACCELERATE_TRACE_EVERY",
                "ACCELERATE_TRACE_STEPS", "ACCELERATE_TRACE_AT",
                "ACCELERATE_TRACE_DIR"):
        monkeypatch.delenv(var, raising=False)
    yield
    tel.disable()


class _FakeDevice:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


V5E = perf.HardwarePeaks("TPU v5 lite", 197e12, 819e9)


@pytest.fixture
def v5e_peaks(monkeypatch):
    """The plumbing from captured cost to per-step mfu/roofline, driven on the
    CPU by standing a v5e's peaks in for the lookup (a CPU itself has none)."""
    monkeypatch.setattr(perf, "peaks_for_device", lambda device=None: V5E)


# ------------------------------------------------------------ peak registry --


@pytest.mark.smoke
def test_peak_table_is_keyed_by_device_kind():
    v5e = perf.peaks_for_device(_FakeDevice("TPU v5 lite"))
    assert v5e.flops == 197e12 and v5e.hbm_bytes_per_s == 819e9
    assert v5e.device_kind == "TPU v5 lite"
    assert v5e.ridge_intensity == pytest.approx(197e12 / 819e9)
    # every entry is one (FLOP/s, bytes/s) pair: no kind has half a peak
    assert all(len(v) == 2 and min(v) > 0 for v in perf.DEVICE_PEAKS.values())


def test_unknown_tpu_kind_raises():
    """A device that is not in the table is an error, not a default: an
    unknown TPU once borrowed the v5e's peaks and reported an MFU against them."""
    with pytest.raises(ValueError, match="TPU v99 mega"):
        perf.peaks_for_device(_FakeDevice("TPU v99 mega"))
    with pytest.raises(ValueError, match="DEVICE_PEAKS"):
        perf.device_peak_flops(_FakeDevice("TPU v5 lite pod"))  # no prefix match either


def test_cpu_has_no_peak():
    """No nominal stand-in: off-TPU there is no peak, so no MFU or roofline."""
    cpu = _FakeDevice("cpu", platform="cpu")
    assert perf.peaks_for_device(cpu) is None
    assert perf.peaks_for_device() is None  # the suite's own CPU devices
    assert perf.device_peak_flops(cpu) == 0.0
    assert perf.device_hbm_bandwidth(cpu) is None
    tpu = _FakeDevice("TPU v4")
    assert perf.device_peak_flops(tpu) == 275e12
    assert perf.device_hbm_bandwidth(tpu) == 1228e9


# ----------------------------------------------------------------- MFU math --


def test_mfu_and_intensity_goldens():
    assert perf.mfu(1e12, 1.0, 197e12) == pytest.approx(1e12 / 197e12)
    assert perf.mfu(5e11, 0.5, 1e12) == pytest.approx(1.0)
    assert perf.mfu(0.0, 1.0, 1e12) is None
    assert perf.mfu(1e12, 1.0, 0.0) is None
    assert perf.arithmetic_intensity(1e9, 1e7) == pytest.approx(100.0)
    assert perf.arithmetic_intensity(0.0, 1e7) is None


def test_roofline_bucket_straddles_ridge():
    peaks = perf.HardwarePeaks("TPU v5e", 197e12, 819e9)
    ridge = peaks.ridge_intensity  # ~240.5 FLOP/B
    assert perf.roofline_bucket(ridge * 2, peaks) == "compute-bound"
    assert perf.roofline_bucket(ridge, peaks) == "compute-bound"  # >= is compute
    assert perf.roofline_bucket(ridge / 2, peaks) == "hbm-bound"
    assert perf.roofline_bucket(None, peaks) is None
    assert perf.roofline_bucket(100.0, None) is None  # off-TPU: no peak, no bucket


def test_train_flops_per_sample_golden():
    class Cfg:
        n_layers, dim = 4, 128

    n_params, seq = 1_000_000, 32
    expected = (6.0 * n_params + 12.0 * 4 * 128 * seq) * seq
    assert perf.train_flops_per_sample(Cfg, seq, n_params) == pytest.approx(expected)


def test_lm_train_mfu_gates_on_real_peak(monkeypatch):
    class Cfg:
        n_layers, dim = 2, 64

    # CPU backend: no absolute peak -> None (bench omits the field)
    assert perf.lm_train_mfu(1000.0, 10_000, Cfg, 16) is None
    monkeypatch.setattr(perf, "device_peak_flops", lambda d: 1e12)
    per_token = perf.train_flops_per_sample(Cfg, 16, 10_000) / 16
    assert perf.lm_train_mfu(1000.0, 10_000, Cfg, 16) == pytest.approx(
        round(1000.0 * per_token / 1e12, 4)
    )


# -------------------------------------------------------------- cost capture --


def _events(tmp_path):
    out = []
    for name in os.listdir(tmp_path):
        if name.endswith(".jsonl"):
            with open(os.path.join(tmp_path, name)) as f:
                out.extend(json.loads(line) for line in f if line.strip())
    return out


def test_capture_compiled_records_cost_and_memory(tmp_path, v5e_peaks):
    tel.enable(str(tmp_path))

    @jax.jit
    def step(x, y):
        return jnp.tanh(x @ y).sum()

    ones = jnp.ones((64, 64), jnp.float32)
    cost = perf.capture_compiled("my_step", step, (ones, ones))
    tel.disable()
    assert cost is not None and cost.flops > 0 and cost.bytes_accessed > 0
    assert cost.intensity == pytest.approx(cost.flops / cost.bytes_accessed)
    assert cost.roofline in ("compute-bound", "hbm-bound")
    assert cost.mfu(1.0) == pytest.approx(cost.flops / cost.peaks.flops)
    assert cost.memory and cost.memory["argument_bytes"] > 0
    events = _events(tmp_path)
    perf_recs = [e for e in events if e["kind"] == "perf"]
    assert len(perf_recs) == 1 and perf_recs[0]["fn"] == "my_step"
    assert perf_recs[0]["flops"] == cost.flops
    assert perf_recs[0]["roofline"] == cost.roofline
    assert any(e["kind"] == "memory_projection" for e in events)


def test_capture_kill_switch(tmp_path, monkeypatch):
    assert not perf.capture_enabled()  # telemetry off
    tel.enable(str(tmp_path))
    assert perf.capture_enabled()
    monkeypatch.setenv("ACCELERATE_PERF_CAPTURE", "0")
    assert not perf.capture_enabled()


def test_capture_tolerates_unlowerable_fn(tmp_path):
    tel.enable(str(tmp_path))
    assert perf.capture_compiled("eager", lambda x: x, (1,)) is None


def test_capture_compile_is_the_functions_one_compile(tmp_path):
    """The capture's AOT compile is counted, once, and the jit call that
    follows reuses its executable: the function costs one compile in all."""
    from accelerate_tpu.telemetry import step_profiler

    tel.enable(str(tmp_path))
    step_profiler.install_compile_listener()

    @jax.jit
    def fn(x):
        return x * 2 + 1

    ones = jnp.ones((8, 8))
    c0, s0 = step_profiler.compile_snapshot()
    perf.capture_compiled("fn", fn, (ones,))
    c1, s1 = step_profiler.compile_snapshot()
    assert c1 == c0 + 1 and s1 > s0
    fn(ones).block_until_ready()
    assert step_profiler.compile_snapshot()[0] == c1


# ----------------------------------------------------- accelerator integration


def _tiny_train(tmp_path, steps=4, handlers=None):
    from accelerate_tpu.models import BertConfig, bert_loss, bert_shard_rules, init_bert
    import dataclasses

    config = dataclasses.replace(BertConfig.tiny(), max_seq_len=32)
    acc = Accelerator(mixed_precision="bf16", rng_seed=0, kwargs_handlers=handlers)
    params = init_bert(config, jax.random.PRNGKey(0))
    params, opt = acc.prepare(params, optax.adamw(1e-4), shard_rules=bert_shard_rules())
    step = acc.prepare_train_step(lambda p, b: bert_loss(p, b, config), opt)
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(rng.integers(0, config.vocab_size, (8, 32)), jnp.int32),
        "attention_mask": jnp.ones((8, 32), jnp.int32),
        "token_type_ids": jnp.zeros((8, 32), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 2, (8,)), jnp.int32),
    }
    opt_state = opt.opt_state
    for _ in range(steps):
        params, opt_state, _m = step(params, opt_state, batch)
        # force completion inside the step so trace windows capture the
        # thunks (async dispatch would otherwise run them past stop_trace)
        float(np.asarray(_m["loss"]))
    acc.end_training()
    return acc


def test_accelerator_steps_carry_mfu_and_roofline(tmp_path, v5e_peaks):
    tel.enable(str(tmp_path))
    _tiny_train(tmp_path)
    tel.disable()
    events = _events(tmp_path)
    perfs = [e for e in events if e["kind"] == "perf"]
    assert len(perfs) == 1 and perfs[0]["fn"] == "train_step"
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 4
    for s in steps:
        assert s["mfu"] > 0
        assert s["roofline"] in ("compute-bound", "hbm-bound")
        assert s["perf_fn"] == "train_step"
        assert s["arithmetic_intensity"] > 0
    # the capture's AOT compile is the step function's one compile (the jit
    # call reuses it), and it lands in the first step's record
    assert sum(s["compiles"] for s in steps) == 1 and steps[0]["compiles"] == 1


def test_accelerator_steps_carry_no_mfu_off_tpu(tmp_path):
    """On the CPU there is no peak: the cost is still captured, the step
    records carry no mfu/roofline rather than a nominal one."""
    tel.enable(str(tmp_path))
    _tiny_train(tmp_path)
    tel.disable()
    events = _events(tmp_path)
    (captured,) = [e for e in events if e["kind"] == "perf"]
    assert captured["flops"] > 0 and captured["peak_flops"] is None
    assert captured["roofline"] is None and captured["device_kind"] == "cpu"
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 4
    assert all("mfu" not in s and "roofline" not in s for s in steps)


def test_accelerator_capture_kill_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("ACCELERATE_PERF_CAPTURE", "0")
    tel.enable(str(tmp_path))
    _tiny_train(tmp_path)
    tel.disable()
    events = _events(tmp_path)
    assert not [e for e in events if e["kind"] == "perf"]
    assert all(e.get("mfu") is None for e in events if e["kind"] == "step")


@pytest.mark.smoke
def test_perf_disabled_path_zero_cost(tmp_path, monkeypatch):
    """Telemetry off: no perf capture, no lowering, no trace window, no file
    — the wrapper's additions are flag checks (test_forensics style)."""
    monkeypatch.chdir(tmp_path)
    lowered = []

    real_capture = perf.capture_compiled
    monkeypatch.setattr(perf, "capture_compiled",
                        lambda *a, **k: lowered.append(a) or real_capture(*a, **k))
    acc = _tiny_train(tmp_path, steps=2)
    assert not lowered  # capture never invoked while telemetry is off
    assert acc._trace_windows is None  # no window driver without config/env
    assert not list(tmp_path.iterdir())  # nothing written anywhere


# ------------------------------------------------------------ xplane parsing --


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(fnum, wt):
    return _varint((fnum << 3) | wt)


def _ld(fnum, payload):
    return _tag(fnum, 2) + _varint(len(payload)) + payload


def _vi(fnum, value):
    return _tag(fnum, 0) + _varint(value)


def _encode_xspace(planes):
    """planes: [(plane_name, [(line_name, [(op, start_ms, dur_ms)]) |
    (line_name, line_ts_ms, [(op, start_ms, dur_ms)])])] — hand-built XSpace
    wire bytes, the parser's ground-truth fixture. Event starts are relative
    to their line's timestamp, exactly like the real schema."""
    space = b""
    for plane_name, lines in planes:
        meta_ids = {}
        plane = _ld(2, plane_name.encode())
        events_by_line = []
        for line in lines:
            line_name, line_ts_ms, events = line if len(line) == 3 else (line[0], 0.0, line[1])
            evs = b""
            for op, start_ms, dur_ms in events:
                mid = meta_ids.setdefault(op, len(meta_ids) + 1)
                # proto3 writers OMIT zero-valued varints: an event at the
                # line epoch has no offset field on the wire — encode the
                # same way so the fixture exercises the parser's default
                offset = b"" if start_ms == 0 else _vi(2, int(start_ms * 1e9))
                evs += _ld(4, _vi(1, mid) + offset + _vi(3, int(dur_ms * 1e9)))
            ts = b"" if line_ts_ms == 0 else _vi(3, int(line_ts_ms * 1e6))  # ns
            events_by_line.append(_ld(2, line_name.encode()) + ts + evs)
        for mid_name, mid in meta_ids.items():
            entry = _vi(1, mid) + _ld(2, _vi(1, mid) + _ld(2, mid_name.encode()))
            plane += _ld(4, entry)
        for line in events_by_line:
            plane += _ld(3, line)
        space += _ld(1, plane)
    return space


def _write_fixture(tmp_path, planes):
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_encode_xspace(planes))
    return str(tmp_path)


def test_xplane_fixture_overlap_and_topk(tmp_path):
    # device plane: compute [0,10]+[14,20]+[24,26] ms, collective [8,16] ms
    # -> collective 8ms, overlapped [8,10]+[14,16] = 4ms -> ratio 0.5;
    # busy union [0,20]+[24,26] = 22ms over a 26ms span -> idle 4ms
    trace_dir = _write_fixture(tmp_path, [
        ("/device:TPU:0", [
            ("stream1", [("fusion.1", 0.0, 10.0), ("fusion.2", 14.0, 6.0),
                         ("fusion.1", 24.0, 2.0)]),
            ("stream2", [("all-reduce.3", 8.0, 8.0)]),
        ]),
        # a host plane next to a device plane is ignored entirely
        ("/host:CPU", [("python", [("$train.py:1 step", 0.0, 26.0)])]),
    ])
    out = xplane.summarize_trace(trace_dir)
    assert out["events"] == 4 and out["ops"] == 3
    assert out["compute_s"] == pytest.approx(18e-3)
    assert out["collective_s"] == pytest.approx(8e-3)
    assert out["collective_overlap_s"] == pytest.approx(4e-3)
    assert out["comms_overlap_ratio"] == pytest.approx(0.5)
    assert out["busy_s"] == pytest.approx(22e-3)
    assert out["idle_s"] == pytest.approx(4e-3)
    assert out["span_s"] == pytest.approx(26e-3)
    top = out["top_ops"]
    assert top[0]["op"] == "fusion.1" and top[0]["count"] == 2
    assert top[0]["total_s"] == pytest.approx(12e-3)
    collective_ops = [t for t in top if t["collective"]]
    assert [t["op"] for t in collective_ops] == ["all-reduce.3"]


def test_xplane_lines_with_different_epochs_align(tmp_path):
    """Event offsets are relative to their LINE's timestamp_ns; lines
    (streams/queues) of one trace carry different epochs. The same physical
    intervals as test_xplane_fixture_overlap_and_topk, expressed with the
    collective line's epoch shifted by +8ms, must summarize identically —
    cross-line overlap is only meaningful after rebasing to absolute time."""
    trace_dir = _write_fixture(tmp_path, [
        ("/device:TPU:0", [
            ("stream1", 0.0, [("fusion.1", 0.0, 10.0), ("fusion.2", 14.0, 6.0),
                              ("fusion.1", 24.0, 2.0)]),
            # absolute [8,16]ms, written as offset 0 from an 8ms line epoch
            ("stream2", 8.0, [("all-reduce.3", 0.0, 8.0)]),
        ]),
    ])
    out = xplane.summarize_trace(trace_dir)
    assert out["collective_overlap_s"] == pytest.approx(4e-3)
    assert out["comms_overlap_ratio"] == pytest.approx(0.5)
    assert out["idle_s"] == pytest.approx(4e-3)


def test_xplane_device_envelope_lines_excluded(tmp_path):
    """Real TPU device planes carry 'Steps'/'XLA Modules' envelope lines
    whose events span whole steps — counting them as compute would cover
    every collective interval and fake comms_overlap_ratio ≈ 1.0. Only the
    op-level 'XLA Ops' (+ async) lines may feed the accounting."""
    trace_dir = _write_fixture(tmp_path, [
        ("/device:TPU:0", [
            # envelope lines: one event covering the whole 30ms step
            ("Steps", [("1", 0.0, 30.0)]),
            ("XLA Modules", [("jit_train_step(1)", 0.0, 30.0)]),
            # the real ops: compute [0,10], collective [12,20] — ZERO overlap
            ("XLA Ops", [("fusion.1", 0.0, 10.0)]),
            ("XLA Async Ops", [("all-reduce.2", 12.0, 8.0)]),
        ]),
    ])
    out = xplane.summarize_trace(trace_dir)
    assert out["events"] == 2  # envelopes excluded entirely
    assert out["compute_s"] == pytest.approx(10e-3)
    assert out["collective_s"] == pytest.approx(8e-3)
    assert out["comms_overlap_ratio"] == pytest.approx(0.0)  # not a fake 1.0
    assert {t["op"] for t in out["top_ops"]} == {"fusion.1", "all-reduce.2"}


def test_trace_windows_honors_both_triggers(tmp_path):
    """An env-seeded one-shot trace_at must not silently disable a periodic
    trace_every configured in code — both fire."""
    cfg = ProfileConfig(trace_every=4, trace_at=2, trace_steps=1)
    tw = xplane.TraceWindows(cfg, str(tmp_path))

    @jax.jit
    def fn(x):
        return x + 1

    x = jnp.ones((8,))
    for step in range(6):
        tw.on_step_start(step)
        fn(x).block_until_ready()
        tw.on_step_end(step)
    tw.close()
    assert [s["step_start"] for s in tw.summaries] == [2, 4]


def test_xplane_no_collectives_yields_null_ratio(tmp_path):
    trace_dir = _write_fixture(
        tmp_path, [("/device:TPU:0", [("s", [("dot.1", 0.0, 5.0)])])]
    )
    out = xplane.summarize_trace(trace_dir)
    assert out["collective_s"] == 0 and out["comms_overlap_ratio"] is None


def test_xplane_host_fallback_excludes_python_and_infra(tmp_path):
    trace_dir = _write_fixture(tmp_path, [
        ("/host:CPU", [
            ("python", [("PjitFunction(f)", 0.0, 50.0)]),
            ("tf_XLAEigen/1", [("dot.4", 0.0, 10.0),
                               ("ThunkExecutor::Execute", 0.0, 40.0),
                               ("$profiler.py:91 start_trace", 0.0, 99.0)]),
        ]),
    ])
    out = xplane.summarize_trace(trace_dir)
    assert out["events"] == 1  # only dot.4 is an op
    assert out["top_ops"][0]["op"] == "dot.4"


def test_chrome_trace_fallback(tmp_path):
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    trace = {"traceEvents": [
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 0.0, "dur": 1000.0, "name": "fusion.9"},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 1000.0, "dur": 500.0, "name": "all-gather.2"},
    ]}
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump(trace, f)
    out = xplane.summarize_trace(str(tmp_path))
    assert out["events"] == 2
    assert out["compute_s"] == pytest.approx(1000e-6)
    assert out["collective_s"] == pytest.approx(500e-6)


def test_real_cpu_trace_parses_to_ops(tmp_path):
    """End-to-end against the real jax.profiler output on this backend."""

    @jax.jit
    def fn(x, y):
        return (x @ y).sum()

    x = jnp.ones((128, 128))
    fn(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        fn(x, x).block_until_ready()
    jax.profiler.stop_trace()
    out = xplane.summarize_trace(str(tmp_path))
    assert out["files"] and out["events"] > 0 and out["busy_s"] > 0
    assert out["top_ops"]


# ------------------------------------------------------------- trace windows --


def test_trace_windows_every_n(tmp_path):
    tel.enable(str(tmp_path / "tel"))
    # 2-step windows: a 1-step CPU window can close before the XLA pool
    # threads flush their TraceMe buffers (the second step forces the flush)
    cfg = ProfileConfig(trace_every=3, trace_steps=2)
    tw = xplane.TraceWindows(cfg, str(tmp_path / "trace"))

    @jax.jit
    def fn(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))
    for step in range(8):
        tw.on_step_start(step)
        fn(x).block_until_ready()
        tw.on_step_end(step)
    tw.close()
    tel.disable()
    assert [s["step_start"] for s in tw.summaries] == [3, 6]
    assert [s["step_end"] for s in tw.summaries] == [4, 7]
    for s in tw.summaries:
        assert s["events"] > 0
        assert os.path.exists(os.path.join(s["trace_dir"], "summary.json"))
    traces = [e for e in _events(tmp_path / "tel") if e["kind"] == "trace"]
    assert len(traces) == 2 and all(t["top_ops"] for t in traces)


def test_trace_windows_one_shot(tmp_path):
    cfg = ProfileConfig(trace_at=3, trace_steps=1)
    tw = xplane.TraceWindows(cfg, str(tmp_path))

    @jax.jit
    def fn(x):
        return x + 1

    x = jnp.ones((8,))
    for step in range(6):
        tw.on_step_start(step)
        fn(x).block_until_ready()
        tw.on_step_end(step)
    tw.close()
    assert len(tw.summaries) == 1 and tw.summaries[0]["step_start"] == 3


def test_trace_windows_stand_down_when_profiler_busy(tmp_path):
    tel.enable(str(tmp_path / "tel"))
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        cfg = ProfileConfig(trace_every=1, trace_steps=1)
        tw = xplane.TraceWindows(cfg, str(tmp_path / "auto"))
        tw.on_step_start(1)
        assert tw.disabled and not tw.tracing
        tw.on_step_start(2)  # stays down, no retry storm
        assert tw.disabled
    finally:
        jax.profiler.stop_trace()
    tel.disable()
    errors = [e for e in _events(tmp_path / "tel")
              if e["kind"] == "trace" and e.get("error")]
    assert len(errors) == 1


def test_profile_config_env_seeding(monkeypatch):
    monkeypatch.setenv("ACCELERATE_TRACE_EVERY", "7")
    monkeypatch.setenv("ACCELERATE_TRACE_STEPS", "2")
    monkeypatch.setenv("ACCELERATE_TRACE_DIR", "/tmp/tracehere")
    cfg = ProfileConfig()
    assert cfg.trace_every == 7 and cfg.trace_steps == 2
    assert cfg.output_trace_dir == "/tmp/tracehere"
    assert cfg.windows_enabled
    monkeypatch.setenv("ACCELERATE_TRACE_EVERY", "garbage")
    assert ProfileConfig().trace_every == 0  # malformed env never crashes


def test_accelerator_trace_windows_emit_trace_events(tmp_path):
    tel.enable(str(tmp_path / "tel"))
    _tiny_train(
        tmp_path,
        steps=6,
        # 2-step window so the CPU pool threads flush into the session
        # before it closes (see test_trace_windows_every_n)
        handlers=[ProfileConfig(trace_every=3, trace_steps=2,
                                output_trace_dir=str(tmp_path / "prof"))],
    )
    tel.disable()
    traces = [e for e in _events(tmp_path / "tel") if e["kind"] == "trace"]
    assert len(traces) == 1  # one window spanning steps 3-4
    assert traces[0]["step_start"] == 3 and traces[0]["step_end"] == 4
    assert traces[0]["events"] > 0 and traces[0]["top_ops"]


# ---------------------------------------------------------- report section --


def _write_perf_stream(path, mfus=(0.4, 0.5, 0.6, 0.7), rank=0, with_trace=True):
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "schema": 1, "run_id": "r",
                            "process_index": rank, "num_processes": 1}) + "\n")
        f.write(json.dumps({
            "kind": "perf", "t": 0.0, "fn": "train_step", "flops": 2e9,
            "bytes_accessed": 4e7, "arithmetic_intensity": 50.0,
            "roofline": "hbm-bound", "peak_flops": 197e12,
            "peak_hbm_bytes_per_s": 819e9, "peak_source": "table",
            "device_kind": "TPU v5e"}) + "\n")
        for i, m in enumerate(mfus):
            f.write(json.dumps({
                "kind": "step", "step": i, "t": float(i), "dur_s": 0.01,
                "compile_s": 0.0, "execute_s": 0.01, "mfu": m,
                "arithmetic_intensity": 50.0, "roofline": "hbm-bound",
                "perf_fn": "train_step"}) + "\n")
        if with_trace:
            f.write(json.dumps({
                "kind": "trace", "t": 9.0, "events": 20, "ops": 4,
                "span_s": 0.1, "busy_s": 0.09, "idle_s": 0.01,
                "compute_s": 0.07, "collective_s": 0.02,
                "collective_overlap_s": 0.01, "comms_overlap_ratio": 0.5,
                "top_ops": [
                    {"op": "fusion.1", "total_s": 0.04, "count": 8,
                     "share": 0.5, "collective": False},
                    {"op": "all-reduce.7", "total_s": 0.02, "count": 4,
                     "share": 0.25, "collective": True},
                ]}) + "\n")


def test_report_performance_section_snapshot(tmp_path):
    _write_perf_stream(tmp_path / "events-rank0.jsonl")
    report = build_report([str(tmp_path)])
    p = report["performance"]
    assert p["mfu"]["count"] == 4 and p["mfu"]["p50"] == pytest.approx(0.5)
    assert p["mfu_trend"]["first_half_mean"] == pytest.approx(0.45)
    assert p["mfu_trend"]["second_half_mean"] == pytest.approx(0.65)
    assert p["mfu_trend"]["delta"] == pytest.approx(0.2)
    fn = p["by_fn"]["train_step"]
    assert fn["roofline"] == "hbm-bound" and fn["flops"] == 2e9
    assert fn["mfu"]["count"] == 4
    tr = p["trace"]
    assert tr["windows"] == 1 and tr["comms_overlap_ratio"] == pytest.approx(0.5)
    assert tr["top_ops"][0]["op"] == "fusion.1"
    text = format_report(report)
    assert "performance:" in text
    assert "MFU over 4 step(s)" in text
    assert "hbm-bound" in text
    assert "top op 1: fusion.1" in text
    assert "50.0% of collective time hidden" in text
    assert "[collective]" in text


def test_report_without_perf_records_omits_section(tmp_path):
    (tmp_path / "events-rank0.jsonl").write_text(
        json.dumps({"kind": "meta", "schema": 1, "run_id": "r", "process_index": 0}) + "\n"
        + json.dumps({"kind": "step", "step": 0, "dur_s": 0.01}) + "\n"
    )
    report = build_report([str(tmp_path)])
    assert report["performance"] is None
    assert "performance:" not in format_report(report)  # old logs still render


def test_report_by_rank_mfu_skew(tmp_path):
    _write_perf_stream(tmp_path / "events-rank0.jsonl", mfus=(0.6, 0.6), rank=0,
                       with_trace=False)
    _write_perf_stream(tmp_path / "events-rank1.jsonl", mfus=(0.3, 0.3), rank=1,
                       with_trace=False)
    report = build_report([str(tmp_path)], by_rank=True)
    ranks = report["ranks"]["per_rank"]
    assert ranks["0"]["mfu"]["p50"] == pytest.approx(0.6)
    assert ranks["1"]["mfu"]["p50"] == pytest.approx(0.3)
    text = format_report(report)
    assert "mfu p50=0.6000" in text and "mfu p50=0.3000" in text


# -------------------------------------------------------- memory projection --


def test_memory_projection_warns_on_overcommit(tmp_path, monkeypatch):
    from accelerate_tpu.telemetry import memory

    monkeypatch.setattr(
        memory, "device_memory_stats",
        lambda: [{"device": 0, "kind": "TPU v5e", "bytes_limit": 800}],
    )
    tel.enable(str(tmp_path))
    # args 600 + outputs 600 + temps 300 - aliased(donated) 600 = 900 > 800
    analysis = {"argument_bytes": 600, "output_bytes": 600, "temp_bytes": 300,
                "alias_bytes": 600}
    with pytest.warns(UserWarning, match="expect an OOM"):
        rec = memory.check_memory_fit("big_step", analysis)
    assert rec["projected_peak_bytes"] == 900 and rec["fits"] is False
    tel.disable()  # flush before reading the stream back
    events = _events(tmp_path)
    proj = [e for e in events if e["kind"] == "memory_projection"]
    assert proj and proj[0]["fn"] == "big_step"


def test_memory_projection_fits_no_warning(tmp_path, monkeypatch):
    import warnings as _warnings

    from accelerate_tpu.telemetry import memory

    monkeypatch.setattr(
        memory, "device_memory_stats",
        lambda: [{"device": 0, "kind": "TPU v5e", "bytes_limit": 10_000}],
    )
    tel.enable(str(tmp_path))
    analysis = {"argument_bytes": 600, "output_bytes": 600, "temp_bytes": 300,
                "alias_bytes": 600}
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        rec = memory.check_memory_fit("ok_step", analysis)
    assert rec["fits"] is True and rec["projected_peak_bytes"] == 900
    tel.disable()  # flush before reading the stream back
    events = _events(tmp_path)
    assert any(e["kind"] == "memory_projection" for e in events)
