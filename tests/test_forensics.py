"""Hang/crash forensics: flight-recorder ring + dumps, watchdog stall
detection (heartbeat sources and blocked phases), collective annotations,
signal post-mortems, cross-rank straggler reporting, the bench probe's
flight artifact, and the zero-cost disabled path."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import pytest

from accelerate_tpu import Accelerator, DataLoader, telemetry as tel
from accelerate_tpu.telemetry import events as tel_events
from accelerate_tpu.telemetry import flight_recorder, watchdog
from accelerate_tpu.telemetry.report import build_report, format_report, main as report_main
from accelerate_tpu.utils import operations as ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")


@pytest.fixture(autouse=True)
def _forensics_clean(monkeypatch):
    for var in (
        "ACCELERATE_TELEMETRY",
        "ACCELERATE_TELEMETRY_DIR",
        "ACCELERATE_WATCHDOG_TIMEOUT",
        "ACCELERATE_WATCHDOG_INTERVAL",
        "ACCELERATE_WATCHDOG_ABORT",
        "ACCELERATE_FLIGHT",
        "ACCELERATE_FLIGHT_DIR",
        "ACCELERATE_RUN_ID",
    ):
        monkeypatch.delenv(var, raising=False)
    yield
    watchdog.stop()
    flight_recorder.uninstall()
    rec = flight_recorder.get_recorder()
    rec.events.clear()
    rec.step = None
    rec.out_dir = None
    tel.disable()


def _subprocess_env():
    return {**os.environ, "JAX_PLATFORMS": "cpu", "ACCELERATE_TELEMETRY": "",
            "ACCELERATE_WATCHDOG_TIMEOUT": ""}


# ------------------------------------------------------------ flight recorder


def test_flight_ring_keeps_last_n_and_dump_has_stacks(tmp_path):
    rec = flight_recorder.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("tick", i=i)
    assert [e["i"] for e in rec.snapshot()] == list(range(12, 20))
    rec.step = 41
    rec.record("with_step")
    assert rec.snapshot()[-1]["step"] == 41
    path = rec.dump("unit test", out_dir=str(tmp_path))
    assert path == str(tmp_path / "flight-rank0.json")
    data = json.load(open(path))
    assert data["reason"] == "unit test" and data["schema"] == 1
    assert data["step"] == 41
    assert data["meta"]["pid"] == os.getpid() and "hostname" in data["meta"]
    # this test's own frame must appear in the all-thread stacks
    assert any(
        "test_flight_ring_keeps_last_n_and_dump_has_stacks" in "".join(t["stack"])
        for t in data["threads"]
    )
    assert data["memory"] is None or "host_rss_bytes" in data["memory"]


def test_flight_phase_nesting_and_current_phases():
    rec = flight_recorder.get_recorder()
    rec.events.clear()
    with flight_recorder.phase("outer"):
        with flight_recorder.phase("collective:gather", op="gather"):
            phases = flight_recorder.current_phases()
            me = phases[threading.current_thread().name]
            assert me["phase"] == "collective:gather" and me["op"] == "gather"
            assert me["age_s"] >= 0
    assert flight_recorder.current_phases() == {}
    kinds = [(e["kind"], e.get("name")) for e in rec.snapshot()]
    assert kinds == [
        ("phase_enter", "outer"),
        ("phase_enter", "collective:gather"),
        ("phase_exit", "collective:gather"),
        ("phase_exit", "outer"),
    ]


def test_collectives_are_phase_annotated():
    rec = flight_recorder.get_recorder()
    rec.events.clear()
    ops.gather(jnp.ones((4,)))
    ops.reduce(jnp.ones((4,)), "mean")
    names = [e.get("name") for e in rec.snapshot() if e["kind"] == "phase_enter"]
    assert "collective:gather" in names and "collective:reduce" in names
    exits = [e for e in rec.snapshot() if e["kind"] == "phase_exit"]
    assert all(e["dur_s"] >= 0 for e in exits)


def test_sigterm_dump_subprocess(tmp_path):
    out = str(tmp_path)
    # a real file (not -c) so the dumped stacks carry source lines
    script = tmp_path / "victim.py"
    script.write_text(
        "import os, signal, sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from accelerate_tpu.telemetry import flight_recorder\n"
        f"flight_recorder.install(out_dir={out!r})\n"
        "for i in range(5):\n"
        "    flight_recorder.record('work', i=i)\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "time.sleep(10)\n"  # not reached: the handler chains to SIG_DFL
    )
    res = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=60, env=_subprocess_env(),
    )
    assert res.returncode == -signal.SIGTERM, (res.returncode, res.stderr[-2000:])
    data = json.load(open(tmp_path / "flight-rank0.json"))
    assert data["reason"] == "signal SIGTERM"
    assert [e["i"] for e in data["events"] if e["kind"] == "work"] == list(range(5))
    assert data["threads"] and any("os.kill" in "".join(t["stack"]) for t in data["threads"])


def test_hard_flush_survives_held_event_log_lock(tmp_path):
    """A SIGTERM can interrupt a frame that holds the EventLog lock (emit
    flushes every 64 events); the crash-path flush must time out and let the
    process die with its dump instead of deadlocking on itself."""
    log = tel_events.EventLog(str(tmp_path))
    log.emit("before")
    with log._lock:  # simulate the interrupted lock-holding frame
        t0 = time.monotonic()
        log.hard_flush()  # must return (bounded acquire), not deadlock
        assert time.monotonic() - t0 < 10
    log.hard_flush()  # lock free again: the buffered event lands, fsynced
    records = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
    assert [r["kind"] for r in records] == ["meta", "before"]
    log.close()


# ------------------------------------------------------------------ watchdog


def test_watchdog_dumps_when_heartbeat_source_stalls(tmp_path):
    wd = watchdog.start(timeout=0.4, interval=0.1, out_dir=str(tmp_path))
    wd.register("fake_producer", depth=2)
    wd.beat("fake_producer", batch=3)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not wd.dump_paths:
        time.sleep(0.05)
    assert wd.dump_paths, "no stall dump within 5s"
    data = json.load(open(wd.dump_paths[0]))
    assert "source 'fake_producer' stalled" in data["reason"]
    assert data["watchdog"]["stalls"][0]["batch"] == 3
    # one dump per stall episode, not one per tick
    count = wd.stall_count
    time.sleep(0.4)
    assert wd.stall_count == count
    # a beat ends the episode and re-arms detection
    wd.beat("fake_producer", batch=4)
    while time.monotonic() < deadline and wd.stall_count == count:
        time.sleep(0.05)
    assert wd.stall_count == count + 1


def test_watchdog_names_the_phase_a_thread_is_stuck_in(tmp_path):
    wd = watchdog.start(timeout=0.3, interval=0.1, out_dir=str(tmp_path))
    release = threading.Event()

    def _stuck():
        with flight_recorder.phase("collective:fake_gather", op="gather"):
            release.wait(8.0)

    worker = threading.Thread(target=_stuck, name="stuck-worker", daemon=True)
    worker.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not wd.dump_paths:
        time.sleep(0.05)
    release.set()
    worker.join()
    assert wd.dump_paths
    data = json.load(open(wd.dump_paths[0]))
    assert "phase 'collective:fake_gather' stalled" in data["reason"]
    assert "stuck-worker" in data["reason"]
    assert data["phases"]["stuck-worker"]["phase"] == "collective:fake_gather"
    assert any("release.wait" in "".join(t["stack"]) for t in data["threads"])


def test_hang_inside_fake_collective_end_to_end(tmp_path):
    """Acceptance: an injected hang inside a fake collective produces
    flight-rank0.json naming the stuck collective, with all-thread stacks,
    within the watchdog timeout — and the hard-flushed JSONL stream carries
    the heartbeat/stall records for the by-rank report."""
    out = str(tmp_path)
    script = tmp_path / "hang.py"  # a real file so stacks carry source lines
    script.write_text(
        "import os, sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from accelerate_tpu.telemetry import events, flight_recorder, watchdog\n"
        f"events.enable({out!r})\n"
        "events.emit('custom', note='pre-hang')\n"
        f"flight_recorder.install(out_dir={out!r})\n"
        f"watchdog.start(timeout=1.0, interval=0.2, abort_on_stall=True, out_dir={out!r})\n"
        "flight_recorder.set_step(7)\n"
        "with flight_recorder.phase('collective:gather', op='gather'):\n"
        "    time.sleep(60)\n"
    )
    t0 = time.monotonic()
    res = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=45, env=_subprocess_env(),
    )
    wall = time.monotonic() - t0
    assert res.returncode == watchdog.ABORT_EXIT_CODE, (res.returncode, res.stderr[-2000:])
    assert wall < 40, f"abort took {wall:.1f}s"
    data = json.load(open(tmp_path / "flight-rank0.json"))
    assert "phase 'collective:gather' stalled" in data["reason"]
    assert data["step"] == 7
    assert data["phases"]["MainThread"]["phase"] == "collective:gather"
    assert data["phases"]["MainThread"]["op"] == "gather"
    stacks = ["".join(t["stack"]) for t in data["threads"]]
    assert any("time.sleep" in s for s in stacks)  # the hung main thread
    assert len(data["threads"]) >= 2  # ... and the watchdog thread itself
    # the EventLog was hard-flushed by the dump: nothing buffered was lost
    records = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
    kinds = {r["kind"] for r in records}
    assert {"custom", "heartbeat", "watchdog_stall"} <= kinds
    stall = [r for r in records if r["kind"] == "watchdog_stall"][-1]
    assert "collective:gather" in stall["reason"]
    # and the report merges the flight record into the by-rank view
    report = build_report([out], by_rank=True)
    flights = report["ranks"]["flight_records"]
    assert flights and "collective:gather" in flights[0]["reason"]


def test_watchdog_env_seeding(tmp_path, monkeypatch):
    from accelerate_tpu.utils.dataclasses import WatchdogConfig

    assert not WatchdogConfig().enabled
    monkeypatch.setenv("ACCELERATE_WATCHDOG_TIMEOUT", "150")
    monkeypatch.setenv("ACCELERATE_WATCHDOG_ABORT", "1")
    cfg = WatchdogConfig()
    assert cfg.enabled and cfg.timeout == 150.0 and cfg.abort_on_stall
    monkeypatch.setenv("ACCELERATE_WATCHDOG_TIMEOUT", "not-a-number")
    assert not WatchdogConfig().enabled  # malformed env never crashes startup
    assert watchdog.env_timeout() == 0.0


def test_accelerator_starts_and_stops_watchdog(tmp_path, monkeypatch):
    monkeypatch.setenv("ACCELERATE_WATCHDOG_TIMEOUT", "60")
    monkeypatch.setenv("ACCELERATE_FLIGHT_DIR", str(tmp_path))
    acc = Accelerator()
    wd = watchdog.get_watchdog()
    assert wd is not None and wd.running and wd.timeout == 60.0
    assert flight_recorder.installed()
    acc.end_training()
    assert watchdog.get_watchdog() is None


# --------------------------------------------------------- disabled-path cost


@pytest.mark.smoke
def test_forensics_disabled_path_no_thread_no_file(tmp_path, monkeypatch):
    """Default runs pay nothing: no watchdog thread, no handler, no file —
    the hot-path helpers are a single flag check."""
    monkeypatch.chdir(tmp_path)
    before = {t.name for t in threading.enumerate()}
    assert watchdog.maybe_start_from_env() is None
    acc = Accelerator()
    assert watchdog.get_watchdog() is None
    assert not flight_recorder.installed()
    watchdog.beat("anything", step=1)  # no-ops, no registration anywhere
    watchdog.register("anything")
    watchdog.unregister("anything")
    after = {t.name for t in threading.enumerate()}
    assert "accelerate-tpu-watchdog" not in after - before
    # nothing opened a file: no telemetry/flight/watchdog artifacts in cwd
    assert not list(tmp_path.iterdir())
    del acc


# ------------------------------------------------------------------- report


def test_report_header_surfaces_per_rank_counts_and_dropped(tmp_path):
    (tmp_path / "events-rank0.jsonl").write_text(
        json.dumps({"kind": "meta", "schema": 1, "run_id": "r", "process_index": 0}) + "\n"
        + json.dumps({"kind": "step", "step": 0, "dur_s": 0.01}) + "\n"
    )
    (tmp_path / "events-rank1.jsonl").write_text(
        json.dumps({"kind": "meta", "schema": 1, "run_id": "r", "process_index": 1}) + "\n"
        + json.dumps({"kind": "dropped", "count": 42}) + "\n"
    )
    report = build_report([str(tmp_path)])
    assert report["per_rank_events"] == {
        "0": {"events": 2, "dropped": 0},
        "1": {"events": 2, "dropped": 42},
    }
    assert report["dropped_events"] == 42
    text = format_report(report)
    assert "events by rank: rank0=2, rank1=2" in text
    assert "WARNING: 42 event(s) DROPPED" in text and "rank1=42" in text


def _write_straggler_streams(out_dir: str) -> None:
    """Synthetic two-rank run: rank 1 is 3x slower on every step and has a
    3s heartbeat gap; its flight record names a stuck gather. Timestamps are
    fixed so the rendered report is byte-deterministic (golden file)."""
    for rank, scale, beat_ts in ((0, 1.0, [0, 1, 2, 3, 4]), (1, 3.0, [0, 1, 4])):
        lines = [
            json.dumps({"kind": "meta", "schema": 1, "run_id": "straggle",
                        "process_index": rank, "num_processes": 2})
        ]
        for s in range(10):
            lines.append(json.dumps({"kind": "step", "step": s, "t": float(s),
                                     "dur_s": round(0.010 * scale, 6)}))
        for t in beat_ts:
            lines.append(json.dumps({"kind": "heartbeat", "t": float(t),
                                     "sources": {"train_step": 0.1}}))
        with open(os.path.join(out_dir, f"events-rank{rank}.jsonl"), "w") as f:
            f.write("\n".join(lines) + "\n")
    with open(os.path.join(out_dir, "flight-rank1.json"), "w") as f:
        json.dump(
            {
                "kind": "flight_record",
                "schema": 1,
                "reason": "watchdog: phase 'collective:gather' stalled for 12.0s "
                          "in thread MainThread (timeout 5s)",
                "step": 7,
                "meta": {"process_index": 1},
                "phases": {"MainThread": {"phase": "collective:gather", "age_s": 12.0}},
                "events": [],
                "threads": [],
            },
            f,
        )


def test_by_rank_report_identifies_straggler(tmp_path):
    _write_straggler_streams(str(tmp_path))
    report = build_report([str(tmp_path)], by_rank=True)
    ranks = report["ranks"]
    assert ranks["steps_compared"] == 10
    assert ranks["straggler"] == {
        "rank": 1, "slowest_steps": 10, "steps_compared": 10, "mean_excess_s": 0.02,
    }
    assert ranks["skew_s"]["p50"] == 0.02 and ranks["skew_s"]["count"] == 10
    assert ranks["slowest_counts"] == {"1": 10}
    assert ranks["per_rank"]["0"]["steps"] == 10
    assert ranks["per_rank"]["1"]["wall_s"]["p50"] == 0.03
    assert ranks["heartbeat_gaps"]["0"]["max_gap_s"] == 1.0
    assert ranks["heartbeat_gaps"]["1"]["max_gap_s"] == 3.0
    flights = ranks["flight_records"]
    assert flights[0]["rank"] == 1 and flights[0]["step"] == 7
    assert flights[0]["phases"]["MainThread"]["phase"] == "collective:gather"


def test_by_rank_report_matches_golden(tmp_path, capsys):
    """Golden-file test over the synthetic straggler scenario: the rendered
    per-rank section is byte-stable. Regenerate after an intentional format
    change with: python tests/test_forensics.py regen"""
    _write_straggler_streams(str(tmp_path))
    assert report_main(["report", str(tmp_path), "--by-rank"]) == 0
    out = capsys.readouterr().out
    section = out[out.index("per-rank stragglers:"):]
    golden = open(os.path.join(GOLDEN, "straggler_report.txt")).read()
    assert section == golden


def test_report_cli_json_includes_ranks(tmp_path, capsys):
    _write_straggler_streams(str(tmp_path))
    assert report_main(["report", str(tmp_path), "--json", "--by-rank"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["ranks"]["straggler"]["rank"] == 1
    # without the flag the section is absent (and the report stays driver-stable)
    assert report_main(["report", str(tmp_path), "--json"]) == 0
    assert "ranks" not in json.loads(capsys.readouterr().out)


@pytest.mark.slow  # the full doctor is minutes of subprocess e2e on a small
# box (fused-zero1 8-device compile child, elastic supervisor children, two
# serving engines + two router replicas, all warmed); `make doctor` runs the
# same thing as its own CI lane, so the timed tier-1 window doesn't pay twice
def test_doctor_self_checks(capsys):
    from accelerate_tpu.telemetry.report import run_doctor

    assert run_doctor() == 0
    out = capsys.readouterr().out
    # dump + stall + straggler + collective divergence + jaxlint
    # + perf cost capture + xplane trace parse + performance report (ISSUE 7)
    # + fused zero1 lint/compiled-collectives (ISSUE 9)
    # + elastic auto-resume (ISSUE 10)
    # + serving engine (ISSUE 11)
    # + replicated serving router (ISSUE 12)
    # + persistent compile cache (ISSUE 13)
    # + prefix cache + COW (ISSUE 14 — the count was left at 14 when that
    #   check landed; fixed here)
    # + observability plane (ISSUE 15)
    # + disaggregated serving (ISSUE 16)
    # + goodput ledger (ISSUE 17)
    # + speculative decoding (ISSUE 18)
    # + live observability plane (ISSUE 19)
    # + fp8 fused zero1 train step (ISSUE 20)
    assert out.count("PASS") == 21 and "FAIL" not in out
    assert "static analyzer (jaxlint)" in out and "collective divergence" in out
    assert "goodput ledger" in out
    assert "speculative decoding" in out
    assert "perf cost capture" in out and "xplane trace parse" in out
    assert "serving engine" in out
    assert "replicated serving router" in out
    assert "fused zero1 compiled collectives" in out
    assert "performance report section" in out
    assert "elastic auto-resume" in out
    assert "persistent compile cache" in out
    assert "prefix cache + COW" in out
    assert "observability plane" in out
    assert "live observability plane" in out
    assert "fp8 fused zero1 train step" in out


# ------------------------------------------------------- integration hookups


@pytest.mark.slow  # pays a full loader-prepare compile (~4s); test_slow shard
def test_prefetch_producer_registers_and_unregisters(tmp_path):
    import numpy as np

    wd = watchdog.start(timeout=60, interval=0.05, out_dir=str(tmp_path))
    acc = Accelerator()
    # enough batches that the bounded queue (depth 2) keeps the producer
    # alive — and registered — while the consumer holds the first batch; a
    # 3-batch epoch let the producer finish and unregister (from its own
    # exit path, by design) before the assertion below could observe it
    data = [{"x": np.ones((4,), np.float32)} for _ in range(240)]
    dl = acc.prepare(DataLoader(data, batch_size=8))
    it = iter(dl)
    next(it)
    sources = wd.sources()
    producer = [s for s in sources if s.startswith("prefetch_producer@")]
    assert producer, sources
    assert "batch" in sources[producer[0]] or "depth" in sources[producer[0]]
    it.close()  # clean shutdown must unregister (not a stall)
    assert not [s for s in wd.sources() if s.startswith("prefetch_producer@")]


def test_train_step_beats_watchdog(tmp_path):
    import numpy as np
    import optax

    wd = watchdog.start(timeout=60, interval=10, out_dir=str(tmp_path))
    acc = Accelerator()
    params = {"w": jnp.ones((4,))}
    optimizer = optax.sgd(1e-2)
    params, optimizer = acc.prepare(params, optimizer)
    step = acc.prepare_train_step(lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2), optimizer)
    batch = {"x": jnp.ones((8, 4))}
    params, opt_state, _ = step(params, optimizer.opt_state, batch)
    assert wd.sources()["train_step"]["step"] == 0
    assert flight_recorder.get_recorder().step == 0
    params, opt_state, _ = step(params, opt_state, batch)
    assert wd.sources()["train_step"]["step"] == 1


if __name__ == "__main__" and "regen" in sys.argv:
    # regenerate the golden straggler report after an intentional format change
    import io
    import tempfile
    from contextlib import redirect_stdout

    with tempfile.TemporaryDirectory() as tmp:
        _write_straggler_streams(tmp)
        buf = io.StringIO()
        with redirect_stdout(buf):
            report_main(["report", tmp, "--by-rank"])
        out = buf.getvalue()
        os.makedirs(GOLDEN, exist_ok=True)
        with open(os.path.join(GOLDEN, "straggler_report.txt"), "w") as f:
            f.write(out[out.index("per-rank stragglers:"):])
    print("regenerated", os.path.join(GOLDEN, "straggler_report.txt"))
