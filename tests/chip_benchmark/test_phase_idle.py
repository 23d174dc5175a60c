"""``benchmarks/chip/phase_idle.py``: the device's idle time by the engine phase
the host was in, on hand-built spans whose answer is worked out in the
comments, and on ``fixtures/v5e_named_kernels_sample.json`` against a 1 us
boolean timeline built here with numpy, not with the module's interval
arithmetic. The fixture is read, never edited."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.chip import phase_idle, trace_reduce  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
MS = 1_000_000  # ns


def _hand_built():
    """One device plane, a window of 100 ms, busy 10-20 (a prefill chunk),
    30-50 and 60-70 (a decode program); idle 0-10, 20-30, 50-60, 70-100.

    outside     0-2, 96-100                      6
    admit       2-4                              2
    prefill     4-6 and 25-27 (its own)          4
    prefill_call 6-10                            4
    prefill_sync 12-25: the chunk ended at 20, so 20-25 is after_device   5
    build       27-30                            3
    dispatch    31-33 (busy)                     0
    fetch       33-90: the last operation to start before 90 ends at 70:
                50-60 is fetch 10, 70-90 fetch.after_device 20
    emit        90-94                            4
    step        94-96 (its own)                  2
    """
    devices = [[("op.a", 10 * MS, 20 * MS), ("op.b", 30 * MS, 50 * MS), ("op.c", 60 * MS, 70 * MS)]]
    spans = [("step", 2, 96), ("admit", 2, 4), ("prefill", 4, 27), ("prefill_call", 6, 12),
             ("prefill_sync", 12, 25), ("build", 27, 31), ("dispatch", 31, 33), ("fetch", 33, 90),
             ("emit", 90, 94)]
    annotations = [("cb.window", 0, 100 * MS), ("cb.engine_step", 2 * MS, 96 * MS),
                   ("atpu.serve.emit", -10 * MS, -2 * MS)]  # before the window: not read
    annotations += [("atpu.serve." + n, s * MS, e * MS) for n, s, e in spans]
    return devices, annotations


HAND_BUILT = {"outside": 6, "admit": 2, "prefill": 4, "prefill_call": 4, "prefill_sync": 0,
              "prefill_sync.after_device": 5, "build": 3, "dispatch": 0, "fetch": 10,
              "fetch.after_device": 20, "emit": 4, "step": 2}


def test_phase_idle_on_hand_built_nested_spans():
    devices, annotations = _hand_built()
    idle = phase_idle.phase_idle(devices, annotations)
    assert idle == pytest.approx({k: v / 1e3 for k, v in HAND_BUILT.items()}, abs=1e-12)
    reduced = trace_reduce.reduce(devices, [a for a in annotations if a[0].startswith("cb.")])
    assert sum(idle.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-12)
    trace = {**reduced, "phase_idle": idle}
    # host 2 + 4 + 3 + 4 + 2, launch 4 + 0 + 10 + 0, return 5 + 20: of 100 ms, with outside 6
    shares = {cause: phase_idle.share(trace, cause) for cause in phase_idle.CAUSES}
    assert shares == pytest.approx({"host": 15.0, "launch": 14.0, "return": 25.0}, rel=1e-12)
    assert sum(shares.values()) + 100 * idle["outside"] / reduced["window_s"] == pytest.approx(
        100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"]), rel=1e-12)


def test_phase_idle_averages_the_planes_and_the_outer_span_opens_first_at_a_tie():
    devices, annotations = _hand_built()
    busy = [("op.whole", 0, 100 * MS)]  # a second plane with no idle time
    idle = phase_idle.phase_idle(devices + [busy], annotations)
    assert idle == pytest.approx({k: v / 2e3 for k, v in HAND_BUILT.items()}, abs=1e-12)
    # a prefill and its first call open at one instant: the call is the inner
    ties = [("cb.window", 0, 30 * MS), ("atpu.serve.prefill", 0, 10 * MS),
            ("atpu.serve.prefill_call", 0, 5 * MS), ("atpu.serve.prefill_sync", 5 * MS, 5 * MS)]
    idle = phase_idle.phase_idle([[("op", 20 * MS, 30 * MS)]], ties)
    assert idle == pytest.approx({"prefill_call": 5e-3, "prefill": 5e-3, "outside": 10e-3,
                                  "prefill_sync": 0.0}, abs=1e-12)


@pytest.fixture(scope="module")
def named_sample():
    sample = json.load(open(os.path.join(FIXTURES, "v5e_named_kernels_sample.json")))
    return [[tuple(op) for op in sample["device_ops"]]], [tuple(a) for a in sample["annotations"]]


def test_reduction_keeps_every_key_it_had_and_adds_phase_idle(named_sample):
    """What a file's reduction returns today (``read_planes`` keeps only the
    runner's ``cb.*`` spans) is every key of ``reduce_planes``' result to the
    last digit, with ``phase_idle`` beside them."""
    devices, annotations = named_sample
    today = trace_reduce.reduce(devices, [a for a in annotations if a[0].startswith("cb.")])
    reduced = phase_idle.reduce_planes(devices, annotations)
    assert set(reduced) == set(today) | {"phase_idle"}
    assert {k: v for k, v in reduced.items() if k != "phase_idle"} == today
    assert reduced["phase_idle"] == phase_idle.phase_idle(devices, annotations)


def _timeline_idle(devices, annotations):
    """The same attribution on a 1 us boolean timeline: ``owner[t]`` is the
    latest-starting phase open at ``t`` (spans painted in start order, the
    outer first at a tie), a wait's ``after_device`` part painted from the end
    of the last operation that started before the wait ended."""
    (lo, hi), = [(s, e) for n, s, e in annotations if n == "cb.window"]
    busy = np.zeros(hi - lo, bool)
    for _, s, e in devices[0]:
        busy[max(s, lo) - lo:min(e, hi) - lo] = True
    spans = sorted(((n[len("atpu.serve."):], max(s, lo) - lo, min(e, hi) - lo)
                    for n, s, e in annotations
                    if n.startswith("atpu.serve.") and e > lo and s < hi),
                   key=lambda span: (span[1], -span[2]))
    names = ["outside"] + sorted({n for n, _, _ in spans} | {
        w + ".after_device" for w in ("fetch", "prefill_sync")})
    owner = np.zeros(hi - lo, int)  # an index into names
    starts = np.array([max(s, lo) - lo for _, s, _ in devices[0]])
    ends = np.array([min(e, hi) - lo for _, _, e in devices[0]])
    for name, s, e in spans:
        owner[s:e] = names.index(name)
        if name in ("fetch", "prefill_sync"):
            started = starts < e
            done = ends[started].max() if started.any() else 0
            owner[max(s, done):e] = names.index(name + ".after_device")
    counts = np.bincount(owner[~busy], minlength=len(names))
    return {n: c * 1e-6 for n, c in zip(names, counts)}


def test_phase_idle_on_the_v5e_sample_against_a_boolean_timeline(named_sample):
    """Times cut to whole microseconds first, so that the timeline is exact."""
    devices, annotations = named_sample
    us = [[(n, round(s / 1e3), round(e / 1e3)) for n, s, e in devices[0]]]
    ann_us = [(n, round(s / 1e3), round(e / 1e3)) for n, s, e in annotations]
    expect = _timeline_idle(us, ann_us)
    found = phase_idle.phase_idle([[(n, s * 1000, e * 1000) for n, s, e in us[0]]],
                                  [(n, s * 1000, e * 1000) for n, s, e in ann_us])
    assert set(found) <= set(expect)
    assert {n: found.get(n, 0.0) for n in expect} == pytest.approx(expect, abs=1e-12)
    assert expect["fetch.after_device"] > 0 and expect["prefill"] > 0
    # and on the recorded nanoseconds, the idle time whole
    reduced = phase_idle.reduce_planes(devices, annotations)
    assert sum(reduced["phase_idle"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)


@pytest.mark.parametrize("cause", sorted(phase_idle.CAUSES))
def test_share_finds_nothing_rather_than_something_wrong(named_sample, cause):
    """None without a trace, without ``phase_idle`` (the harness as it is),
    and on a slice that holds a ``prefill`` but no ``prefill_sync``: the
    program before that span, whose prefill's own time holds its calls and
    its sync. The v5e sample is such a slice."""
    devices, annotations = named_sample
    assert phase_idle.share(None, cause) is None
    today = trace_reduce.reduce(devices, [a for a in annotations if a[0].startswith("cb.")])
    assert phase_idle.share(today, cause) is None
    old_program = phase_idle.reduce_planes(devices, annotations)
    assert "prefill" in old_program["phase_idle"]
    assert phase_idle.share(old_program, cause) is None
    devices, annotations = _hand_built()
    assert phase_idle.share(phase_idle.reduce_planes(devices, annotations), cause) > 0
    assert phase_idle.phase_idle(devices, [a for a in annotations if a[0] != "cb.window"]) is None
    assert phase_idle.phase_idle([], annotations) is None


def test_read_planes_keeps_what_trace_reduce_reads_and_adds_the_phases(tmp_path):
    """On a CPU profiler trace: the ``cb.*`` spans are ``trace_reduce``'s to
    the nanosecond, and the phases come with their names alone."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("cb.window"):
            with jax.profiler.TraceAnnotation("atpu.serve.step", engine=3, step=7):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    devices, annotations = phase_idle.read_planes(path)
    assert (devices, [a for a in annotations if a[0].startswith("cb.")]) == (
        trace_reduce.read_planes(path))
    assert [a[0] for a in annotations if not a[0].startswith("cb.")] == ["atpu.serve.step"]
    assert phase_idle.read_planes(path, ("cb.",)) == trace_reduce.read_planes(path)
