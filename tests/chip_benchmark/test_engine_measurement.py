"""The per-layer metrics that read the serving engine's own measurement (PR 25):
the program-span readers against a ring built by hand, with the expected
numbers worked out by hand in the comments, and the two kernel-share readers
against ``fixtures/v5e_named_kernels_sample.json``, a stretch of a recorded v5e
trace of ``mistral-7b.chat-r80`` in which the Pallas kernels carry their names.

Nothing here is a device number measured by the test: the fixture's times were
recorded on the chip, the ring's are invented."""

import collections
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import harness, program_spans, trace_reduce  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
MS = 1_000_000  # ns

SPAN_READERS = ("host_gap_ms.serve", "decode_phase_ms.serve", "prefill_phase_ms_per_ktok.serve",
                "queue_wait_p95_ms.serve", "engine_ttft_p95_ms.serve")
TRACE_READERS = ("paged_decode_share.serve", "paged_prefill_share.serve")


def _record(steps=3, trace=None):
    return harness.Record(correct=True, attempted=0, failed=0, end_to_end={}, facts={},
                          clocks={"steps": steps}, trace=trace)


def _hand_built_ring():
    """Engine 7's first four steps and four requests, and leftovers of an
    earlier engine 3. The window is steps 0-2; step 3 is the traced slice.

    step 0, 200 ms: admit 1, prefill 60 (600 tokens), prefill 40 (400 tokens),
                    grow 1, build 2, dispatch 3, fetch 90, emit 2
    step 1, 100 ms: admit 0.5, grow 0.5, build 2, dispatch 2, fetch 94, emit 0.5
    step 2,  10 ms: admit 1 (nothing was running: no decode)
    step 3, 500 ms: prefill 400 (100 tokens), build 2, dispatch 3, fetch 90   [not the window]
    """
    ring = collections.deque(maxlen=1000)
    t = [0]

    def span(name, ms, engine=7, step=0, **key):
        ring.append(("atpu.serve." + name, t[0], t[0] + int(ms * MS),
                     dict(engine=engine, step=step, **key)))
        t[0] += int(ms * MS)

    span("step", 999, engine=3, step=0)          # an earlier engine: never read
    span("dispatch", 999, engine=3, step=0)
    for name, ms, key in (("admit", 1, {}), ("prefill", 60, dict(rid=1, tokens=600, cached=0)),
                          ("prefill", 40, dict(rid=2, tokens=400, cached=0)), ("grow", 1, {}),
                          ("build", 2, dict(batch=2, slot_bucket=32, block_bucket=48)),
                          ("dispatch", 3, {}), ("fetch", 90, {}), ("emit", 2, {})):
        span(name, ms, step=0, **key)
    ring.append(("atpu.serve.step", 0, 200 * MS, dict(engine=7, step=0)))
    for name, ms in (("admit", .5), ("grow", .5), ("build", 2), ("dispatch", 2), ("fetch", 94),
                     ("emit", .5)):
        span(name, ms, step=1)
    ring.append(("atpu.serve.step", 0, 100 * MS, dict(engine=7, step=1)))
    span("admit", 1, step=2)
    ring.append(("atpu.serve.step", 0, 10 * MS, dict(engine=7, step=2)))
    for name, ms, key in (("prefill", 400, dict(rid=4, tokens=100, cached=0)), ("build", 2, {}),
                          ("dispatch", 3, {}), ("fetch", 90, {})):
        span(name, ms, step=3, **key)
    ring.append(("atpu.serve.step", 0, 500 * MS, dict(engine=7, step=3)))

    def request(rid, arrival, admit, first, admit_step, engine=7):
        ring.append(("atpu.request", 0, 0, dict(
            engine=engine, rid=rid, arrival_t=arrival, admit_t=admit, first_token_t=first,
            finish_t=first + 1.0, admit_step=admit_step, prompt_tokens=8, new_tokens=4,
            preemptions=0)))

    request(9, 0.0, 90.0, 99.0, 0, engine=3)     # the earlier engine's
    request(1, 10.0, 10.2, 10.5, 0)              # waited 200 ms, first token after 500 ms
    request(2, 10.1, 10.2, 10.9, 0)              # waited 100 ms, first token after 800 ms
    request(3, 11.0, 11.05, 11.1, 2)             # waited  50 ms, first token after 100 ms
    request(4, 11.0, 15.0, 19.0, 3)              # admitted in step 3: not the window's
    return ring


@pytest.mark.parametrize("name,expected", [
    # step less prefill, dispatch, fetch: 200-100-3-90 = 7, 100-2-94 = 4, 10: mean 7
    ("host_gap_ms.serve", 7.0),
    # build + dispatch + fetch of the steps that decoded: 95 and 98, median 96.5
    ("decode_phase_ms.serve", 96.5),
    # (60 + 40) ms over (600 + 400) / 1000 thousand tokens
    ("prefill_phase_ms_per_ktok.serve", 100.0),
    # 200, 100, 50 ms: the 95th percentile by nearest rank of three is the largest
    ("queue_wait_p95_ms.serve", 200.0),
    # 500, 800, 100 ms
    ("engine_ttft_p95_ms.serve", 800.0),
])
def test_program_span_reader_on_a_hand_built_ring(monkeypatch, name, expected):
    monkeypatch.setattr(tracing, "_RING", _hand_built_ring())
    assert harness.layer_metric_reader(name)(_record(steps=3)) == pytest.approx(expected, rel=1e-9)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_program_span_reader_finds_nothing_rather_than_something_wrong(monkeypatch, name):
    """None, and no exception: with an empty ring, with a program that has no
    ring at all (the parent commit, under this PR's benchmark files), without
    the runner's ``steps`` clock, and when the ring has dropped part of the
    window (a step's own span is gone)."""
    read = harness.layer_metric_reader(name)
    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=8))
    assert read(_record()) is None
    full = _hand_built_ring()
    monkeypatch.setattr(tracing, "_RING", full)
    assert read(harness.Record(True, 0, 0, {}, {}, {})) is None
    dropped = collections.deque(
        (r for r in full if not (r[0] == "atpu.serve.step" and r[3] == dict(engine=7, step=0))
         and r[0] != "atpu.request"), maxlen=1000)
    monkeypatch.setattr(tracing, "_RING", dropped)
    assert read(_record()) is None
    monkeypatch.delattr(tracing, "recorded")
    assert read(_record()) is None


def test_window_is_the_last_engines_steps_below_the_runners_count(monkeypatch):
    monkeypatch.setattr(tracing, "_RING", _hand_built_ring())
    steps = program_spans.window_steps(_record(steps=3))
    assert sorted(steps) == [0, 1, 2]
    assert [(k["rid"], k["tokens"]) for k in program_spans.attributes(steps, "prefill")] == [
        (1, 600), (2, 400)]
    assert program_spans.attributes(steps, "build")[0]["block_bucket"] == 48
    assert program_spans.total(steps[0], "prefill") == pytest.approx(0.1)
    assert sorted(program_spans.window_steps(_record(steps=4))) == [0, 1, 2, 3]
    assert [r["rid"] for r in program_spans.window_requests(_record(steps=3))] == [1, 2, 3]
    assert [r["rid"] for r in program_spans.window_requests(_record(steps=4))] == [1, 2, 3, 4]


# ------------------------------------------------- the named kernels' fixture


@pytest.fixture(scope="module")
def named_sample():
    sample = json.load(open(os.path.join(FIXTURES, "v5e_named_kernels_sample.json")))
    reduced = trace_reduce.reduce(
        [[tuple(op) for op in sample["device_ops"]]], [tuple(a) for a in sample["annotations"]])
    return sample, reduced


def test_named_kernels_sample_holds_a_prefill_and_a_decode_program_with_whole_texts(named_sample):
    sample, _ = named_sample
    layers = sample["n_layers"]
    kernels = [op for op in sample["device_ops"] if trace_reduce.KERNEL_MARK in op[0]]
    shorts = [trace_reduce.short_name(op[0]) for op in kernels]
    assert sum("paged_prefill" in s for s in shorts) == layers  # one chunk's program
    assert sum("paged_decode" in s for s in shorts) == layers   # the decode step behind it
    assert not any("closed_call" in s for s in shorts)
    for op in kernels:  # the instruction, not a short name: `%name = type custom-call(...)`
        assert op[0].startswith("%paged_") and " = " in op[0] and "custom-call(" in op[0]
    names = {a[0] for a in sample["annotations"]}
    assert {"cb.window", "cb.engine_step", "atpu.serve.step", "atpu.serve.prefill",
            "atpu.serve.fetch"} <= names


@pytest.mark.parametrize("name,key", [("paged_decode_share.serve", "paged_decode_s"),
                                      ("paged_prefill_share.serve", "paged_prefill_s")])
def test_kernel_share_reader_on_the_named_kernels_sample(named_sample, name, key):
    """Against sums the cutter took over the operations' durations by name,
    not with ``trace_reduce``'s tables."""
    sample, reduced = named_sample
    expect = sample["expect"]
    assert reduced["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    value = harness.layer_metric_reader(name)(_record(trace=reduced))
    assert value == pytest.approx(100.0 * expect[key] / expect["busy_s"], rel=1e-9)
    assert 0.0 < value < 100.0


def test_decode_plus_prefill_kernel_time_is_all_the_kernel_time(named_sample):
    """``kernel_share.serve`` keeps reading the same thing (``tpu_custom_call``
    in the event's text), and the two named shares split it with no rest."""
    sample, reduced = named_sample
    expect = sample["expect"]
    assert reduced["kernel_s"] == pytest.approx(expect["kernel_s"], rel=1e-9)
    assert expect["paged_decode_s"] + expect["paged_prefill_s"] == pytest.approx(
        expect["kernel_s"], rel=1e-9)
    record = _record(trace=reduced)
    shares = [harness.layer_metric_reader(n)(record) for n in TRACE_READERS]
    assert sum(shares) == pytest.approx(harness.layer_metric_reader("kernel_share.serve")(record),
                                        rel=1e-9)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_kernel_share_reader_without_a_trace_or_a_named_kernel_reads_nothing(name):
    read = harness.layer_metric_reader(name)
    assert read(_record(trace=None)) is None
    unnamed = {"window_s": 1.0, "busy_s": 0.9, "kernel_s": 0.6,
               "device_ops": [["closed_call.4", 0.3], ["closed_call.5", 0.3], ["fusion.1 kLoop", 0.3]],
               "idle_gaps": []}
    assert read(_record(trace=unnamed)) is None  # the parent commit's kernels carry no name
