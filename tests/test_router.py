"""Fault-tolerant serving router tests (ISSUE 12).

The acceptance lines these tests hold:

- **no lost or duplicated requests**: a replica SIGKILLed or wedged forever
  mid-decode loses nothing — every admitted request completes EXACTLY once,
  with tokens bitwise-equal to the single-stream ``greedy_generate``
  reference (failover resumes from the streamed ``generated``-so-far via the
  scheduler's preempt/resume state, so the retry is token-exact);
- **graceful overload**: the token bucket and bounded priority queues shed
  with a distinct ``SHED`` status (by priority: batch displaced before
  interactive), deadlines expire queued work instead of decoding it late,
  and the router never wedges — it fails requests loudly when no replica
  can ever run them.

Host-side dispatch/health/failover logic runs against in-test FakeReplicas
(microseconds); the token-exact failover line runs against real
thread-backed engines in tier-1 and against real subprocess replicas with
real SIGKILL / wedge-forever chaos in the slow-marked e2e.
"""

import dataclasses
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from accelerate_tpu.generation import greedy_generate
from accelerate_tpu.models import LlamaConfig
from accelerate_tpu.resilience import chaos
from accelerate_tpu.resilience.chaos import ChaosFaultError, ChaosSchedule, Fault
from accelerate_tpu.serving import (
    PRIORITY_BATCH,
    PRIORITY_INTERACTIVE,
    AdmissionController,
    CanaryGolden,
    CanaryProbe,
    LocalReplica,
    ProcessReplica,
    ReplicaSpec,
    ReplicaState,
    RouterRequestStatus,
    ServingRouter,
    TokenBucket,
    precompute_goldens,
)

CONFIG = LlamaConfig.tiny()


def _spec(**kw) -> ReplicaSpec:
    base = dict(
        model=dataclasses.asdict(CONFIG), num_blocks=33, block_size=8,
        max_slots=2, slot_buckets=(2,), block_buckets=(4,), prefill_buckets=(32,),
    )
    base.update(kw)
    return ReplicaSpec(**base)


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CONFIG.vocab_size, (n,)).astype(np.int32) for n in lengths]


class FakeReplica:
    """Scriptable replica: the router's dispatch/health/failover logic under
    test without paying an engine."""

    transport = "fake"

    def __init__(self, name, max_slots=4):
        self.name = name
        self.state = ReplicaState.HEALTHY
        self.spec = SimpleNamespace(max_slots=max_slots)
        self.submitted = []
        self._events = []
        self._alive = True

    def submit(self, payload):
        self.submitted.append(payload)

    def drain_events(self):
        ev, self._events = self._events, []
        return ev

    def alive(self):
        return self._alive

    def kill(self):
        self._alive = False

    def stop(self):
        pass

    def close(self, timeout=0.0):
        self._alive = False

    # test helpers
    def push(self, **ev):
        self._events.append(ev)

    def die(self):
        self._alive = False


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# admission control


@pytest.mark.smoke
def test_token_bucket_refill_and_all_or_nothing():
    clock = FakeClock()
    bucket = TokenBucket(rate_per_s=10.0, burst=30.0, clock=clock)
    assert bucket.take(30)  # starts full
    assert not bucket.take(1)  # empty, all-or-nothing
    clock.t += 2.0  # +20 tokens
    assert bucket.available() == pytest.approx(20.0)
    assert not bucket.take(25)
    assert bucket.take(20)
    clock.t += 100.0  # refill caps at burst
    assert bucket.available() == pytest.approx(30.0)
    with pytest.raises(ValueError):
        TokenBucket(rate_per_s=0, burst=10)


def test_admission_priority_order_and_requeue_front():
    ctl = AdmissionController(max_queue=8, clock=FakeClock())
    reqs = [SimpleNamespace(priority=p, rid=i) for i, p in enumerate([1, 0, 1, 0])]
    for r in reqs:
        assert ctl.try_admit(r, cost=1).admitted
    # interactive (0) drains before batch (1), FIFO within a class
    assert [r.rid for r in ctl.queued()] == [1, 3, 0, 2]
    popped = ctl.pop_next()
    assert popped.rid == 1
    # a failover requeue goes back to the FRONT of its class
    ctl.requeue_front(popped)
    assert [ctl.pop_next().rid for _ in range(4)] == [1, 3, 0, 2]
    assert ctl.pop_next() is None


def test_admission_queue_full_sheds_lowest_priority():
    ctl = AdmissionController(max_queue=2, clock=FakeClock())
    b1 = SimpleNamespace(priority=PRIORITY_BATCH, rid="b1")
    b2 = SimpleNamespace(priority=PRIORITY_BATCH, rid="b2")
    assert ctl.try_admit(b1, 1).admitted and ctl.try_admit(b2, 1).admitted
    # an interactive newcomer displaces the most recent batch request...
    hi = SimpleNamespace(priority=PRIORITY_INTERACTIVE, rid="hi")
    verdict = ctl.try_admit(hi, 1)
    assert verdict.admitted and [v.rid for v in verdict.evicted] == ["b2"]
    # ...but a batch newcomer cannot displace its own class or better
    b3 = SimpleNamespace(priority=PRIORITY_BATCH, rid="b3")
    verdict = ctl.try_admit(b3, 1)
    assert not verdict.admitted and verdict.reason == "queue-full"
    assert ctl.depth == 2 and ctl.depth_by_priority() == {0: 1, 1: 1}


def test_admission_never_evicts_failover_requeues():
    """A failover re-queue (retries > 0) is ALREADY-ADMITTED, partially
    decoded work: priority eviction must pass over it — shedding it would
    lose a request the router promised to finish — and fall back to the
    newest never-dispatched victim, or shed the newcomer."""
    ctl = AdmissionController(max_queue=2, clock=FakeClock())
    fresh = SimpleNamespace(priority=PRIORITY_BATCH, rid="fresh", retries=0)
    resumed = SimpleNamespace(priority=PRIORITY_BATCH, rid="resumed", retries=1)
    assert ctl.try_admit(fresh, 1).admitted
    ctl.requeue_front(resumed)
    # the newest batch entry is `fresh`... but even if the requeue were
    # newest, it must be skipped: evict `fresh`, the only retries==0 victim
    hi = SimpleNamespace(priority=PRIORITY_INTERACTIVE, rid="hi")
    verdict = ctl.try_admit(hi, 1)
    assert verdict.admitted and [v.rid for v in verdict.evicted] == ["fresh"]
    # queue now holds only the resumed request below interactive: a second
    # interactive newcomer finds NO evictable victim and is shed itself
    hi2 = SimpleNamespace(priority=PRIORITY_INTERACTIVE, rid="hi2")
    verdict = ctl.try_admit(hi2, 1)
    assert not verdict.admitted and verdict.reason == "queue-full"
    assert resumed in ctl.queued()  # the admitted work survived overload


# ---------------------------------------------------------------------------
# router: shed / deadline / dispatch (FakeReplica, host-only)


def test_router_sheds_with_distinct_status_and_reports(tmp_path):
    from accelerate_tpu.telemetry import events as tel
    from accelerate_tpu.telemetry.report import build_report, format_report

    clock = FakeClock()
    # replicas still warming: nothing dispatches, the queues fill honestly
    rep = FakeReplica("r0")
    rep.state = ReplicaState.STARTING
    tel.enable(out_dir=str(tmp_path), run_id="router-shed")
    try:
        router = ServingRouter(
            [rep],
            admission=AdmissionController(
                max_queue=2, rate_tokens_per_s=10.0, burst_tokens=40.0, clock=clock
            ),
            clock=clock,
        )
        prompt = np.arange(4, dtype=np.int32) + 1
        ok1 = router.submit(prompt, 8, priority=PRIORITY_BATCH)  # cost 12
        ok2 = router.submit(prompt, 8, priority=PRIORITY_BATCH)  # cost 12
        # bucket now holds 16: a 20-cost request is rate-shed
        rate_shed = router.submit(prompt, 16, priority=PRIORITY_BATCH)
        # queue is full (2): interactive displaces the newest batch request,
        # another batch request sheds outright
        displacing = router.submit(prompt, 4, priority=PRIORITY_INTERACTIVE)
        full_shed = router.submit(prompt, 4, priority=PRIORITY_BATCH)
        router.poll()
    finally:
        tel.disable()

    assert ok1.status is RouterRequestStatus.QUEUED
    assert rate_shed.status is RouterRequestStatus.SHED
    assert "rate-limited" in rate_shed.error
    assert displacing.status is RouterRequestStatus.QUEUED
    assert ok2.status is RouterRequestStatus.SHED  # displaced victim
    assert "displaced" in ok2.error
    assert full_shed.status is RouterRequestStatus.SHED
    assert "queue-full" in full_shed.error
    # every submitted request has exactly one definite state; nothing vanished
    assert router.stats()["shed"] == 3
    assert router.stats()["shed_by_reason"] == {
        "rate-limited": 1, "displaced by higher-priority admission": 1, "queue-full": 1,
    }
    report = build_report([str(tmp_path)])
    section = report["router"]
    assert section["shed"] == 3
    assert section["shed_reasons"]["rate-limited"] == 1
    assert section["outcomes"]["shed"] == 3
    text = format_report(report)
    assert "router:" in text and "shed 3" in text


def test_router_deadline_expires_queued_work():
    clock = FakeClock()
    rep = FakeReplica("r0")
    rep.state = ReplicaState.STARTING  # nothing dispatches yet
    router = ServingRouter([rep], clock=clock)
    prompt = np.arange(3, dtype=np.int32) + 1
    doomed = router.submit(prompt, 4, deadline_s=5.0)
    safe = router.submit(prompt, 4)  # no deadline
    clock.t += 6.0
    done = router.poll()
    assert doomed.status is RouterRequestStatus.EXPIRED
    assert "deadline" in doomed.error and doomed in done
    assert safe.status is RouterRequestStatus.QUEUED
    assert rep.submitted == []  # the expired request never reached a replica
    assert router.stats()["expired"] == 1


def test_router_dispatches_by_least_outstanding_tokens():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    router = ServingRouter([r0, r1])
    big = router.submit(np.arange(10, dtype=np.int32) + 1, 10)  # 20 tokens
    small = router.submit(np.arange(2, dtype=np.int32) + 1, 2)  # 4 tokens
    third = router.submit(np.arange(2, dtype=np.int32) + 1, 2)
    router.poll()
    # big -> r0 (tie broken by order), small -> r1 (0 < 20), third -> r1 (4 < 20)
    assert big.replica == "r0" and small.replica == "r1" and third.replica == "r1"
    assert router.outstanding_tokens("r0") == 20
    assert router.outstanding_tokens("r1") == 8
    # progress shrinks the owed budget: streamed tokens reduce the load metric
    r1.push(event="step", step=1, progress={small.rid: [5]})
    router.poll()
    assert router.outstanding_tokens("r1") == 5  # 4-token req: prefill paid, 1 left


def test_router_failover_resumes_with_progress_exactly_once():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    router = ServingRouter([r0, r1], max_retries=3)
    req = router.submit(np.asarray([1, 2, 3], np.int32), 5)
    router.poll()
    assert req.replica == "r0" and req.status is RouterRequestStatus.DISPATCHED
    r0.push(event="step", step=1, progress={req.rid: [7, 8]})
    router.poll()
    assert req.generated == [7, 8] and req.first_token_t is not None
    r0.die()
    router.poll()
    # dead replica's work re-dispatched WITH its streamed progress, same poll
    assert r0.state is ReplicaState.DEAD
    assert req.replica == "r1" and req.retries == 1
    assert r1.submitted[-1]["generated"] == [7, 8]
    assert router.failovers == 1
    # the survivor owes the FULL re-prefill (prompt 3 + resumed 2) plus the
    # remaining budget (3): a freshly burdened survivor must not look light
    assert router.outstanding_tokens("r1") == 3 + 2 + 3
    # a zombie's late completion must not double-complete the request
    r0.push(event="done", rid=req.rid, status="finished", tokens=[7, 8, 0, 0, 0])
    router.poll()
    assert req.status is RouterRequestStatus.DISPATCHED  # still r1's to finish
    r1.push(event="done", rid=req.rid, status="finished",
            tokens=[7, 8, 9, 10, 11], preemptions=0)
    r1.push(event="done", rid=req.rid, status="finished",
            tokens=[7, 8, 9, 10, 11], preemptions=0)  # duplicate: ignored
    done = router.poll()
    assert req.status is RouterRequestStatus.FINISHED
    assert req.generated == [7, 8, 9, 10, 11]
    assert router.completed == 1 and len(done) == 1


def test_router_hang_detection_uses_heartbeat_staleness():
    clock = FakeClock()
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    router = ServingRouter([r0, r1], health_timeout_s=2.0, clock=clock)
    req = router.submit(np.asarray([1, 2], np.int32), 4)
    router.poll()
    assert req.replica == "r0"
    # r0 stays alive() but silent WITH work in flight -> stalled -> DEAD;
    # r1 is just as silent but idle, so it is NOT declared dead
    clock.t += 3.0
    router.poll()
    assert r0.state is ReplicaState.DEAD and "stale" in r0.reason
    assert not r0.alive()  # the router reaps what it declares dead
    assert r1.state is ReplicaState.HEALTHY
    assert req.replica == "r1" and req.retries == 1


def test_router_finalizes_fully_streamed_request_on_death():
    r0 = FakeReplica("r0")
    router = ServingRouter([r0])
    req = router.submit(np.asarray([1, 2], np.int32), 3)
    router.poll()
    r0.push(event="step", step=1, progress={req.rid: [4, 5, 6]})  # all 3 streamed
    router.poll()
    r0.die()
    done = router.poll()
    # nothing left to decode: the death only lost the done event, not work
    assert req.status is RouterRequestStatus.FINISHED
    assert req.generated == [4, 5, 6] and req in done
    assert router.completed == 1


def test_router_bounds_retries_and_fails_without_replicas():
    r0 = FakeReplica("r0")
    # per-replica outstanding bound of 1: the second request must WAIT — the
    # bounded-dispatch backpressure, and the setup for the no-replicas path
    router = ServingRouter([r0], max_retries=0, max_outstanding_per_replica=1)
    inflight = router.submit(np.asarray([1, 2], np.int32), 4)
    queued = router.submit(np.asarray([1, 2], np.int32), 4)
    router.poll()
    assert queued.status is RouterRequestStatus.QUEUED  # backpressure held it
    assert inflight.status is RouterRequestStatus.DISPATCHED
    r0.die()
    done = router.poll()
    # the in-flight request exhausted its retry budget; the queued one can
    # never run (no live replicas) — both FAILED loudly, nothing wedged
    assert inflight.status is RouterRequestStatus.FAILED
    assert "replica deaths" in inflight.error
    assert queued.status is RouterRequestStatus.FAILED
    assert "no live replicas" in queued.error
    assert set(done) == {inflight, queued}


def test_router_drain_stops_dispatch_but_finishes_inflight():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    router = ServingRouter([r0, r1])
    first = router.submit(np.asarray([1], np.int32), 2)
    router.poll()
    assert first.replica == "r0"
    router.drain("r0")
    assert r0.state is ReplicaState.DRAINING
    later = router.submit(np.asarray([1], np.int32), 2)
    router.poll()
    assert later.replica == "r1"  # draining replicas get nothing new
    r0.push(event="done", rid=first.rid, status="finished", tokens=[9, 9])
    router.poll()
    assert first.status is RouterRequestStatus.FINISHED  # in-flight finished
    # draining the WHOLE fleet with work still queued must fail that work
    # loudly (DRAINING never returns to HEALTHY) — not wedge until timeout
    router.drain("r1")  # `later` stays in flight on r1 and still finishes
    stranded = router.submit(np.asarray([1], np.int32), 2)
    done = router.poll()
    assert stranded.status is RouterRequestStatus.FAILED and stranded in done
    assert "draining" in stranded.error
    r1.push(event="done", rid=later.rid, status="finished", tokens=[8, 8])
    router.poll()
    assert later.status is RouterRequestStatus.FINISHED  # drain kept its word


# ---------------------------------------------------------------------------
# chaos + watchdog integration


def test_chaos_serving_decode_point():
    schedule = ChaosSchedule.seeded(
        7, steps=10, kinds=("sigkill",), n_faults=1, point="serving_decode"
    )
    assert schedule.faults[0].point == "serving_decode"
    assert schedule.to_json() == ChaosSchedule.seeded(
        7, steps=10, kinds=("sigkill",), n_faults=1, point="serving_decode"
    ).to_json()
    chaos.arm(ChaosSchedule(faults=[Fault(kind="crash", point="serving_decode", step=2)]))
    try:
        chaos.maybe_inject("serving_decode", step=1)  # wrong step: no fire
        chaos.maybe_inject("train_step", step=2)  # wrong point: no fire
        with pytest.raises(ChaosFaultError):
            chaos.maybe_inject("serving_decode", step=2)
        chaos.maybe_inject("serving_decode", step=2)  # once: spent
    finally:
        chaos.arm(None)


def test_watchdog_stall_names_replica_source(tmp_path):
    import json

    from accelerate_tpu.telemetry import watchdog

    wd = watchdog.start(timeout=0.3, interval=0.1, out_dir=str(tmp_path))
    try:
        ServingRouter([FakeReplica("wedged")])
        # registered at router construction; never beaten -> a stall dump
        # that NAMES the replica, same forensics as a stuck train step
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not wd.dump_paths:
            time.sleep(0.05)
        assert wd.dump_paths, "no stall dump within 5s"
        with open(wd.dump_paths[0]) as f:
            reason = json.load(f)["reason"]
        assert "serving_replica:wedged" in reason
    finally:
        watchdog.stop()


# ---------------------------------------------------------------------------
# real engines: token-exact failover (tier-1: thread replicas)


def test_local_replica_failover_bitwise_parity(tmp_path):
    """Kill one of two thread-backed replicas mid-decode: every request must
    finish exactly once with output bitwise-equal to the single-stream
    reference — the resumed requests continue from their streamed progress,
    not from scratch blindly trusted."""
    from accelerate_tpu.telemetry import events as tel
    from accelerate_tpu.telemetry.report import build_report, format_report

    spec = _spec()
    tel.enable(out_dir=str(tmp_path), run_id="router-failover")
    router = None
    try:
        router = ServingRouter(
            [LocalReplica(f"r{i}", spec) for i in range(2)], health_timeout_s=5.0
        )
        router.wait_ready(timeout_s=300)
        prompts = _prompts(1, (5, 13, 9, 16, 7, 11))
        reqs = [router.submit(p, 12, rng_seed=i) for i, p in enumerate(prompts)]
        # let tokens flow until r0 holds partially decoded work, then kill it
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            router.poll()
            if any(
                r.replica == "r0" and len(r.generated) >= 2 and not r.status.terminal
                for r in reqs
            ):
                break
            time.sleep(0.002)
        victims = [r.rid for r in reqs if r.replica == "r0" and not r.status.terminal]
        assert victims, "r0 never held in-flight work"
        router.replicas["r0"].kill()
        done = router.run(timeout_s=240)
    finally:
        if router is not None:
            router.close()
        tel.disable()

    assert router.replicas["r0"].state is ReplicaState.DEAD
    assert router.failovers >= 1
    # exactly once: every request terminal exactly one time, none duplicated
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    params = spec.build_params()
    for i, (p, req) in enumerate(zip(_prompts(1, (5, 13, 9, 16, 7, 11)), reqs)):
        assert req.status is RouterRequestStatus.FINISHED, (i, req.status, req.error)
        ref = greedy_generate(params, p[None], CONFIG, max_new_tokens=12)
        assert np.array_equal(np.asarray(ref[0]), req.output_ids()), f"request {i}"
    assert any(r.retries >= 1 for r in reqs)  # failover actually resumed work
    report = build_report([str(tmp_path)])
    section = report["router"]
    assert section["completed"] == len(reqs)
    assert section["failovers"] == router.failovers
    assert section["replicas"]["r0"]["state"] == "dead"
    assert section["requests"]["retried"] >= 1
    text = format_report(report)
    assert "router:" in text and "r0: dead" in text


def test_engine_resume_submit_is_token_exact():
    """The failover resume primitive in isolation: engine B continuing a
    request from engine A's generated-so-far produces the same tokens as one
    uninterrupted run — across DIFFERENT engine instances, which is exactly
    the cross-replica case."""
    spec = _spec(slot_buckets=(1,), block_buckets=(4,), prefill_buckets=(32,), max_slots=1)
    engine_a = spec.build_engine()
    engine_a.warmup()
    prompt = _prompts(3, (9,))[0]
    partial = engine_a.submit(prompt, 4, rng_seed=5)
    engine_a.run()
    assert len(partial.generated) == 4
    engine_b = spec.build_engine()
    engine_b.warmup()
    resumed = engine_b.submit(prompt, 10, rng_seed=5, generated=list(partial.generated))
    engine_b.run()
    ref = greedy_generate(spec.build_params(), prompt[None], CONFIG, max_new_tokens=10)
    assert np.array_equal(np.asarray(ref[0]), resumed.output_ids())
    with pytest.raises(ValueError, match="nothing left to decode"):
        engine_b.submit(prompt, 4, generated=[1, 2, 3, 4])


def test_engine_step_beats_watchdog_serving_decode(tmp_path):
    from accelerate_tpu.telemetry import watchdog

    spec = _spec(slot_buckets=(1,), block_buckets=(4,), prefill_buckets=(32,), max_slots=1)
    engine = spec.build_engine(heartbeat_name="serving_decode:solo")
    engine.warmup()
    wd = watchdog.start(timeout=60, interval=5, out_dir=str(tmp_path))
    try:
        engine.submit(_prompts(4, (5,))[0], 5)
        engine.step()  # request still live after this step -> source beats
        sources = wd.sources()
        assert "serving_decode:solo" in sources  # beats per step, with the step
        assert sources["serving_decode:solo"]["step"] == engine.steps
        engine.run()
        # drained-to-idle engines deregister: a quiet traffic window must
        # never read as a decode stall (or 101-abort a serving process)
        assert "serving_decode:solo" not in wd.sources()
    finally:
        watchdog.stop()


# ---------------------------------------------------------------------------
# the chaos e2e: real processes, real SIGKILL, real wedge-forever hang


@pytest.mark.slow  # 3 subprocess replicas each paying jax import + warmup
def test_process_replica_sigkill_and_hang_chaos_poisson_parity():
    """ISSUE 12 acceptance: seeded chaos (replica SIGKILL + wedge-forever
    hang, both mid-decode) under a Poisson open-loop load — every admitted
    request completes exactly once, bitwise-equal to its single-stream
    reference; the two chaos'd replicas die, the survivor absorbs the
    failovers."""
    import os

    spec = _spec()
    sigkill = ChaosSchedule(
        faults=[Fault(kind="sigkill", point="serving_decode", step=3)]
    ).to_json()
    hang = ChaosSchedule(
        faults=[Fault(kind="hang", point="serving_decode", step=4, duration_s=None)]
    ).to_json()
    # children inherit env verbatim (no implicit platform pinning) — pin CPU
    # here so the test is hermetic even when the runner didn't export it
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    router = None
    try:
        router = ServingRouter(
            [
                ProcessReplica("r0", spec, chaos_schedule=sigkill, env=env),
                ProcessReplica("r1", spec, chaos_schedule=hang, env=env),
                ProcessReplica("r2", spec, env=env),
            ],
            health_timeout_s=3.0,
        )
        router.wait_ready(timeout_s=300)
        # seeded Poisson open loop: exponential inter-arrival gaps, submitted
        # on the router's wall clock while it polls
        rng = np.random.default_rng(42)
        n = 10
        gaps = rng.exponential(0.03, n)
        lengths = rng.integers(4, 20, n)
        prompts = [
            rng.integers(0, CONFIG.vocab_size, (int(s),)).astype(np.int32)
            for s in lengths
        ]
        reqs = []
        done = []  # every poll's terminal requests — exactly-once needs ALL
        for i in range(n):
            t0 = time.monotonic()
            while time.monotonic() - t0 < gaps[i]:
                done.extend(router.poll())
                time.sleep(0.001)
            reqs.append(router.submit(prompts[i], 10, rng_seed=i))
        done.extend(router.run(timeout_s=300))
    finally:
        if router is not None:
            router.close()

    dead = {n for n, r in router.replicas.items() if r.state is ReplicaState.DEAD}
    assert dead == {"r0", "r1"}, f"chaos'd replicas should both be dead: {dead}"
    assert router.replicas["r2"].state is ReplicaState.HEALTHY
    assert router.failovers >= 2
    # exactly once, nothing lost, nothing duplicated
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    assert router.completed == len(reqs)
    params = spec.build_params()
    for i, (p, req) in enumerate(zip(prompts, reqs)):
        assert req.status is RouterRequestStatus.FINISHED, (i, req.status, req.error)
        ref = greedy_generate(params, p[None], CONFIG, max_new_tokens=10)
        assert np.array_equal(np.asarray(ref[0]), req.output_ids()), f"request {i}"


def test_router_report_absent_without_records(tmp_path):
    from accelerate_tpu.telemetry.report import build_report, format_report

    (tmp_path / "events-rank0.jsonl").write_text(
        '{"kind": "meta", "schema": 1, "run_id": "r", "process_index": 0, '
        '"num_processes": 1}\n'
    )
    report = build_report([str(tmp_path)])
    assert report["router"] is None
    assert "router:" not in format_report(report)


# ---------------------------------------------------------------------------
# router self-healing (ISSUE 13 satellite): a chaos-killed fleet heals back
# to N via respawn-from-spec under a bounded budget with backoff


class RespawnableFake(FakeReplica):
    """FakeReplica that can be respawned from itself (generation counted)."""

    def __init__(self, name, max_slots=4, generation=0):
        super().__init__(name, max_slots=max_slots)
        self.generation = generation

    def respawn(self):
        return RespawnableFake(
            self.name, max_slots=self.spec.max_slots, generation=self.generation + 1
        )


def test_router_self_heal_respawns_within_budget_then_gives_up():
    clock = FakeClock()
    rep = RespawnableFake("r0")
    router = ServingRouter(
        [rep], clock=clock, self_heal=True, max_respawns_per_replica=1,
        respawn_backoff_base_s=0.0,
    )
    rep.die()
    router.poll()
    healed = router.replicas["r0"]
    assert healed is not rep and healed.generation == 1
    assert healed.state is ReplicaState.HEALTHY
    assert router.respawns == 1
    assert router.stats()["per_replica"]["r0"]["respawns"] == 1
    # budget exhausted: the second death stays dead, and queued work fails
    # loudly instead of waiting for a heal that can never come
    healed.die()
    router.poll()
    assert router.replicas["r0"].state is ReplicaState.DEAD
    req = router.submit(np.arange(4, dtype=np.int32), 4)
    router.poll()
    assert req.status is RouterRequestStatus.FAILED
    assert "no live replicas" in req.error


def test_router_self_heal_backoff_defers_second_respawn():
    clock = FakeClock()
    rep = RespawnableFake("r0")
    router = ServingRouter(
        [rep], clock=clock, self_heal=True, max_respawns_per_replica=3,
        respawn_backoff_base_s=10.0,
    )
    rep.die()
    router.poll()  # first respawn is immediate
    assert router.replicas["r0"].generation == 1
    router.replicas["r0"].die()
    router.poll()  # second respawn gated behind the backoff window
    assert router.replicas["r0"].state is ReplicaState.DEAD
    # queued work WAITS (budget remains) instead of failing
    req = router.submit(np.arange(4, dtype=np.int32), 4)
    router.poll()
    assert req.status is RouterRequestStatus.QUEUED
    clock.t += 10.1
    router.poll()
    assert router.replicas["r0"].generation == 2
    assert router.replicas["r0"].state is ReplicaState.HEALTHY


def test_router_self_heal_ignores_replicas_without_spec():
    clock = FakeClock()
    rep = FakeReplica("r0")  # no respawn()
    router = ServingRouter([rep], clock=clock, self_heal=True)
    rep.die()
    router.poll()
    assert router.replicas["r0"] is rep
    assert router.replicas["r0"].state is ReplicaState.DEAD
    assert router.respawns == 0


def test_router_self_heals_killed_fleet_back_to_n_bitwise(tmp_path):
    """The e2e: one of two thread-backed replicas is killed mid-decode. The
    router must (a) fail the work over with bitwise parity, (b) respawn the
    dead replica from its stored spec — warm-booted from the compile cache —
    and (c) end with the fleet back at N serving bitwise-identical output
    from the RESPAWNED replica."""
    spec = _spec(compile_cache_dir=str(tmp_path / "cache"))
    router = None
    try:
        router = ServingRouter(
            [LocalReplica(f"r{i}", spec) for i in range(2)],
            health_timeout_s=5.0, self_heal=True, max_respawns_per_replica=2,
            respawn_backoff_base_s=0.05,
        )
        router.wait_ready(timeout_s=300)
        prompts = _prompts(1, (5, 13, 9, 16, 7, 11))
        reqs = [router.submit(p, 12, rng_seed=i) for i, p in enumerate(prompts)]
        # poll() hands each terminal request back exactly once: on a loaded
        # box some finish (on r1) while this loop still waits for r0's second
        # token, and run() below then returns only the rest
        done = []
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            done.extend(router.poll())
            if any(
                r.replica == "r0" and len(r.generated) >= 2 and not r.status.terminal
                for r in reqs
            ):
                break
            time.sleep(0.002)
        assert any(r.replica == "r0" and not r.status.terminal for r in reqs)
        router.replicas["r0"].kill()
        done.extend(router.run(timeout_s=240))
        assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
        params = spec.build_params()
        for i, (p, req) in enumerate(zip(prompts, reqs)):
            assert req.status is RouterRequestStatus.FINISHED, (i, req.status, req.error)
            ref = greedy_generate(params, p[None], CONFIG, max_new_tokens=12)
            assert np.array_equal(np.asarray(ref[0]), req.output_ids()), f"request {i}"
        # the fleet heals back to N: the replacement boots (warm), goes ready
        assert router.respawns >= 1
        router.wait_ready(timeout_s=300)
        assert all(
            r.state is ReplicaState.HEALTHY for r in router.replicas.values()
        ), {n: r.state for n, r in router.replicas.items()}
        healed = router.replicas["r0"]
        # warm boot: the respawned engine loaded its whole lattice from cache
        # (incl. the prefix-cache COW point, one extra warmed shape)
        assert healed._worker is not None
        assert healed._worker.engine.cache_stats["hit"] == spec.lattice().warmup_points(
            prefix_cache=True
        )
        # drain the survivor so the next request MUST run on the respawned
        # replica — and its output must still be bitwise-correct
        router.drain("r1")
        p_new = _prompts(9, (8,))[0]
        req_new = router.submit(p_new, 8, rng_seed=42)
        done = router.run(timeout_s=240)
        assert req_new.status is RouterRequestStatus.FINISHED, req_new.error
        assert req_new.replica == "r0"
        ref = greedy_generate(params, p_new[None], CONFIG, max_new_tokens=8)
        assert np.array_equal(np.asarray(ref[0]), req_new.output_ids())
    finally:
        if router is not None:
            router.close()


def test_router_self_heal_never_resurrects_drained_replica():
    """drain() is a requested scale-down: a drained replica that then dies
    must stay dead — self-heal respawning it would undo the operator's
    decommission."""
    clock = FakeClock()
    reps = [RespawnableFake("r0"), RespawnableFake("r1")]
    router = ServingRouter(
        reps, clock=clock, self_heal=True, max_respawns_per_replica=3,
        respawn_backoff_base_s=0.0,
    )
    router.drain("r0")
    reps[0].die()
    router.poll()
    assert router.replicas["r0"] is reps[0]  # not replaced
    assert router.replicas["r0"].state is ReplicaState.DEAD
    assert router.respawns == 0
    # a CRASHED (never drained) replica still heals
    reps[1].die()
    router.poll()
    assert router.replicas["r1"].generation == 1
    # and queued work does not wait on the decommissioned one once the
    # healthy survivor exists
    req = router.submit(np.arange(4, dtype=np.int32), 4)
    router.poll()
    assert req.status is RouterRequestStatus.DISPATCHED


# ---------------------------------------------------------------------------
# bitwise correctness canaries (ISSUE 19, serving/canary.py)


def _canary_probe(**kw):
    golden = CanaryGolden(name="g0", prompt=(1, 2, 3), max_new_tokens=3,
                          expected=(7, 8, 9), rng_seed=5)
    kw.setdefault("interval_s", 1000.0)
    return CanaryProbe([golden], **kw)


def test_canary_probe_check_names_first_mismatch():
    g = CanaryGolden("g", (1,), 4, expected=(7, 8, 9, 10))
    assert CanaryProbe.check(g, [7, 8, 9, 10]) is None
    m = CanaryProbe.check(g, [7, 99, 9, 10])
    assert (m["mismatch_index"], m["expected_token"], m["got_token"]) == (1, 8, 99)
    short = CanaryProbe.check(g, [7, 8, 9])     # wrong length IS a mismatch
    assert short["mismatch_index"] == 3 and short["got_token"] is None
    assert (short["expected_len"], short["got_len"]) == (4, 3)


def test_canary_mismatch_drains_replica_and_match_does_not(tmp_path):
    """A scripted fleet: 'bad' answers the golden with a corrupted token,
    'good' answers bitwise-exact. The mismatch must emit canary +
    canary_failure records naming the differing token, drain the bad
    replica, and leave zero false positives on the healthy one — all
    invisible to the user-facing request counters."""
    from accelerate_tpu.telemetry import events as tel
    from accelerate_tpu.telemetry.report import build_report, format_report

    clock = FakeClock()
    bad, good = FakeReplica("bad"), FakeReplica("good")
    probe = _canary_probe()
    tel.enable(out_dir=str(tmp_path), run_id="canary")
    try:
        router = ServingRouter([bad, good], canary=probe, clock=clock)
        router.poll()
        # round-robin over sorted targets: the first probe lands on 'bad'
        assert bad.submitted and bad.submitted[0]["rid"] == "canary-1"
        assert bad.submitted[0]["prompt"] == [1, 2, 3]
        assert bad.submitted[0]["rng_seed"] == 5
        bad.push(event="done", rid="canary-1", status="finished",
                 tokens=[7, 99, 9])
        router.poll()
        assert bad.state is ReplicaState.DRAINING
        # next due probe can only target the healthy survivor
        clock.t += 1001.0
        router.poll()
        assert good.submitted and good.submitted[0]["rid"] == "canary-2"
        good.push(event="done", rid="canary-2", status="finished",
                  tokens=[7, 8, 9])
        router.poll()
        assert good.state is ReplicaState.HEALTHY
    finally:
        tel.disable()

    assert probe.stats() == {
        "probes": 2, "failures": 1,
        "by_replica": {"bad": {"probes": 1, "failures": 1},
                       "good": {"probes": 1, "failures": 0}},
    }
    stats = router.stats()
    assert stats["canary"]["failed_replicas"] == ["bad"]
    # canaries are invisible to the user-facing ledgers
    assert stats["completed"] == 0 and stats["shed"] == 0 and stats["failed"] == 0
    assert router.admission.depth == 0
    report = build_report([str(tmp_path)])
    sec = report["canary"]
    assert sec["probes"] == 2 and sec["failures"] == 1
    (mm,) = sec["mismatches"]
    assert mm["replica"] == "bad" and mm["mismatch_index"] == 1
    assert mm["expected_token"] == 8 and mm["got_token"] == 99 and mm["drained"]
    text = format_report(report)
    assert "canaries: 2 probe(s), 1 MISMATCH(ES)" in text
    assert "MISMATCH on bad: golden g0 token 1 expected 8 got 99" in text
    # router section shows the drained replica
    assert any("bad: draining" in line for line in text.splitlines())


def test_canary_failed_replica_loses_dispatch_ties():
    """With drain_on_failure=False the failed replica stays HEALTHY but
    joins the DRAINING-pressure set: user work prefers clean replicas at
    equal load, exactly like an SLO-burning replica."""
    clock = FakeClock()
    bad, good = FakeReplica("a-bad"), FakeReplica("b-good")
    probe = _canary_probe(drain_on_failure=False)
    router = ServingRouter([bad, good], canary=probe, clock=clock)
    router.poll()
    bad.push(event="done", rid="canary-1", status="finished", tokens=[0, 0, 0])
    router.poll()
    assert bad.state is ReplicaState.HEALTHY        # kept serving...
    req = router.submit(np.asarray([1, 2], np.int32), 2)
    router.poll()
    assert req.replica == "b-good"                  # ...but loses the tie
    assert router.stats()["canary"]["failed_replicas"] == ["a-bad"]


def test_canary_dropped_not_failed_over_on_replica_death(tmp_path):
    """A probe's job is to test THIS replica: when the replica dies with the
    probe inflight, the probe is dropped as inconclusive — never re-dispatched
    (failover would launder the evidence) and never counted as a mismatch."""
    from accelerate_tpu.telemetry import events as tel

    clock = FakeClock()
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    probe = _canary_probe()
    tel.enable(out_dir=str(tmp_path), run_id="canary-drop")
    try:
        router = ServingRouter([r0, r1], canary=probe, clock=clock)
        router.poll()
        assert r0.submitted and r0.submitted[0]["rid"] == "canary-1"
        r0.die()
        router.poll()
        assert r0.state is ReplicaState.DEAD
    finally:
        tel.disable()
    assert router.failovers == 0 and r1.submitted == []
    assert router.canary_inconclusive == 1
    assert probe.stats()["probes"] == 0             # no verdict recorded
    recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
    assert not [r for r in recs if r["kind"] == "canary_failure"]


def test_canary_engine_rejection_is_inconclusive(tmp_path):
    """A probe the engine rejects (pool/lattice cap) says nothing about
    token correctness: inconclusive, no verdict against the replica."""
    from accelerate_tpu.telemetry import events as tel

    clock = FakeClock()
    r0 = FakeReplica("r0")
    probe = _canary_probe()
    tel.enable(out_dir=str(tmp_path), run_id="canary-rej")
    try:
        router = ServingRouter([r0], canary=probe, clock=clock)
        router.poll()
        r0.push(event="done", rid="canary-1", status="rejected",
                error="prompt too long")
        router.poll()
    finally:
        tel.disable()
    assert r0.state is ReplicaState.HEALTHY
    assert router.canary_inconclusive == 1
    assert probe.stats()["probes"] == 0
    recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
    (canary_rec,) = [r for r in recs if r["kind"] == "canary"]
    assert canary_rec["result"] == "inconclusive"


def test_canary_real_fleet_corrupt_weights_drained_bitwise(tmp_path):
    """End to end against real thread-backed engines: the bad replica shares
    the spec but builds its params from a different seed — deterministic
    init makes that genuinely corrupt weights, so its canary answers diverge
    bitwise while the healthy replica's match (zero false positives)."""
    from accelerate_tpu.telemetry import events as tel

    spec = _spec()
    goldens = precompute_goldens(spec, max_new_tokens=4)
    assert goldens and all(len(g.expected) == 4 for g in goldens)
    probe = CanaryProbe(goldens, interval_s=0.05)
    tel.enable(out_dir=str(tmp_path), run_id="canary-real")
    router = None
    try:
        router = ServingRouter(
            [
                LocalReplica("good", spec),
                LocalReplica("bad", dataclasses.replace(spec, param_seed=1234)),
            ],
            canary=probe,
            health_timeout_s=10.0,
        )
        router.wait_ready(timeout_s=300)
        deadline = time.monotonic() + 300
        while (probe.by_replica.get("bad", {}).get("failures", 0) < 1
               or probe.by_replica.get("good", {}).get("probes", 0) < 1
               or router._inflight):
            router.poll()
            if time.monotonic() > deadline:
                raise AssertionError(f"canary probes stalled: {probe.stats()}")
            time.sleep(0.002)
    finally:
        if router is not None:
            router.close()
        tel.disable()
    assert router.replicas["bad"].state is ReplicaState.DRAINING
    assert probe.by_replica["bad"]["failures"] >= 1
    assert probe.by_replica["good"]["failures"] == 0
