"""Live observability plane tests (ISSUE 15): request-scoped distributed
tracing, the streaming metrics exporter, and SLO burn-rate monitoring.

The acceptance lines these tests hold:

- one request = ONE coherent span tree across router → replica → engine
  (admission/queue wait, dispatch, per-chunk prefill, batched decode steps,
  completion), across BOTH replica transports, with failover retry lineage
  (a chaos-killed replica's request shows two dispatch spans under one
  trace_id) — and ZERO cost when tracing is disarmed;
- the /metrics endpoint serves parseable Prometheus text whose histograms
  agree with the report CLI (same fixed-bucket math — the repo's ONE
  histogram/percentile implementation, ratcheted);
- SLO burn rates fire exactly one violation record per episode over the
  fast/slow window pair (synthetic clock).
"""

import dataclasses
import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import LlamaConfig, init_llama
from accelerate_tpu.serving import (
    AdmissionController,
    BucketLattice,
    LocalReplica,
    ProcessReplica,
    ReplicaSpec,
    RouterRequestStatus,
    ServingEngine,
    ServingRouter,
)
from accelerate_tpu.telemetry import events as tel
from accelerate_tpu.telemetry import metrics, slo, tracing

CONFIG = LlamaConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(CONFIG, jax.random.PRNGKey(0))
    )


@pytest.fixture(autouse=True)
def _clean_observability_state():
    """Every test starts and ends with the plane disarmed (module-level
    singletons, same discipline as the events tests)."""
    tracing.disarm()
    metrics.disable()
    tel.disable()
    yield
    tracing.disarm()
    metrics.disable()
    tel.disable()


def _replica_spec(**overrides) -> ReplicaSpec:
    kw = dict(
        model=dataclasses.asdict(CONFIG), num_blocks=33, block_size=8,
        max_slots=2, slot_buckets=(2,), block_buckets=(6,), prefill_buckets=(16,),
    )
    kw.update(overrides)
    return ReplicaSpec(**kw)


# ---------------------------------------------------------------------------
# histogram / percentile math (the shared implementation)


class TestHistogram:
    def test_bucket_counts_are_cumulative_with_inf_overflow(self):
        h = metrics.Histogram("h", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.01, 0.05, 0.5, 7.0):
            h.observe(v)
        # le is inclusive: 0.01 lands in its own bucket, 7.0 only in +Inf
        assert h.cumulative_counts() == [2, 3, 4]
        assert h.count == 5 and h.max == 7.0
        assert h.sum == pytest.approx(7.565)

    def test_quantile_interpolates_within_the_covering_bucket(self):
        h = metrics.Histogram("h", buckets=(1.0, 2.0, 4.0))
        h.observe_many([0.5] * 2 + [1.5] * 2)  # cumulative [2, 4, 4]
        # rank 2 sits exactly at the first bound; rank 3 is halfway into
        # (1, 2]
        assert h.quantile(0.5) == pytest.approx(1.0)
        assert h.quantile(0.75) == pytest.approx(1.5)
        # past the last finite bound: the honest answer is that bound
        h2 = metrics.Histogram("h2", buckets=(1.0,))
        h2.observe(5.0)
        assert h2.quantile(0.99) == 1.0

    def test_dict_roundtrip_preserves_quantiles(self):
        h = metrics.Histogram("h")
        h.observe_many([0.004, 0.03, 0.03, 0.4, 2.0, 80.0])
        rt = metrics.Histogram.from_dict("h", h.to_dict())
        assert rt.cumulative_counts() == h.cumulative_counts()
        for q in (0.5, 0.9, 0.99):
            assert rt.quantile(q) == pytest.approx(h.quantile(q))

    def test_hist_dist_matches_a_scrape_of_the_same_values(self):
        """The report-vs-scrape agreement in miniature: hist_dist (the
        serving/router report sections) and a parsed /metrics scrape of the
        same observations must compute identical percentiles."""
        values = [0.004, 0.031, 0.032, 0.41, 0.09, 0.02]
        reg = metrics.MetricsRegistry()
        reg.histogram("accelerate_x_seconds").observe_many(values)
        scraped = metrics.histogram_from_scrape(
            metrics.parse_prometheus_text(reg.render()), "accelerate_x_seconds"
        )
        dist = metrics.hist_dist(values)
        assert scraped.count == dist["count"]
        assert scraped.quantile(0.5) == pytest.approx(dist["p50"], abs=1e-9)
        assert scraped.quantile(0.99) == pytest.approx(dist["p99"], abs=1e-9)

    def test_percentile_is_nearest_rank(self):
        assert metrics.percentile([], 50) == 0.0
        assert metrics.percentile([3.0, 1.0, 2.0], 50) == 2.0
        assert metrics.percentile([1.0, 2.0, 3.0, 4.0], 99) == 4.0

    def test_no_private_percentile_helpers_remain(self):
        """ISSUE 15 ratchet (the PR 7 peak-registry pattern): the repo has
        exactly ONE percentile/histogram implementation —
        telemetry/metrics.py. A reintroduced private `def percentile` /
        `def _percentile` anywhere in shipped code is a regression."""
        import os
        import re

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pattern = re.compile(r"^\s*def\s+_?percentile\s*\(", re.M)
        offenders = []
        roots = ["accelerate_tpu", "benchmarks", "tools", "bench.py"]
        for root in roots:
            root_path = os.path.join(repo, root)
            files = (
                [root_path] if root_path.endswith(".py")
                else [
                    os.path.join(dirpath, f)
                    for dirpath, _, names in os.walk(root_path)
                    for f in names
                    if f.endswith(".py")
                ]
            )
            for path in files:
                if path.endswith(os.path.join("telemetry", "metrics.py")):
                    continue
                with open(path) as fh:
                    if pattern.search(fh.read()):
                        offenders.append(os.path.relpath(path, repo))
        assert offenders == [], (
            f"private percentile helpers reintroduced: {offenders} — "
            "import telemetry.metrics.percentile instead"
        )


# ---------------------------------------------------------------------------
# metrics registry + exporter


class TestMetricsExporter:
    def test_prometheus_text_format_golden(self):
        """The exposition format is a wire contract — hold it to a golden."""
        reg = metrics.MetricsRegistry()
        reg.counter("accelerate_requests_total").inc(3, outcome="finished")
        reg.counter("accelerate_requests_total").inc(1, outcome="shed")
        reg.gauge("accelerate_queue_depth").set(4)
        reg.histogram("accelerate_ttft_seconds", buckets=(0.1, 1.0)).observe_many(
            [0.05, 0.5, 0.5]
        )
        assert reg.render() == (
            "# HELP accelerate_queue_depth \n"
            "# TYPE accelerate_queue_depth gauge\n"
            "accelerate_queue_depth 4\n"
            "# HELP accelerate_requests_total \n"
            "# TYPE accelerate_requests_total counter\n"
            'accelerate_requests_total{outcome="finished"} 3\n'
            'accelerate_requests_total{outcome="shed"} 1\n'
            "# HELP accelerate_ttft_seconds \n"
            "# TYPE accelerate_ttft_seconds histogram\n"
            'accelerate_ttft_seconds_bucket{le="0.1"} 1\n'
            'accelerate_ttft_seconds_bucket{le="1"} 3\n'
            'accelerate_ttft_seconds_bucket{le="+Inf"} 3\n'
            "accelerate_ttft_seconds_sum 1.05\n"
            "accelerate_ttft_seconds_count 3\n"
        )

    def test_http_endpoint_serves_and_parses(self):
        metrics.enable()
        metrics.observe("accelerate_ttft_seconds", 0.02)
        metrics.inc("accelerate_requests_total", outcome="finished")
        try:
            metrics.serve(0)
            port = metrics.server_port()
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ).read().decode()
            families = metrics.parse_prometheus_text(body)
            assert families["accelerate_requests_total"]["type"] == "counter"
            hist = metrics.histogram_from_scrape(families, "accelerate_ttft_seconds")
            assert hist is not None and hist.count == 1
            # non-metrics paths 404
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"http://127.0.0.1:{port}/nope", timeout=10)
        finally:
            metrics.disable()
        assert metrics.server_port() is None

    def test_healthz_readiness_endpoint(self):
        """``/healthz`` answers 200 while the exporter is live and 503 the
        moment shutdown begins — the readiness flag flips BEFORE the socket
        dies, so a probe racing stop_server() sees not-ready instead of a
        connection reset, and a re-serve() re-arms readiness."""
        metrics.enable()
        try:
            metrics.serve(0)
            port = metrics.server_port()
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            )
            assert resp.status == 200 and resp.read() == b"ok\n"
            # the shutdown window: readiness flips first, socket still up
            metrics._SHUTTING_DOWN = True
            try:
                with pytest.raises(urllib.error.HTTPError) as excinfo:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=10
                    )
                assert excinfo.value.code == 503
                assert excinfo.value.read() == b"shutting down\n"
            finally:
                metrics._SHUTTING_DOWN = False
            metrics.stop_server()
            assert metrics.server_port() is None
            # a fresh serve() must not inherit the stale shutdown flag
            metrics.serve(0)
            port = metrics.server_port()
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            )
            assert resp.status == 200
        finally:
            metrics.disable()

    def test_snapshot_record_lands_in_telemetry(self, tmp_path):
        tel.enable(out_dir=str(tmp_path), run_id="m")
        metrics.enable()
        metrics.inc("accelerate_decode_tokens_total", 7)
        metrics.observe("accelerate_ttft_seconds", 0.2)
        metrics.snapshot_now()
        tel.disable()
        recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
        snaps = [r for r in recs if r["kind"] == "metrics"]
        assert len(snaps) == 1
        payload = snaps[0]["metrics"]
        assert payload["accelerate_decode_tokens_total"]["value"] == 7
        assert payload["accelerate_ttft_seconds"]["count"] == 1
        # a persisted histogram rebuilds into the same quantile math
        h = metrics.Histogram.from_dict(
            "accelerate_ttft_seconds", payload["accelerate_ttft_seconds"]
        )
        assert h.quantile(0.5) > 0

    def test_maybe_snapshot_is_throttled(self, tmp_path, monkeypatch):
        monkeypatch.setenv(metrics.METRICS_SNAPSHOT_ENV_VAR, "3600")
        tel.enable(out_dir=str(tmp_path), run_id="m")
        metrics.enable()
        metrics.inc("x_total")
        # explicit clock readings near zero: a freshly booted machine, where
        # time.monotonic() is smaller than the interval, must still snapshot
        assert metrics.maybe_snapshot(now=5.0) is True
        assert metrics.maybe_snapshot(now=3000.0) is False  # inside the interval
        tel.disable()
        recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
        assert sum(1 for r in recs if r["kind"] == "metrics") == 1

    def test_port_env_arms_registry_and_server(self, monkeypatch):
        monkeypatch.setenv(metrics.METRICS_PORT_ENV_VAR, "0")
        try:
            assert metrics.maybe_enable_from_env() is not None
            assert metrics.server_port() is not None
        finally:
            metrics.disable()

    def test_label_values_escape_and_roundtrip(self):
        """Label values are user-controlled (replica names): quotes, commas,
        backslashes and newlines must render as valid exposition and parse
        back to the original value."""
        reg = metrics.MetricsRegistry()
        hostile = 'r"0,\\weird\nname'
        reg.counter("accelerate_replica_deaths_total").inc(2, replica=hostile)
        text = reg.render()
        sample_lines = [l for l in text.splitlines() if not l.startswith("#")]
        assert len(sample_lines) == 1  # the raw newline was escaped, not emitted
        fams = metrics.parse_prometheus_text(text)
        samples = fams["accelerate_replica_deaths_total"]["samples"]
        (name, labels, value), = samples
        assert labels == {"replica": hostile} and value == 2

    def test_serve_never_crashes_on_bind_conflict_or_port_change(self):
        """A bind failure (a child inheriting the parent's fixed port) must
        degrade to registry-only with a warning, and a second serve() on a
        different port must warn instead of silently lying about where the
        exporter listens."""
        import socket

        blocker = socket.socket()
        blocker.bind(("127.0.0.1", 0))
        taken = blocker.getsockname()[1]
        try:
            with pytest.warns(UserWarning, match="could not bind"):
                assert metrics.serve(taken) is None
            assert metrics.get_registry() is not None  # armed despite the miss
            assert metrics.server_port() is None
            first = metrics.serve(0)
            assert first is not None
            with pytest.warns(UserWarning, match="already bound"):
                assert metrics.serve(taken) is first  # kept, loudly
        finally:
            blocker.close()
            metrics.disable()

    def test_process_replica_child_env_drops_the_metrics_port(self, monkeypatch):
        """ProcessReplica children must NOT inherit ACCELERATE_METRICS_PORT:
        the router host owns the scrape endpoint, and N children racing one
        fixed port would each degrade to a warning serving nobody."""
        import io

        from accelerate_tpu.serving import replica as replica_mod

        captured = {}

        class _FakeProc:
            stdout = io.StringIO("")

            def __init__(self, cmd, env=None, **kw):
                captured["env"] = env

            stdin = io.StringIO()

            def poll(self):
                return None

            def kill(self):
                pass

        monkeypatch.setattr(
            replica_mod.subprocess, "Popen", lambda *a, **kw: _FakeProc(a, **kw)
        )
        monkeypatch.setenv(metrics.METRICS_PORT_ENV_VAR, "9102")
        ProcessReplica("p", _replica_spec())
        assert metrics.METRICS_PORT_ENV_VAR not in captured["env"]
        assert replica_mod.REPLICA_SPEC_ENV_VAR in captured["env"]


# ---------------------------------------------------------------------------
# tracing: span model + propagation


class TestTracing:
    def test_span_tree_validation_catches_gaps(self):
        tracing.arm(1.0)
        ctx = tracing.new_trace()
        root = tracing.span_open(ctx, "request")
        child = tracing.span_open(ctx, "work", parent_id=root["span_id"])
        tracing.span_close(child)
        tracing.span_close(root)
        assert tracing.validate_span_tree([root, child]) == []
        # orphan parent
        orphan = dict(child, parent_id="deadbeef", span_id="f00d")
        assert any("orphaned" in p for p in tracing.validate_span_tree([root, orphan]))
        # two roots
        root2 = tracing.span_close(tracing.span_open(ctx, "request2"))
        assert any("root" in p for p in tracing.validate_span_tree([root, root2]))
        # never closed
        open_span = tracing.span_open(ctx, "hang", parent_id=root["span_id"])
        assert any("never closed" in p
                   for p in tracing.validate_span_tree([root, open_span]))

    def test_sampling_is_deterministic_per_trace_and_forced_emit_wins(self, tmp_path):
        tracing.arm(0.5)
        kept = [tracing.new_trace().sampled for _ in range(400)]
        assert 0.35 < sum(kept) / len(kept) < 0.65
        # an unsampled trace still emits when forced (the SHED/FAILED path)
        tel.enable(out_dir=str(tmp_path), run_id="t")
        ctx = tracing.new_trace(sampled=False)
        span = tracing.span_close(tracing.span_open(ctx, "request"))
        assert tracing.finish_trace(ctx, [span]) is False
        assert tracing.finish_trace(ctx, [span], forced=True) is True
        tel.disable()
        recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
        assert sum(1 for r in recs if r["kind"] == "span") == 1

    def test_arm_from_env(self, monkeypatch):
        monkeypatch.setenv(tracing.TRACE_SAMPLE_ENV_VAR, "0.25")
        assert tracing.maybe_arm_from_env() == 0.25
        tracing.disarm()
        monkeypatch.setenv(tracing.TRACE_SAMPLE_ENV_VAR, "garbage")
        assert tracing.maybe_arm_from_env() is None
        monkeypatch.setenv(tracing.TRACE_SAMPLE_ENV_VAR, "1")
        assert tracing.maybe_arm_from_env() == 1.0

    def test_chrome_trace_export_shape(self):
        tracing.arm(1.0)
        ctx = tracing.new_trace()
        root = tracing.span_close(tracing.span_open(ctx, "request", component="router"))
        out = tracing.chrome_trace([root])
        events = [e for e in out["traceEvents"] if e["ph"] == "X"]
        assert events[0]["name"] == "request" and events[0]["ts"] >= 0
        assert any(e["ph"] == "M" for e in out["traceEvents"])  # lane names


class TestEngineTracing:
    def test_engine_spans_cover_queue_prefill_chunks_and_decode(self, params, tmp_path):
        tel.enable(out_dir=str(tmp_path), run_id="eng")
        tracing.arm(1.0)
        engine = ServingEngine(
            params, CONFIG, num_blocks=33, block_size=8, max_slots=4,
            lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(8,),
                                  prefill_buckets=(16, 32)),
        )
        engine.warmup()
        req = engine.submit(np.arange(1, 40, dtype=np.int32), 5)  # chunks past 32
        engine.run()
        tel.disable()
        assert req._trace_owner
        assert tracing.validate_span_tree(req.trace_spans) == []
        names = [s["name"] for s in req.trace_spans]
        assert names.count("prefill_chunk") == 2  # 32-bucket chunk + 16-bucket tail
        assert names.count("decode_step") == 4  # 5 tokens, first from prefill
        chunk_buckets = [
            s["attrs"]["bucket"] for s in req.trace_spans if s["name"] == "prefill_chunk"
        ]
        assert chunk_buckets == [32, 16]
        # the engine owned the trace: every span is in the event stream
        recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
        assert sum(1 for r in recs if r["kind"] == "span") == len(req.trace_spans)

    def test_prefix_cache_annotations_ride_the_prefill_span(self, params):
        tracing.arm(1.0)
        engine = ServingEngine(
            params, CONFIG, num_blocks=65, block_size=8, max_slots=4,
            lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(8,),
                                  prefill_buckets=(32,)),
            prefix_cache=True,
        )
        engine.warmup()
        rng = np.random.default_rng(3)
        shared = rng.integers(0, CONFIG.vocab_size, (24,)).astype(np.int32)
        a = engine.submit(np.concatenate([shared, np.arange(5, dtype=np.int32)]), 4,
                          rng_seed=0)
        engine.step()
        b = engine.submit(np.concatenate([shared, np.arange(9, dtype=np.int32)]), 4,
                          rng_seed=1)
        engine.run()
        prefill_a = next(s for s in a.trace_spans if s["name"] == "prefill")
        prefill_b = next(s for s in b.trace_spans if s["name"] == "prefill")
        assert prefill_a["attrs"]["cached_tokens"] == 0
        assert prefill_b["attrs"]["cached_tokens"] == 24  # the shared 3 blocks

    def test_unsampled_trace_skips_per_token_spans(self, params):
        """The sampling knob bounds RECORDING cost, not just emission: an
        unsampled context keeps only the cheap structural spans (root/queue/
        prefill) — no decode_step dict per generated token."""
        tracing.arm(1.0)
        engine = ServingEngine(
            params, CONFIG, num_blocks=17, block_size=8, max_slots=2,
            lattice=BucketLattice(slot_buckets=(2,), block_buckets=(4,),
                                  prefill_buckets=(16,)),
        )
        engine.warmup()
        ctx = tracing.new_trace(sampled=False)
        req = engine.submit(np.arange(1, 6, dtype=np.int32), 6, trace=dict(ctx))
        engine.run()
        names = [s["name"] for s in req.trace_spans]
        assert "decode_step" not in names
        assert "prefill" in names and "engine_request" in names
        # a sampled ctx on the same engine records the full detail
        req2 = engine.submit(
            np.arange(1, 6, dtype=np.int32), 6,
            trace=dict(tracing.new_trace(sampled=True)),
        )
        engine.run()
        assert [s["name"] for s in req2.trace_spans].count("decode_step") == 5

    def test_disabled_path_zero_cost(self, params, tmp_path, monkeypatch):
        """Tracing/metrics disarmed: no context, no spans, no registry, no
        exporter thread, no files — the hot-path additions are one branch
        (the PR 4/7 smoke pattern)."""
        monkeypatch.chdir(tmp_path)
        before = {t.name for t in threading.enumerate()}
        engine = ServingEngine(
            params, CONFIG, num_blocks=17, block_size=8, max_slots=2,
            lattice=BucketLattice(slot_buckets=(2,), block_buckets=(4,),
                                  prefill_buckets=(16,)),
        )
        engine.warmup()
        req = engine.submit(np.arange(1, 6, dtype=np.int32), 3)
        engine.run()
        assert req.trace is None and req.trace_spans == []
        assert req._span_root is None and not req._trace_owner
        assert metrics.get_registry() is None
        assert metrics.server_port() is None
        assert not tracing.is_armed()
        after = {t.name for t in threading.enumerate()}
        assert "accelerate-tpu-metrics" not in after - before
        assert not list(tmp_path.iterdir())  # no artifacts anywhere


# ---------------------------------------------------------------------------
# cross-transport propagation + failover continuity


class TestRouterTracing:
    def test_local_replica_failover_keeps_one_trace_with_two_dispatch_spans(self):
        """Trace continuity through an abrupt replica death (thread
        transport): the retried request's tree stays gap-free and shows its
        retry lineage — two dispatch spans, one trace_id, the first closed
        ``failover`` and the last ``finished``."""
        tracing.arm(1.0)
        router = ServingRouter(
            [LocalReplica(f"r{i}", _replica_spec()) for i in range(2)],
            admission=AdmissionController(max_queue=16),
            health_timeout_s=5.0,
        )
        try:
            router.wait_ready(timeout_s=300)
            rng = np.random.default_rng(0)
            reqs = [
                router.submit(
                    rng.integers(0, CONFIG.vocab_size, (8,)).astype(np.int32),
                    24, rng_seed=i,
                )
                for i in range(4)
            ]
            deadline = time.monotonic() + 120
            while not any(len(r.generated) >= 2 for r in reqs):
                router.poll()
                time.sleep(0.002)
                assert time.monotonic() < deadline, "no tokens flowed"
            router.replicas["r0"].kill()
            router.run(timeout_s=300)
        finally:
            router.close()
        assert router.failovers >= 1
        assert all(r.status is RouterRequestStatus.FINISHED for r in reqs)
        for r in reqs:
            assert tracing.validate_span_tree(r.trace_spans) == []
        retried = [r for r in reqs if r.retries > 0]
        assert retried
        for r in retried:
            assert len({s["trace_id"] for s in r.trace_spans}) == 1
            dispatches = [s for s in r.trace_spans if s["name"] == "dispatch"]
            assert len(dispatches) >= 2
            outcomes = [s["attrs"].get("outcome") for s in dispatches]
            assert "failover" in outcomes and outcomes[-1] == "finished"
            assert [s["attrs"]["attempt"] for s in dispatches] == list(
                range(len(dispatches))
            )

    def test_process_replica_propagates_context_and_ships_spans(self):
        """The JSON-lines transport carries the context out and the spans
        back: a ProcessReplica child (its own OS process) parents its engine
        spans under the router's dispatch span."""
        tracing.arm(1.0)
        router = ServingRouter(
            [ProcessReplica("p0", _replica_spec(), env=dict(
                __import__("os").environ, JAX_PLATFORMS="cpu"
            ))],
            admission=AdmissionController(max_queue=8),
            health_timeout_s=120.0,
        )
        try:
            router.wait_ready(timeout_s=300)
            req = router.submit(np.arange(1, 9, dtype=np.int32), 4, rng_seed=0)
            router.run(timeout_s=300)
        finally:
            router.close()
        assert req.status is RouterRequestStatus.FINISHED
        assert tracing.validate_span_tree(req.trace_spans) == []
        names = [s["name"] for s in req.trace_spans]
        for want in ("request", "admission", "dispatch", "engine_request",
                     "queue_wait", "prefill", "decode_step"):
            assert want in names, (want, names)
        dispatch = next(s for s in req.trace_spans if s["name"] == "dispatch")
        engine_root = next(s for s in req.trace_spans if s["name"] == "engine_request")
        assert engine_root["parent_id"] == dispatch["span_id"]
        assert engine_root["trace_id"] == dispatch["trace_id"]

    @pytest.mark.slow  # real SIGKILL needs a second warmed child process
    def test_process_replica_sigkill_failover_trace_continuity(self):
        """The ISSUE 15 tier: a seeded chaos SIGKILL takes a ProcessReplica
        down mid-decode; the survivor finishes the work and the retried
        request's trace shows both dispatch hops under one trace_id."""
        import os

        from accelerate_tpu.resilience.chaos import ChaosSchedule, Fault

        tracing.arm(1.0)
        schedule = ChaosSchedule(
            faults=[Fault(kind="sigkill", point="serving_decode", step=6)]
        ).to_json()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        router = ServingRouter(
            [
                ProcessReplica("k0", _replica_spec(), chaos_schedule=schedule, env=env),
                ProcessReplica("k1", _replica_spec(), env=env),
            ],
            admission=AdmissionController(max_queue=16),
            health_timeout_s=120.0,
        )
        try:
            router.wait_ready(timeout_s=600)
            rng = np.random.default_rng(1)
            reqs = [
                router.submit(
                    rng.integers(0, CONFIG.vocab_size, (8,)).astype(np.int32),
                    16, rng_seed=i,
                )
                for i in range(4)
            ]
            router.run(timeout_s=600)
        finally:
            router.close()
        assert router.failovers >= 1
        assert all(r.status is RouterRequestStatus.FINISHED for r in reqs)
        retried = [r for r in reqs if r.retries > 0]
        assert retried
        for r in retried:
            assert tracing.validate_span_tree(r.trace_spans) == []
            assert len({s["trace_id"] for s in r.trace_spans}) == 1
            assert sum(1 for s in r.trace_spans if s["name"] == "dispatch") >= 2

    def test_shed_request_trace_is_force_emitted(self, tmp_path):
        """SHED/FAILED traces are kept even when unsampled — the requests an
        operator is guaranteed to ask about."""
        tel.enable(out_dir=str(tmp_path), run_id="shed")
        tracing.arm(0.000001)  # nothing would survive sampling
        router = ServingRouter(
            [LocalReplica("r0", _replica_spec())],
            admission=AdmissionController(max_queue=1),
            health_timeout_s=30.0,
        )
        try:
            router.wait_ready(timeout_s=300)
            small = np.arange(4, dtype=np.int32) + 1
            keep = [router.submit(small, 4, rng_seed=i) for i in range(3)]
            shed = [r for r in keep if r.status is RouterRequestStatus.SHED]
            assert shed  # queue bound 1: the overflow shed at submit
            router.run(timeout_s=300)
        finally:
            router.close()
        tel.disable()
        recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
        spans = [r for r in recs if r["kind"] == "span"]
        shed_roots = [
            s for s in spans
            if not s.get("parent_id") and s.get("attrs", {}).get("outcome") == "shed"
        ]
        assert len(shed_roots) == len(shed)

    def test_router_disabled_path_zero_cost(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        router = ServingRouter(
            [LocalReplica("r0", _replica_spec())],
            admission=AdmissionController(max_queue=8),
        )
        try:
            router.wait_ready(timeout_s=300)
            req = router.submit(np.arange(1, 6, dtype=np.int32), 3, rng_seed=0)
            router.run(timeout_s=300)
        finally:
            router.close()
        assert req.status is RouterRequestStatus.FINISHED
        assert req.trace is None and req.trace_spans == []
        assert metrics.get_registry() is None
        assert not list(tmp_path.iterdir())


# ---------------------------------------------------------------------------
# SLO burn rates (synthetic clock)


class TestSLO:
    def _monitor(self, clock, **kw):
        objective = slo.SLObjective(
            name="ttft", kind="latency", threshold_s=0.1, target=0.99,
            fast_window_s=300.0, slow_window_s=3600.0, burn_threshold=14.4,
        )
        return slo.SLOMonitor([objective], clock=clock, **kw)

    def test_violation_needs_both_windows_and_min_events(self):
        clock = [0.0]
        mon = self._monitor(lambda: clock[0], min_events=10)
        # below min_events: even 100% bad must not page
        for _ in range(5):
            clock[0] += 1
            mon.observe("ttft", value=9.0)
        assert not mon.evaluate(emit=False)[0]["violating"]
        for _ in range(10):
            clock[0] += 1
            mon.observe("ttft", value=9.0)
        rec = mon.evaluate(emit=False)[0]
        assert rec["violating"] and rec["fast_burn"] >= 14.4 <= rec["slow_burn"]

    def test_one_record_per_episode_with_fast_window_recovery(self, tmp_path):
        clock = [0.0]
        mon = self._monitor(lambda: clock[0], min_events=5)
        tel.enable(out_dir=str(tmp_path), run_id="slo")
        for _ in range(10):
            clock[0] += 1
            mon.observe("ttft", value=9.0)
        mon.evaluate()
        mon.evaluate()  # still burning: same episode, no second record
        assert mon.stats()["ttft"]["violations"] == 1
        # fast window ages the bad events out under good traffic -> re-arm
        for _ in range(40):
            clock[0] += 15
            mon.observe("ttft", value=0.01)
        assert not mon.evaluate()[0]["violating"]
        for _ in range(10):
            clock[0] += 1
            mon.observe("ttft", value=9.0)
        mon.evaluate()
        assert mon.stats()["ttft"]["violations"] == 2
        tel.disable()
        recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
        violations = [r for r in recs if r["kind"] == "slo_violation"]
        assert len(violations) == 2
        assert violations[0]["slo"] == "ttft" and violations[0]["fast_burn"] > 14.4

    def test_fast_blip_alone_does_not_violate_slow_window(self):
        """The multi-window point: a burst that saturates the fast window
        but is diluted across the slow one must not page."""
        clock = [0.0]
        mon = self._monitor(lambda: clock[0], min_events=10)
        # 3000 good events spread over 50 minutes
        for _ in range(3000):
            clock[0] += 1
            mon.observe("ttft", value=0.01)
        # a 60-event bad blip at the end: ~20% of the fast window is bad
        # (burn 20x), but the slow window still holds the 3000 good events
        # (burn ~2x) — no page
        for _ in range(60):
            clock[0] += 1
            mon.observe("ttft", value=9.0)
        rec = mon.evaluate(emit=False)[0]
        assert rec["fast_burn"] >= 14.4
        assert rec["slow_burn"] < 14.4
        assert not rec["violating"]

    def test_burning_sources_attributes_the_bad_replica(self):
        clock = [0.0]
        mon = self._monitor(lambda: clock[0], min_events=5)
        for _ in range(10):
            clock[0] += 1
            mon.observe("ttft", value=0.01, source="r0")
            mon.observe("ttft", value=9.0, source="r1")
        assert mon.burning_sources("ttft") == ["r1"]

    def test_router_deprioritizes_burning_replica(self):
        """The DRAINING-pressure hook: with r0 burning its ttft window, new
        dispatch prefers r1 even when r0 has fewer outstanding tokens."""
        monitor = slo.SLOMonitor(
            slo.serving_slos(ttft_threshold_s=0.1), min_events=2,
        )
        router = ServingRouter(
            [LocalReplica(f"r{i}", _replica_spec()) for i in range(2)],
            admission=AdmissionController(max_queue=8),
            slo_monitor=monitor,
            slo_eval_interval_s=0.0,
        )
        try:
            router.wait_ready(timeout_s=300)
            for _ in range(6):
                monitor.observe("ttft", value=9.0, source="r0")
            router.poll()
            assert router._burning_replicas == {"r0"}
            req = router.submit(np.arange(1, 6, dtype=np.int32), 3, rng_seed=0)
            router.poll()
            assert req.replica == "r1"
            router.run(timeout_s=300)
        finally:
            router.close()

    def test_failover_survivor_is_not_blamed_for_inflated_ttft(self):
        """A failed-over request's ttft was inflated by the DEAD replica
        (death detection + re-prefill); attributing it to the survivor
        would drain exactly the replica that absorbed the work. Retried
        requests count toward the global burn only (source=None)."""
        from accelerate_tpu.serving.router import RouterRequest, RouterRequestStatus

        monitor = slo.SLOMonitor(slo.serving_slos(ttft_threshold_s=0.1), min_events=2)
        router = ServingRouter(
            [LocalReplica("r1", _replica_spec())],
            admission=AdmissionController(max_queue=4),
            slo_monitor=monitor,
        )
        try:
            router.wait_ready(timeout_s=300)
            req = RouterRequest(prompt=np.arange(1, 5, dtype=np.int32), max_new_tokens=4)
            req.replica = "r1"       # the SURVIVOR that finished the work
            req.retries = 1          # ...after a failover
            req.first_token_t = 9.0  # inflated by the dead replica's hop
            req.arrival_t = 0.0
            for _ in range(4):
                router._observe_slo(req, RouterRequestStatus.FINISHED, now=9.5)
            assert monitor.burning_sources("ttft", now=9.5) == []  # r1 not blamed
            # same events on an UN-retried request DO attribute
            req.retries = 0
            for _ in range(4):
                router._observe_slo(req, RouterRequestStatus.FINISHED, now=9.5)
            assert monitor.burning_sources("ttft", now=9.5) == ["r1"]
        finally:
            router.close()

    def test_stock_serving_slos_env_tuning(self, monkeypatch):
        monkeypatch.setenv(slo.SLO_TTFT_ENV_VAR, "0.25")
        monkeypatch.setenv(slo.SLO_AVAILABILITY_TARGET_ENV_VAR, "0.95")
        objectives = {o.name: o for o in slo.serving_slos()}
        assert objectives["ttft"].threshold_s == 0.25
        assert objectives["availability"].target == 0.95

    def test_accelerator_arms_step_latency_slo_from_env(self, monkeypatch):
        """ACCELERATE_SLO_STEP_LATENCY_S arms the Accelerator's step monitor
        (observe-per-step, evaluate-per-second); unset leaves the hot path a
        None-check. The end-to-end violation firing is proven by the
        supervisor test below (same monitor machinery)."""
        from accelerate_tpu import Accelerator

        acc = Accelerator()
        assert acc._step_slo_monitor is None
        monkeypatch.setenv(slo.SLO_STEP_LATENCY_ENV_VAR, "0.5")
        acc2 = Accelerator()
        mon = acc2._step_slo_monitor
        assert mon is not None and "step_latency" in mon.objectives
        assert mon.objectives["step_latency"].threshold_s == 0.5
        monkeypatch.setenv(slo.SLO_STEP_LATENCY_ENV_VAR, "garbage")
        assert Accelerator()._step_slo_monitor is None

    def test_supervisor_restart_downtime_slo_record(self, tmp_path, monkeypatch):
        """Training-side: a supervised child that dies once emits a restart
        record; with the downtime objective armed (tight threshold), the
        supervisor writes an slo_violation next to it."""
        import sys

        from accelerate_tpu.resilience.supervisor import RestartPolicy, Supervisor

        monkeypatch.setenv(slo.SLO_RESTART_DOWNTIME_ENV_VAR, "0.000001")
        done = tmp_path / "DONE"
        child = (
            "import os, signal\n"
            "if os.environ.get('ACCELERATE_RESTART_GENERATION', '0') == '0':\n"
            "    os.kill(os.getpid(), signal.SIGKILL)\n"
            f"open({str(done)!r}, 'w').write('ok')\n"
        )
        sup = Supervisor(
            [[sys.executable, "-c", child]],
            policy=RestartPolicy(max_restarts=2, backoff_base_s=0.05,
                                 grace_period_s=1.0),
            telemetry_dir=str(tmp_path),
        )
        assert sup.run() == 0
        recs = [
            json.loads(l) for l in open(tmp_path / "events-supervisor.jsonl")
        ]
        violations = [r for r in recs if r["kind"] == "slo_violation"]
        assert len(violations) == 1
        assert violations[0]["slo"] == "restart_downtime"
        assert violations[0]["generation"] == 1


# ---------------------------------------------------------------------------
# report CLI: SLO section, --request timeline, --trace-out


class TestReportIntegration:
    def _traced_run(self, params, out_dir):
        tel.enable(out_dir=str(out_dir), run_id="rep")
        tracing.arm(1.0)
        metrics.enable()
        engine = ServingEngine(
            params, CONFIG, num_blocks=33, block_size=8, max_slots=4,
            lattice=BucketLattice(slot_buckets=(2, 4), block_buckets=(8,),
                                  prefill_buckets=(32,)),
        )
        engine.warmup()
        reqs = [
            engine.submit(np.arange(1, 8 + i, dtype=np.int32), 4 + i, rng_seed=i)
            for i in range(2)
        ]
        engine.run()
        metrics.snapshot_now()
        tel.disable()
        return engine, reqs

    def test_request_timeline_and_chrome_export(self, params, tmp_path, capsys):
        from accelerate_tpu.telemetry.report import main as report_main

        _, reqs = self._traced_run(params, tmp_path)
        rid = reqs[0].rid
        trace_out = tmp_path / "t.json"
        assert report_main([
            "report", str(tmp_path), "--request", str(rid),
            "--trace-out", str(trace_out),
        ]) == 0
        out = capsys.readouterr().out
        assert f"request {rid}" in out
        for stage in ("engine_request", "queue_wait", "prefill", "decode_step"):
            assert stage in out
        assert "WARNING" not in out  # the tree is gap-free
        chrome = json.loads(trace_out.read_text())
        assert chrome["traceEvents"] and any(
            e.get("name") == "prefill" for e in chrome["traceEvents"]
        )
        # unknown rid: helpful failure naming what IS traced
        assert report_main(["report", str(tmp_path), "--request", "nope"]) == 1
        assert "no trace found" in capsys.readouterr().out

    def test_report_serving_ttft_matches_registry_histogram(self, params, tmp_path):
        """The scrape-vs-report acceptance line at unit scale: the serving
        section's ttft percentiles equal the registry histogram's quantiles
        over the same run (both are the shared fixed-bucket math)."""
        from accelerate_tpu.telemetry.report import build_report

        engine, reqs = self._traced_run(params, tmp_path)
        hist = metrics.get_registry().histogram("accelerate_engine_ttft_seconds")
        report = build_report([str(tmp_path)])
        ttft = report["serving"]["requests"]["ttft_s"]
        assert hist.count == ttft["count"] == len(reqs)
        # records round at 1e-6: agree to that precision
        assert hist.quantile(0.50) == pytest.approx(ttft["p50"], abs=2e-6)
        assert hist.quantile(0.99) == pytest.approx(ttft["p99"], abs=2e-6)

    def test_slo_section_renders(self, tmp_path):
        from accelerate_tpu.telemetry.report import build_report, format_report

        (tmp_path / "events-rank0.jsonl").write_text(
            json.dumps({"kind": "meta", "schema": 1, "run_id": "s",
                        "process_index": 0, "num_processes": 1}) + "\n"
            + json.dumps({
                "kind": "slo_violation", "t": 1.0, "slo": "ttft",
                "slo_kind": "latency", "target": 0.99, "threshold_s": 0.25,
                "fast_burn": 33.0, "slow_burn": 20.0, "fast_window_s": 300.0,
                "slow_window_s": 3600.0, "burn_threshold": 14.4,
                "violating": True,
            }) + "\n"
        )
        report = build_report([str(tmp_path)])
        section = report["slo"]
        assert section["violations"] == 1
        assert section["by_slo"]["ttft"]["worst_fast_burn"] == 33.0
        text = format_report(report)
        assert "SLO: 1 violation episode(s)" in text
        assert "ttft: 1 episode(s)" in text and "99.00% good @ 250ms" in text

    def test_report_without_slo_or_spans_omits_sections(self, tmp_path):
        from accelerate_tpu.telemetry.report import build_report, format_report

        (tmp_path / "events-rank0.jsonl").write_text(
            '{"kind": "meta", "schema": 1, "run_id": "r", "process_index": 0, '
            '"num_processes": 1}\n'
            # a legacy EventLog.span TIMING record (no trace_id) must not
            # read as a request trace
            '{"kind": "span", "t": 1.0, "name": "my_region", "dur_s": 0.5}\n'
        )
        report = build_report([str(tmp_path)])
        assert report["slo"] is None and report["traces"] == 0
        text = format_report(report)
        assert "SLO:" not in text and "traces:" not in text


# ---------------------------------------------------------------------------
# PR 25: the request's span tree and its stamps are one set of clock reads;
# the five Pallas kernels carry the names a device trace tells them apart by


class TestStampsAndSpansAgree:
    def test_armed_span_tree_is_valid_and_its_edges_are_the_request_stamps(self, params):
        """``queue_wait`` ends at ``admit_t``, the first ``prefill`` ends at
        ``first_token_t`` (after the token's sync, so the chunks lie inside
        it) and the root ends at ``finish_t``: the spans are closed with the
        very reads the stamps come from, not with reads of their own."""
        tracing.arm(1.0)
        engine = ServingEngine(
            params, CONFIG, num_blocks=10, block_size=8, max_slots=4, max_blocks_per_seq=8,
            lattice=BucketLattice(slot_buckets=(1, 2, 4), block_buckets=(4, 8),
                                  prefill_buckets=(16, 32)),
        )
        engine.warmup()
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, CONFIG.vocab_size, (n,)).astype(np.int32)
                   for n in (16, 14, 15, 40)]
        reqs = [engine.submit(p, 16, rng_seed=i) for i, p in enumerate(prompts)]
        engine.run()
        assert engine.scheduler.preemption_count >= 1  # a resumed request among them
        for req in reqs:
            assert tracing.validate_span_tree(req.trace_spans) == []
            by_name = {}
            for span in req.trace_spans:
                by_name.setdefault(span["name"], []).append(span)
            (root,), (queue,) = by_name["engine_request"], by_name["queue_wait"]
            prefill = by_name["prefill"][0]
            assert queue["t1_ns"] / 1e9 == req.admit_t
            assert prefill["t1_ns"] / 1e9 == req.first_token_t
            assert root["t1_ns"] / 1e9 == req.finish_t
            assert queue["t1_ns"] <= prefill["t0_ns"]
            assert len(by_name["prefill"]) == 1 + req.preemptions
            assert by_name["prefill"][-1]["attrs"]["resume"] == (req.preemptions > 0)
            # the span opens at the read its atpu.serve.prefill phase opens at
            phases = [r for r in tracing.recorded("atpu.serve.prefill")
                      if r[0] == "atpu.serve.prefill"
                      and r[3]["engine"] == engine.engine_id and r[3]["rid"] == req.rid]
            assert [p[1] for p in phases] == [s["t0_ns"] for s in by_name["prefill"]]
            # and a chunk's at the read its atpu.serve.prefill_call opens at
            calls = {r[1] for r in tracing.recorded("atpu.serve.prefill_call")
                     if r[3]["engine"] == engine.engine_id}
            for chunk in by_name["prefill_chunk"]:
                assert chunk["t0_ns"] in calls
                assert any(p["t0_ns"] <= chunk["t0_ns"] and chunk["t1_ns"] <= p["t1_ns"]
                           for p in by_name["prefill"])

    def test_ttft_histogram_and_request_event_read_the_sound_stamp(self, params, tmp_path):
        """``accelerate_engine_ttft_seconds`` and ``serving_request.ttft_s`` hold
        the request's own prefill (made slow here), and the event carries the
        queue wait beside it."""
        import time as _time

        tel.enable(out_dir=str(tmp_path), run_id="ttft")
        metrics.enable()
        engine = ServingEngine(
            params, CONFIG, num_blocks=33, block_size=8, max_slots=2,
            lattice=BucketLattice(slot_buckets=(2,), block_buckets=(8,), prefill_buckets=(16,)),
        )
        engine.warmup()
        fast = engine.prefill_fn

        def slow_prefill(*args):
            _time.sleep(0.05)
            return fast(*args)

        engine.prefill_fn = slow_prefill
        req = engine.submit(np.arange(1, 13, dtype=np.int32), 3)
        engine.run()
        tel.disable()
        recs = [json.loads(l) for l in open(tmp_path / "events-rank0.jsonl")]
        (done,) = [r for r in recs if r["kind"] == "serving_request"]
        assert done["ttft_s"] >= 0.05 and done["ttft_s"] == round(
            req.first_token_t - req.arrival_t, 6)
        assert done["queue_s"] == round(req.admit_t - req.arrival_t, 6)
        assert done["queue_s"] <= done["ttft_s"] - 0.05 + 1e-5  # the wait holds no prefill
        hist = metrics.get_registry().get("accelerate_engine_ttft_seconds")
        assert hist.count == 1 and hist.sum == req.first_token_t - req.arrival_t >= 0.05


def _kernel_jaxprs():
    import importlib

    fa = importlib.import_module("accelerate_tpu.ops.flash_attention")
    q, kv = jnp.zeros((1, 128, 2, 32)), jnp.zeros((1, 128, 1, 32))

    def flash(q, k, v):
        return fa._flash_kernel(q, k, v, None, causal=True, sm_scale=1.0, window=None,
                                interpret=True)

    def flash_grads(q, k, v):
        return jax.grad(lambda *a: flash(*a).sum(), argnums=(0, 1, 2))(q, k, v)

    pool = jnp.zeros((8, 8, 1, 32))
    tables = jnp.zeros((2, 4), jnp.int32)
    return {
        "flash_fwd": lambda: jax.make_jaxpr(flash)(q, kv, kv),
        "flash_dq": lambda: jax.make_jaxpr(flash_grads)(q, kv, kv),
        "flash_dkdv": lambda: jax.make_jaxpr(flash_grads)(q, kv, kv),
        "paged_decode": lambda: jax.make_jaxpr(
            lambda x: fa.paged_attention_decode(x, pool, pool, tables, jnp.ones((2,), jnp.int32),
                                                interpret=True))(jnp.zeros((2, 1, 2, 32))),
        "paged_prefill": lambda: jax.make_jaxpr(
            lambda x: fa.paged_attention_prefill(x, pool, pool, tables[:1],
                                                 jnp.zeros((1, 8), jnp.int32),
                                                 interpret=True))(jnp.zeros((1, 8, 2, 32))),
    }


@pytest.mark.parametrize(
    "kernel", ["flash_fwd", "flash_dq", "flash_dkdv", "paged_decode", "paged_prefill"])
def test_every_pallas_kernel_carries_its_name(kernel):
    """The ``name=`` of each ``pallas_call`` is what the compiled instruction
    is called (``%paged_decode.3``), which is how a device trace's reader
    tells the kernels apart; an unnamed kernel is a ``closed_call`` there."""
    text = str(_kernel_jaxprs()[kernel]())
    assert "pallas_call[" in text and re.search(rf"^\s+name={kernel}$", text, re.M)
