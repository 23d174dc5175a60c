"""Serving microbench: continuous vs static batching under Poisson load.

Replays ONE seeded open-loop workload — Poisson arrivals (exponential
inter-arrival gaps measured in engine steps), uniformly random prompt and
completion lengths — through the :class:`~accelerate_tpu.serving.engine.
ServingEngine` twice:

- ``continuous``: in-flight batching — requests join the running batch at
  step granularity, finished slots are backfilled immediately;
- ``static``: gang admission — a batch is admitted only into an idle engine
  and drained to the LAST member's completion before the next forms (the
  classic serving baseline continuous batching exists to beat).

Both legs share the warmed bucket lattice, so every timed step runs
compiled code; the ratio isolates scheduling, not compilation. Reports
aggregate generated tok/s (wall), mean batch occupancy, and p50/p99
per-request latency + TTFT (arrival -> finish, wall). Emits one JSON line
per the bench.py conventions; ``make bench-serve`` runs it, and bench.py's
``serving`` config carries it in the round payload.

The **replicated leg** (ISSUE 12) drains the same seeded workload through
the ``ServingRouter`` over 1 and N thread-backed replicas (aggregate tok/s
scaling), then once more with one replica killed mid-load: zero requests
may be lost, the kill run's outputs must be bitwise-identical to the
unkilled run (token-exact failover resume), and the p99 shows the failover
latency tax.

The **disaggregated leg** (ISSUE 16) replays a long-prompt-heavy Poisson
ramp through a monolithic 1-replica router and through the 1-prefill +
1-decode ``DisaggRouter`` with the SLO autoscaler armed: ≥1 decode
scale-up must fire mid-load, the joiner must boot warm off the pre-shipped
compile cache (``join_compiles == 0``), outputs must stay bitwise-identical
to the monolith, and the payload carries the ttft/latency p99 across the
scale transition.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _common import detect_backend, emit, percentile as _percentile


def build_workload(n_requests, seed, prompt_lens, new_tokens, rate, vocab_size,
                   shared_len=0):
    """Seeded open-loop arrival schedule: [(arrival_step, prompt, max_new)].
    ``rate`` is mean arrivals per engine step (Poisson: exponential gaps).
    ``shared_len > 0`` prepends one shared head of that many tokens to every
    prompt (``prompt_lens`` then sizes the private suffix) — the system-prompt
    workload shape automatic prefix caching exists to exploit."""
    import numpy as np

    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab_size, (shared_len,)).astype(np.int32)
    t = 0.0
    workload = []
    for _ in range(n_requests):
        t += rng.exponential(1.0 / rate)
        suffix = rng.integers(0, vocab_size, (int(rng.integers(*prompt_lens)),))
        prompt = np.concatenate([shared, suffix.astype(np.int32)])
        workload.append((int(t), prompt, int(rng.integers(*new_tokens))))
    return workload


def _drive(engine, workload):
    """Open-loop drive: submit each request at its arrival step, step the
    engine while work is live, idle-tick otherwise. Returns (terminal
    requests partitioned FINISHED/other, wall seconds)."""
    from accelerate_tpu.serving import RequestStatus

    terminal = []
    next_req = 0
    step = 0
    t0 = time.monotonic()
    while next_req < len(workload) or not engine.scheduler.idle():
        while next_req < len(workload) and workload[next_req][0] <= step:
            _, prompt, max_new = workload[next_req]
            engine.submit(prompt, max_new, rng_seed=next_req)
            next_req += 1
        if engine.scheduler.idle():
            step += 1  # idle tick: nothing due yet, no device work
            continue
        terminal.extend(engine.step())
        step += 1
    wall = time.monotonic() - t0
    finished = [r for r in terminal if r.status is RequestStatus.FINISHED]
    other = [r for r in terminal if r.status is not RequestStatus.FINISHED]
    return finished, other, wall


def run_leg(params, config, workload, *, continuous, max_slots, num_blocks,
            block_size, lattice):
    """One scheduling policy over the shared workload; returns its metrics."""
    from accelerate_tpu.serving import ServingEngine

    engine = ServingEngine(
        params, config, num_blocks=num_blocks, block_size=block_size,
        max_slots=max_slots, lattice=lattice, continuous=continuous,
    )
    engine.warmup()  # all buckets compiled before the clock starts
    # step() also returns REJECTED requests (pool/lattice misconfiguration):
    # keep them out of the throughput/latency aggregates — and out of the
    # continuous/static comparison — but report them (a silently shrunken
    # workload would fake the ratio)
    completed, rejected, wall = _drive(engine, workload)
    tokens = sum(len(r.generated) for r in completed)
    latencies = [r.finish_t - r.arrival_t for r in completed]
    ttfts = [r.first_token_t - r.arrival_t for r in completed if r.first_token_t]
    stats = engine.stats()
    return {
        "completed": len(completed),
        "rejected": len(rejected),
        "tokens": tokens,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / max(wall, 1e-9), 2),
        "engine_steps": stats["steps"],
        "mean_occupancy": stats["mean_occupancy"],
        "preemptions": stats["preemptions"],
        "p50_latency_ms": round(_percentile(latencies, 50) * 1e3, 2),
        "p99_latency_ms": round(_percentile(latencies, 99) * 1e3, 2),
        "p50_ttft_ms": round(_percentile(ttfts, 50) * 1e3, 2),
        "continuous": continuous,
    }


def _drain_through_router(spec, workload, *, n_replicas, kill_after=None,
                          health_timeout_s=10.0, traced=False):
    """Drain the whole workload as a backlog through a router over
    ``n_replicas`` thread-backed replicas; optionally SIGKILL-equivalent one
    replica after ``kill_after`` completions (abrupt: in-flight work is
    failed over with token-exact resume). Returns the leg metrics plus every
    request's output tokens so the kill leg can be parity-checked against
    the unkilled one.

    ``traced=True`` arms request-scoped tracing (telemetry/tracing.py) for
    the leg and reports ``span_trees_complete``: every FINISHED request must
    carry a gap-free admission→dispatch→prefill→decode span tree, failover
    hops included — the ISSUE 15 acceptance invariant, measured on the same
    workload the untraced legs time."""
    import time as _time

    from accelerate_tpu.serving import (
        AdmissionController,
        LocalReplica,
        RouterRequestStatus,
        ServingRouter,
    )
    from accelerate_tpu.telemetry import tracing as _tracing

    if traced:
        _tracing.arm(1.0)
    replicas = [LocalReplica(f"r{i}", spec) for i in range(n_replicas)]
    router = ServingRouter(
        replicas,
        # the whole workload is submitted as one backlog: size the queue so
        # the throughput legs never shed (shedding is admission.py's job and
        # has its own tests; here it would just shrink the measured work)
        admission=AdmissionController(max_queue=len(workload) + 1),
        health_timeout_s=health_timeout_s,
    )
    try:
        router.wait_ready()
        t0 = _time.monotonic()
        reqs = [
            router.submit(prompt, max_new, rng_seed=i)
            for i, (_, prompt, max_new) in enumerate(workload)
        ]
        killed = False
        # every-request-terminal, not a poll-return count: SHED finalizes at
        # submit time and never appears in poll()'s terminal list
        while not all(r.status.terminal for r in reqs):
            router.poll()
            finished = sum(
                1 for r in reqs if r.status is RouterRequestStatus.FINISHED
            )
            if kill_after is not None and not killed and finished >= kill_after:
                router.replicas["r0"].kill()
                killed = True
            _time.sleep(0.001)
            if _time.monotonic() - t0 > 600:
                raise RuntimeError("replicated leg wedged (>600s)")
        wall = _time.monotonic() - t0
        completed = [r for r in reqs if r.status is RouterRequestStatus.FINISHED]
        tokens = sum(len(r.generated) for r in completed)
        latencies = [r.finish_t - r.arrival_t for r in completed]
        leg = {
            "replicas": n_replicas,
            "completed": len(completed),
            "lost": len(reqs) - len(completed),
            "tokens": tokens,
            "wall_s": round(wall, 4),
            "tokens_per_s": round(tokens / max(wall, 1e-9), 2),
            "failovers": router.failovers,
            "p50_latency_ms": round(_percentile(latencies, 50) * 1e3, 2),
            "p99_latency_ms": round(_percentile(latencies, 99) * 1e3, 2),
            "outputs": [[int(t) for t in r.generated] for r in reqs],
        }
        if traced:
            broken = [
                r.rid for r in completed
                if _tracing.validate_span_tree(r.trace_spans)
            ]
            retried = [r for r in completed if r.retries > 0]
            lineage = all(
                sum(1 for s in r.trace_spans if s["name"] == "dispatch") >= 2
                for r in retried
            )
            leg["traced"] = True
            leg["span_trees_complete"] = not broken and lineage
            leg["broken_span_trees"] = len(broken)
        return leg
    finally:
        router.close()
        if traced:
            _tracing.disarm()


def run_bench_replicated(
    on_tpu: bool,
    requests: int = 16,
    seed: int = 0,
    n_replicas: int = 2,
    max_slots: int = 4,
    num_blocks: int = 49,
    block_size: int = 8,
) -> dict:
    """The router leg (ISSUE 12): the SAME seeded workload drained through 1
    replica, through ``n_replicas``, and through ``n_replicas`` with one
    replica killed mid-load. Reports aggregate tok/s scaling, the kill leg's
    p99 + failover count, and whether the kill leg's outputs are bitwise
    identical to the unkilled run (greedy decode is deterministic, so any
    difference means failover resume corrupted a stream)."""
    import dataclasses

    from accelerate_tpu.models import LlamaConfig
    from accelerate_tpu.serving import ReplicaSpec

    config = LlamaConfig.tiny()
    prompt_lens, new_tokens = (4, 24), (2, 40)
    max_len = prompt_lens[1] + new_tokens[1]
    # one coarse bucket per axis: replicated legs pay one decode + one
    # prefill compile per replica engine instead of the full lattice
    spec = ReplicaSpec(
        model=dataclasses.asdict(config),
        num_blocks=num_blocks,
        block_size=block_size,
        max_slots=max_slots,
        slot_buckets=(max_slots,),
        block_buckets=(-(-max_len // block_size) + 1,),
        prefill_buckets=(prompt_lens[1] + new_tokens[1],),
    )
    workload = build_workload(
        requests, seed, prompt_lens, new_tokens, 2.0, config.vocab_size
    )
    one = _drain_through_router(spec, workload, n_replicas=1)
    many = _drain_through_router(spec, workload, n_replicas=n_replicas)
    kill = _drain_through_router(
        spec, workload, n_replicas=n_replicas, kill_after=max(1, requests // 4)
    )
    # the ISSUE 15 leg: the SAME kill workload with tracing armed — outputs
    # must stay bitwise-identical, every completion must carry a gap-free
    # span tree (failover hops included), and the tok/s ratio against the
    # untraced kill leg reports the tracing tax honestly
    traced = _drain_through_router(
        spec, workload, n_replicas=n_replicas,
        kill_after=max(1, requests // 4), traced=True,
    )
    parity = kill["outputs"] == many["outputs"]
    traced_parity = traced["outputs"] == many["outputs"]
    for leg in (one, many, kill, traced):
        leg.pop("outputs")
    return {
        "bench": "serving_replicated",
        "unit": f"tokens_per_s_scaling({n_replicas}r/1r)",
        "value": round(many["tokens_per_s"] / max(one["tokens_per_s"], 1e-9), 3),
        "one_replica": one,
        "replicated": many,
        "replica_kill": kill,
        "replica_kill_traced": traced,
        "kill_outputs_match_unkilled": parity,
        "traced_outputs_match_unkilled": traced_parity,
        "tracing_tokens_per_s_ratio": round(
            traced["tokens_per_s"] / max(kill["tokens_per_s"], 1e-9), 3
        ),
        "requests": requests,
        "n_replicas": n_replicas,
        "on_tpu": on_tpu,
    }


def run_prefix_leg(params, config, workload, *, prefix_cache, max_slots,
                   num_blocks, block_size, lattice):
    """One prefix-cache setting over the shared-prefix workload; returns the
    leg metrics, every request's output tokens (for the cross-leg bitwise
    parity check) and the post-warmup recompile count (must be 0 — the cache
    introduces no new shapes). Rejected requests are reported, not silently
    dropped (a shrunken workload would fake the prefill-token reduction)."""
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry.step_profiler import RecompileWatcher

    engine = ServingEngine(
        params, config, num_blocks=num_blocks, block_size=block_size,
        max_slots=max_slots, lattice=lattice, prefix_cache=prefix_cache,
    )
    engine.warmup()
    watcher = RecompileWatcher()
    watcher.register("prefill", engine.prefill_fn)
    watcher.register("decode", engine.decode_fn)
    if prefix_cache:
        # the COW block copy is the one jit fn the cache adds: the
        # zero-recompile signal must watch it too
        watcher.register("cow", engine.cow_fn)
    completed, rejected, wall = _drive(engine, workload)
    tokens = sum(len(r.generated) for r in completed)
    ttfts = [r.first_token_t - r.arrival_t for r in completed if r.first_token_t]
    stats = engine.stats()
    outputs = {r.rid: [int(t) for t in r.output_ids()] for r in completed}
    return {
        "prefix_cache": prefix_cache,
        "completed": len(completed),
        "rejected": len(rejected),
        "tokens": tokens,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / max(wall, 1e-9), 2),
        "p50_ttft_ms": round(_percentile(ttfts, 50) * 1e3, 2),
        "prefill_tokens": stats["prefill_tokens"],
        "prefix_hit_rate": stats.get("prefix_hit_rate", 0.0),
        "prefill_tokens_saved": stats.get("prefill_tokens_saved", 0),
        "cow_copies": stats.get("cow_copies", 0),
        "recompiles": sum(watcher.poll(emit=False).values()),
    }, [outputs[k] for k in sorted(outputs)]


def run_bench_prefix_cache(
    on_tpu: bool,
    requests: int = 24,
    rate: float = 2.0,
    seed: int = 0,
    max_slots: int = 4,
    num_blocks: int = 97,
    block_size: int = 8,
) -> dict:
    """The shared-prefix leg (ISSUE 14): ONE seeded Poisson workload whose
    prompts share a long system prompt, replayed with the prefix cache on
    and off. The cache-on leg must cut prefill tokens (the `value` is the
    measured reduction), improve tok/s and TTFT p50, produce bitwise
    -identical outputs per request, and stay recompile-free — the
    acceptance line `make bench-serve` holds."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, init_llama
    from accelerate_tpu.serving import BucketLattice

    if on_tpu:
        config = LlamaConfig(vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
                             n_kv_heads=8, max_seq_len=512)
        shared_len, suffix_lens, new_tokens = 128, (8, 48), (8, 32)
        max_slots, num_blocks, block_size = max(max_slots, 8), 320, 16
    else:
        config = LlamaConfig.tiny()
        # a long shared system prompt vs short private suffixes: the
        # workload shape where prefix caching pays (most prompt tokens are
        # the shared head, so the cached leg's prefill runs a small bucket
        # instead of the largest)
        shared_len, suffix_lens, new_tokens = 64, (2, 14), (2, 20)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(config, jax.random.PRNGKey(0))
    )
    max_len = shared_len + suffix_lens[1] + new_tokens[1]
    lattice = BucketLattice.from_limits(
        max_slots, -(-max_len // block_size) + 1, shared_len + suffix_lens[1]
    )
    workload = build_workload(
        requests, seed, suffix_lens, new_tokens, rate, config.vocab_size,
        shared_len=shared_len,
    )
    kw = dict(max_slots=max_slots, num_blocks=num_blocks,
              block_size=block_size, lattice=lattice)
    cached, cached_out = run_prefix_leg(params, config, workload,
                                        prefix_cache=True, **kw)
    plain, plain_out = run_prefix_leg(params, config, workload,
                                      prefix_cache=False, **kw)
    reduction = (
        1.0 - cached["prefill_tokens"] / plain["prefill_tokens"]
        if plain["prefill_tokens"] else 0.0
    )
    return {
        "bench": "serving_prefix_cache",
        "unit": "prefill_token_reduction(cached vs off)",
        "value": round(reduction, 4),
        "cached": cached,
        "uncached": plain,
        "prefix_hit_rate": cached["prefix_hit_rate"],
        "prefill_tokens_saved": cached["prefill_tokens_saved"],
        "tokens_per_s_ratio": round(
            cached["tokens_per_s"] / max(plain["tokens_per_s"], 1e-9), 3
        ),
        "ttft_p50_ratio": round(
            cached["p50_ttft_ms"] / max(plain["p50_ttft_ms"], 1e-9), 3
        ),
        "outputs_match": cached_out == plain_out,
        "zero_recompiles": cached["recompiles"] == 0 and plain["recompiles"] == 0,
        "requests": requests,
        "shared_prefix_len": shared_len,
        "on_tpu": on_tpu,
    }


def _drain_through_disagg(pspec, dspec, workload, *, arrival_dt_s,
                          cache_root=None, timeout_s=600.0):
    """Drain the seeded Poisson workload through a 1-prefill + 1-decode
    DisaggRouter with the SLO autoscaler armed under an artificially tight
    ttft objective (threshold 1µs: the open-loop ramp is violating by
    construction, so ≥1 decode scale-up MUST fire mid-load). Arrival steps
    are replayed open-loop at ``arrival_dt_s`` wall seconds per step.
    Returns the leg metrics — per-request outputs for the monolith parity
    check, pre/post-transition ttft+latency percentiles, and the joiner's
    compile count (0 == the pre-ship made the join warm)."""
    import time as _time

    from accelerate_tpu.serving import (
        AdmissionController,
        AutoscalerPolicy,
        DisaggRouter,
        LocalReplica,
        RouterRequestStatus,
    )
    from accelerate_tpu.telemetry.slo import SLOMonitor, serving_slos

    autoscaler = AutoscalerPolicy(
        dspec,
        min_decode=1,
        max_decode=2,
        cooldown_s=30.0,
        idle_shrink_after_s=3600.0,  # this leg measures the scale-UP path
        source_cache_dir=(
            os.path.join(cache_root, "warm") if cache_root else None
        ),
        joiner_cache_dir=(
            (lambda name: os.path.join(cache_root, name)) if cache_root else None
        ),
    )
    router = DisaggRouter(
        [LocalReplica("p0", pspec)],
        [LocalReplica("d0", dspec)],
        admission=AdmissionController(max_queue=len(workload) + 1),
        health_timeout_s=30.0,
        # a 1µs ttft threshold saturates the burn windows as soon as
        # min_events completions land — the deterministic scale trigger
        slo_monitor=SLOMonitor(serving_slos(ttft_threshold_s=1e-6), min_events=4),
        slo_eval_interval_s=0.0,
        autoscaler=autoscaler,
    )
    try:
        router.wait_ready(timeout_s=300)
        t0 = _time.monotonic()
        reqs = []
        next_req = 0
        while next_req < len(workload) or not all(r.status.terminal for r in reqs):
            now = _time.monotonic()
            while (next_req < len(workload)
                   and workload[next_req][0] * arrival_dt_s <= now - t0):
                _, prompt, max_new = workload[next_req]
                reqs.append(router.submit(prompt, max_new, rng_seed=next_req))
                next_req += 1
            router.poll()
            _time.sleep(0.001)
            if now - t0 > timeout_s:
                raise RuntimeError(f"disagg leg wedged (>{timeout_s}s)")
        # let an in-flight join finish warming so its compile count lands
        while autoscaler.stats()["pending_joins"]:
            router.poll()
            _time.sleep(0.01)
            if _time.monotonic() - t0 > timeout_s:
                break
        wall = _time.monotonic() - t0
        completed = [r for r in reqs if r.status is RouterRequestStatus.FINISHED]
        tokens = sum(len(r.generated) for r in completed)
        scale_ups = [e for e in autoscaler.events if e["action"] == "scale_up"]
        joins = [e for e in autoscaler.events if e["action"] == "join_ready"]

        def _phase(rs):
            lat = [r.finish_t - r.arrival_t for r in rs]
            ttft = [r.first_token_t - r.arrival_t for r in rs if r.first_token_t]
            return {
                "completed": len(rs),
                "p50_latency_ms": round(_percentile(lat, 50) * 1e3, 2),
                "p99_latency_ms": round(_percentile(lat, 99) * 1e3, 2),
                "p50_ttft_ms": round(_percentile(ttft, 50) * 1e3, 2),
                "p99_ttft_ms": round(_percentile(ttft, 99) * 1e3, 2),
            }

        # the transition cut: requests finishing before the first scale-up
        # ran on the founding fleet; everything after shares the joiner
        t_scale = scale_ups[0]["t"] if scale_ups else None
        leg = {
            "completed": len(completed),
            "lost": len(reqs) - len(completed),
            "tokens": tokens,
            "wall_s": round(wall, 4),
            "tokens_per_s": round(tokens / max(wall, 1e-9), 2),
            "handoffs": router.handoffs,
            "handoff_corrupt": router.handoff_corrupt,
            "scale_events": len(autoscaler.events),
            "scale_ups": len(scale_ups),
            "first_scale_after_s": (
                round(t_scale - t0, 4) if t_scale is not None else None
            ),
            "join_compiles": sum(int(j.get("join_compiles", 0)) for j in joins),
            "warm_joins": sum(1 for j in joins if j.get("warm")),
            "joins": len(joins),
            "time_to_ready_s": [j.get("time_to_ready_s") for j in joins],
            "outputs": [[int(t) for t in r.generated] for r in reqs],
        }
        if t_scale is not None:
            pre = [r for r in completed if r.finish_t < t_scale]
            post = [r for r in completed if r.finish_t >= t_scale]
            leg["transition"] = {"pre_scale": _phase(pre), "post_scale": _phase(post)}
        return leg
    finally:
        router.close()


def run_bench_disagg(
    on_tpu: bool,
    requests: int = 16,
    seed: int = 0,
    max_slots: int = 2,
    num_blocks: int = 49,
    block_size: int = 8,
) -> dict:
    """The disaggregated leg (ISSUE 16): ONE seeded long-prompt-heavy Poisson
    ramp drained through a monolithic 1-replica router and through the
    1-prefill + 1-decode DisaggRouter with the SLO autoscaler armed. The
    tight ttft objective forces ≥1 decode scale-up mid-load; the payload
    reports the ttft/latency p99 across that transition, the monolith-vs
    -disagg comparison, bitwise output parity, zero lost requests, and the
    joiner's compile count (the pre-shipped join must be warm:
    ``join_compiles == 0``)."""
    import dataclasses
    import tempfile

    from accelerate_tpu.models import LlamaConfig
    from accelerate_tpu.serving import ReplicaSpec

    config = LlamaConfig.tiny()
    # long-prompt-heavy: most work is prefill, the mix disaggregation exists
    # to isolate from decode interference
    prompt_lens, new_tokens = (16, 48), (2, 12)
    max_len = prompt_lens[1] + new_tokens[1]
    spec = ReplicaSpec(
        model=dataclasses.asdict(config),
        num_blocks=num_blocks,
        block_size=block_size,
        max_slots=max_slots,
        slot_buckets=(max_slots,),
        block_buckets=(-(-max_len // block_size) + 1,),
        prefill_buckets=(prompt_lens[1] + new_tokens[1],),
    )
    workload = build_workload(
        requests, seed, prompt_lens, new_tokens, 2.0, config.vocab_size
    )
    mono = _drain_through_router(spec, workload, n_replicas=1)
    with tempfile.TemporaryDirectory(prefix="bench-disagg-cache-") as cache_root:
        # founding replicas warm into (and the joiner pre-ships from) a
        # shared source cache dir; each joiner gets its OWN dir so the
        # pre-ship is real file movement, not a shared-directory freebie
        warm_dir = os.path.join(cache_root, "warm")
        pspec = dataclasses.replace(spec, role="prefill",
                                    compile_cache_dir=warm_dir)
        dspec = dataclasses.replace(spec, role="decode",
                                    compile_cache_dir=warm_dir)
        disagg = _drain_through_disagg(
            pspec, dspec, workload, arrival_dt_s=0.02, cache_root=cache_root,
        )
    parity = disagg["outputs"] == mono["outputs"]
    for leg in (mono, disagg):
        leg.pop("outputs")
    return {
        "bench": "serving_disagg",
        "unit": "tokens_per_s_ratio(disagg/monolith)",
        "value": round(
            disagg["tokens_per_s"] / max(mono["tokens_per_s"], 1e-9), 3
        ),
        "monolith": mono,
        "disagg": disagg,
        "outputs_match_monolith": parity,
        "zero_lost": disagg["lost"] == 0,
        "scale_up_fired": disagg["scale_ups"] >= 1,
        "join_compiles": disagg["join_compiles"],
        "warm_join": disagg["joins"] > 0
        and disagg["warm_joins"] == disagg["joins"],
        "requests": requests,
        "prompt_lens": list(prompt_lens),
        "new_tokens": list(new_tokens),
        "on_tpu": on_tpu,
    }


def run_spec_leg(params, config, workload, *, spec_tokens, draft_layers,
                 max_slots, num_blocks, block_size, lattice):
    """One speculation setting over the shared workload; returns the leg
    metrics, every request's output tokens (for the cross-leg bitwise parity
    check — bitwise-accept means speculation may change HOW FAST tokens come
    out, never WHICH) and the post-warmup recompile count across all four
    jit functions (draft + verify are warmed at every decode point)."""
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry.step_profiler import RecompileWatcher

    kw = {}
    if spec_tokens:
        kw = dict(spec_tokens=spec_tokens, draft_layers=draft_layers)
    engine = ServingEngine(
        params, config, num_blocks=num_blocks, block_size=block_size,
        max_slots=max_slots, lattice=lattice, **kw,
    )
    engine.warmup()
    watcher = RecompileWatcher()
    watcher.register("prefill", engine.prefill_fn)
    watcher.register("decode", engine.decode_fn)
    if spec_tokens:
        watcher.register("draft", engine.draft_fn)
        watcher.register("verify", engine.verify_fn)
    completed, rejected, wall = _drive(engine, workload)
    tokens = sum(len(r.generated) for r in completed)
    # per-token decode latency: the metric speculation exists to cut —
    # first-token to finish divided by the tokens decoded in that span
    per_tok = [
        (r.finish_t - r.first_token_t) / max(len(r.generated) - 1, 1)
        for r in completed if r.first_token_t and len(r.generated) > 1
    ]
    stats = engine.stats()
    outputs = {r.rid: [int(t) for t in r.output_ids()] for r in completed}
    leg = {
        "spec_tokens": spec_tokens,
        "draft_layers": draft_layers if spec_tokens else None,
        "completed": len(completed),
        "rejected": len(rejected),
        "tokens": tokens,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / max(wall, 1e-9), 2),
        "engine_steps": stats["steps"],
        "p50_per_token_ms": round(_percentile(per_tok, 50) * 1e3, 3),
        "p99_per_token_ms": round(_percentile(per_tok, 99) * 1e3, 3),
        "recompiles": sum(watcher.poll(emit=False).values()),
    }
    if spec_tokens:
        leg["draft_proposed_tokens"] = stats["draft_proposed_tokens"]
        leg["draft_accepted_tokens"] = stats["draft_accepted_tokens"]
        leg["spec_accept_rate"] = stats["spec_accept_rate"]
        leg["spec_accept_hist"] = stats["spec_accept_hist"]
    return leg, [outputs[k] for k in sorted(outputs)]


def _prefill_kernel_microbench(on_tpu: bool, *, iters: int = 20):
    """Paged-attention prefill chunk: XLA gather path vs the Pallas kernel.
    On TPU both run compiled and the ratio is the ISSUE 18 kernel win; on
    CPU the kernel only runs under the Pallas interpreter (a correctness
    vehicle, orders of magnitude slower by construction), so the kernel
    column is timed once and flagged — the gather column is still an honest
    CPU baseline for the chunk shape."""
    import numpy as np

    import jax.numpy as jnp

    from accelerate_tpu.ops.flash_attention import paged_attention_prefill
    from accelerate_tpu.ops.flash_attention import paged_attention_gather as gather_ref

    if on_tpu:
        B, S, H, Hkv, D, bs, nb, W = 8, 64, 16, 8, 128, 16, 256, 24
    else:
        B, S, H, Hkv, D, bs, nb, W = 2, 8, 4, 2, 32, 8, 16, 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((nb, bs, Hkv, D)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((nb, bs, Hkv, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * W].reshape(B, W), jnp.int32
    )
    # the chunk sits at the very end of the table: every earlier block is
    # landed-prefix KV, the max-work shape for a chunk of S queries
    qpos = jnp.asarray(
        (W * bs - S) + np.arange(S)[None, :] + np.zeros((B, 1), np.int32),
        jnp.int32,
    )
    n_tok = B * S

    def _time(fn, reps):
        fn().block_until_ready()  # warm (compile / first trace)
        t0 = time.perf_counter()
        for _ in range(reps):
            fn().block_until_ready()
        return (time.perf_counter() - t0) / reps

    import jax

    gather_jit = jax.jit(gather_ref)
    gather_s = _time(lambda: gather_jit(q, k_pool, v_pool, tables, qpos), iters)
    if on_tpu:
        kernel_s = _time(
            lambda: paged_attention_prefill(q, k_pool, v_pool, tables, qpos),
            iters,
        )
        kernel_mode = "compiled"
    else:
        t0 = time.perf_counter()
        paged_attention_prefill(
            q, k_pool, v_pool, tables, qpos, interpret=True
        ).block_until_ready()
        kernel_s = time.perf_counter() - t0
        kernel_mode = "interpret"
    return {
        "shape": {"B": B, "S": S, "H": H, "Hkv": Hkv, "D": D,
                  "block_size": bs, "table_width": W},
        "gather_us_per_token": round(gather_s * 1e6 / n_tok, 3),
        "kernel_us_per_token": round(kernel_s * 1e6 / n_tok, 3),
        "kernel_mode": kernel_mode,
        # only meaningful when both columns are compiled (TPU)
        "kernel_speedup": (
            round(gather_s / max(kernel_s, 1e-12), 3) if on_tpu else None
        ),
    }


def run_bench_spec_decode(
    on_tpu: bool,
    requests: int = 12,
    rate: float = 2.0,
    seed: int = 0,
    spec_tokens: int = 3,
    draft_layers: int = 1,
    max_slots: int = 4,
    num_blocks: int = 49,
    block_size: int = 8,
) -> dict:
    """The speculative-decoding leg (ISSUE 18): ONE seeded Poisson workload
    replayed with speculation off and with a k-token truncated-layer
    self-draft on. Bitwise-accept makes the comparison exact: outputs must
    match token-for-token, so the legs differ only in steps taken. Reports
    the per-token latency improvement at the measured accept rate, the
    engine-step reduction, bitwise parity, and the zero-recompile line
    (draft + verify included); plus the prefill-kernel chunk microbench."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, init_llama
    from accelerate_tpu.serving import BucketLattice

    if on_tpu:
        config = LlamaConfig(vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
                             n_kv_heads=8, max_seq_len=512)
        prompt_lens, new_tokens = (16, 96), (16, 64)
        max_slots, num_blocks, block_size = max(max_slots, 8), 160, 16
        draft_layers = max(draft_layers, 2)
    else:
        config = LlamaConfig.tiny()
        # decode-heavy: long completions are where accepted drafts compound
        prompt_lens, new_tokens = (4, 16), (8, 40)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(config, jax.random.PRNGKey(0))
    )
    max_len = prompt_lens[1] + new_tokens[1]
    lattice = BucketLattice.from_limits(
        max_slots, -(-max_len // block_size) + 1, prompt_lens[1]
    )
    workload = build_workload(
        requests, seed, prompt_lens, new_tokens, rate, config.vocab_size
    )
    kw = dict(max_slots=max_slots, num_blocks=num_blocks,
              block_size=block_size, lattice=lattice)
    spec, spec_out = run_spec_leg(params, config, workload,
                                  spec_tokens=spec_tokens,
                                  draft_layers=draft_layers, **kw)
    plain, plain_out = run_spec_leg(params, config, workload,
                                    spec_tokens=0, draft_layers=None, **kw)
    return {
        "bench": "serving_spec_decode",
        "unit": "per_token_latency_ratio(spec/off)",
        "value": round(
            spec["p50_per_token_ms"] / max(plain["p50_per_token_ms"], 1e-9), 3
        ),
        "speculative": spec,
        "baseline": plain,
        "spec_accept_rate": spec["spec_accept_rate"],
        "tokens_per_s_ratio": round(
            spec["tokens_per_s"] / max(plain["tokens_per_s"], 1e-9), 3
        ),
        "engine_step_ratio": round(
            spec["engine_steps"] / max(plain["engine_steps"], 1), 3
        ),
        "outputs_match": spec_out == plain_out,
        "zero_recompiles": spec["recompiles"] == 0 and plain["recompiles"] == 0,
        "prefill_kernel": _prefill_kernel_microbench(on_tpu),
        "requests": requests,
        "spec_tokens": spec_tokens,
        "draft_layers": draft_layers,
        "on_tpu": on_tpu,
    }


def run_bench_serving(
    on_tpu: bool,
    requests: int = 32,
    rate: float = 2.0,
    seed: int = 0,
    max_slots: int = 4,
    num_blocks: int = 49,
    block_size: int = 8,
) -> dict:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, init_llama
    from accelerate_tpu.serving import BucketLattice

    if on_tpu:
        config = LlamaConfig(vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
                             n_kv_heads=8, max_seq_len=512)
        prompt_lens, new_tokens = (16, 96), (8, 64)
        max_slots, num_blocks, block_size = max(max_slots, 8), 160, 16
    else:
        config = LlamaConfig.tiny()
        # heterogeneous completion lengths are the whole point: static
        # batching drains every gang to its slowest member while continuous
        # backfills the freed slots at step granularity
        prompt_lens, new_tokens = (4, 24), (2, 40)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(config, jax.random.PRNGKey(0))
    )
    max_len = prompt_lens[1] + new_tokens[1]
    lattice = BucketLattice.from_limits(
        max_slots, -(-max_len // block_size) + 1, prompt_lens[1] + new_tokens[1]
    )
    workload = build_workload(
        requests, seed, prompt_lens, new_tokens, rate, config.vocab_size
    )
    kw = dict(max_slots=max_slots, num_blocks=num_blocks, block_size=block_size,
              lattice=lattice)
    continuous = run_leg(params, config, workload, continuous=True, **kw)
    static = run_leg(params, config, workload, continuous=False, **kw)
    return {
        "bench": "serving",
        "unit": "throughput_ratio(continuous/static)",
        "value": round(
            continuous["tokens_per_s"] / max(static["tokens_per_s"], 1e-9), 3
        ),
        "continuous": continuous,
        "static": static,
        "p99_latency_ms": continuous["p99_latency_ms"],
        "requests": requests,
        "arrival_rate_per_step": rate,
        "prompt_lens": list(prompt_lens),
        "new_tokens": list(new_tokens),
        "max_slots": max_slots,
        "num_blocks": num_blocks,
        "block_size": block_size,
        "on_tpu": on_tpu,
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="mean Poisson arrivals per engine step (open loop)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--num-blocks", type=int, default=49)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--replicated-requests", type=int, default=16,
                    help="workload size for the router leg (0 skips it)")
    ap.add_argument("--n-replicas", type=int, default=2)
    ap.add_argument("--prefix-requests", type=int, default=24,
                    help="workload size for the shared-prefix leg (0 skips it)")
    ap.add_argument("--disagg-requests", type=int, default=16,
                    help="workload size for the disaggregated leg (0 skips it)")
    ap.add_argument("--spec-requests", type=int, default=12,
                    help="workload size for the spec-decode leg (0 skips it)")
    ap.add_argument("--spec-tokens", type=int, default=3)
    ap.add_argument("--draft-layers", type=int, default=1)
    args = ap.parse_args()
    on_tpu = detect_backend()
    out = run_bench_serving(
        on_tpu=on_tpu,
        requests=args.requests,
        rate=args.rate,
        seed=args.seed,
        max_slots=args.max_slots,
        num_blocks=args.num_blocks,
        block_size=args.block_size,
    )
    if args.replicated_requests > 0:
        out["replicated"] = run_bench_replicated(
            on_tpu=on_tpu,
            requests=args.replicated_requests,
            seed=args.seed,
            n_replicas=args.n_replicas,
            max_slots=args.max_slots,
            num_blocks=args.num_blocks,
            block_size=args.block_size,
        )
    if args.prefix_requests > 0:
        out["prefix_cache"] = run_bench_prefix_cache(
            on_tpu=on_tpu,
            requests=args.prefix_requests,
            rate=args.rate,
            seed=args.seed,
        )
    if args.disagg_requests > 0:
        out["disagg"] = run_bench_disagg(
            on_tpu=on_tpu,
            requests=args.disagg_requests,
            seed=args.seed,
        )
    if args.spec_requests > 0:
        out["spec_decode"] = run_bench_spec_decode(
            on_tpu=on_tpu,
            requests=args.spec_requests,
            rate=args.rate,
            seed=args.seed,
            spec_tokens=args.spec_tokens,
            draft_layers=args.draft_layers,
        )
    emit(out)
