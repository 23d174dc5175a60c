"""The serve runner: ``ServingEngine`` under a load that one thread offers on
the wall clock.

The loop: submit what is due, ``engine.step()``, stamp the tokens that step
produced when it returns, sleep only when the engine is idle. Every end-to-end
time is the runner's own: a user has a token when the ``step()`` that made it
returns. (The engine's own stamps and phases, read inside the step, are what
the program-span readers of ``layer_metrics/`` take from its ring.) A request
is timed from when it was *due*, not from when the loop got round to
submitting it.

Two kinds of cell, told apart by the traffic mix alone: with every request due
at 0 the engine is saturated and the result is tokens per second; with an open
loop below the knee the results are the tails of the time to first token and
of the gap between tokens."""

from __future__ import annotations

import collections
import statistics
import time

import numpy as np

from benchmarks.chip import flops, harness, models, reference, traffic

TRACE_LEAD_S = 0.5    # after the profiler has started, before the traced slice
TRACE_SLICE_S = 2.0   # the traced slice of a traced run


class _Tracker:
    __slots__ = ("spec", "request", "submit_s", "stamps", "left")

    def __init__(self, spec, request, submit_s):
        self.spec, self.request, self.submit_s = spec, request, submit_s
        self.stamps = []   # when each output token was seen, seconds from the window's start
        self.left = None   # "finished" | "rejected"


def _make_weights(kind, cfg, key_seed: int, dtype):
    """The weights on the device, in one jitted call from the seed, cast leaf
    by leaf inside the jit: 3.8 B float32 parameters would not fit the chip."""
    import jax

    def make(key):
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), kind["init"](cfg, key))

    return jax.jit(make)(jax.random.PRNGKey(key_seed))


def _check(cell, kind, params, trackers, seed: int) -> dict:
    """A seeded sample of finished requests against the kind's float32
    reference: one teacher-forced forward over prompt + output, and at every
    generated position the reference's logit of the engine's token has to lie
    within ``margin`` logit deviations of the reference's largest. "Every"
    is the rule unless the cell's ``check`` gives ``margin_quantile``: then
    that percentile of the positions' margins (nearest rank) is held to
    ``margin``, and the largest is reported beside it."""
    import jax
    import jax.numpy as jnp

    want = cell.spec["check"]
    pad_to = int(want["max_tokens"])
    quantile = want.get("margin_quantile", 100)  # the 100th percentile is the largest
    eligible = [t for t in trackers if t.left == "finished"
                and t.request.output_ids().size <= pad_to]
    rng = np.random.default_rng(int(seed) % (2**63))
    sample = [eligible[i] for i in rng.permutation(len(eligible))[: int(want["requests"])]]
    logits_fn = kind["reference_logits"](cell.config)  # built once: a layer is jitted once

    margins = []
    with jax.default_matmul_precision("highest"):
        for t in sample:
            out = t.request.output_ids()
            n_prompt, n_new = int(t.request.prompt.size), len(t.request.generated)
            ids = np.zeros(pad_to, np.int32)
            ids[: out.size] = out  # causal: what is padded behind changes nothing before it
            logits = np.asarray(logits_fn(params, jnp.asarray(ids))[n_prompt - 1: n_prompt - 1 + n_new])
            margins.extend(reference.greedy_margins(logits, out[n_prompt:]).tolist())
    result = {
        "requests": len(sample), "positions": len(margins),
        "max_margin_deviations": max(margins) if margins else None,
        "margin_quantile": quantile,
        "margin_at_quantile": harness.nearest_rank(margins, quantile) if margins else None,
        "argmax_agreement": sum(m == 0.0 for m in margins) / len(margins) if margins else None,
        "margin_allowed": want["margin"], "agreement_required": want["agreement"],
    }
    result["ok"] = bool(
        len(sample) == int(want["requests"])
        and result["margin_at_quantile"] <= want["margin"]
        and result["argmax_agreement"] >= want["agreement"]
    )
    return result


def latencies_ms(done) -> tuple[list, list]:
    """``(times to first token, gaps between tokens)`` of finished requests,
    in milliseconds. The first token is timed from when the request was DUE:
    the wait a stall imposes on a request that was submitted late is the
    system's, not the generator's."""
    ttft = [1e3 * (t.stamps[0] - t.spec.due_s) for t in done]
    gaps = [1e3 * (b - a) for t in done for a, b in zip(t.stamps, t.stamps[1:])]
    return ttft, gaps


def run(cell, *, seed: int, seconds: float, trace: bool, process_t0: float,
        allow_cpu: bool = False) -> harness.Record:
    """One run of a serve cell. ``allow_cpu`` is for the tests alone."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.serving import BucketLattice, RequestStatus, ServingEngine

    harness.require_device(cell.chips, allow_cpu=allow_cpu)
    spec, mix, kind = cell.spec, cell.traffic, models.kind_of(cell.config, cell.root)
    eng = spec["engine"]
    n_layers = models.depth(cell)
    cfg = kind["program_config"](cell.config, n_layers=n_layers, max_seq_len=eng["max_seq_len"])
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[spec["dtype"]]
    params = _make_weights(kind, cfg, int(seed) % (2**31 - 1), dtype)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    engine = ServingEngine(
        params, cfg, num_blocks=eng["num_blocks"], block_size=eng["block_size"],
        max_slots=eng["max_slots"], cache_dtype=dtype,
        admit_watermark_blocks=eng.get("admit_watermark_blocks", 0),
        lattice=BucketLattice(slot_buckets=tuple(eng["slot_buckets"]),
                              block_buckets=tuple(eng["block_buckets"]),
                              prefill_buckets=tuple(eng["prefill_buckets"])),
    )
    warmed = engine.warmup()  # the cell's own lattice, and nothing else

    saturated = mix["arrival"]["kind"] == "at_zero"
    lead_s = TRACE_LEAD_S + TRACE_SLICE_S if trace else 0.0
    pending = collections.deque(traffic.requests(mix, cfg.vocab_size, seed, seconds + lead_s))
    trackers, by_rid = [], {}
    step_s, step_prefill_tokens, step_running = [], [], []
    compiles_before = harness.compile_count()

    t_start = time.perf_counter()
    mono_start = time.monotonic()
    setup_s = t_start - process_t0

    def pump(until_s: float, submit_before_s: float) -> None:
        """Serve until ``until_s`` (seconds from the window's start), or, with
        nothing more to submit, until the engine is idle."""
        while True:
            now = time.perf_counter() - t_start
            if now >= until_s:
                return
            with harness.annotate("cb.submit"):
                while pending and pending[0].due_s <= now and pending[0].due_s < submit_before_s:
                    s = pending.popleft()
                    request = engine.submit(s.prompt, s.max_new_tokens,
                                            arrival_t=mono_start + s.due_s)
                    tracker = _Tracker(s, request, time.perf_counter() - t_start)
                    trackers.append(tracker)
                    by_rid[request.rid] = tracker
            if engine.scheduler.idle():
                next_due = pending[0].due_s if pending else None
                if next_due is None or next_due >= submit_before_s:
                    return
                with harness.annotate("cb.sleep"):
                    time.sleep(max(0.0, min(next_due, until_s) - now))
                continue
            prefilled = engine.prefill_tokens
            t0 = time.perf_counter()
            with harness.annotate("cb.engine_step"):
                left = engine.step()
            t1 = time.perf_counter()
            stamp = t1 - t_start
            for request in engine.scheduler.running() + left:
                tracker = by_rid[request.rid]
                new = len(request.generated) - len(tracker.stamps)
                if new > 0:
                    tracker.stamps.extend([stamp] * new)
            for request in left:
                by_rid[request.rid].left = (
                    "finished" if request.status is RequestStatus.FINISHED else "rejected")
            step_s.append(t1 - t0)
            step_prefill_tokens.append(engine.prefill_tokens - prefilled)
            step_running.append(len(engine.scheduler.running()))

    # ------------------------------------------------------------ the window
    pump(seconds, seconds)
    window_s = time.perf_counter() - t_start
    stats = engine.stats()
    n_steps = len(step_s)
    late_compiles = harness.compile_count() - compiles_before

    out, slice_steps = {"trace": None}, None
    if trace:
        with harness.profiler_slice(out):
            t = time.perf_counter() - t_start  # starting the profiler took a while
            pump(t + TRACE_LEAD_S, t + lead_s)
            slice_first = engine.steps
            with harness.annotate("cb.window"):  # whole steps, each ending in its `fetch`
                pump(t + lead_s, t + lead_s)
            slice_steps = [slice_first, engine.steps]
    if not saturated:
        # nothing new is submitted: what was due in the window gets `drain_s` to finish
        # (the engine goes idle sooner), and what has not finished by then has failed
        pump(time.perf_counter() - t_start + float(spec["drain_s"]), seconds)
    cache_grew = engine.jit_cache_sizes() != warmed
    in_window = [t for t in trackers if t.spec.due_s < seconds]
    pool_bytes = int(sum(x.nbytes for x in jax.tree_util.tree_leaves(engine.pool)))
    engine.pool = None  # the reference's float32 layers want the room

    check = _check(cell, kind, params, in_window, seed)

    # ----------------------------------------------------------- the numbers
    def finished_by(t, end_s) -> bool:
        return t.left == "finished" and t.stamps[-1] <= end_s

    tokens_in_window = sum(1 for t in in_window for s in t.stamps if s <= window_s)
    finished_in_window = [t for t in in_window if finished_by(t, window_s)]
    if saturated:  # the queue is never meant to empty: what left the engine was attempted
        attempted = finished_in_window + [t for t in in_window if t.left == "rejected"]
    else:          # every request due in the window, given `drain_s` after it to finish
        attempted = in_window
    done = [t for t in attempted if t.left == "finished"]
    ttft_ms, gaps_ms = latencies_ms(done)
    late_ms = [1e3 * (t.submit_s - t.spec.due_s) for t in in_window]
    end_to_end = {"setup_s": setup_s, "serve_tokens_per_s": tokens_in_window / window_s}
    if ttft_ms and gaps_ms:
        end_to_end["ttft_p95_ms"] = harness.nearest_rank(ttft_ms, 95)
        end_to_end["itl_p95_ms"] = harness.nearest_rank(gaps_ms, 95)

    forward_flops = None
    if in_window:  # the matmul operations a token needs at the mean length of the window's requests
        mean_len = float(np.mean([t.spec.prompt.size + t.spec.max_new_tokens for t in in_window]))
        forward_flops = kind["forward_flops_per_token"](cell.config, mean_len, n_layers)
    decode_only = [s for s, p, r in zip(step_s[:n_steps], step_prefill_tokens, step_running)
                   if p == 0 and r > 0]
    decode_median_s = statistics.median(decode_only) if decode_only else None
    prefill_steps = [(s, p) for s, p in zip(step_s[:n_steps], step_prefill_tokens) if p > 0]
    device = harness.device_record()
    return harness.Record(
        correct=bool(check["ok"] and late_compiles == 0 and not cache_grew),
        attempted=len(attempted),
        failed=len(attempted) - len(done),
        end_to_end=end_to_end,
        clocks={
            "window_s": window_s, "steps": n_steps,
            "mean_occupancy": stats["mean_occupancy"],
            "decode_step_median_s": decode_median_s,
            "prefill_step_s": float(sum(s for s, _ in prefill_steps)),
            "prefill_steps": len(prefill_steps),
            "prefill_tokens": int(sum(p for _, p in prefill_steps)),
            "generator_late_ms": late_ms,
            # every token the window's steps put through the model, prompt or output
            "model_tokens_per_s": (stats["prefill_tokens"] + stats["decode_tokens"]) / window_s,
            "forward_flops_per_token": forward_flops,
            # the engine's steps inside the traced slice's `cb.window`: [first, last)
            "slice_steps": slice_steps,
            "device_kind": device["kind"], "chips": cell.chips,
        },
        facts={
            "n_params": n_params, "n_layers": n_layers, "window_s": window_s, "steps": n_steps,
            "requests_due": len(in_window),
            "requests_finished_in_window": len(finished_in_window),
            "requests_per_s_completed": len(finished_in_window) / window_s,
            "tokens_in_window": tokens_in_window,
            # the backlog: due in the window's first or second half, not finished at its end
            "unfinished_at_window_end": [
                sum(1 for t in in_window if lo <= t.spec.due_s < hi and not finished_by(t, window_s))
                for lo, hi in ((0.0, seconds / 2), (seconds / 2, seconds))],
            "ttft_samples": len(ttft_ms), "itl_samples": len(gaps_ms),
            "ttft_p50_ms": harness.nearest_rank(ttft_ms, 50) if ttft_ms else None,
            "itl_p50_ms": harness.nearest_rank(gaps_ms, 50) if gaps_ms else None,
            "check": check, "late_compiles": late_compiles, "jit_cache_grew": cache_grew,
            "warmed": warmed,
            "engine": {k: stats[k] for k in (
                "steps", "decode_tokens", "prefill_tokens", "prefill_calls", "preemptions",
                "max_running", "mean_occupancy", "prefill_tokens_saved", "usable_blocks")},
            "pool_bytes": pool_bytes,
        },
        trace=out["trace"],
        compared={
            "requests_checked": (check["requests"], cell.spec["check"]["requests"]),
            "margin_deviations": (check["margin_at_quantile"], check["margin_allowed"]),
            "argmax_agreement": (check["argmax_agreement"], check["agreement_required"]),
            "late_compiles": (late_compiles, 0), "jit_cache_grew": (int(cache_grew), 0),
        },
    )
