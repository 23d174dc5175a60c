"""Aggregate telemetry JSONL streams into a human/driver-readable report.

``python -m accelerate_tpu.telemetry report <dir-or-file>...`` reads every
``*.jsonl`` stream (one per rank), merges them, and prints:

- per-step wall-time / data-wait / execute percentiles (p50/p90/p99),
- compile totals and the recompile count per compiled function — a nonzero
  recompile total after warmup is the classic silent reshape cliff,
- a data-pipeline section: per-phase input wait (fetch / transfer / stall),
  prefetch queue occupancy and the overlap ratio — how much of the input
  pipeline was hidden behind device compute,
- a checkpoints section: saves, bytes written, per-phase time
  (snapshot / serialize / write / commit / backpressure) and the
  exposed-vs-hidden split — how many checkpoint seconds the train loop
  actually paid vs how many the async writer overlapped,
- a performance section (telemetry/perf.py + xplane.py): per-step MFU
  distribution and first→last trend, a per-function roofline table (XLA
  cost-analysis FLOPs, arithmetic intensity, compute-vs-HBM-bound bucket,
  projected memory fit), and the trace-window accounting — top-k op/fusion
  durations, the compute/collective/idle device-time split and the
  comms-overlap ratio,
- device/host memory peaks,
- comms traffic per collective op (calls + payload bytes),
- per-rank event counts and the dropped-event total in the header — silent
  data loss must read as a warning, not as "clean run".

``--by-rank`` adds the cross-rank forensics section: per-step rank skew with
slowest-rank attribution (the straggler), per-rank heartbeat-gap timelines
from the watchdog's records, and merged ``flight-rank<k>.json`` crash/hang
post-mortems. ``--json`` emits the raw report dict for drivers. The
``doctor`` subcommand self-checks the forensics pipeline end to end
(flight dump → watchdog stall detection → straggler report).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Iterable, Optional

# THE percentile/histogram implementations live in telemetry.metrics — the
# report re-exports `percentile` for its callers but owns no private math
# (tests/test_observability.py ratchets that across the repo)
from . import goodput as _goodput
from . import regress as _regress
from .metrics import hist_dist, percentile

PERCENTILES = (50, 90, 99)


def iter_event_files(paths: Iterable[str]) -> "list[str]":
    files: list[str] = []
    for path in paths:
        if os.path.isdir(path):
            files.extend(
                sorted(
                    os.path.join(path, name)
                    for name in os.listdir(path)
                    if name.endswith(".jsonl")
                )
            )
        else:
            files.append(path)
    return files


def load_events(paths: Iterable[str]) -> "list[dict]":
    events: list[dict] = []
    for file in iter_event_files(paths):
        try:
            with open(file) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn tail line from a killed run
                    if isinstance(rec, dict):
                        rec.setdefault("_file", os.path.basename(file))
                        events.append(rec)
        except OSError:
            continue
    return events


def _dist(values: "list[float]") -> dict:
    values = sorted(values)
    if not values:
        return {"count": 0}
    return {
        "count": len(values),
        "mean": round(sum(values) / len(values), 6),
        "max": round(values[-1], 6),
        # presorted: one sort per distribution, not four
        **{f"p{p}": round(percentile(values, p, presorted=True), 6) for p in PERCENTILES},
    }


def _rank_of_event(event: dict, file_rank: "dict[str, int]") -> Optional[int]:
    """Rank attribution for a merged event: the stream's ``meta`` record wins,
    the ``events-rank<k>`` filename is the fallback for torn streams whose
    meta line never made it to disk."""
    file = event.get("_file")
    if file in file_rank:
        return file_rank[file]
    m = re.search(r"rank(\d+)", file or "")
    return int(m.group(1)) if m else None


def _per_rank_counts(events: "list[dict]", file_rank: "dict[str, int]") -> "dict":
    per_rank: dict = {}
    for e in events:
        rank = _rank_of_event(e, file_rank)
        key = "?" if rank is None else str(rank)
        rec = per_rank.setdefault(key, {"events": 0, "dropped": 0})
        rec["events"] += 1
        if e.get("kind") == "dropped":
            rec["dropped"] += int(e.get("count", 0))
    return dict(sorted(per_rank.items()))


def _collective_divergence(schedules: "dict[int, dict]") -> Optional[dict]:
    """Cross-rank comparison of the flight recorder's collective-schedule
    fingerprints — the runtime confirmation of a jaxlint R4 finding.

    Equal (count, hash) across ranks means every rank issued the same
    collectives with the same payload shapes in the same order. On mismatch,
    the overlapping portions of the per-rank ``recent`` windows name the
    first differing call when the divergence is recent enough to still be
    in the window."""
    if len(schedules) < 2:
        return None
    per_rank = {
        str(r): {"count": s.get("count", 0), "hash": s.get("hash")}
        for r, s in sorted(schedules.items())
    }
    out: dict = {"per_rank": per_rank, "diverged": False}

    # a rank dumped before its first collective has an (empty) schedule that
    # is trivially a prefix of every other — exclude it from the comparison
    # (but DON'T let it mask divergence among the remaining ranks)
    compared = {r: s for r, s in schedules.items() if s.get("count", 0) > 0}
    zero_ranks = sorted(set(schedules) - set(compared))
    if zero_ranks:
        out["prefix_skew"] = {
            str(r): s.get("count", 0) for r, s in sorted(schedules.items())
        }
    if len(compared) < 2:
        return out

    hashes = {(s.get("count", 0), s.get("hash")) for s in compared.values()}
    if len(hashes) <= 1:
        return out

    # count skew alone is not divergence: dumps are taken at slightly
    # different moments, so a healthy run shows one rank a call or two
    # ahead with an IDENTICAL common prefix. The per-seq cumulative hashes
    # in the recent windows let us check: if every compared rank agrees on
    # the hash at the minimum common count, the shorter schedules are
    # prefixes of the longer ones.
    counts = [s.get("count", 0) for s in compared.values()]
    min_count = min(counts)
    hash_at_min: "dict[int, str]" = {}
    for rank, sched in compared.items():
        if sched.get("count", 0) == min_count and sched.get("hash"):
            hash_at_min[rank] = sched["hash"]
        else:
            for entry in sched.get("recent") or []:
                if entry.get("seq") == min_count:
                    hash_at_min[rank] = entry.get("hash")
                    break
    prefix_provable = len(hash_at_min) == len(compared)
    if (
        prefix_provable
        and len(set(hash_at_min.values())) == 1
        and len(set(counts)) > 1
    ):
        skew = {
            str(r): s.get("count", 0) - min_count for r, s in sorted(compared.items())
        }
        out["prefix_skew"] = {**out.get("prefix_skew", {}), **skew}
        return out

    # align recent windows by seq and find the first disagreement visible
    by_seq: "dict[int, dict]" = {}
    for rank, sched in compared.items():
        for entry in sched.get("recent") or []:
            seq = entry.get("seq")
            if seq is None:
                continue
            by_seq.setdefault(int(seq), {})[rank] = (
                entry.get("op"),
                entry.get("sig"),
            )
    first = None
    for seq in sorted(by_seq):
        calls = by_seq[seq]
        if len(calls) >= 2 and len(set(calls.values())) > 1:
            first = {
                "seq": seq,
                "calls": {
                    str(r): {"op": op, "sig": sig}
                    for r, (op, sig) in sorted(calls.items())
                },
            }
            break
    if len(set(counts)) > 1:
        out["count_skew"] = {
            str(r): s.get("count", 0) for r, s in sorted(compared.items())
        }
    if first is not None:
        out["diverged"] = True  # a same-seq call provably differs
        out["first_divergence"] = first
    elif prefix_provable and len(set(hash_at_min.values())) > 1:
        # the cumulative hashes at the minimum common count disagree:
        # provably divergent at or before that call, even though the
        # differing entry itself rotated out of every window
        out["diverged"] = True
        out["first_divergence"] = None
    elif len(set(counts)) == 1:
        # equal lengths, unequal hashes: provably divergent even though the
        # differing call has rotated out of every window
        out["diverged"] = True
        out["first_divergence"] = None
    else:
        # counts differ and the skew outran the recent windows: cannot
        # distinguish dump-timing skew from divergence — report as
        # indeterminate rather than crying deadlock on a healthy run
        out["indeterminate"] = True
    return out


def _rank_section(events: "list[dict]", file_rank: "dict[str, int]", paths) -> dict:
    """Cross-rank straggler forensics: per-step skew + slowest-rank
    attribution, heartbeat-gap timelines, and merged flight records."""
    from .flight_recorder import load_flight_records

    steps_by_rank: "dict[int, dict[int, float]]" = {}
    mfu_by_rank: "dict[int, list[float]]" = {}
    heartbeats: "dict[int, list[float]]" = {}
    ranks: "dict[int, dict]" = {}
    for e in events:
        rank = _rank_of_event(e, file_rank)
        if rank is None:
            continue
        info = ranks.setdefault(rank, {"events": 0, "steps": 0, "dropped": 0})
        info["events"] += 1
        kind = e.get("kind")
        if kind == "step":
            info["steps"] += 1
            if e.get("step") is not None:
                steps_by_rank.setdefault(rank, {})[int(e["step"])] = float(
                    e.get("dur_s", 0.0)
                )
            if e.get("mfu") is not None:
                mfu_by_rank.setdefault(rank, []).append(float(e["mfu"]))
        elif kind == "heartbeat":
            heartbeats.setdefault(rank, []).append(float(e.get("t", 0.0)))
        elif kind == "dropped":
            info["dropped"] += int(e.get("count", 0))

    # per-step skew over the steps at least two ranks both measured
    per_step: "list[dict]" = []
    slowest_counts: "dict[int, int]" = {}
    excess: "dict[int, list[float]]" = {}
    all_steps = sorted({s for per in steps_by_rank.values() for s in per})
    for s in all_steps:
        durs = {r: per[s] for r, per in steps_by_rank.items() if s in per}
        if len(durs) < 2:
            continue
        slowest = max(durs, key=durs.get)
        fastest_dur = min(durs.values())
        slowest_counts[slowest] = slowest_counts.get(slowest, 0) + 1
        for r, d in durs.items():
            excess.setdefault(r, []).append(d - fastest_dur)
        per_step.append(
            {
                "step": s,
                "skew_s": round(durs[slowest] - fastest_dur, 6),
                "slowest_rank": slowest,
                "durs_s": {str(r): round(d, 6) for r, d in sorted(durs.items())},
            }
        )
    straggler = None
    if slowest_counts:
        rank = max(slowest_counts, key=slowest_counts.get)
        exc = excess.get(rank, [])
        straggler = {
            "rank": rank,
            "slowest_steps": slowest_counts[rank],
            "steps_compared": len(per_step),
            "mean_excess_s": round(sum(exc) / len(exc), 6) if exc else 0.0,
        }

    heartbeat_gaps: dict = {}
    for rank, ts in sorted(heartbeats.items()):
        ts = sorted(ts)
        gaps = [b - a for a, b in zip(ts, ts[1:])]
        heartbeat_gaps[str(rank)] = {
            "beats": len(ts),
            "max_gap_s": round(max(gaps), 6) if gaps else 0.0,
            "p50_gap_s": round(percentile(sorted(gaps), 50), 6) if gaps else 0.0,
        }

    flights = []
    schedules: "dict[int, dict]" = {}
    for rec in load_flight_records(paths):
        phases = rec.get("phases") or {}
        rank = (rec.get("meta") or {}).get("process_index")
        flights.append(
            {
                "file": rec.get("_file"),
                "rank": rank,
                "reason": rec.get("reason"),
                "step": rec.get("step"),
                "phases": {
                    t: {"phase": p.get("phase"), "age_s": p.get("age_s")}
                    for t, p in phases.items()
                },
            }
        )
        sched = rec.get("collective_schedule")
        if rank is not None and isinstance(sched, dict):
            schedules[int(rank)] = sched

    return {
        "per_rank": {
            str(r): dict(
                info,
                wall_s=_dist(list(steps_by_rank.get(r, {}).values())),
                mfu=_dist(mfu_by_rank.get(r, [])),
            )
            for r, info in sorted(ranks.items())
        },
        "steps_compared": len(per_step),
        "skew_s": _dist([p["skew_s"] for p in per_step]),
        "worst_steps": sorted(per_step, key=lambda p: -p["skew_s"])[:5],
        "slowest_counts": {str(r): n for r, n in sorted(slowest_counts.items())},
        "straggler": straggler,
        "heartbeat_gaps": heartbeat_gaps,
        "flight_records": flights,
        "collective_divergence": _collective_divergence(schedules),
    }


def _performance_section(events: "list[dict]", steps: "list[dict]") -> Optional[dict]:
    """MFU/roofline/trace attribution (telemetry/perf.py + xplane.py):
    ``None`` when the streams predate the performance layer (no ``perf`` /
    ``trace`` records and no step carries ``mfu``)."""
    perfs = [e for e in events if e.get("kind") == "perf"]
    traces = [e for e in events if e.get("kind") == "trace" and not e.get("error")]
    projections = [e for e in events if e.get("kind") == "memory_projection"]
    mfu_steps = [s for s in steps if s.get("mfu") is not None]
    if not perfs and not traces and not mfu_steps:
        return None

    proj_by_fn = {str(p.get("fn", "?")): p for p in projections}
    by_fn: dict = {}
    for p in perfs:
        fn = str(p.get("fn", "?"))
        rec = {
            "flops": float(p.get("flops", 0.0)),
            "bytes_accessed": float(p.get("bytes_accessed", 0.0)),
            "arithmetic_intensity": p.get("arithmetic_intensity"),
            "roofline": p.get("roofline"),
            "peak_flops": p.get("peak_flops"),
            "peak_hbm_bytes_per_s": p.get("peak_hbm_bytes_per_s"),
            "device_kind": p.get("device_kind"),
        }
        proj = proj_by_fn.get(fn)
        if proj:
            rec["projected_peak_bytes"] = proj.get("projected_peak_bytes")
            rec["memory_fits"] = proj.get("fits")
        by_fn[fn] = rec
    for fn, rec in by_fn.items():
        rec["mfu"] = _dist(
            [float(s["mfu"]) for s in mfu_steps if s.get("perf_fn") == fn]
        )

    mfus = [float(s["mfu"]) for s in mfu_steps]
    trend = None
    if len(mfus) >= 2:
        half = len(mfus) // 2
        first = sum(mfus[:half]) / half
        last = sum(mfus[half:]) / (len(mfus) - half)
        trend = {
            "first_half_mean": round(first, 6),
            "second_half_mean": round(last, 6),
            "delta": round(last - first, 6),
        }

    trace_section = None
    if traces:
        top: dict = {}
        for t in traces:
            for op in t.get("top_ops") or []:
                rec = top.setdefault(
                    str(op.get("op", "?")),
                    {"op": str(op.get("op", "?")), "total_s": 0.0, "count": 0,
                     "collective": bool(op.get("collective"))},
                )
                rec["total_s"] += float(op.get("total_s", 0.0))
                rec["count"] += int(op.get("count", 0))
        collective_s = sum(float(t.get("collective_s", 0.0)) for t in traces)
        overlap_s = sum(float(t.get("collective_overlap_s", 0.0)) for t in traces)
        op_total = sum(r["total_s"] for r in top.values())
        top_ops = sorted(top.values(), key=lambda r: -r["total_s"])[:10]
        for rec in top_ops:
            rec["total_s"] = round(rec["total_s"], 6)
            rec["share"] = round(rec["total_s"] / op_total, 4) if op_total else 0.0
        trace_section = {
            "windows": len(traces),
            "events": sum(int(t.get("events", 0)) for t in traces),
            "compute_s": round(sum(float(t.get("compute_s", 0.0)) for t in traces), 6),
            "collective_s": round(collective_s, 6),
            "idle_s": round(sum(float(t.get("idle_s", 0.0)) for t in traces), 6),
            "collective_overlap_s": round(overlap_s, 6),
            "comms_overlap_ratio": round(overlap_s / collective_s, 4) if collective_s else None,
            "top_ops": top_ops,
        }

    return {
        "mfu": _dist(mfus),
        "mfu_trend": trend,
        "by_fn": dict(sorted(by_fn.items())),
        "trace": trace_section,
        "trace_errors": sum(1 for e in events if e.get("kind") == "trace" and e.get("error")),
    }


def _spec_decode_dist(steps: "list[dict]") -> Optional[dict]:
    """Aggregate the speculative-decoding fields of ``serving`` step records
    (``serving/engine.py``): draft proposed/accepted token totals, the accept
    rate, and the per-slot-step accepted-count histogram (index = draft
    tokens accepted, summed elementwise over the per-step deltas). ``None``
    when no step carried spec-decode fields (the engine ran without it)."""
    proposed = sum(int(s.get("draft_proposed_tokens", 0)) for s in steps)
    accepted = sum(int(s.get("draft_accepted_tokens", 0)) for s in steps)
    hist: "list[int]" = []
    for s in steps:
        h = s.get("spec_accept_hist")
        if not isinstance(h, list):
            continue
        if len(h) > len(hist):
            hist += [0] * (len(h) - len(hist))
        for i, c in enumerate(h):
            hist[i] += int(c)
    if not hist and not proposed:
        return None
    return {
        "draft_proposed_tokens": proposed,
        "draft_accepted_tokens": accepted,
        "draft_rejected_tokens": proposed - accepted,
        "accept_rate": round(accepted / proposed, 6) if proposed else 0.0,
        "accept_hist": hist,
    }


def _serving_section(events: "list[dict]") -> Optional[dict]:
    """Aggregate the serving engine's per-step ``serving`` records and
    per-completion ``serving_request`` records (``serving/engine.py``):
    queue depth / batch occupancy / block-pool distributions, the
    prefill-vs-decode token split, aggregate decode tokens/s over the record
    span, and per-request latency + time-to-first-token percentiles.
    ``None`` when the streams carry no serving records."""
    steps = [e for e in events if e.get("kind") == "serving" and e.get("phase") == "step"]
    reqs = [e for e in events if e.get("kind") == "serving_request"]
    if not steps and not reqs:
        return None
    decode_tokens = sum(int(s.get("decode_tokens", 0)) for s in steps)
    prefill_tokens = sum(int(s.get("prefill_tokens", 0)) for s in steps)
    # prompt tokens served straight from the prefix cache (prefill skipped);
    # hit rate is over ALL prompt tokens = saved / (saved + prefilled)
    prefix_hit_tokens = sum(int(s.get("prefix_hit_tokens", 0)) for s in steps)
    prompt_tokens = prefix_hit_tokens + prefill_tokens
    ts = sorted(float(s.get("t", 0.0)) for s in steps)
    span = ts[-1] - ts[0] if len(ts) >= 2 else 0.0
    completed = [r for r in reqs if not r.get("error")]
    section = {
        "steps": len(steps),
        "queue_depth": _dist([float(s.get("queue_depth", 0)) for s in steps]),
        "occupancy": _dist([float(s.get("occupancy", 0.0)) for s in steps]),
        "block_occupancy": _dist([float(s.get("block_occupancy", 0.0)) for s in steps]),
        "fragmentation": _dist([float(s.get("fragmentation", 0.0)) for s in steps]),
        "decode_tokens": decode_tokens,
        "prefill_tokens": prefill_tokens,
        "prefill_tokens_saved": prefix_hit_tokens,
        "prefix_hit_rate": (
            round(prefix_hit_tokens / prompt_tokens, 6) if prompt_tokens else 0.0
        ),
        "tokens_per_s": round(decode_tokens / span, 2) if span > 0 else None,
        "preemptions": max((int(s.get("preemptions", 0)) for s in steps), default=0),
        "spec_decode": _spec_decode_dist(steps),
        "requests": {
            "completed": len(completed),
            "rejected": sum(1 for r in reqs if r.get("error")),
            "preempted": sum(1 for r in completed if r.get("preemptions")),
            "new_tokens": sum(int(r.get("new_tokens", 0)) for r in completed),
            # latency/ttft go through the SHARED fixed-bucket histogram
            # (telemetry.metrics), so these percentiles are bit-identical to
            # what a live /metrics scrape of the same run computes
            "latency_s": hist_dist(
                [float(r["latency_s"]) for r in completed if r.get("latency_s") is not None]
            ),
            "ttft_s": hist_dist(
                [float(r["ttft_s"]) for r in completed if r.get("ttft_s") is not None]
            ),
        },
    }
    return section


def _slo_section(events: "list[dict]") -> Optional[dict]:
    """Aggregate ``slo_violation`` records (``telemetry/slo.py``): one per
    burn-episode ENTRY, so the count is "how many times did we start burning
    through the budget", with the worst observed burn rates per objective.
    ``None`` when the streams carry no SLO records — runs without a monitor
    armed don't grow an empty section."""
    violations = [e for e in events if e.get("kind") == "slo_violation"]
    if not violations:
        return None
    by_slo: dict = {}
    for v in violations:
        name = str(v.get("slo", "?"))
        rec = by_slo.setdefault(
            name,
            {
                "violations": 0,
                "kind": v.get("slo_kind"),
                "target": v.get("target"),
                "threshold_s": v.get("threshold_s"),
                "burn_threshold": v.get("burn_threshold"),
                "worst_fast_burn": 0.0,
                "worst_slow_burn": 0.0,
                "fast_window_s": v.get("fast_window_s"),
                "slow_window_s": v.get("slow_window_s"),
            },
        )
        rec["violations"] += 1
        rec["worst_fast_burn"] = max(rec["worst_fast_burn"], float(v.get("fast_burn", 0.0)))
        rec["worst_slow_burn"] = max(rec["worst_slow_burn"], float(v.get("slow_burn", 0.0)))
    return {"violations": len(violations), "by_slo": dict(sorted(by_slo.items()))}


def format_slo_section(slo: dict) -> str:
    """Human rendering of the SLO burn-rate violations (see
    ``docs/observability.md`` for how to write an objective)."""
    lines = [f"SLO: {slo['violations']} violation episode(s)"]
    for name, rec in (slo.get("by_slo") or {}).items():
        target = rec.get("target")
        thr = rec.get("threshold_s")
        obj = f"{target:.2%} good" if target is not None else "?"
        if thr is not None:
            obj += f" @ {thr * 1e3:.0f}ms"
        lines.append(
            f"  {name}: {rec['violations']} episode(s) — objective {obj}, worst "
            f"burn fast={rec['worst_fast_burn']:.1f}x slow={rec['worst_slow_burn']:.1f}x "
            f"(threshold {rec.get('burn_threshold')}x over "
            f"{rec.get('fast_window_s', 0) / 60:.0f}m/{rec.get('slow_window_s', 0) / 60:.0f}m)"
        )
    return "\n".join(lines)


def _compile_cache_section(events: "list[dict]") -> Optional[dict]:
    """Aggregate the persistent compile cache's ``compile_cache`` records
    (``compile_cache/runtime.py``): hit/miss/store/corrupt/fallback counts,
    bytes loaded+stored, load seconds saved into milliseconds paid, and the
    per-function outcome table. ``None`` when the streams carry no cache
    records. A nonzero ``corrupt`` count names quarantined entries — the run
    survived them by fallback compiles, but the operator should look."""
    all_recs = [e for e in events if e.get("kind") == "compile_cache"]
    # supervisor pre-touch probes carry `status` instead of `event`
    pretouch = [str(r.get("status")) for r in all_recs if r.get("status")]
    recs = [r for r in all_recs if r.get("event")]
    degraded = any(s in ("missing", "readonly", "error") for s in pretouch)
    if not recs and not degraded:
        # an unconfigured/healthy pre-touch alone is not a cache story —
        # don't grow every supervised run's report with an empty section
        return None
    by_event: dict = {}
    by_fn: dict = {}
    quarantined: "list[str]" = []
    bytes_loaded = 0
    bytes_stored = 0
    load_s = 0.0
    for r in recs:
        ev = str(r.get("event", "?"))
        by_event[ev] = by_event.get(ev, 0) + 1
        fn = str(r.get("fn", "?"))
        by_fn.setdefault(fn, {})[ev] = by_fn.setdefault(fn, {}).get(ev, 0) + 1
        if ev == "hit":
            bytes_loaded += int(r.get("bytes", 0) or 0)
            load_s += float(r.get("load_s", 0.0) or 0.0)
        elif ev.startswith("store"):
            bytes_stored += int(r.get("bytes", 0) or 0)
        if ev == "corrupt" and r.get("quarantined_to"):
            quarantined.append(str(r["quarantined_to"]))
    pretouch_counts: dict = {}
    for s in pretouch:
        pretouch_counts[s] = pretouch_counts.get(s, 0) + 1
    return {
        "events": len(all_recs),
        "pretouch": dict(sorted(pretouch_counts.items())),
        "hits": by_event.get("hit", 0),
        "misses": by_event.get("miss", 0),
        "stores": by_event.get("store", 0),
        "corrupt": by_event.get("corrupt", 0),
        "fallbacks": by_event.get("fallback", 0),
        "by_event": dict(sorted(by_event.items())),
        "by_fn": dict(sorted(by_fn.items())),
        "bytes_loaded": bytes_loaded,
        "bytes_stored": bytes_stored,
        "load_s": round(load_s, 6),
        "quarantined": quarantined,
    }


def _router_section(events: "list[dict]") -> Optional[dict]:
    """Aggregate the serving router's ``router`` records (``phase: "poll"``
    carries cumulative counters, ``phase: "request"`` one terminal outcome
    per request) and per-replica ``serving_replica`` records
    (``serving/router.py``): replica health table, dispatch/failover totals,
    shed/expired attribution, and finished-request latency percentiles.
    ``None`` when the streams carry no router records."""
    polls = [e for e in events if e.get("kind") == "router" and e.get("phase") == "poll"]
    reqs = [e for e in events if e.get("kind") == "router" and e.get("phase") == "request"]
    reps = [e for e in events if e.get("kind") == "serving_replica"]
    handoffs = [e for e in events if e.get("kind") == "kv_handoff"]
    if not polls and not reqs and not reps:
        return None
    outcomes: dict = {}
    shed_reasons: dict = {}
    for r in reqs:
        outcome = str(r.get("outcome", "?"))
        outcomes[outcome] = outcomes.get(outcome, 0) + 1
        if outcome == "shed" and r.get("error"):
            reason = str(r["error"]).split("shed: ", 1)[-1]
            shed_reasons[reason] = shed_reasons.get(reason, 0) + 1
    finished = [r for r in reqs if r.get("outcome") == "finished"]

    def _cum(key: str) -> int:
        # poll records carry cumulative counters; fall back to per-request
        # outcome counts when only request records made it into the stream
        if polls:
            return max(int(p.get(key, 0)) for p in polls)
        return 0

    # request-record reconstructions for poll-less streams (must stay
    # consistent with `requests.retried` — a section claiming retries
    # happened with zero failovers would read as data loss)
    retries_total = sum(int(r.get("retries", 0)) for r in reqs)
    ran = [r for r in reqs if r.get("outcome") in ("finished", "failed") and r.get("replica")]

    replicas: dict = {}
    for r in reps:
        name = str(r.get("replica", "?"))
        rec = replicas.setdefault(
            name,
            {"state": "?", "role": "serving", "dispatched": 0, "completed": 0,
             "failovers": 0},
        )
        rec["state"] = str(r.get("state", rec["state"]))  # records are in order
        if r.get("role"):
            rec["role"] = str(r["role"])
        for key in ("dispatched", "completed", "failovers"):
            if r.get(key) is not None:
                rec[key] = max(rec[key], int(r[key]))

    # -- disaggregated tiers: only when any record carries the role/handoff
    # markers (monolithic streams keep the old shape + a None tiers key) ------
    disagg_reqs = [r for r in reqs if r.get("prefill_replica")]
    tiers = None
    if handoffs or disagg_reqs or any(
        rec["role"] in ("prefill", "decode") for rec in replicas.values()
    ):
        ho_outcomes: dict = {}
        for h in handoffs:
            o = str(h.get("outcome", "?"))
            ho_outcomes[o] = ho_outcomes.get(o, 0) + 1
        disagg_finished = [r for r in disagg_reqs if r.get("outcome") == "finished"]
        tiers = {
            "prefill_replicas": sorted(
                n for n, rec in replicas.items() if rec["role"] == "prefill"
            ),
            "decode_replicas": sorted(
                n for n, rec in replicas.items() if rec["role"] != "prefill"
            ),
            "handoffs": len(handoffs),
            "handoff_outcomes": dict(sorted(ho_outcomes.items())),
            "handoff_blocks": sum(int(h.get("blocks", 0)) for h in handoffs),
            "handoff_bytes": sum(int(h.get("bytes", 0)) for h in handoffs),
            # the prefill hop's dispatch->handoff wall time, per finished
            # request — the decode hop is latency_s minus this
            "prefill_s": hist_dist(
                [float(r["prefill_s"]) for r in disagg_finished
                 if r.get("prefill_s") is not None]
            ),
            "disagg_finished": len(disagg_finished),
        }
    return {
        "polls": len(polls),
        "queue_depth": _dist([float(p.get("queued", 0)) for p in polls]),
        "dispatched": _cum("dispatched") or len(ran) + retries_total,
        "completed": _cum("completed") or outcomes.get("finished", 0),
        "failovers": _cum("failovers") or retries_total,
        "shed": _cum("shed") or outcomes.get("shed", 0),
        "expired": _cum("expired") or outcomes.get("expired", 0),
        "failed": _cum("failed") or outcomes.get("failed", 0),
        "outcomes": dict(sorted(outcomes.items())),
        "shed_reasons": dict(sorted(shed_reasons.items())),
        "requests": {
            "finished": len(finished),
            "retried": sum(1 for r in finished if int(r.get("retries", 0)) > 0),
            # the shared fixed-bucket histogram (telemetry.metrics): report
            # percentiles == live-scrape percentiles over the same events
            "latency_s": hist_dist(
                [float(r["latency_s"]) for r in finished if r.get("latency_s") is not None]
            ),
            "ttft_s": hist_dist(
                [float(r["ttft_s"]) for r in finished if r.get("ttft_s") is not None]
            ),
        },
        "replicas": dict(sorted(replicas.items())),
        "tiers": tiers,
    }


def _autoscaler_section(events: "list[dict]") -> Optional[dict]:
    """Aggregate the :class:`~accelerate_tpu.serving.autoscaler.
    AutoscalerPolicy`'s ``autoscale`` records: every scale decision with its
    trigger objective, and for each join whether it was warm (zero compiles,
    thanks to pre-shipping) plus its time-to-ready. ``None`` when the stream
    carries no autoscale records."""
    recs = [e for e in events if e.get("kind") == "autoscale"]
    if not recs:
        return None
    actions: dict = {}
    for r in recs:
        a = str(r.get("action", "?"))
        actions[a] = actions.get(a, 0) + 1
    joins = [r for r in recs if r.get("action") == "join_ready"]
    warm = sum(1 for j in joins if j.get("warm"))
    return {
        "actions": dict(sorted(actions.items())),
        "scale_ups": actions.get("scale_up", 0),
        "scale_downs": actions.get("scale_down", 0),
        "joins": {
            "ready": len(joins),
            "failed": actions.get("join_failed", 0),
            "warm": warm,
            "cold": len(joins) - warm,
            "compiles": sum(int(j.get("join_compiles", 0)) for j in joins),
            "time_to_ready_s": _dist(
                [float(j["time_to_ready_s"]) for j in joins
                 if j.get("time_to_ready_s") is not None]
            ),
        },
        "events": [
            {
                k: r.get(k)
                for k in ("action", "replica", "trigger", "fast_burn", "warm",
                          "join_compiles", "time_to_ready_s", "idle_s", "reason")
                if r.get(k) is not None
            }
            for r in recs
        ],
    }


def build_report(paths: Iterable[str], by_rank: bool = False) -> dict:
    events = load_events(paths)
    return build_report_from_events(events, by_rank=by_rank, paths=paths)


def build_report_from_events(
    events: "list[dict]", by_rank: bool = False, paths: Optional[Iterable[str]] = None
) -> dict:
    """Build the report from already-loaded records.

    This is THE aggregation path: :func:`build_report` is ``load_events``
    plus this, and the live hub (:mod:`.hub`) feeds its tailed stream
    through the same function — the shared-formatter invariant (live and
    post-hoc views render the same numbers for the same records) holds
    because there is only one fold. Records must be in per-file order
    (``load_events`` and the hub's tailing both guarantee that; sections
    only rely on within-file ordering)."""
    metas = [e for e in events if e.get("kind") == "meta"]
    steps = [e for e in events if e.get("kind") == "step"]
    misses = [e for e in events if e.get("kind") == "jit_cache_miss"]
    memory = [e for e in events if e.get("kind") == "memory"]
    comms = [e for e in events if e.get("kind") == "comm"]
    waits = [e for e in events if e.get("kind") == "data_wait"]

    file_rank = {
        m["_file"]: int(m["process_index"])
        for m in metas
        if m.get("_file") and m.get("process_index") is not None
    }
    per_rank_events = _per_rank_counts(events, file_rank)

    by_fn: dict = {}
    for m in misses:
        fn = str(m.get("fn", "?"))
        by_fn[fn] = by_fn.get(fn, 0) + int(m.get("recompiles", 0))
    comm_ops: dict = {}
    for c in comms:
        op = str(c.get("op", "?"))
        rec = comm_ops.setdefault(op, {"calls": 0, "bytes": 0})
        rec["calls"] += 1
        rec["bytes"] += int(c.get("bytes", 0))

    # -- data pipeline: per-phase waits + prefetch overlap --------------------
    by_phase: dict = {}
    critical_wait = 0.0
    for w in waits:
        phase = str(w.get("phase", "?"))
        dur = float(w.get("dur_s", 0.0))
        by_phase.setdefault(phase, []).append(dur)
        # records predating the async pipeline carry no flag: they were
        # synchronous, i.e. critical
        if w.get("critical", True):
            critical_wait += dur
    summaries = [e for e in events if e.get("kind") == "prefetch_summary"]
    occupancy = [
        float(e.get("value", 0))
        for e in events
        if e.get("kind") == "gauge" and e.get("name") == "prefetch_queue"
    ]
    prefetch: dict = {
        "epochs": len(summaries),
        "batches": sum(int(s.get("batches", 0)) for s in summaries),
        "fetch_s": round(sum(float(s.get("fetch_s", 0.0)) for s in summaries), 6),
        "transfer_s": round(sum(float(s.get("transfer_s", 0.0)) for s in summaries), 6),
        "stall_s": round(sum(float(s.get("stall_s", 0.0)) for s in summaries), 6),
        "queue_occupancy": _dist(occupancy),
    }
    busy = prefetch["fetch_s"] + prefetch["transfer_s"]
    if busy > 0:
        prefetch["overlap_ratio"] = round(
            max(0.0, min(1.0, 1.0 - prefetch["stall_s"] / busy)), 6
        )

    # -- checkpoints: per-phase time, exposed (train loop blocked) vs hidden --
    ckpts = [e for e in events if e.get("kind") == "checkpoint"]
    ck_phases: dict = {}
    ck_exposed = 0.0
    ck_hidden = 0.0
    for c in ckpts:
        phase = str(c.get("phase", "?"))
        dur = float(c.get("dur_s", 0.0))
        ck_phases.setdefault(phase, []).append(dur)
        # records predating the async writer carry no flag: they were
        # synchronous, i.e. exposed stall on the train loop
        if c.get("hidden", False):
            ck_hidden += dur
        else:
            ck_exposed += dur
    checkpoints = {
        "saves": sum(1 for c in ckpts if c.get("phase") == "commit" and c.get("committed", True)),
        "bytes": sum(int(c.get("bytes", 0)) for c in ckpts if c.get("phase") == "write"),
        "exposed_s": round(ck_exposed, 6),
        "hidden_s": round(ck_hidden, 6),
        "phases": {
            p: dict(_dist(v), total=round(sum(v), 6)) for p, v in sorted(ck_phases.items())
        },
    }

    report = {
        "schema": max((int(m.get("schema", 0)) for m in metas), default=0),
        "runs": sorted({str(m.get("run_id")) for m in metas if m.get("run_id")}),
        "processes": len({m.get("process_index") for m in metas}) or None,
        "events": len(events),
        "per_rank_events": per_rank_events,
        "dropped_events": sum(r["dropped"] for r in per_rank_events.values()),
        "steps": {
            "count": len(steps),
            "wall_s": _dist([float(s.get("dur_s", 0.0)) for s in steps]),
            "data_wait_s": _dist([float(s.get("data_wait_s", 0.0)) for s in steps]),
            "execute_s": _dist([float(s.get("execute_s", 0.0)) for s in steps]),
            "compile_s_total": round(sum(float(s.get("compile_s", 0.0)) for s in steps), 6),
        },
        "recompiles": {
            "total": sum(by_fn.values()),
            "initial_compiles": sum(1 for m in misses if m.get("first")),
            "by_fn": dict(sorted(by_fn.items())),
        },
        "memory": {
            "device_peak_bytes": max((int(m.get("device_peak_bytes", 0)) for m in memory), default=0),
            "live_array_peak_bytes": max((int(m.get("live_array_bytes", 0)) for m in memory), default=0),
            "host_rss_peak_bytes": max((int(m.get("host_rss_bytes", 0)) for m in memory), default=0),
        },
        "comms": {
            "total_calls": sum(r["calls"] for r in comm_ops.values()),
            "total_bytes": sum(r["bytes"] for r in comm_ops.values()),
            "by_op": dict(sorted(comm_ops.items())),
        },
        "data_pipeline": {
            "phases": {
                p: dict(_dist(v), total=round(sum(v), 6)) for p, v in sorted(by_phase.items())
            },
            "critical_wait_s": round(critical_wait, 6),
            "prefetch": prefetch,
        },
        "data_wait_events": len(waits),
        "checkpoints": checkpoints,
        "performance": _performance_section(events, steps),
        "serving": _serving_section(events),
        "router": _router_section(events),
        "autoscaler": _autoscaler_section(events),
        "slo": _slo_section(events),
        # trace roots only: legacy EventLog.span timing records share the
        # kind but carry no trace_id
        "traces": sum(
            1 for e in events
            if e.get("kind") == "span" and e.get("trace_id") and not e.get("parent_id")
        ),
        "restarts": _restarts_section(events),
        "compile_cache": _compile_cache_section(events),
        "anomalies": _anomaly_section(events),
        "canary": _canary_section(events),
        "goodput": _goodput.build_ledger(events, by_rank=by_rank),
    }
    if by_rank:
        report["ranks"] = _rank_section(events, file_rank, paths or [])
    return report


def _anomaly_section(events: "list[dict]") -> dict:
    """Fold the online detectors' ``anomaly`` records (:mod:`.anomaly`):
    episode counts per detector plus the most recent episode's cause
    hypothesis — the post-hoc trace of what the live plane paged on."""
    recs = [e for e in events if e.get("kind") == "anomaly"]
    by_det: dict = {}
    for r in recs:
        det = str(r.get("detector", "?"))
        ent = by_det.setdefault(det, {"episodes": 0, "last": None})
        ent["episodes"] += 1
        ent["last"] = {
            "value": r.get("value"),
            "z": r.get("z"),
            "slope": r.get("slope"),
            "cause": r.get("cause"),
            "source": r.get("source"),
        }
    return {"episodes": len(recs), "by_detector": dict(sorted(by_det.items()))}


def _canary_section(events: "list[dict]") -> dict:
    """Fold the router's ``canary`` / ``canary_failure`` records
    (:mod:`accelerate_tpu.serving.canary`): per-replica probe pass/fail
    tallies and the named bitwise mismatches."""
    probes = [e for e in events if e.get("kind") == "canary"]
    failures = [e for e in events if e.get("kind") == "canary_failure"]
    by_replica: dict = {}
    for p in probes:
        name = str(p.get("replica", "?"))
        ent = by_replica.setdefault(name, {"probes": 0, "failures": 0})
        ent["probes"] += 1
        if p.get("result") == "mismatch":
            ent["failures"] += 1
    return {
        "probes": len(probes),
        "failures": len(failures),
        "by_replica": dict(sorted(by_replica.items())),
        "mismatches": [
            {
                "replica": f.get("replica"),
                "rid": f.get("rid"),
                "golden": f.get("golden"),
                "mismatch_index": f.get("mismatch_index"),
                "expected_token": f.get("expected_token"),
                "got_token": f.get("got_token"),
                "drained": bool(f.get("drained")),
            }
            for f in failures
        ],
    }


def _restarts_section(events: "list[dict]") -> dict:
    """Aggregate the elastic supervisor's ``restart``/``elastic`` records
    (``events-supervisor.jsonl``): generation count, total downtime, cause
    attribution (each restart record carries the classified cause and the
    flight-dump link the supervisor harvested), and how the run ended."""
    restarts = [e for e in events if e.get("kind") == "restart"]
    elastic = [e for e in events if e.get("kind") == "elastic"]
    reshards = [e for e in elastic if e.get("phase") == "reshard"]
    chaos = [e for e in events if e.get("kind") == "chaos_fault"]
    dumps: "list[str]" = []
    for r in restarts:
        if r.get("dump"):
            dumps.append(str(r["dump"]))
    gave_up = next((r for r in restarts if r.get("gave_up")), None)
    # THE downtime/cause computation is goodput.restart_stats — shared with
    # the goodput ledger so the two sections agree by construction
    stats = _goodput.restart_stats(events)
    section = {
        "count": stats["count"],
        "generations": max(
            [int(r.get("generation", 0)) for r in restarts + elastic] or [0]
        ),
        "downtime_s": stats["downtime_s"],
        "causes": stats["causes"],
        "dumps": dumps,
        "reshards": [
            {"saved_mesh": r.get("saved_mesh"), "current_mesh": r.get("current_mesh")}
            for r in reshards
        ],
        "chaos_faults": len(chaos),
        "completed": any(e.get("phase") == "done" for e in elastic),
    }
    if gave_up is not None:
        section["gave_up"] = {
            "cause": gave_up.get("cause"),
            "step": gave_up.get("step"),
            "budget_exhausted": bool(gave_up.get("budget_exhausted")),
        }
    return section


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} TiB"


def format_report(report: dict) -> str:
    lines = []
    runs = ", ".join(report.get("runs") or []) or "<none>"
    lines.append(f"telemetry report — run(s): {runs}, "
                 f"{report.get('processes') or 0} process(es), {report['events']} events")
    per_rank = report.get("per_rank_events") or {}
    if per_rank:
        lines.append(
            "  events by rank: "
            + ", ".join(f"rank{r}={c['events']}" for r, c in per_rank.items())
        )
    dropped = report.get("dropped_events", 0)
    if dropped:
        by_rank_drops = ", ".join(
            f"rank{r}={c['dropped']}" for r, c in per_rank.items() if c["dropped"]
        )
        lines.append(
            f"  WARNING: {dropped} event(s) DROPPED on flush failure ({by_rank_drops}) "
            "— these streams are incomplete"
        )
    s = report["steps"]
    lines.append(f"steps: {s['count']}")
    for key, label in (("wall_s", "step time"), ("data_wait_s", "data wait"), ("execute_s", "execute")):
        d = s[key]
        if d.get("count"):
            lines.append(
                f"  {label:<10} p50={d['p50'] * 1e3:.2f}ms  p90={d['p90'] * 1e3:.2f}ms  "
                f"p99={d['p99'] * 1e3:.2f}ms  max={d['max'] * 1e3:.2f}ms"
            )
    lines.append(f"  compile total: {s['compile_s_total'] * 1e3:.2f}ms")
    r = report["recompiles"]
    lines.append(f"recompiles: {r['total']} (initial compiles: {r['initial_compiles']})")
    for fn, n in r["by_fn"].items():
        if n:
            lines.append(f"  {fn}: {n} recompile(s) — check for varying input shapes/dtypes")
    dp = report.get("data_pipeline") or {}
    if dp.get("phases"):
        lines.append(
            f"data pipeline: critical wait {dp['critical_wait_s'] * 1e3:.2f}ms"
        )
        for phase, d in dp["phases"].items():
            if d.get("count"):
                lines.append(
                    f"  {phase:<10} n={d['count']}  total={d['total'] * 1e3:.2f}ms  "
                    f"p50={d['p50'] * 1e3:.2f}ms  max={d['max'] * 1e3:.2f}ms"
                )
        pf = dp.get("prefetch") or {}
        if pf.get("epochs"):
            ratio = pf.get("overlap_ratio")
            ratio_s = f"{ratio * 100:.1f}% of input work hidden" if ratio is not None else "n/a"
            occ = pf.get("queue_occupancy") or {}
            occ_s = f", queue occupancy p50={occ['p50']:.1f}" if occ.get("count") else ""
            lines.append(
                f"  prefetch: {pf['batches']} batch(es) over {pf['epochs']} epoch(s), "
                f"overlap {ratio_s}{occ_s}"
            )
    ck = report.get("checkpoints") or {}
    if ck.get("saves") or (ck.get("phases") or {}):
        lines.append(
            f"checkpoints: {ck.get('saves', 0)} save(s), {_fmt_bytes(ck.get('bytes', 0))} "
            f"written — exposed stall {ck.get('exposed_s', 0.0) * 1e3:.2f}ms, "
            f"hidden (overlapped) {ck.get('hidden_s', 0.0) * 1e3:.2f}ms"
        )
        for phase, d in (ck.get("phases") or {}).items():
            if d.get("count"):
                lines.append(
                    f"  {phase:<12} n={d['count']}  total={d['total'] * 1e3:.2f}ms  "
                    f"p50={d['p50'] * 1e3:.2f}ms  max={d['max'] * 1e3:.2f}ms"
                )
    rs = report.get("restarts") or {}
    if (rs.get("count") or rs.get("generations") or rs.get("gave_up")
            or rs.get("chaos_faults") or rs.get("reshards")):
        ended = "completed" if rs.get("completed") else (
            "GAVE UP" if rs.get("gave_up") else "in flight/unknown"
        )
        lines.append(
            f"restarts: {rs.get('count', 0)} restart(s) over "
            f"{rs.get('generations', 0) + 1} generation(s), downtime "
            f"{rs.get('downtime_s', 0.0):.1f}s — run {ended}"
        )
        for cause, n in (rs.get("causes") or {}).items():
            lines.append(f"  cause {cause}: {n}")
        for r in rs.get("reshards") or []:
            lines.append(
                f"  elastic reshard: {r.get('saved_mesh')} -> {r.get('current_mesh')}"
            )
        if rs.get("dumps"):
            lines.append(f"  flight dump(s): {', '.join(rs['dumps'][-3:])}")
        if rs.get("chaos_faults"):
            lines.append(f"  chaos faults injected: {rs['chaos_faults']}")
        gu = rs.get("gave_up")
        if gu:
            why = "restart budget exhausted" if gu.get("budget_exhausted") else (
                f"poison step {gu.get('step')}" if gu.get("cause") == "poison_step"
                else str(gu.get("cause"))
            )
            lines.append(f"  gave up: {why}")
    perf = report.get("performance")
    if perf:
        lines.append(format_performance_section(perf))
    serving = report.get("serving")
    if serving:
        lines.append(format_serving_section(serving))
    router = report.get("router")
    if router:
        lines.append(format_router_section(router))
    autoscaler = report.get("autoscaler")
    if autoscaler:
        lines.append(format_autoscaler_section(autoscaler))
    slo = report.get("slo")
    if slo:
        lines.append(format_slo_section(slo))
    anomalies = report.get("anomalies")
    if anomalies and anomalies.get("episodes"):
        lines.append(format_anomaly_section(anomalies))
    canary = report.get("canary")
    if canary and canary.get("probes"):
        lines.append(format_canary_section(canary))
    if report.get("traces"):
        lines.append(
            f"traces: {report['traces']} request trace(s) recorded — "
            "`report --request <id>` renders one, `--trace-out` exports Chrome JSON"
        )
    ccache = report.get("compile_cache")
    if ccache:
        lines.append(format_compile_cache_section(ccache))
    gp = report.get("goodput")
    if gp:
        lines.append(format_goodput_section(gp))
    m = report["memory"]
    lines.append(
        "memory peaks: device "
        + _fmt_bytes(m["device_peak_bytes"])
        + ", live arrays "
        + _fmt_bytes(m["live_array_peak_bytes"])
        + ", host rss "
        + _fmt_bytes(m["host_rss_peak_bytes"])
    )
    c = report["comms"]
    lines.append(f"comms: {c['total_calls']} call(s), {_fmt_bytes(c['total_bytes'])} total")
    for op, rec in c["by_op"].items():
        lines.append(f"  {op}: {rec['calls']} call(s), {_fmt_bytes(rec['bytes'])}")
    if report.get("ranks"):
        lines.append(format_rank_section(report["ranks"]))
    return "\n".join(lines)


def _fmt_flops(n: float) -> str:
    n = float(n)
    for unit in ("", "K", "M", "G", "T", "P"):
        if abs(n) < 1000 or unit == "P":
            return f"{n:.1f} {unit}FLOP" if unit else f"{n:.0f} FLOP"
        n /= 1000.0
    return f"{n:.1f} PFLOP"


def format_goodput_section(gp: dict) -> str:
    """Human rendering of the fleet goodput/badput ledger
    (:mod:`~accelerate_tpu.telemetry.goodput`)."""
    lines = [f"goodput: {gp.get('verdict', '')}"]
    good = gp.get("good_by_category") or {}
    if good:
        lines.append(
            "  good: " + ", ".join(f"{c} {v:.2f}s" for c, v in good.items())
        )
    wall = gp.get("wall_s") or 0.0
    bad = gp.get("badput_s") or {}
    if bad:
        parts = [
            f"{c} {v:.2f}s ({v / wall * 100:.1f}%)" if wall else f"{c} {v:.2f}s"
            for c, v in sorted(bad.items(), key=lambda kv: -kv[1])
        ]
        lines.append("  badput: " + ", ".join(parts))
    ua = gp.get("unattributed_s") or 0.0
    uf = gp.get("unattributed_fraction")
    lines.append(
        f"  unattributed: {ua:.2f}s"
        + (f" ({uf * 100:.1f}%)" if uf is not None else "")
    )
    if gp.get("overattributed"):
        lines.append(
            "  WARNING: attributed seconds exceed wall-clock — overlapping "
            "records; fractions are approximate"
        )
    gens = gp.get("by_generation") or {}
    if len(gens) > 1 or any(g.get("restart_downtime_s") for g in gens.values()):
        for gen, g in gens.items():
            frac = g["good_s"] / g["wall_s"] * 100 if g.get("wall_s") else 0.0
            down = (
                f", restart downtime {g['restart_downtime_s']:.2f}s"
                if g.get("restart_downtime_s")
                else ""
            )
            lines.append(
                f"  gen {gen}: wall {g['wall_s']:.2f}s, good {frac:.1f}%{down}"
            )
    ranks = gp.get("by_rank") or {}
    if ranks:
        skew = gp.get("rank_skew")
        skew_s = f" (goodput skew {skew * 100:.1f}pp)" if skew is not None else ""
        lines.append(
            "  by rank" + skew_s + ": "
            + ", ".join(
                f"rank{r}={v['goodput_fraction'] * 100:.1f}%"
                for r, v in ranks.items()
            )
        )
    tok = gp.get("tokens")
    if tok:
        frac = tok.get("token_goodput_fraction")
        frac_s = f" ({frac * 100:.1f}%)" if frac is not None else ""
        lines.append(
            f"  tokens: computed {tok['computed_tokens']}, "
            f"useful {tok['useful_tokens']}{frac_s}"
        )
        waste = {
            c: n for c, n in (tok.get("waste_by_cause") or {}).items() if n
        }
        if waste or tok.get("shed_requests"):
            parts = [f"{c} {n}" for c, n in sorted(waste.items(), key=lambda kv: -kv[1])]
            if tok.get("shed_requests"):
                parts.append(f"shed {tok['shed_requests']} request(s)")
            lines.append("    waste: " + ", ".join(parts))
    return "\n".join(lines)


def format_performance_section(perf: dict) -> str:
    """Human rendering of the MFU/roofline/trace attribution."""
    lines = ["performance:"]
    d = perf.get("mfu") or {}
    if d.get("count"):
        trend = perf.get("mfu_trend")
        trend_s = ""
        if trend:
            arrow = "↑" if trend["delta"] >= 0 else "↓"
            trend_s = (
                f"  trend {trend['first_half_mean']:.4f}→"
                f"{trend['second_half_mean']:.4f} {arrow}"
            )
        lines.append(
            f"  MFU over {d['count']} step(s): p50={d['p50']:.4f}  "
            f"mean={d['mean']:.4f}  max={d['max']:.4f}{trend_s}"
        )
    by_fn = perf.get("by_fn") or {}
    if by_fn:
        sample = next(iter(by_fn.values()))
        peak_s = ""
        if sample.get("peak_flops"):
            bw = sample.get("peak_hbm_bytes_per_s")
            peak_s = (
                f" (peaks [{sample.get('device_kind')}]: "
                f"{sample['peak_flops'] / 1e12:.1f} TFLOP/s"
                + (f", {bw / 1e9:.0f} GB/s" if bw else "")
                + ")"
            )
        lines.append(f"  roofline{peak_s}:")
        for fn, rec in by_fn.items():
            ai = rec.get("arithmetic_intensity")
            mfu_d = rec.get("mfu") or {}
            mfu_s = f"  mfu p50={mfu_d['p50']:.4f}" if mfu_d.get("count") else ""
            fit = rec.get("memory_fits")
            fit_s = "" if fit is None else ("" if fit else "  MEMORY OVER CAPACITY")
            lines.append(
                f"    {fn:<18} {_fmt_flops(rec.get('flops', 0.0))}/step  "
                f"AI={ai:.1f} FLOP/B  {rec.get('roofline') or '?'}{mfu_s}{fit_s}"
                if ai is not None
                else f"    {fn:<18} {_fmt_flops(rec.get('flops', 0.0))}/step  "
                f"{rec.get('roofline') or '?'}{mfu_s}{fit_s}"
            )
    tr = perf.get("trace")
    if tr:
        lines.append(
            f"  trace windows: {tr['windows']} ({tr['events']} device event(s)) — "
            f"compute {tr['compute_s'] * 1e3:.2f}ms, collective "
            f"{tr['collective_s'] * 1e3:.2f}ms, idle {tr['idle_s'] * 1e3:.2f}ms"
        )
        ratio = tr.get("comms_overlap_ratio")
        lines.append(
            f"  comms overlap: {ratio * 100:.1f}% of collective time hidden under compute"
            if ratio is not None
            else "  comms overlap: n/a (no collective device time traced)"
        )
        for i, op in enumerate(tr.get("top_ops") or [], 1):
            tag = "  [collective]" if op.get("collective") else ""
            lines.append(
                f"    top op {i}: {op['op']}  {op['total_s'] * 1e3:.2f}ms "
                f"({op['share'] * 100:.1f}%, n={op['count']}){tag}"
            )
    if perf.get("trace_errors"):
        lines.append(
            f"  WARNING: {perf['trace_errors']} trace window(s) failed to start "
            "(another profiler session was active)"
        )
    return "\n".join(lines)


def format_serving_section(serving: dict) -> str:
    """Human rendering of the serving engine's queue/occupancy/latency
    aggregation (see ``docs/serving.md`` for how to read it)."""
    lines = ["serving:"]
    tok_s = serving.get("tokens_per_s")
    lines.append(
        f"  {serving['steps']} engine step(s) — decode {serving['decode_tokens']} "
        f"token(s), prefill {serving['prefill_tokens']} token(s)"
        + (f", {tok_s:.1f} decode tok/s" if tok_s is not None else "")
    )
    occ = serving.get("occupancy") or {}
    qd = serving.get("queue_depth") or {}
    blk = serving.get("block_occupancy") or {}
    if occ.get("count"):
        lines.append(
            f"  batch occupancy p50={occ['p50']:.2f} max={occ['max']:.2f}  "
            f"queue depth p50={qd['p50']:.1f} max={qd['max']:.0f}  "
            f"block occupancy p50={blk['p50']:.2f} max={blk['max']:.2f}"
        )
    if serving.get("prefill_tokens_saved"):
        lines.append(
            f"  prefix cache: {serving['prefill_tokens_saved']} prefill token(s) "
            f"saved (hit rate {serving['prefix_hit_rate']:.1%})"
        )
    spec = serving.get("spec_decode") or {}
    if spec.get("accept_hist"):
        hist = spec["accept_hist"]
        bars = " ".join(f"{i}:{c}" for i, c in enumerate(hist))
        lines.append(
            f"  spec decode: accept rate {spec['accept_rate']:.1%} "
            f"({spec['draft_accepted_tokens']}/{spec['draft_proposed_tokens']} "
            f"draft token(s)), accepted-per-step histogram [{bars}]"
        )
    if serving.get("preemptions"):
        lines.append(f"  preemptions: {serving['preemptions']} (pool pressure evictions)")
    reqs = serving.get("requests") or {}
    if reqs.get("completed"):
        lat = reqs.get("latency_s") or {}
        ttft = reqs.get("ttft_s") or {}
        lat_s = (
            f"  latency p50={lat['p50'] * 1e3:.1f}ms p99={lat['p99'] * 1e3:.1f}ms"
            if lat.get("count") else ""
        )
        ttft_s = f"  ttft p50={ttft['p50'] * 1e3:.1f}ms" if ttft.get("count") else ""
        lines.append(
            f"  requests: {reqs['completed']} completed "
            f"({reqs.get('preempted', 0)} preempted-and-resumed, "
            f"{reqs.get('rejected', 0)} rejected), "
            f"{reqs['new_tokens']} token(s) generated{lat_s}{ttft_s}"
        )
    return "\n".join(lines)


def format_compile_cache_section(ccache: dict) -> str:
    """Human rendering of the persistent compile cache outcomes (see
    ``docs/compile_cache.md`` for how to read it)."""
    lines = ["compile cache:"]
    lines.append(
        f"  {ccache.get('hits', 0)} hit(s) ({_fmt_bytes(ccache.get('bytes_loaded', 0))} "
        f"loaded in {ccache.get('load_s', 0.0) * 1e3:.1f}ms), "
        f"{ccache.get('misses', 0)} miss(es), {ccache.get('stores', 0)} store(s) "
        f"({_fmt_bytes(ccache.get('bytes_stored', 0))})"
    )
    for fn, evs in (ccache.get("by_fn") or {}).items():
        parts = ", ".join(f"{ev} x{n}" for ev, n in sorted(evs.items()))
        lines.append(f"    {fn}: {parts}")
    if ccache.get("corrupt"):
        lines.append(
            f"  WARNING: {ccache['corrupt']} corrupt entr(ies) quarantined, "
            f"{ccache.get('fallbacks', 0)} fallback compile(s) paid"
        )
        for q in (ccache.get("quarantined") or [])[-3:]:
            lines.append(f"    quarantined: {q}")
    degraded = {
        s: n for s, n in (ccache.get("pretouch") or {}).items()
        if s in ("missing", "readonly", "error")
    }
    if degraded:
        parts = ", ".join(f"{s} x{n}" for s, n in degraded.items())
        lines.append(
            f"  WARNING: supervisor pre-touch found the cache {parts} — "
            "those generations cold-started"
        )
    return "\n".join(lines)


def format_anomaly_section(anomalies: dict) -> str:
    """Human rendering of the online detectors' episode fold
    (:mod:`~accelerate_tpu.telemetry.anomaly`)."""
    lines = [f"anomalies: {anomalies.get('episodes', 0)} episode(s)"]
    for det, ent in (anomalies.get("by_detector") or {}).items():
        last = ent.get("last") or {}
        detail = []
        if last.get("z") is not None:
            detail.append(f"z={last['z']:.1f}")
        if last.get("slope") is not None:
            detail.append(f"slope={last['slope']:.4f}")
        if last.get("source"):
            detail.append(f"source={last['source']}")
        suffix = f" ({', '.join(detail)})" if detail else ""
        lines.append(f"  {det}: {ent.get('episodes', 0)} episode(s){suffix}")
        if last.get("cause"):
            lines.append(f"    hypothesis: {last['cause']}")
    return "\n".join(lines)


def format_canary_section(canary: dict) -> str:
    """Human rendering of the bitwise correctness-canary fold
    (:mod:`~accelerate_tpu.serving.canary`)."""
    failures = canary.get("failures", 0)
    verdict = "ALL BITWISE" if not failures else f"{failures} MISMATCH(ES)"
    lines = [f"canaries: {canary.get('probes', 0)} probe(s), {verdict}"]
    for name, ent in (canary.get("by_replica") or {}).items():
        lines.append(
            f"  {name}: {ent.get('probes', 0)} probe(s), "
            f"{ent.get('failures', 0)} failure(s)"
        )
    for m in canary.get("mismatches") or []:
        drained = ", replica drained" if m.get("drained") else ""
        lines.append(
            f"  MISMATCH on {m.get('replica')}: golden {m.get('golden')} token "
            f"{m.get('mismatch_index')} expected {m.get('expected_token')} "
            f"got {m.get('got_token')}{drained}"
        )
    return "\n".join(lines)


def format_router_section(router: dict) -> str:
    """Human rendering of the serving router's replica-health / failover /
    shed aggregation (see ``docs/serving.md`` "Running replicated")."""
    lines = ["router:"]
    replicas = router.get("replicas") or {}
    if replicas:
        by_state: dict = {}
        for rec in replicas.values():
            by_state[rec["state"]] = by_state.get(rec["state"], 0) + 1
        states = ", ".join(f"{n} {s}" for s, n in sorted(by_state.items()))
        lines.append(f"  replicas: {len(replicas)} ({states})")
        for name, rec in replicas.items():
            fo = f", {rec['failovers']} failover(s)" if rec.get("failovers") else ""
            role = rec.get("role", "serving")
            role_s = f" [{role}]" if role in ("prefill", "decode") else ""
            lines.append(
                f"    {name}{role_s}: {rec['state']} — dispatched {rec['dispatched']}, "
                f"completed {rec['completed']}{fo}"
            )
    tiers = router.get("tiers")
    if tiers:
        lines.append(
            f"  tiers: {len(tiers.get('prefill_replicas') or [])} prefill / "
            f"{len(tiers.get('decode_replicas') or [])} decode — "
            f"{tiers.get('handoffs', 0)} KV handoff(s), "
            f"{tiers.get('handoff_blocks', 0)} block(s), "
            f"{_fmt_bytes(tiers.get('handoff_bytes', 0))}"
        )
        bad = {
            o: n for o, n in (tiers.get("handoff_outcomes") or {}).items()
            if o != "ok" and n
        }
        if bad:
            lines.append(
                "    handoff outcomes: "
                + ", ".join(f"{o} {n}" for o, n in sorted(bad.items()))
            )
        pf = tiers.get("prefill_s") or {}
        if pf.get("count"):
            lines.append(
                f"    prefill hop p50={pf['p50'] * 1e3:.1f}ms "
                f"p99={pf['p99'] * 1e3:.1f}ms over "
                f"{tiers.get('disagg_finished', 0)} disaggregated request(s)"
            )
    lines.append(
        f"  dispatched {router.get('dispatched', 0)}, completed "
        f"{router.get('completed', 0)}, failover re-dispatches "
        f"{router.get('failovers', 0)}"
    )
    qd = router.get("queue_depth") or {}
    if qd.get("count"):
        lines.append(f"  queue depth p50={qd['p50']:.1f} max={qd['max']:.0f}")
    shed = router.get("shed", 0)
    expired = router.get("expired", 0)
    failed = router.get("failed", 0)
    if shed or expired or failed:
        reasons = router.get("shed_reasons") or {}
        reason_s = (
            " (" + ", ".join(f"{r} {n}" for r, n in reasons.items()) + ")"
            if reasons else ""
        )
        lines.append(f"  shed {shed}{reason_s}, expired {expired}, failed {failed}")
    reqs = router.get("requests") or {}
    if reqs.get("finished"):
        lat = reqs.get("latency_s") or {}
        ttft = reqs.get("ttft_s") or {}
        lat_s = (
            f"  latency p50={lat['p50'] * 1e3:.1f}ms p99={lat['p99'] * 1e3:.1f}ms"
            if lat.get("count") else ""
        )
        ttft_s = f"  ttft p50={ttft['p50'] * 1e3:.1f}ms" if ttft.get("count") else ""
        lines.append(
            f"  requests: {reqs['finished']} finished "
            f"({reqs.get('retried', 0)} resumed across replicas){lat_s}{ttft_s}"
        )
    return "\n".join(lines)


def format_autoscaler_section(autoscaler: dict) -> str:
    """Human rendering of the SLO-driven autoscaler's decision log (see
    ``docs/observability.md`` "Autoscaler signal")."""
    joins = autoscaler.get("joins") or {}
    lines = [
        "autoscaler: "
        f"{autoscaler.get('scale_ups', 0)} scale-up(s), "
        f"{autoscaler.get('scale_downs', 0)} scale-down(s), "
        f"{joins.get('ready', 0)} join(s) "
        f"({joins.get('warm', 0)} warm, {joins.get('cold', 0)} cold, "
        f"{joins.get('failed', 0)} failed)"
    ]
    ttr = joins.get("time_to_ready_s") or {}
    if ttr.get("count"):
        lines.append(
            f"  time-to-ready p50={ttr['p50']:.2f}s max={ttr['max']:.2f}s, "
            f"join compiles {joins.get('compiles', 0)} "
            f"(0 == every warmup point pre-shipped)"
        )
    for ev in autoscaler.get("events") or []:
        action = ev.get("action", "?")
        if action == "scale_up":
            detail = f"+{ev.get('replica')} (trigger {ev.get('trigger')})"
        elif action == "scale_down":
            detail = (
                f"-{ev.get('replica')} (trigger {ev.get('trigger')}, "
                f"idle {ev.get('idle_s', 0):.1f}s)"
            )
        elif action == "join_ready":
            detail = (
                f"{ev.get('replica')} ready in {ev.get('time_to_ready_s', 0):.2f}s, "
                f"{ev.get('join_compiles', 0)} compile(s) "
                f"({'warm' if ev.get('warm') else 'COLD'})"
            )
        else:
            detail = f"{ev.get('replica')} ({ev.get('reason', '?')})"
        lines.append(f"  {action}: {detail}")
    return "\n".join(lines)


def format_rank_section(ranks: dict) -> str:
    """Human rendering of the ``--by-rank`` straggler forensics."""
    lines = ["per-rank stragglers:"]
    for rank, info in (ranks.get("per_rank") or {}).items():
        wall = info.get("wall_s") or {}
        wall_s = (
            f", wall p50={wall['p50'] * 1e3:.2f}ms max={wall['max'] * 1e3:.2f}ms"
            if wall.get("count")
            else ""
        )
        rank_mfu = info.get("mfu") or {}
        mfu_s = f", mfu p50={rank_mfu['p50']:.4f}" if rank_mfu.get("count") else ""
        dropped_s = f", {info['dropped']} dropped" if info.get("dropped") else ""
        lines.append(
            f"  rank {rank}: {info['events']} event(s), {info['steps']} step(s)"
            f"{wall_s}{mfu_s}{dropped_s}"
        )
    skew = ranks.get("skew_s") or {}
    if skew.get("count"):
        lines.append(
            f"  step skew over {ranks['steps_compared']} shared step(s): "
            f"p50={skew['p50'] * 1e3:.2f}ms  p90={skew['p90'] * 1e3:.2f}ms  "
            f"max={skew['max'] * 1e3:.2f}ms"
        )
    straggler = ranks.get("straggler")
    if straggler:
        lines.append(
            f"  straggler: rank {straggler['rank']} — slowest in "
            f"{straggler['slowest_steps']}/{straggler['steps_compared']} step(s), "
            f"mean excess {straggler['mean_excess_s'] * 1e3:.2f}ms over the fastest rank"
        )
    for step in ranks.get("worst_steps") or []:
        durs = "  ".join(f"rank{r}={d * 1e3:.2f}ms" for r, d in step["durs_s"].items())
        lines.append(
            f"    step {step['step']}: skew {step['skew_s'] * 1e3:.2f}ms "
            f"(slowest rank {step['slowest_rank']}: {durs})"
        )
    gaps = ranks.get("heartbeat_gaps") or {}
    if gaps:
        lines.append(
            "  heartbeat gaps: "
            + ", ".join(
                f"rank{r} max={g['max_gap_s']:.2f}s over {g['beats']} beat(s)"
                for r, g in gaps.items()
            )
        )
    div = ranks.get("collective_divergence")
    if div:
        if div.get("diverged"):
            lines.append(
                "  COLLECTIVE SCHEDULE DIVERGENCE: ranks issued different "
                "collective sequences (deadlock risk — see jaxlint R4)"
            )
            for r, s in (div.get("per_rank") or {}).items():
                lines.append(f"    rank {r}: {s['count']} collective(s), hash {s['hash']}")
            first = div.get("first_divergence")
            if first:
                calls = ", ".join(
                    f"rank{r}={c['op']}({c['sig']})"
                    for r, c in first["calls"].items()
                )
                lines.append(f"    first visible divergence at call #{first['seq']}: {calls}")
        elif div.get("indeterminate"):
            lines.append(
                "  collective schedules: INDETERMINATE — counts differ and "
                "the skew outran the recent-call windows; re-dump closer "
                "together (or raise the window) to distinguish timing skew "
                "from divergence"
            )
        elif div.get("prefix_skew"):
            ahead = ", ".join(
                f"rank{r}+{n}" for r, n in div["prefix_skew"].items() if n
            )
            lines.append(
                "  collective schedules: identical common prefix, dump-timing "
                f"skew only ({ahead} call(s) ahead) — not divergence"
            )
        else:
            sample = next(iter((div.get("per_rank") or {}).values()), {})
            lines.append(
                f"  collective schedules: consistent across ranks "
                f"({sample.get('count', 0)} call(s), hash {sample.get('hash')})"
            )
    flights = ranks.get("flight_records") or []
    if flights:
        lines.append("  flight records:")
        for rec in flights:
            phases = ", ".join(
                f"{t}:{p['phase']}@{p['age_s']}s" for t, p in (rec["phases"] or {}).items()
            )
            step_s = f" (step {rec['step']})" if rec.get("step") is not None else ""
            lines.append(
                f"    {rec['file']}: {rec['reason']}{step_s}"
                + (f" — open phases: {phases}" if phases else "")
            )
    return "\n".join(lines)


def find_request_trace(events: "list[dict]", rid: str) -> "tuple[Optional[str], list[dict]]":
    """Locate one request's trace among merged ``span`` records: by the root
    span's ``rid`` attribute (the router's ``q<n>`` / the engine's integer
    rid) or by a raw trace id. Returns ``(trace_id, spans)``."""
    from . import tracing as _tracing

    traces = _tracing.spans_by_trace(events)
    if rid in traces:
        return rid, traces[rid]
    for tid, spans in traces.items():
        for s in spans:
            if not s.get("parent_id") and str((s.get("attrs") or {}).get("rid")) == str(rid):
                return tid, spans
    return None, []


def render_request(paths: Iterable[str], rid: str,
                   trace_out: Optional[str] = None) -> "tuple[int, str]":
    """The ``report --request <id>`` body: one request's span timeline
    (queue → dispatch → prefill chunks → decode steps → failover hops) from
    the trace records, optionally exported as Chrome ``trace.json``."""
    from . import tracing as _tracing

    events = load_events(paths)
    trace_id, spans = find_request_trace(events, rid)
    if not spans:
        available = sorted(
            str((s.get("attrs") or {}).get("rid"))
            for t in _tracing.spans_by_trace(events).values()
            for s in t
            if not s.get("parent_id")
        )
        hint = f" (traced requests: {', '.join(available[:10])})" if available else (
            " (no span records — was ACCELERATE_TRACE_SAMPLE set on the serving run?)"
        )
        return 1, f"no trace found for request {rid!r}{hint}"
    problems = _tracing.validate_span_tree(spans)
    root = next((s for s in spans if not s.get("parent_id")), spans[0])
    attrs = root.get("attrs") or {}
    header = (
        f"request {rid} — trace {trace_id}, {len(spans)} span(s), "
        f"outcome {attrs.get('outcome', '?')}"
        + (f", {attrs.get('retries')} failover retr(ies)" if attrs.get("retries") else "")
    )
    lines = [header, _tracing.format_timeline(spans)]
    if problems:
        lines.append("  WARNING: span tree has gaps: " + "; ".join(problems))
    if trace_out:
        with open(trace_out, "w") as f:
            json.dump(_tracing.chrome_trace(spans), f)
        lines.append(f"  chrome trace written to {trace_out}")
    return 0, "\n".join(lines)


def export_traces(paths: Iterable[str], trace_out: str) -> "tuple[int, str]":
    """``report --trace-out`` without ``--request``: every recorded span as
    one Chrome trace file (all requests side by side)."""
    from . import tracing as _tracing

    events = load_events(paths)
    # trace spans only (legacy EventLog.span timing records have no trace_id)
    spans = [e for e in events if e.get("kind") == "span" and e.get("trace_id")]
    with open(trace_out, "w") as f:
        json.dump(_tracing.chrome_trace(spans), f)
    return 0, f"{len(spans)} span(s) written to {trace_out}"


def run_doctor() -> int:
    """Self-check the forensics pipeline: flight dump → watchdog stall
    detection → straggler report. Exercises the real code paths against
    synthetic inputs in a temp dir; prints one PASS/FAIL line per check."""
    import tempfile
    import threading
    import time as _time

    from . import flight_recorder
    from .flight_recorder import FlightRecorder
    from .watchdog import Watchdog

    failures = 0

    def _check(name: str, ok: bool, detail: str = "") -> None:
        nonlocal failures
        print(f"doctor: {name:<28} {'PASS' if ok else 'FAIL'}"
              + (f" ({detail})" if detail and not ok else ""))
        failures += 0 if ok else 1

    with tempfile.TemporaryDirectory() as tmp:
        # 1. flight recorder: ring + dump with all-thread stacks
        rec = FlightRecorder(capacity=32)
        for i in range(40):
            rec.record("doctor_tick", i=i)
        path = rec.dump("doctor self-check", out_dir=tmp)
        ok = False
        detail = "dump returned None"
        if path and os.path.exists(path):
            data = json.load(open(path))
            ok = (
                len(data["events"]) == 32
                and any("run_doctor" in "".join(t["stack"]) for t in data["threads"])
                and data["reason"] == "doctor self-check"
            )
            detail = "dump missing ring/stacks/reason"
        _check("flight recorder dump", ok, detail)

        # 2. watchdog: a thread blocked in a phase must produce a named dump
        wd = Watchdog(timeout=0.3, interval=0.1, out_dir=tmp).start()

        def _stall():
            with flight_recorder.phase("doctor:fake_stall"):
                _time.sleep(1.2)

        worker = threading.Thread(target=_stall, name="doctor-staller", daemon=True)
        worker.start()
        deadline = _time.monotonic() + 5.0
        while _time.monotonic() < deadline and not wd.dump_paths:
            _time.sleep(0.05)
        worker.join()
        wd.stop()
        ok = bool(wd.dump_paths)
        detail = "no stall dump within 5s"
        if ok:
            data = json.load(open(wd.dump_paths[0]))
            ok = "doctor:fake_stall" in data["reason"]
            detail = "dump does not name the stalled phase"
        _check("watchdog stall detection", ok, detail)

        # 3. straggler report over synthetic two-rank streams (rank 1 3x slower)
        for rank, scale in ((0, 1.0), (1, 3.0)):
            with open(os.path.join(tmp, f"events-rank{rank}.jsonl"), "w") as f:
                f.write(json.dumps({"kind": "meta", "schema": 1, "run_id": "doctor",
                                    "process_index": rank, "num_processes": 2}) + "\n")
                for s in range(8):
                    f.write(json.dumps({"kind": "step", "step": s, "t": float(s),
                                        "dur_s": 0.01 * scale}) + "\n")
        rep = build_report([tmp], by_rank=True)
        straggler = (rep.get("ranks") or {}).get("straggler") or {}
        _check(
            "straggler attribution",
            straggler.get("rank") == 1 and rep["ranks"]["skew_s"]["count"] == 8,
            f"straggler={straggler}",
        )

        # 4. collective-schedule divergence: rank 0 took an extra gather
        # (the `if is_main_process: gather()` shape) while rank 1 moved on
        # to the barrier — their call #3 disagrees
        for rank, ops in ((0, ["gather", "reduce:mean", "gather", "barrier"]),
                          (1, ["gather", "reduce:mean", "barrier"])):
            fr = FlightRecorder(capacity=16)
            for op in ops:
                fr.record_collective(op, "(8, 4)/float32")
            with open(os.path.join(tmp, f"flight-rank{rank}.json"), "w") as f:
                json.dump(
                    {
                        "kind": "flight_record",
                        "reason": "doctor divergence",
                        "meta": {"process_index": rank},
                        "collective_schedule": fr.collective_schedule(),
                    },
                    f,
                )
        rep = build_report([tmp], by_rank=True)
        div = (rep.get("ranks") or {}).get("collective_divergence") or {}
        _check(
            "collective divergence",
            bool(div.get("diverged"))
            and (div.get("first_divergence") or {}).get("seq") == 3,
            f"divergence={div}",
        )

        # 5. static analyzer: a seeded host-sync + rank-divergent collective
        # must both be caught by the lint engine (make lint's substrate)
        snippet = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "from accelerate_tpu.utils.operations import gather\n\n"
            "@jax.jit\n"
            "def step(params, batch):\n"
            "    loss = jnp.mean(batch['x'] @ params['w'])\n"
            "    return float(loss)\n\n"
            "def log_metrics(state, metrics):\n"
            "    if state.is_main_process:\n"
            "        return gather(metrics)\n"
            "    return None\n"
        )
        lint_dir = os.path.join(tmp, "lint")
        os.makedirs(lint_dir, exist_ok=True)
        with open(os.path.join(lint_dir, "doctor_lint_case.py"), "w") as f:
            f.write(snippet)
        try:
            from ..analysis import run_lint

            result = run_lint([lint_dir], use_baseline=False)
            rules_hit = {f.rule for f in result.new_findings}
            _check(
                "static analyzer (jaxlint)",
                {"R1", "R4"} <= rules_hit,
                f"rules_hit={sorted(rules_hit)}",
            )
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("static analyzer (jaxlint)", False, f"{type(exc).__name__}: {exc}")

        # 6. perf cost capture: XLA cost analysis of a real jitted fn must
        # yield FLOPs and bytes, and — where the device has a peak on record,
        # i.e. on a TPU — an MFU and a roofline placement (telemetry/perf.py)
        try:
            import jax
            import jax.numpy as jnp

            from . import perf as _perf

            @jax.jit
            def _doctor_step(x, y):
                return jnp.tanh(x @ y).sum()

            ones = jnp.ones((64, 64), jnp.float32)
            compiled = _doctor_step.lower(ones, ones).compile()
            cost = _perf.cost_from_compiled("doctor_step", compiled)
            ok = cost is not None and cost.flops > 0 and (cost.intensity or 0) > 0
            if ok and cost.peaks is not None:
                ok = (cost.mfu(1e-3) or 0) > 0 and cost.roofline in (
                    "compute-bound", "hbm-bound")
            _check("perf cost capture", ok, f"cost={cost}")
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("perf cost capture", False, f"{type(exc).__name__}: {exc}")

        # 7. xplane trace parse: a real jax.profiler window must decode into
        # op events with durations (telemetry/xplane.py, no-TF pb parser).
        # Builds its own jitted fixture: a check-6 failure must not leak a
        # NameError here and misdiagnose the trace parser.
        try:
            import jax
            import jax.numpy as jnp

            from . import xplane as _xplane

            @jax.jit
            def _trace_step(x, y):
                return jnp.tanh(x @ y).sum()

            ones = jnp.ones((64, 64), jnp.float32)
            trace_dir = os.path.join(tmp, "trace")
            jax.profiler.start_trace(trace_dir)
            for _ in range(3):
                _trace_step(ones, ones).block_until_ready()
            jax.profiler.stop_trace()
            summary = _xplane.summarize_trace(trace_dir)
            ok = summary["events"] > 0 and bool(summary["top_ops"]) and summary["busy_s"] > 0
            _check("xplane trace parse", ok,
                   f"events={summary.get('events')} files={summary.get('files')}")
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("xplane trace parse", False, f"{type(exc).__name__}: {exc}")

        # 8. performance report section: synthetic cost-analysis + trace
        # fixture must render with non-zero MFU and an overlap ratio
        try:
            _doctor_performance_section(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("performance report section", False, f"{type(exc).__name__}: {exc}")

        # 9. fused ZeRO-1 weight update (ISSUE 9): the fused step's module must
        # lint clean under the donation (R3) + collectives (R4) rules, and its
        # COMPILED form on an 8-virtual-device mesh must contain collectives
        # moving real bytes (run in a subprocess — the device count is fixed at
        # backend init, which already happened in this process)
        try:
            _doctor_fused_zero1(_check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("fused zero1 weight update", False, f"{type(exc).__name__}: {exc}")

        # 11. elastic auto-resume (ISSUE 10): the resilience supervisor must
        # ride through a SIGKILLed toy run — restart within the budget, let
        # generation 1 finish, and leave restart telemetry the "restarts"
        # report section can attribute
        try:
            _doctor_elastic(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("elastic auto-resume", False, f"{type(exc).__name__}: {exc}")

        # 12. serving engine (ISSUE 11): continuous batching over the paged
        # KV cache on CPU — staggered variable-length requests must all match
        # their single-stream reference, batch occupancy must exceed 1, and
        # the warmed bucket lattice must absorb all churn with ZERO
        # post-warmup recompiles (the jit caches are the oracle)
        try:
            _doctor_serving(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("serving engine", False, f"{type(exc).__name__}: {exc}")

        # 13. replicated serving router (ISSUE 12): two warmed CPU replicas
        # behind the router, a seeded chaos fault killing one MID-LOAD — the
        # survivor must absorb the failover with token-exact resume, every
        # request must complete exactly once bitwise-equal to its
        # single-stream reference, and the router report section must render
        try:
            _doctor_router(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("replicated serving router", False, f"{type(exc).__name__}: {exc}")

        # 14. persistent compile cache (ISSUE 13): a subprocess compiles a
        # jitted step into a temp cache and commits it; a SECOND subprocess
        # ("the restart") must hit that entry with ZERO backend compiles and
        # zero jit-cache growth; then the entry is bit-flipped and a third
        # subprocess must fall back to a clean fresh compile with the poison
        # quarantined — never a crash, never a wrong result
        try:
            _doctor_compile_cache(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("persistent compile cache", False, f"{type(exc).__name__}: {exc}")

        # 15. prefix-cached paged KV + copy-on-write (ISSUE 14): two requests
        # sharing a long prefix then diverging must produce outputs
        # bitwise-equal to unshared single-stream runs, shared blocks must
        # never be freed while referenced (pool-churn use-after-free probe),
        # and the jit caches must stay frozen post-warmup with the cache on
        try:
            _doctor_prefix_cache(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("prefix cache + COW", False, f"{type(exc).__name__}: {exc}")

        # 16. observability plane (ISSUE 15): a 2-replica CPU router with
        # tracing + metrics ON under a seeded workload with one injected
        # kill — every completed request must carry a GAP-FREE span tree
        # (admission→dispatch→prefill→decode, failover hops included), the
        # live /metrics scrape's ttft histogram count must equal the
        # completions (and its quantiles match the report's serving
        # section), and one slo_violation must fire under an artificially
        # tight ttft objective
        try:
            _doctor_observability(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("observability plane", False, f"{type(exc).__name__}: {exc}")

        # 17. disaggregated prefill/decode (ISSUE 16): a 2-tier fleet (2
        # prefill + 2 decode) under a seeded chaos kill at the kv_handoff
        # point (prefill dies after prefilling, before the handoff lands)
        # plus one seeded handoff corruption — the router must re-run
        # prefill exactly-once in both cases and every request must finish
        # bitwise-equal to its single-stream greedy reference, with the
        # report rendering the per-tier breakdown
        try:
            _doctor_disagg(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("disaggregated serving", False, f"{type(exc).__name__}: {exc}")

        # 18. goodput ledger (ISSUE 17): a supervised toy run under a seeded
        # SIGKILL + slow-data chaos schedule — the ledger must attribute the
        # injected badput to restart_downtime and data_wait, leave <5% of
        # fleet wall-clock unattributed, agree with the restarts section
        # (one shared restart_stats computation), and render with a verdict
        try:
            _doctor_goodput(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("goodput ledger", False, f"{type(exc).__name__}: {exc}")

        # 19. speculative decoding (ISSUE 18): the CPU engine with a
        # truncated-layer self-draft proposing k tokens per step — every
        # completion must stay bitwise-equal to the non-speculative
        # single-stream reference, the jit caches must freeze at the warmed
        # counts (draft + k-verify lattice points included), and the
        # accept-rate histogram must render in the report's serving section
        try:
            _doctor_spec_decode(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("speculative decoding", False, f"{type(exc).__name__}: {exc}")

        # 20. live observability plane (ISSUE 19): a supervised fleet under
        # seeded chaos (one SIGKILL restart, one injected slow fault) tailed
        # LIVE by the hub while its streams grow — the step-latency detector
        # must fire exactly one episode with a cause hypothesis, a seeded
        # canary corruption (one replica built from different param_seed)
        # must drain the bad replica with the bitwise mismatch named and
        # zero false positives on the healthy one, and `top --once` must
        # render the degraded fleet through the report CLI's own section
        # formatters (the shared-formatter invariant, asserted string-exact)
        try:
            _doctor_live_plane(tmp, _check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("live observability plane", False, f"{type(exc).__name__}: {exc}")

        # 21. fp8 through fused ZeRO-1 (ISSUE 20): an fp8 train step on an
        # 8-virtual-device mesh must KEEP the fused bucketed path engaged —
        # the delayed-scaling meta leaves ride as passthrough slots, the
        # optimizer state shards 1/N per replica, losses match the
        # replicated stage-0 baseline, and the compiled step's jit cache is
        # frozen after warmup (run in a subprocess — the device count is
        # fixed at backend init, which already happened in this process)
        try:
            _doctor_fp8_train_step(_check)
        except Exception as exc:  # pragma: no cover - doctor must not crash
            _check("fp8 fused zero1 train step", False, f"{type(exc).__name__}: {exc}")

    print("doctor: all checks passed" if not failures
          else f"doctor: {failures} check(s) FAILED")
    return 1 if failures else 0


def _doctor_compile_cache(tmp: str, _check) -> None:
    """Doctor check 14 body: three subprocess generations against one temp
    cache dir — gen A compiles a jitted step and commits it; gen B (the
    restart) must load it with a cache HIT, zero backend compiles and zero
    jit-cache growth (RecompileWatcher); after a bit-flip, gen C must
    quarantine the poison and fall back to a clean fresh compile producing
    the same result."""
    import subprocess
    import sys

    from ..compile_cache import PAYLOAD_NAME, CompileCache

    cache_dir = os.path.join(tmp, "compile-cache")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(p for p in (repo, env.get("PYTHONPATH")) if p)
    child = (
        "import json, os, sys\n"
        "import jax, jax.numpy as jnp\n"
        "from accelerate_tpu import compile_cache as cc\n"
        "from accelerate_tpu.telemetry import step_profiler as sp\n"
        "sp.install_compile_listener()\n"
        "step = jax.jit(lambda p, x: {'w': p['w'] - 0.1 * (p['w'] @ x)[:, None] * x[None, :]})\n"
        "params = {'w': jnp.ones((16, 16))}\n"
        "x = jnp.ones((16,))\n"
        "watcher = sp.RecompileWatcher()\n"
        "watcher.register('doctor_step', step)\n"
        "c0 = sp.compile_snapshot()[0]\n"
        f"ex, outcome = cc.aot_compile('doctor_step', step, (params, x), directory={cache_dir!r})\n"
        "out = (ex if ex is not None else step)(params, x)\n"
        "c1 = sp.compile_snapshot()[0]\n"
        "print(json.dumps({'outcome': outcome, 'backend_compiles': c1 - c0,\n"
        "                  'jit_entries': int(step._cache_size()),\n"
        "                  'recompiles': sum(watcher.poll(emit=False).values()),\n"
        "                  'result': float(out['w'][0, 0])}))\n"
    )

    def _gen() -> dict:
        res = subprocess.run(
            [sys.executable, "-c", child], env=env, capture_output=True,
            text=True, timeout=240,
        )
        if res.returncode != 0:
            raise RuntimeError(f"child rc={res.returncode}: {res.stderr[-800:]}")
        return json.loads(res.stdout.strip().splitlines()[-1])

    a = _gen()  # cold: compile + commit
    b = _gen()  # restart: must hit with zero compiles anywhere
    cache = CompileCache(cache_dir)
    entry = cache.entries()[0] if cache.entries() else None
    if entry is not None:  # poison: flip one payload byte
        payload = os.path.join(entry, PAYLOAD_NAME)
        blob = bytearray(open(payload, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(payload, "wb").write(bytes(blob))
    c = _gen()  # poisoned restart: quarantine + clean fallback compile
    quarantined = cache.stats()["quarantined"]
    ok = (
        a["outcome"] == "miss" and a["backend_compiles"] >= 1
        and b["outcome"] == "hit" and b["backend_compiles"] == 0
        and b["jit_entries"] == 0 and b["recompiles"] == 0
        and b["result"] == a["result"]
        and entry is not None
        and c["outcome"] == "corrupt" and c["backend_compiles"] >= 1
        and c["result"] == a["result"]
        and quarantined >= 1
    )
    _check(
        "persistent compile cache",
        ok,
        f"cold={a} restart={b} poisoned={c} quarantined={quarantined}",
    )


def _doctor_elastic(tmp: str, _check) -> None:
    """Doctor check 11 body: supervise a toy child that SIGKILLs itself in
    generation 0 and completes in generation 1; the supervisor must classify
    the kill, restart within the budget, exit 0, and emit restart records
    that aggregate into the report's restarts section."""
    import sys

    from ..resilience.supervisor import RestartPolicy, Supervisor

    sup_dir = os.path.join(tmp, "elastic")
    os.makedirs(sup_dir, exist_ok=True)
    done = os.path.join(sup_dir, "DONE")
    child = (
        "import os, signal\n"
        "if os.environ.get('ACCELERATE_RESTART_GENERATION', '0') == '0':\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"
        f"open({done!r}, 'w').write('ok')\n"
    )
    sup = Supervisor(
        [[sys.executable, "-c", child]],
        policy=RestartPolicy(max_restarts=2, backoff_base_s=0.05, grace_period_s=1.0),
        telemetry_dir=sup_dir,
    )
    rc = sup.run()
    rep = build_report([sup_dir])
    rs = rep.get("restarts") or {}
    text = format_report(rep)
    ok = (
        rc == 0
        and sup.restarts_used == 1
        and os.path.isfile(done)
        and rs.get("count") == 1
        and rs.get("completed")
        and rs.get("causes", {}).get("killed") == 1
        and "restarts: 1 restart(s)" in text
    )
    _check("elastic auto-resume", ok, f"rc={rc} restarts={rs}")


def _doctor_serving(tmp: str, _check) -> None:
    """Doctor check 12 body: spin up the serving engine on the CPU backend,
    submit staggered variable-length greedy requests, and require (a) every
    completion identical to its single-stream ``greedy_generate`` reference,
    (b) batch occupancy > 1 at some step (continuous batching actually
    batched), (c) jit caches frozen at the warmed bucket counts (zero
    post-warmup recompiles), and (d) the serving report section renders."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..generation import greedy_generate
    from ..models import LlamaConfig, init_llama
    from ..serving import BucketLattice, ServingEngine
    from . import events as tel_events

    config = LlamaConfig.tiny()
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(config, jax.random.PRNGKey(0))
    )
    serve_dir = os.path.join(tmp, "serving")
    tel_events.enable(out_dir=serve_dir, run_id="doctor-serving")
    try:
        engine = ServingEngine(
            params, config, num_blocks=33, block_size=8, max_slots=4,
            lattice=BucketLattice(
                slot_buckets=(2, 4), block_buckets=(4,), prefill_buckets=(32,)
            ),
        )
        warmed = engine.warmup()
        rng = np.random.default_rng(0)
        specs = [(5, 7), (13, 11), (21, 5), (9, 9), (12, 6)]
        prompts = [rng.integers(0, config.vocab_size, (s,)).astype(np.int32) for s, _ in specs]
        # staggered arrivals: two up front, the rest injected mid-flight
        reqs = [engine.submit(prompts[i], specs[i][1], rng_seed=i) for i in range(2)]
        for i in range(2, len(specs)):
            engine.step()
            reqs.append(engine.submit(prompts[i], specs[i][1], rng_seed=i))
        engine.run()
    finally:
        tel_events.disable()
    mismatched = []
    for i, ((_, max_new), req) in enumerate(zip(specs, reqs)):
        ref = greedy_generate(params, prompts[i][None], config, max_new_tokens=max_new)
        if not np.array_equal(np.asarray(ref[0]), req.output_ids()):
            mismatched.append(i)
    stats = engine.stats()
    report = build_report([serve_dir])
    serving = report.get("serving") or {}
    text = format_report(report)
    ok = (
        not mismatched
        and stats["max_running"] > 1
        and engine.jit_cache_sizes() == warmed
        and (serving.get("requests") or {}).get("completed") == len(specs)
        and "serving:" in text
        and "batch occupancy" in text
    )
    _check(
        "serving engine",
        ok,
        f"mismatched={mismatched} max_running={stats['max_running']} "
        f"caches={engine.jit_cache_sizes()} warmed={warmed}",
    )


def _doctor_spec_decode(tmp: str, _check) -> None:
    """Doctor check 19 body: the serving engine with speculative decoding on
    (k=3 draft tokens from a 1-layer truncated self-draft) must (a) complete
    every staggered greedy request bitwise-equal to the single-stream
    ``greedy_generate`` reference — the bitwise-accept contract, (b) keep the
    jit caches frozen at the warmed counts with the draft and k-verify
    lattice points included, and (c) render the accept-rate histogram in the
    report's serving section."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..generation import greedy_generate
    from ..models import LlamaConfig, init_llama
    from ..serving import BucketLattice, ServingEngine
    from . import events as tel_events

    config = LlamaConfig.tiny()
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(config, jax.random.PRNGKey(0))
    )
    serve_dir = os.path.join(tmp, "spec_decode")
    tel_events.enable(out_dir=serve_dir, run_id="doctor-spec-decode")
    try:
        engine = ServingEngine(
            params, config, num_blocks=33, block_size=8, max_slots=4,
            lattice=BucketLattice(
                slot_buckets=(2, 4), block_buckets=(4,), prefill_buckets=(32,)
            ),
            spec_tokens=3, draft_layers=1,
        )
        warmed = engine.warmup()
        rng = np.random.default_rng(0)
        specs = [(5, 7), (13, 11), (21, 5), (9, 9), (12, 6)]
        prompts = [rng.integers(0, config.vocab_size, (s,)).astype(np.int32) for s, _ in specs]
        reqs = [engine.submit(prompts[i], specs[i][1], rng_seed=i) for i in range(2)]
        for i in range(2, len(specs)):
            engine.step()
            reqs.append(engine.submit(prompts[i], specs[i][1], rng_seed=i))
        engine.run()
    finally:
        tel_events.disable()
    mismatched = []
    for i, ((_, max_new), req) in enumerate(zip(specs, reqs)):
        ref = greedy_generate(params, prompts[i][None], config, max_new_tokens=max_new)
        if not np.array_equal(np.asarray(ref[0]), req.output_ids()):
            mismatched.append(i)
    stats = engine.stats()
    report = build_report([serve_dir])
    serving = report.get("serving") or {}
    spec = serving.get("spec_decode") or {}
    text = format_report(report)
    caches = engine.jit_cache_sizes()
    ok = (
        not mismatched
        and caches == warmed
        and "verify_compiles" in warmed
        and "draft_compiles" in warmed
        and stats["draft_proposed_tokens"] > 0
        and sum(spec.get("accept_hist") or []) > 0
        and "spec decode: accept rate" in text
    )
    _check(
        "speculative decoding",
        ok,
        f"mismatched={mismatched} caches={caches} warmed={warmed} "
        f"accept_rate={stats.get('spec_accept_rate')}",
    )


def _doctor_prefix_cache(tmp: str, _check) -> None:
    """Doctor check 15 body: automatic prefix caching with copy-on-write must
    be INVISIBLE in every output. Two requests share a long block-aligned
    prefix then diverge; the first finishes and frees while the second still
    decodes, and fresh requests are submitted immediately after so any
    erroneously-freed shared block would be reclaimed and overwritten under
    the survivor (the use-after-free probe — corruption would break its
    bitwise parity). Requires (a) every completion bitwise-equal to its
    unshared single-stream ``greedy_generate`` reference, (b) the shared
    prefix actually shared (shared block count and hit tokens > 0 mid-flight),
    (c) jit caches frozen at the warmed counts with the cache enabled, and
    (d) the serving report section renders the prefix-cache savings line."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..generation import greedy_generate
    from ..models import LlamaConfig, init_llama
    from ..serving import BucketLattice, RequestStatus, ServingEngine
    from . import events as tel_events

    config = LlamaConfig.tiny()
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(config, jax.random.PRNGKey(0))
    )
    serve_dir = os.path.join(tmp, "prefix_cache")
    tel_events.enable(out_dir=serve_dir, run_id="doctor-prefix-cache")
    try:
        engine = ServingEngine(
            params, config, num_blocks=33, block_size=8, max_slots=4,
            lattice=BucketLattice(
                slot_buckets=(2, 4), block_buckets=(8,), prefill_buckets=(32,)
            ),
            prefix_cache=True,
        )
        warmed = engine.warmup()
        rng = np.random.default_rng(15)
        shared = rng.integers(0, config.vocab_size, (24,)).astype(np.int32)  # 3 full blocks
        tails = [rng.integers(0, config.vocab_size, (n,)).astype(np.int32)
                 for n in (6, 10)]
        prompts = [np.concatenate([shared, t]) for t in tails]
        a = engine.submit(prompts[0], 6, rng_seed=0)
        engine.step()  # a prefilled: its full blocks are content-indexed
        b = engine.submit(prompts[1], 14, rng_seed=1)
        engine.step()  # b admitted: maps a's 3 shared blocks (refcount 2)
        shared_mid = engine.allocator.shared_blocks()
        reqs = [a, b]
        churned = False
        while not engine.scheduler.idle():
            engine.step()
            if not churned and a.status is RequestStatus.FINISHED:
                # a freed its references while b still decodes: flood the pool
                # with fresh requests so a wrongly-freed shared block would be
                # reclaimed and OVERWRITTEN under b before it finishes
                churned = True
                for i in (2, 3):
                    p = rng.integers(0, config.vocab_size, (20,)).astype(np.int32)
                    prompts.append(p)
                    reqs.append(engine.submit(p, 8, rng_seed=i))
    finally:
        tel_events.disable()
    mismatched = []
    for i, req in enumerate(reqs):
        ref = greedy_generate(
            params, prompts[i][None], config, max_new_tokens=req.max_new_tokens
        )
        if not np.array_equal(np.asarray(ref[0]), req.output_ids()):
            mismatched.append(i)
    hit_tokens = engine.allocator.prefix_hit_tokens
    text = format_report(build_report([serve_dir]))
    ok = (
        not mismatched
        and churned
        and shared_mid >= 3
        and hit_tokens >= 24
        and engine.jit_cache_sizes() == warmed
        and "prefix cache:" in text
    )
    _check(
        "prefix cache + COW",
        ok,
        f"mismatched={mismatched} churned={churned} shared_mid={shared_mid} "
        f"hit_tokens={hit_tokens} caches={engine.jit_cache_sizes()} warmed={warmed}",
    )


def _doctor_router(tmp: str, _check) -> None:
    """Doctor check 13 body: spin two thread-backed CPU replicas behind the
    ServingRouter, arm a seeded chaos ``crash`` fault at the serving_decode
    point (the in-process stand-in for SIGKILL — the real-SIGKILL /
    wedge-forever variants run as the slow-marked subprocess tests in
    ``tests/test_router.py``), kill one replica mid-load, and require (a)
    exactly one replica DEAD with ≥1 failover, (b) every request FINISHED
    exactly once with output bitwise-equal to its single-stream
    ``greedy_generate`` reference, (c) an overload burst sheds by priority
    against a bounded queue (batch displaced by interactive, overflow shed
    with the distinct SHED status, everything admitted still finishing),
    and (d) the router report section renders with the replica table."""
    import dataclasses

    import numpy as np

    from ..models import LlamaConfig
    from ..resilience import chaos
    from ..resilience.chaos import ChaosSchedule, Fault
    from ..serving import (
        PRIORITY_INTERACTIVE,
        AdmissionController,
        LocalReplica,
        ReplicaSpec,
        ReplicaState,
        RouterRequestStatus,
        ServingRouter,
    )
    from . import events as tel_events

    config = LlamaConfig.tiny()
    spec = ReplicaSpec(
        model=dataclasses.asdict(config), num_blocks=33, block_size=8,
        max_slots=2, slot_buckets=(2,), block_buckets=(4,), prefill_buckets=(16,),
    )
    router_dir = os.path.join(tmp, "router")
    tel_events.enable(out_dir=router_dir, run_id="doctor-router")
    router = None
    try:
        # the fault is once-matched under a lock, so EXACTLY one replica
        # thread dies when it reaches engine step 4 mid-decode
        chaos.arm(ChaosSchedule(
            faults=[Fault(kind="crash", point="serving_decode", step=4)]
        ))
        replicas = [LocalReplica(f"r{i}", spec) for i in range(2)]
        router = ServingRouter(
            replicas,
            admission=AdmissionController(max_queue=8),
            health_timeout_s=10.0,
        )
        router.wait_ready(timeout_s=300)
        rng = np.random.default_rng(0)
        reqs = []
        for i in range(6):
            prompt = rng.integers(0, config.vocab_size, (int(rng.integers(4, 12)),))
            reqs.append((prompt.astype(np.int32), 8,
                         router.submit(prompt.astype(np.int32), 8, rng_seed=i)))
        router.run(timeout_s=300)

        # overload burst against the 8-deep bound, submitted without polling
        # so nothing dispatches: batch fills the queue, interactive displaces
        # the newest batch entry, batch overflow sheds outright
        small = np.arange(4, dtype=np.int32) + 1
        burst = [router.submit(small, 4, rng_seed=50 + i) for i in range(8)]
        displacer = router.submit(small, 4, priority=PRIORITY_INTERACTIVE, rng_seed=60)
        overflow = router.submit(small, 4, rng_seed=61)
        depth_bounded = router.admission.depth <= 8
        router.run(timeout_s=300)
    finally:
        chaos.arm(None)
        if router is not None:
            router.close()
        tel_events.disable()

    from ..generation import greedy_generate

    params = spec.build_params()
    mismatched = []
    not_finished = []
    for i, (prompt, max_new, req) in enumerate(reqs):
        if req.status is not RouterRequestStatus.FINISHED:
            not_finished.append((i, req.status.value, req.error))
            continue
        ref = greedy_generate(params, prompt[None], config, max_new_tokens=max_new)
        if not np.array_equal(np.asarray(ref[0]), req.output_ids()):
            mismatched.append(i)
    dead = [n for n, r in router.replicas.items() if r.state is ReplicaState.DEAD]
    report = build_report([router_dir])
    text = format_report(report)
    section = report.get("router") or {}
    admitted_burst = [r for r in burst if r.status is not RouterRequestStatus.SHED]
    shed_ok = (
        depth_bounded
        # interactive displaced exactly one batch request, overflow was shed
        and displacer.status is RouterRequestStatus.FINISHED
        and overflow.status is RouterRequestStatus.SHED
        and "queue-full" in (overflow.error or "")
        and sum(1 for r in burst if r.status is RouterRequestStatus.SHED) == 1
        and "displaced" in (burst[-1].error or "")
        and all(r.status is RouterRequestStatus.FINISHED for r in admitted_burst)
    )
    ok = (
        not not_finished
        and not mismatched
        and len(dead) == 1
        and router.failovers >= 1
        and shed_ok
        and section.get("completed") == len(reqs) + len(admitted_burst) + 1
        and (section.get("shed_reasons") or {}).get("queue-full") == 1
        and "router:" in text
        and "failover re-dispatches" in text
        and any(f"{dead[0]}: dead" in line for line in text.splitlines())
    )
    _check(
        "replicated serving router",
        ok,
        f"not_finished={not_finished} mismatched={mismatched} dead={dead} "
        f"failovers={router.failovers} shed_ok={shed_ok} "
        f"section_completed={section.get('completed')}",
    )


def _doctor_observability(tmp: str, _check) -> None:
    """Doctor check 16 body: two thread-backed CPU replicas behind the
    router with tracing + metrics + SLO monitoring armed, a seeded chaos
    ``crash`` killing one replica mid-decode. Requires (a) every FINISHED
    request carries a gap-free span tree and the failover survivor shows
    its retry lineage (two dispatch spans, one trace_id), (b) the live
    ``/metrics`` scrape's router-ttft histogram count equals the
    completions and its quantiles match the report CLI's router section
    (same shared histogram math), and (c) at least one ``slo_violation``
    fires under an artificially tight ttft objective."""
    import dataclasses
    import urllib.request

    import numpy as np

    from ..models import LlamaConfig
    from ..resilience import chaos
    from ..resilience.chaos import ChaosSchedule, Fault
    from ..serving import (
        AdmissionController,
        LocalReplica,
        ReplicaSpec,
        ReplicaState,
        RouterRequestStatus,
        ServingRouter,
    )
    from . import events as tel_events
    from . import metrics as _metrics
    from . import tracing as _tracing
    from .slo import SLOMonitor, serving_slos

    config = LlamaConfig.tiny()
    spec = ReplicaSpec(
        model=dataclasses.asdict(config), num_blocks=33, block_size=8,
        max_slots=2, slot_buckets=(2,), block_buckets=(4,), prefill_buckets=(16,),
    )
    obs_dir = os.path.join(tmp, "observability")
    tel_events.enable(out_dir=obs_dir, run_id="doctor-observability")
    router = None
    try:
        _tracing.arm(1.0)
        # earlier checks ran serving engines with telemetry on, which arms
        # the process-wide registry — this check compares scrape counts
        # against ITS run, so it starts from a fresh one
        _metrics.disable()
        _metrics.enable()
        _metrics.serve(0)  # a real HTTP scrape, not a registry shortcut
        port = _metrics.server_port()
        chaos.arm(ChaosSchedule(
            faults=[Fault(kind="crash", point="serving_decode", step=4)]
        ))
        monitor = SLOMonitor(
            # ttft threshold of 1µs: every request is "bad", the burn rate
            # saturates, and the violation machinery must fire
            serving_slos(ttft_threshold_s=1e-6), min_events=4,
        )
        replicas = [LocalReplica(f"r{i}", spec) for i in range(2)]
        router = ServingRouter(
            replicas,
            admission=AdmissionController(max_queue=16),
            health_timeout_s=10.0,
            slo_monitor=monitor,
            slo_eval_interval_s=0.0,
        )
        router.wait_ready(timeout_s=300)
        rng = np.random.default_rng(16)
        reqs = []
        for i in range(8):
            prompt = rng.integers(0, config.vocab_size, (int(rng.integers(4, 12)),))
            reqs.append(router.submit(prompt.astype(np.int32), 8, rng_seed=i))
        router.run(timeout_s=300)
        scrape = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
    finally:
        chaos.arm(None)
        if router is not None:
            router.close()
        _tracing.disarm()
        _metrics.disable()
        tel_events.disable()

    finished = [r for r in reqs if r.status is RouterRequestStatus.FINISHED]
    tree_problems = {
        r.rid: _tracing.validate_span_tree(r.trace_spans)
        for r in finished
        if _tracing.validate_span_tree(r.trace_spans)
    }
    retried = [r for r in reqs if r.retries > 0]
    lineage_ok = bool(retried) and all(
        sum(1 for s in r.trace_spans if s["name"] == "dispatch") >= 2
        and len({s["trace_id"] for s in r.trace_spans}) == 1
        for r in retried
    )
    dead = [n for n, r in router.replicas.items() if r.state is ReplicaState.DEAD]

    hist = _metrics.histogram_from_scrape(
        _metrics.parse_prometheus_text(scrape), "accelerate_router_ttft_seconds"
    )
    report = build_report([obs_dir])
    router_section = report.get("router") or {}
    report_ttft = (router_section.get("requests") or {}).get("ttft_s") or {}
    scrape_matches = (
        hist is not None
        and hist.count == len(finished)
        # identical bucket math: the record values round at 1e-6, so agree
        # to that precision
        and abs(hist.quantile(0.50) - report_ttft.get("p50", -1)) < 2e-6
        and abs(hist.quantile(0.99) - report_ttft.get("p99", -1)) < 2e-6
    )
    slo_section = report.get("slo") or {}
    text = format_report(report)
    ok = (
        len(finished) == len(reqs)
        and not tree_problems
        and len(dead) == 1
        and lineage_ok
        and scrape_matches
        and (slo_section.get("by_slo") or {}).get("ttft", {}).get("violations", 0) >= 1
        and "SLO:" in text
    )
    _check(
        "observability plane",
        ok,
        f"finished={len(finished)}/{len(reqs)} tree_problems={tree_problems} "
        f"dead={dead} lineage_ok={lineage_ok} hist_count={getattr(hist, 'count', None)} "
        f"report_ttft={report_ttft} slo={slo_section}",
    )


def _doctor_disagg(tmp: str, _check) -> None:
    """Doctor check 17 body: 2 prefill + 2 decode thread-backed CPU replicas
    behind the DisaggRouter. A seeded chaos ``crash`` at the ``kv_handoff``
    point kills one prefill replica after it prefilled but before the
    handoff shipped (the handoff is DROPPED), and a seeded ``corrupt``
    fault damages one handoff payload in flight. Requires (a) every request
    FINISHED exactly once with output bitwise-equal to its single-stream
    ``greedy_generate`` reference (the router re-ran prefill from scratch
    in both fault cases), (b) exactly one prefill replica DEAD and at least
    one corrupt handoff detected by the wire verify, and (c) the router
    report section renders the per-tier breakdown with handoff counts."""
    import dataclasses

    import numpy as np

    from ..models import LlamaConfig
    from ..resilience import chaos
    from ..resilience.chaos import ChaosSchedule, Fault
    from ..serving import (
        DisaggRouter,
        LocalReplica,
        ReplicaSpec,
        ReplicaState,
        RouterRequestStatus,
    )
    from . import events as tel_events

    config = LlamaConfig.tiny()
    spec = ReplicaSpec(
        model=dataclasses.asdict(config), num_blocks=33, block_size=8,
        max_slots=2, slot_buckets=(2,), block_buckets=(4,), prefill_buckets=(16,),
    )
    pspec = dataclasses.replace(spec, role="prefill")
    dspec = dataclasses.replace(spec, role="decode")
    disagg_dir = os.path.join(tmp, "disagg")
    tel_events.enable(out_dir=disagg_dir, run_id="doctor-disagg")
    router = None
    try:
        # once-matched under a lock: exactly one prefill thread dies
        # mid-handoff (crash) and exactly one handoff arrives damaged
        # (corrupt) — the router must recover both without duplicating or
        # losing a single token
        chaos.arm(ChaosSchedule(faults=[
            Fault(kind="corrupt", point="kv_handoff", step=1),
            Fault(kind="crash", point="kv_handoff", step=2),
        ]))
        router = DisaggRouter(
            [LocalReplica(f"p{i}", pspec) for i in range(2)],
            [LocalReplica(f"d{i}", dspec) for i in range(2)],
            health_timeout_s=10.0,
        )
        router.wait_ready(timeout_s=300)
        rng = np.random.default_rng(17)
        reqs = []
        for i in range(6):
            prompt = rng.integers(0, config.vocab_size, (int(rng.integers(4, 14)),))
            reqs.append((prompt.astype(np.int32), 7,
                         router.submit(prompt.astype(np.int32), 7, rng_seed=i)))
        router.run(timeout_s=300)
    finally:
        chaos.arm(None)
        if router is not None:
            router.close()
        tel_events.disable()

    from ..generation import greedy_generate

    params = spec.build_params()
    mismatched = []
    not_finished = []
    for i, (prompt, max_new, req) in enumerate(reqs):
        if req.status is not RouterRequestStatus.FINISHED:
            not_finished.append((i, req.status.value, req.error))
            continue
        ref = greedy_generate(params, prompt[None], config, max_new_tokens=max_new)
        if not np.array_equal(np.asarray(ref[0]), req.output_ids()):
            mismatched.append(i)
    dead = [n for n, r in router.replicas.items() if r.state is ReplicaState.DEAD]
    report = build_report([disagg_dir])
    text = format_report(report)
    tiers = (report.get("router") or {}).get("tiers") or {}
    ok = (
        not not_finished
        and not mismatched
        and len(dead) == 1
        and dead[0] in ("p0", "p1")
        and router.completed == len(reqs)
        and router.handoffs >= len(reqs)
        and router.handoff_corrupt >= 1
        and tiers.get("handoffs", 0) >= len(reqs)
        and (tiers.get("handoff_outcomes") or {}).get("corrupt", 0) >= 1
        and "  tiers: " in text
        and "KV handoff" in text
    )
    _check(
        "disaggregated serving",
        ok,
        f"not_finished={not_finished} mismatched={mismatched} dead={dead} "
        f"completed={router.completed} handoffs={router.handoffs} "
        f"corrupt={router.handoff_corrupt} tiers={tiers}",
    )


def _doctor_goodput(tmp: str, _check) -> None:
    """Doctor check 18 body: a supervised toy training run under a seeded
    chaos schedule — a SIGKILL at train_step 4 in generation 0 (restart
    downtime) plus persistent slow faults at the prefetch point (data-wait
    stalls). The goodput ledger over the run's event streams must attribute
    the injected badput to its causes (restart_downtime > 0, data_wait
    evidence), leave <5% of fleet wall-clock unattributed, agree with the
    report's restarts section by construction (shared restart_stats), and
    render as the report's ``goodput`` section with a verdict line."""
    import subprocess as _subprocess
    import sys

    from . import goodput as _goodput
    from ..resilience.chaos import ChaosSchedule, Fault
    from ..resilience.supervisor import RestartPolicy, Supervisor

    sup_dir = os.path.join(tmp, "goodput")
    os.makedirs(sup_dir, exist_ok=True)
    schedule = ChaosSchedule(faults=[
        Fault(kind="sigkill", point="train_step", step=4, generation=0),
        Fault(kind="slow", point="prefetch", duration_s=0.1, once=False),
    ])
    env = dict(os.environ)
    env.update({
        "ACCELERATE_TELEMETRY": "1",
        "ACCELERATE_TELEMETRY_DIR": sup_dir,
        "JAX_PLATFORMS": "cpu",
        "ACCELERATE_CHAOS_SCHEDULE": schedule.to_json(),
    })
    sup = Supervisor(
        [[sys.executable, "-m", "accelerate_tpu.resilience._toy_train",
          "--project-dir", os.path.join(sup_dir, "project"),
          "--steps", "20", "--save-every", "8"]],
        env=env,
        policy=RestartPolicy(max_restarts=2, backoff_base_s=0.05, grace_period_s=1.0),
        telemetry_dir=sup_dir,
    )
    rc = sup.run()
    rep = build_report([sup_dir])
    gp = rep.get("goodput") or {}
    badput = gp.get("badput_s") or {}
    unattr = gp.get("unattributed_fraction")
    # the unified-computation satellite, asserted: the ledger's restart
    # stats and the restarts section are the same restart_stats() output
    rs = rep.get("restarts") or {}
    agree = (
        (gp.get("restarts") or {}).get("count") == rs.get("count")
        and (gp.get("restarts") or {}).get("downtime_s") == rs.get("downtime_s")
    )
    text = format_report(rep)
    ok = (
        rc == 0
        and sup.restarts_used == 1
        and badput.get("restart_downtime", 0.0) > 0
        and badput.get("data_wait", 0.0) > 0.04
        and unattr is not None and unattr < 0.05
        and agree
        and "goodput: goodput " in text
        and "restart_downtime" in text
    )
    _check(
        "goodput ledger",
        ok,
        f"rc={rc} restarts={sup.restarts_used} "
        f"downtime={badput.get('restart_downtime')} "
        f"data_wait={badput.get('data_wait')} unattributed={unattr} "
        f"agree={agree}",
    )


def _doctor_fused_zero1(_check) -> None:
    """Doctor check 9 body: jaxlint R3/R4 over the fused-update module +
    accelerator, then a subprocess self_check compiling the fused step and
    summing collective bytes out of its HLO."""
    import subprocess
    import sys

    from ..analysis import run_lint

    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    targets = [
        os.path.join(pkg_dir, "parallel", "weight_update.py"),
        os.path.join(pkg_dir, "accelerator.py"),
    ]
    result = run_lint(targets, use_baseline=False)
    bad = [f for f in result.new_findings if f.rule in ("R3", "R4")]
    _check(
        "fused zero1 lints clean (R3/R4)",
        not bad,
        "; ".join(f"{f.rule}:{os.path.basename(f.file)}:{f.line}" for f in bad),
    )

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # self_check sets the virtual device count
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import json; from accelerate_tpu.parallel.weight_update import "
            "self_check; print(json.dumps(self_check()))",
        ],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=os.path.dirname(pkg_dir),
    )
    ok = False
    detail = f"exit {proc.returncode}: {proc.stderr[-300:]}"
    if proc.returncode == 0:
        try:
            payload = json.loads(proc.stdout.strip().splitlines()[-1])
            ok = (
                payload["hlo_total_collective_bytes"] > 0
                and payload["plan_collective_bytes"] > 0
                and payload["opt_state_shard_fraction"] == 1.0 / payload["n_devices"]
                and payload["parity_max_abs_delta"] < 1.5e-7
            )
            detail = f"payload={payload}"
        except Exception as exc:
            detail = f"unparseable self_check output: {exc}"
    _check("fused zero1 compiled collectives", ok, detail)


def _doctor_fp8_train_step(_check) -> None:
    """Doctor check 21 body: subprocess ``ops.fp8.self_check`` — the fp8
    train step through the FUSED ZeRO-1 path on 8 virtual devices. The
    payload must show the fused path engaged (not demoted by the meta
    leaves), meta riding as passthrough slots, 1/N opt-state sharding,
    loss parity with the replicated stage-0 baseline, rolled amax
    histories, and a jit cache frozen after the warmup compile."""
    import subprocess
    import sys

    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # self_check sets the virtual device count
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import json; from accelerate_tpu.ops.fp8 import "
            "self_check; print(json.dumps(self_check()))",
        ],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=os.path.dirname(pkg_dir),
    )
    ok = False
    detail = f"exit {proc.returncode}: {proc.stderr[-300:]}"
    if proc.returncode == 0:
        try:
            payload = json.loads(proc.stdout.strip().splitlines()[-1])
            ok = (
                payload["fused_engaged"] is True
                and payload["plan_fused"] is True
                and payload["passthrough_leaves"] > 0
                and payload["opt_state_shard_fraction"] == 1.0 / payload["n_devices"]
                and payload["loss_parity_max_rel_delta"] < 1.5e-7
                and payload["meta_histories_rolled"] is True
                and payload["jit_cache_at_end"] == payload["jit_cache_after_warmup"] == 1
            )
            detail = f"payload={payload}"
        except Exception as exc:
            detail = f"unparseable self_check output: {exc}"
    _check("fp8 fused zero1 train step", ok, detail)


def _doctor_performance_section(tmp: str, _check) -> None:
    """Doctor check 8 body: synthetic perf/step/trace records must aggregate
    and render as a performance section with non-zero MFU."""
    perf_dir = os.path.join(tmp, "perfrep")
    os.makedirs(perf_dir, exist_ok=True)
    with open(os.path.join(perf_dir, "events-rank0.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "meta", "schema": 1, "run_id": "doctor",
                            "process_index": 0, "num_processes": 1}) + "\n")
        f.write(json.dumps({
            "kind": "perf", "t": 0.0, "fn": "train_step", "flops": 1e9,
            "bytes_accessed": 1e7, "arithmetic_intensity": 100.0,
            "roofline": "compute-bound", "peak_flops": 197e12,
            "peak_hbm_bytes_per_s": 819e9,
            "device_kind": "TPU v5 lite"}) + "\n")
        for s in range(4):
            f.write(json.dumps({
                "kind": "step", "step": s, "t": float(s), "dur_s": 0.02,
                "compile_s": 0.0, "execute_s": 0.02, "mfu": 0.5,
                "arithmetic_intensity": 100.0, "roofline": "compute-bound",
                "perf_fn": "train_step"}) + "\n")
        f.write(json.dumps({
            "kind": "trace", "t": 5.0, "events": 10, "ops": 3,
            "span_s": 0.1, "busy_s": 0.09, "idle_s": 0.01,
            "compute_s": 0.08, "collective_s": 0.02,
            "collective_overlap_s": 0.015, "comms_overlap_ratio": 0.75,
            "top_ops": [{"op": "fusion.1", "total_s": 0.05, "count": 4,
                         "share": 0.6, "collective": False},
                        {"op": "all-reduce.2", "total_s": 0.02, "count": 2,
                         "share": 0.24, "collective": True}]}) + "\n")
    rep = build_report([perf_dir])
    perf_section = rep.get("performance") or {}
    text = format_report(rep)
    ok = (
        (perf_section.get("mfu") or {}).get("p50", 0) > 0
        and (perf_section.get("trace") or {}).get("comms_overlap_ratio") == 0.75
        and "performance:" in text
        and "compute-bound" in text
        and "75.0% of collective time hidden" in text
    )
    _check("performance report section", ok, f"performance={perf_section}")


def _doctor_live_plane(tmp: str, _check) -> None:
    """Doctor check 20 body: the live observability plane end to end.

    Four sub-scenarios share one telemetry dir: (a) a supervised child is
    SIGKILLed in generation 0 and completes in generation 1, streaming
    live ``supervisor`` status records; (b) the hub tails a rank stream
    WHILE it grows — across a slow-step burst and a torn trailing line —
    and the step-latency detector fires exactly one episode, live, with a
    cause hypothesis; (c) a two-replica CPU fleet under a seeded slow
    fault runs bitwise canaries where one replica's params come from a
    different seed (genuinely corrupt weights): the bad replica must
    drain on its first mismatch with the differing token named, and the
    healthy replica must show zero false positives; (d) ``top --once``
    over the same dir must contain the post-hoc report's router and
    canary sections string-exact — the shared-formatter invariant."""
    import dataclasses
    import io
    import sys
    import time

    from ..models import LlamaConfig
    from ..resilience import chaos
    from ..resilience.chaos import ChaosSchedule, Fault
    from ..resilience.supervisor import RestartPolicy, Supervisor
    from ..serving import (
        CanaryProbe,
        LocalReplica,
        ReplicaSpec,
        ReplicaState,
        ServingRouter,
        precompute_goldens,
    )
    from . import events as tel_events
    from .anomaly import AnomalyEngine
    from .hub import EventHub, run_top

    live_dir = os.path.join(tmp, "live")
    os.makedirs(live_dir, exist_ok=True)

    # (a) supervised fleet under seeded SIGKILL: generation 0 kills itself,
    # generation 1 completes; status_interval_s=0 streams a `supervisor`
    # status record every watch iteration for the hub to fold live.
    child = (
        "import os, signal\n"
        "if os.environ.get('ACCELERATE_RESTART_GENERATION', '0') == '0':\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"
    )
    sup = Supervisor(
        [[sys.executable, "-c", child]],
        policy=RestartPolicy(max_restarts=2, backoff_base_s=0.05, grace_period_s=1.0),
        telemetry_dir=live_dir,
        status_interval_s=0.0,
    )
    sup_rc = sup.run()

    # (b) tail a stream WHILE it grows: three installments with a hub poll
    # between each — warmup steps, then a slow burst ending in a torn
    # line, then the torn line's completion. The burst must fire exactly
    # one live episode; the torn record must parse exactly once, whole.
    hub = EventHub([live_dir], anomaly=AnomalyEngine(emit_records=False))
    hub.poll()
    sup_folded = hub.model.supervisor is not None and hub.model.generation == 1
    rank_path = os.path.join(live_dir, "events-rank7.jsonl")
    with open(rank_path, "w") as f:
        f.write(json.dumps({"kind": "meta", "schema": 1, "run_id": "doctor-live",
                            "process_index": 7, "num_processes": 8}) + "\n")
        for s in range(30):
            f.write(json.dumps({"kind": "step", "step": s, "t": float(s),
                                "dur_s": 0.01, "execute_s": 0.01}) + "\n")
    n1 = len(hub.poll())
    episodes_warm = hub.anomaly.step_latency.episodes
    with open(rank_path, "a") as f:
        for s in range(30, 36):
            f.write(json.dumps({"kind": "step", "step": s, "t": float(s),
                                "dur_s": 0.2, "execute_s": 0.2}) + "\n")
        f.write('{"kind": "step", "step": 36, "t"')  # torn mid-record
    n2 = len(hub.poll())  # 6 slow steps + 1 synthetic anomaly record
    episodes_live = hub.anomaly.step_latency.episodes
    with open(rank_path, "a") as f:
        f.write(': 36.0, "dur_s": 0.01}\n')  # the writer finishes the line
    n3 = len(hub.poll())
    first_anomaly = hub.anomaly.anomalies[0] if hub.anomaly.anomalies else {}
    tail_ok = (
        sup_folded
        and n1 == 31 and episodes_warm == 0
        and n2 == 7 and episodes_live == 1
        and n3 == 1
        and hub.anomaly.step_latency.episodes == 1  # hysteresis held
        and "straggler" in str(first_anomaly.get("cause"))
        and first_anomaly.get("source") == "events-rank7.jsonl"
    )

    # (c) bitwise canaries against a seeded corruption: the bad replica
    # shares the fleet spec but builds its params from a different seed —
    # init is deterministic, so its weights are genuinely wrong and its
    # canary answers diverge bitwise while the healthy replica's match,
    # even with a seeded slow fault injected into the decode path.
    config = LlamaConfig.tiny()
    spec = ReplicaSpec(
        model=dataclasses.asdict(config), num_blocks=33, block_size=8,
        max_slots=2, slot_buckets=(2,), block_buckets=(4,), prefill_buckets=(16,),
    )
    bad_spec = dataclasses.replace(spec, param_seed=1234)
    goldens = precompute_goldens(spec, max_new_tokens=6)
    probe = CanaryProbe(goldens, interval_s=0.05)
    tel_events.enable(out_dir=live_dir, run_id="doctor-live")
    router = None
    try:
        chaos.arm(ChaosSchedule(
            faults=[Fault(kind="slow", point="serving_decode", step=4,
                          duration_s=0.2, once=True)]
        ))
        router = ServingRouter(
            [LocalReplica("good", spec), LocalReplica("bad", bad_spec)],
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
                raise RuntimeError("canary scenario timed out")
            time.sleep(0.002)
    finally:
        chaos.arm(None)
        if router is not None:
            router.close()
        tel_events.disable()

    # (d) the shared-formatter invariant: `top --once` must render the
    # degraded fleet through the report CLI's own section formatters, so
    # the post-hoc report's router and canary sections appear in the live
    # frame string-exact.
    post = build_report([live_dir])
    canary_sec = post.get("canary") or {}
    mismatches = canary_sec.get("mismatches") or []
    buf = io.StringIO()
    rc_top = run_top([live_dir], once=True, out=buf)
    frame = buf.getvalue()
    shared_ok = (
        rc_top == 0
        and format_router_section(post.get("router") or {}) in frame
        and format_canary_section(canary_sec) in frame
        and "bad: draining" in frame
    )
    canary_ok = (
        router.replicas["bad"].state is ReplicaState.DRAINING
        and probe.by_replica.get("good", {}).get("failures") == 0
        and probe.by_replica.get("bad", {}).get("failures", 0) >= 1
        and bool(mismatches)
        and mismatches[0].get("replica") == "bad"
        and mismatches[0].get("mismatch_index") is not None
    )
    ok = sup_rc == 0 and sup.restarts_used == 1 and tail_ok and canary_ok and shared_ok
    _check(
        "live observability plane",
        ok,
        f"sup_rc={sup_rc} restarts={sup.restarts_used} tail_ok={tail_ok} "
        f"(n1={n1} n2={n2} n3={n3} episodes={hub.anomaly.step_latency.episodes}) "
        f"canary_ok={canary_ok} (probe={probe.stats()}) shared_ok={shared_ok}",
    )


def main(argv: Optional["list[str]"] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m accelerate_tpu.telemetry",
        description="Aggregate accelerate_tpu telemetry JSONL event streams.",
    )
    sub = parser.add_subparsers(dest="command")
    rep = sub.add_parser("report", help="aggregate one or more event dirs/files")
    rep.add_argument("paths", nargs="+", help="telemetry dir(s) or .jsonl file(s)")
    rep.add_argument("--json", action="store_true", help="print the raw report dict")
    rep.add_argument(
        "--by-rank",
        action="store_true",
        help="cross-rank straggler section: per-step rank skew, heartbeat gaps, "
        "flight records",
    )
    rep.add_argument(
        "--request",
        metavar="ID",
        help="render one request's distributed-trace span timeline (router rid "
        "like q3, an engine rid, or a raw trace id) instead of the aggregate report",
    )
    rep.add_argument(
        "--trace-out",
        metavar="FILE",
        help="write the span records as a Chrome trace.json (with --request: "
        "that request only; alone: every recorded trace)",
    )
    rep.add_argument(
        "--follow",
        action="store_true",
        help="stream: tail the event files live and re-render the report "
        "whenever they grow (telemetry/hub.py)",
    )
    rep.add_argument(
        "--interval",
        type=float,
        default=2.0,
        metavar="S",
        help="poll interval for --follow / top (seconds, default 2)",
    )
    rep.add_argument(
        "--follow-ticks",
        type=int,
        default=None,
        metavar="N",
        help="stop --follow after N polls (tests/CI; default: run forever)",
    )
    top = sub.add_parser(
        "top",
        help="live fleet dashboard over the tailed event streams "
        "(telemetry/hub.py): replica health, queues, SLO burn, anomalies, "
        "canaries",
    )
    top.add_argument("paths", nargs="+", help="telemetry dir(s) or .jsonl file(s)")
    top.add_argument(
        "--once",
        action="store_true",
        help="render a single frame with no ANSI clear and exit (tests/CI)",
    )
    top.add_argument("--interval", type=float, default=2.0, metavar="S",
                     help="refresh interval (seconds, default 2)")
    top.add_argument("--ticks", type=int, default=None, metavar="N",
                     help="stop after N frames (default: run until ^C)")
    sub.add_parser("doctor", help="self-check the watchdog/flight-recorder/report pipeline")
    _regress.add_parser(sub)
    args = parser.parse_args(argv)
    if args.command == "doctor":
        return run_doctor()
    if args.command == "regress":
        return _regress.run_from_args(args)
    if args.command == "top":
        # lazy import: hub imports this module — the CLI edge must not
        # turn that into an import cycle at load time
        from . import hub as _hub

        return _hub.run_top(
            args.paths, once=args.once, interval_s=args.interval,
            max_ticks=args.ticks,
        )
    if args.command != "report":
        parser.print_help()
        return 2
    if args.follow:
        from . import hub as _hub

        return _hub.run_follow(
            args.paths, by_rank=args.by_rank, interval_s=args.interval,
            max_ticks=args.follow_ticks,
        )
    if args.request is not None:
        rc, text = render_request(args.paths, args.request, trace_out=args.trace_out)
        print(text)
        return rc
    if args.trace_out is not None:
        rc, text = export_traces(args.paths, args.trace_out)
        print(text)
        return rc
    report = build_report(args.paths, by_rank=args.by_rank)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(format_report(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
