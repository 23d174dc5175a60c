"""Compile-time benches.

Default mode — **restart/boot cold vs warm** (`make bench-compile`): the
zero-cold-start recovery numbers the persistent compile cache
(``accelerate_tpu/compile_cache``) exists for. Two subprocess pairs against
one shared cache directory:

- ``train``: restart-to-first-step through the real Accelerator stack —
  generation 0 cold (compiles + exports), generation 1 warm (probes the
  cache before tracing and runs the deserialized executable);
- ``serve``: replica-boot-to-first-token through a ``ReplicaSpec``-built
  serving engine — cold warmup compiles the whole bucket lattice, warm
  warmup loads it.

The parent only sequences the children and never touches JAX: each child in
turn is the one process that holds the chip, and says which device it ran on.
The cache directory the legs share is the repo's own AOT cache; JAX's
persistent compilation cache is switched off in the children (a hit there
would make the cold leg warm) and is never moved.

The payload carries both wall times per leg plus the ``compile_cache``
telemetry counts (hit/miss/store/corrupt), so a "warm" leg that silently
recompiled is visible as miss>0 instead of a fake win.

``--regional`` keeps the original bench: regional (scan-over-layers) vs
fully unrolled compilation (reference ``benchmarks/torch.compile``), via
``bench.run_bench_compile_time``.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _common import detect_backend, emit

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD = os.path.join(HERE, "restart_child.py")


def _cache_event_counts(telemetry_dir: str) -> dict:
    """Aggregate ``compile_cache`` record counts from one leg's telemetry."""
    counts: dict = {}
    try:
        names = os.listdir(telemetry_dir)
    except OSError:
        return counts
    for name in names:
        if not (name.startswith("events-rank") and name.endswith(".jsonl")):
            continue
        with open(os.path.join(telemetry_dir, name)) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") != "compile_cache":
                    continue
                ev = rec.get("event")
                counts[ev] = counts.get(ev, 0) + 1
    return counts


def _run_leg(mode: str, cache_dir: str, telemetry_dir: str, generation: int,
             timeout: int = 300) -> dict:
    os.makedirs(telemetry_dir, exist_ok=True)
    res = subprocess.run(
        [
            sys.executable, CHILD, "--mode", mode,
            "--cache-dir", cache_dir,
            "--telemetry-dir", telemetry_dir,
            "--generation", str(generation),
        ],
        capture_output=True, text=True, timeout=timeout, env=dict(os.environ),
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"restart bench child ({mode}, gen {generation}) failed "
            f"rc={res.returncode}\n{res.stderr[-2000:]}"
        )
    child = json.loads(res.stdout.strip().splitlines()[-1])
    child["compile_cache_events"] = _cache_event_counts(telemetry_dir)
    return child


def run_restart_bench(root: str, modes: "tuple[str, ...]" = ("train", "serve")) -> dict:
    cache_dir = os.path.join(root, "cache")
    os.makedirs(cache_dir, exist_ok=True)
    legs = {}
    device = None
    metrics = {"train": "restart_to_first_step_s", "serve": "boot_to_first_token_s"}
    for mode, metric in ((m, metrics[m]) for m in modes):
        cold = _run_leg(mode, cache_dir, os.path.join(root, f"tel-{mode}-cold"), 0)
        warm = _run_leg(mode, cache_dir, os.path.join(root, f"tel-{mode}-warm"), 1)
        device = warm["device"]
        legs[mode] = {
            "metric": metric,
            "cold_s": cold[metric],
            "warm_s": warm[metric],
            "speedup": round(cold[metric] / max(warm[metric], 1e-9), 3),
            "cold_cache_events": cold["compile_cache_events"],
            "warm_cache_events": warm["compile_cache_events"],
        }
        # bitwise sanity: the warm generation must produce the same first
        # result as the cold one (a wrong executable load would show here)
        if mode == "serve":
            legs[mode]["first_token_match"] = cold["first_token"] == warm["first_token"]
    first = next(iter(legs.values()))
    return {
        "bench": "compile_time_restart",
        "unit": "speedup(cold/warm restart-to-first-step)",
        "value": legs.get("train", first)["speedup"],
        "device": device,
        "on_tpu": device["platform"] == "tpu",
        **legs,
    }


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--regional", action="store_true",
                        help="the original regional-vs-unrolled compile bench")
    parser.add_argument("--keep-dir", default=None,
                        help="run the restart bench under this dir (kept)")
    parser.add_argument("--modes", default="train,serve",
                        help="comma list of restart legs (train, serve)")
    args = parser.parse_args()
    if args.regional:
        from bench import run_bench_compile_time

        emit(run_bench_compile_time(on_tpu=detect_backend()))
    else:
        modes = tuple(m.strip() for m in args.modes.split(",") if m.strip())
        if args.keep_dir:
            os.makedirs(args.keep_dir, exist_ok=True)
            emit(run_restart_bench(args.keep_dir, modes))
        else:
            with tempfile.TemporaryDirectory() as tmp:
                emit(run_restart_bench(tmp, modes))
