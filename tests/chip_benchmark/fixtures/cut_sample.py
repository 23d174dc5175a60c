"""Cut ``v5e_trace_sample.json`` from a traced run's ``.xplane.pb``:

    CHIP_BENCHMARK_KEEP_TRACE=chiprun_out/bert.xplane.pb python3 benchmarks/chip/run.py \
        --workload bert-base.seqcls-s128 --seed 12 --seconds 10 --trace 1     # on the chip
    python3 tests/chip_benchmark/fixtures/cut_sample.py chiprun_out/bert.xplane.pb 400

keeps the last N device operations inside the run's ``cb.window`` (the end of
the last step and the host's fetch of the loss behind it) with the host's
``cb.*`` spans clipped to that stretch, and writes beside them what the
reduction has to find. The expected numbers are worked out here on a 1 ns
timeline with numpy, not with ``trace_reduce``'s interval arithmetic."""

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, REPO)

from benchmarks.chip import trace_reduce  # noqa: E402


def main(path: str, n_ops: int) -> None:
    devices, annotations = trace_reduce.read_planes(path)
    window = next(a for a in annotations if a[0] == trace_reduce.WINDOW)
    ops = sorted((op for op in devices[0] if op[1] >= window[1] and op[2] <= window[2]),
                 key=lambda op: op[1])[-n_ops:]
    lo, hi = int(ops[0][1]), int(window[2])
    spans = [[n, max(int(s), lo), min(int(e), hi)] for n, s, e in annotations
             if n != trace_reduce.WINDOW and e > lo and s < hi]
    ops = [[n, int(s) - lo, int(e) - lo] for n, s, e in ops]
    spans = [[n, s - lo, e - lo] for n, s, e in spans]
    length = hi - lo

    busy = np.zeros(length, bool)
    totals = {}
    for name, s, e in ops:
        busy[s:e] = True
        short = trace_reduce.short_name(name)
        totals[short] = totals.get(short, 0) + (e - s)
    gap_name, gap_ns = None, 0
    for name in sorted({n for n, _, _ in spans}):
        covered = np.zeros(length, bool)
        for n, s, e in spans:
            if n == name:
                covered[s:e] = True
        idle = int(np.sum(covered & ~busy))
        if idle > gap_ns:
            gap_name, gap_ns = name, idle
    sample = {
        "from": "a traced run of bert-base.seqcls-s128 on one TPU v5 lite (my chip run, PR 23)",
        "device_ops": ops,
        "annotations": [[trace_reduce.WINDOW, 0, length]] + spans,
        "expect": {
            "window_s": length / 1e9,
            "busy_share": float(busy.mean()),
            "top_op": max(totals, key=totals.get),
            "gap_name": gap_name,
            "gap_s": gap_ns / 1e9,
        },
    }
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "v5e_trace_sample.json")
    with open(out, "w") as f:
        json.dump(sample, f, indent=0)
    print(out, os.path.getsize(out), "bytes", sample["expect"])


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
