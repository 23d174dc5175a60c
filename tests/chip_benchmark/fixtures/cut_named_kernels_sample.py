"""Cut ``v5e_named_kernels_sample.json`` from a traced serve run's ``.xplane.pb``:

    CHIP_BENCHMARK_KEEP_TRACE=chiprun_out/r80.xplane.pb python3 benchmarks/chip/run.py \
        --workload mistral-7b.chat-r80 --seed 25 --seconds 51 --trace 1     # on the chip
    python3 tests/chip_benchmark/fixtures/cut_named_kernels_sample.py chiprun_out/r80.xplane.pb 16

keeps, from inside the run's ``cb.window``, one stretch of the device's
operations that runs from the first kernel of a prefill chunk's program to the
last kernel of the decode program behind it (``n_layers`` ``paged_prefill``
calls, then ``n_layers`` ``paged_decode`` calls: the kernels are named by the
``name=`` of their ``pallas_call``), every operation with its whole text, and
the host's spans (the runner's ``cb.*`` and the program's ``atpu.*``) clipped
to the stretch. Beside them goes what the readers have to find, worked out
here by plain sums over the operations, not with ``trace_reduce``.

``cut_sample.py`` beside this file cuts the other fixture and writes to its
fixed path; this cutter is its own file so that neither fixture's is edited."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, REPO)

from benchmarks.chip import trace_reduce  # noqa: E402

DECODE, PREFILL = "paged_decode", "paged_prefill"
HOST_PREFIXES = ("cb.", "atpu.")


def host_spans(path: str):
    """The host's ``cb.*`` and ``atpu.*`` annotations (``read_planes`` keeps
    only ``cb.*``); a ``TraceAnnotation``'s keyword arguments ride behind a
    ``#`` in the event's name and are dropped."""
    from jax.profiler import ProfileData

    return [(e.name.split("#")[0], e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(HOST_PREFIXES)]


def main(path: str, n_layers: int, out: str) -> None:
    devices, annotations = trace_reduce.read_planes(path)
    window = next(a for a in annotations if a[0] == trace_reduce.WINDOW)
    ops = sorted((op for op in devices[0] if op[1] >= window[1] and op[2] <= window[2]),
                 key=lambda op: op[1])
    kernels = [i for i, op in enumerate(ops) if trace_reduce.KERNEL_MARK in op[0]]
    which = [PREFILL if PREFILL in trace_reduce.short_name(ops[i][0]) else DECODE for i in kernels]
    # the first decode kernel that follows a whole prefill chunk's program
    k = next(j for j in range(n_layers, len(which) - n_layers + 1)
             if which[j] == DECODE and all(w == PREFILL for w in which[j - n_layers:j])
             and all(w == DECODE for w in which[j:j + n_layers]))
    # from behind the kernel before them to before the kernel after them: both programs whole
    first = kernels[k - n_layers - 1] + 1 if k > n_layers else 0
    last = kernels[k + n_layers] - 1 if k + n_layers < len(kernels) else len(ops) - 1
    ops = ops[first:last + 1]
    lo, hi = int(ops[0][1]), int(ops[-1][2])
    spans = [[n, max(int(s), lo) - lo, min(int(e), hi) - lo] for n, s, e in host_spans(path)
             if n != trace_reduce.WINDOW and e > lo and s < hi]
    ops = [[n, int(s) - lo, int(e) - lo] for n, s, e in ops]

    def seconds(mark):
        return sum(e - s for n, s, e in ops if mark in trace_reduce.short_name(n)) / 1e9

    busy_ns, covered_to = 0, 0  # the union of the operations' intervals, in start order
    for _, s, e in ops:
        busy_ns += max(0, e - max(s, covered_to))
        covered_to = max(covered_to, e)
    sample = {
        "from": "a traced run of mistral-7b.chat-r80 on one TPU v5 lite (my chip run, PR 25)",
        "n_layers": n_layers,
        "device_ops": ops,
        "annotations": [[trace_reduce.WINDOW, 0, hi - lo]] + spans,
        "expect": {
            "window_s": (hi - lo) / 1e9,
            "busy_s": busy_ns / 1e9,
            "kernel_s": sum(e - s for n, s, e in ops if trace_reduce.KERNEL_MARK in n) / 1e9,
            "paged_decode_s": seconds(DECODE),
            "paged_prefill_s": seconds(PREFILL),
            "paged_decode_calls": sum(DECODE in trace_reduce.short_name(n) for n, _, _ in ops),
            "paged_prefill_calls": sum(PREFILL in trace_reduce.short_name(n) for n, _, _ in ops),
        },
    }
    with open(out, "w") as f:
        json.dump(sample, f, indent=0)
    print(out, os.path.getsize(out), "bytes", len(ops), "operations", sample["expect"])


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]),
         sys.argv[3] if len(sys.argv) > 3 else os.path.join(
             os.path.dirname(os.path.abspath(__file__)), "v5e_named_kernels_sample.json"))
