"""From a profiler trace (``.xplane.pb``) to what the per-layer metrics read:
the device's busy time as the union of its operations' intervals, the
operations that took most time by short name with how often each ran, and the
device's idle gaps named by the host annotation (``cb.*``) that covers them.

Only the part of the trace inside the runner's ``cb.window`` annotation is
reduced: the profiler's own start and stop leave the device idle, and that is
not the system's doing. Read with nothing but JAX
(``jax.profiler.ProfileData``)."""

from __future__ import annotations

import re

WINDOW = "cb.window"
PREFIX = "cb."
OPS_LINE = "XLA Ops"
KERNEL_MARK = "tpu_custom_call"  # a Pallas kernel, as the compiled program names it


def short_name(op: str) -> str:
    """``%fusion.468 = (...) fusion(...), kind=kLoop, calls=...`` ->
    ``fusion.468 kLoop``: the text before `` = `` and the fusion kind, never
    the whole instruction."""
    head = op.split(" = ", 1)[0].strip().lstrip("%")
    kind = re.search(r"\bkind=(k\w+)", op)
    return f"{head} {kind.group(1)}" if kind else head


def _union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(start, end, lo, hi):
    return max(start, lo), min(end, hi)


def read_planes(path: str):
    """``(device planes, host annotations)``: per device plane the list of
    ``(name, start_ns, end_ns)`` of its operations, and the host's ``cb.*``
    spans as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    devices, annotations = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            annotations += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for line in plane.lines for e in line.events
                            if e.name.startswith(PREFIX)]
    return devices, annotations


def reduce(devices, annotations) -> dict | None:
    """The reduction proper, on plain tuples (so that a test can feed it a cut
    sample). Seconds are averaged over the device planes. None where the trace
    holds no window or no device operation."""
    windows = [a for a in annotations if a[0] == WINDOW]
    if not windows or not devices:
        return None
    lo, hi = windows[0][1], windows[0][2]
    spans = [(n, *_clip(s, e, lo, hi)) for n, s, e in annotations if n != WINDOW and e > lo and s < hi]
    busy_ns, op_ns, op_calls, kernel_ns, gap_ns = 0.0, {}, {}, 0.0, {}
    for ops in devices:
        inside = [(n, *_clip(s, e, lo, hi)) for n, s, e in ops if e > lo and s < hi]
        merged = _union((s, e) for _, s, e in inside)
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in inside:
            short = short_name(name)
            op_ns[short] = op_ns.get(short, 0.0) + (e - s)
            op_calls[short] = op_calls.get(short, 0) + 1
            if KERNEL_MARK in name:
                kernel_ns += e - s
        edges = [lo] + [t for pair in merged for t in pair] + [hi]
        for gap_start, gap_end in zip(edges[0::2], edges[1::2]):
            if gap_end <= gap_start:
                continue
            covered = 0.0
            for name, s, e in spans:  # the gap goes to the host spans that overlap it
                overlap = min(e, gap_end) - max(s, gap_start)
                if overlap > 0:
                    gap_ns[name] = gap_ns.get(name, 0.0) + overlap
                    covered += overlap
            if gap_end - gap_start > covered:
                gap_ns["unattributed"] = gap_ns.get("unattributed", 0.0) + (
                    gap_end - gap_start - covered)
    n = len(devices)

    def ranked(table):
        return [[k, v / n / 1e9] for k, v in sorted(table.items(), key=lambda kv: -kv[1])]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "kernel_s": kernel_ns / n / 1e9,
        "n_devices": n,
        "device_ops": ranked(op_ns),
        "device_op_calls": {k: v / n for k, v in op_calls.items()},  # a device, like the seconds
        "idle_gaps": ranked(gap_ns),
    }


def reduce_file(path: str) -> dict | None:
    return reduce(*read_planes(path))
