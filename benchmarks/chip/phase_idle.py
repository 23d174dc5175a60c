"""The device's idle time in a traced slice, by the serving engine's phase the
host was in: every idle nanosecond inside ``cb.window`` goes to the innermost
``atpu.serve.*`` span that covers it, on the profiler's own clock.

The engine writes each of its phases twice (``accelerate_tpu.telemetry.
tracing.phase``): in its ring, on the monotonic clock, and as a profiler
annotation, beside the device's operations in the same trace. So the phases
are read here from the trace itself, and nothing converts between clocks.
They nest (``step`` > ``prefill`` > ``prefill_call``), so the innermost span
at an instant is the latest-starting one that is open. Inside a wait for a
program's result (``fetch``, ``prefill_sync``) idle after the end of the last
device operation that started before the wait ended is booked apart, as
``<wait>.after_device``: the result's way back and the host's wake-up. Idle
earlier in a wait stays the wait's: the program has not started or has not
finished. Idle under no phase is ``outside``: the runner's loop, ``cb.submit``,
``cb.sleep``. The entries sum to ``window_s - busy_s``.

``CAUSES`` sorts the entries three ways, each a share of the slice on the base
of ``device_idle_share.serve``: ``host`` (the host computing while the device
waits), ``launch`` (argument transfer, enqueue, and the wait before a program
has run), ``return`` (the ``after_device`` entries); with ``outside`` they add
up to the idle share.

The harness does not call this yet: ``reduce_file`` here is what
``trace_reduce.reduce_file`` would return with the key ``phase_idle`` added,
and ``python3 -m benchmarks.chip.phase_idle <trace.xplane.pb> ...`` prints it
for a trace kept with ``CHIP_BENCHMARK_KEEP_TRACE``."""

from __future__ import annotations

import bisect
import json
import sys

from benchmarks.chip import trace_reduce

PHASE = "atpu.serve."
WAITS = ("fetch", "prefill_sync")  # the host waits here for a program's result
AFTER = ".after_device"
OUTSIDE = "outside"
CAUSES = {
    "host": ("admit", "grow", "build", "emit", "prefill", "step"),
    "launch": ("dispatch", "prefill_call") + WAITS,
    "return": tuple(wait + AFTER for wait in WAITS),
}


def read_planes(path: str, prefixes=(trace_reduce.PREFIX, PHASE)):
    """``trace_reduce.read_planes`` with the host's spans of every prefix in
    ``prefixes``. Where a ``TraceAnnotation``'s keywords ride behind a ``#``
    in the event's name they are cut off."""
    from jax.profiler import ProfileData

    devices, annotations = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == trace_reduce.OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            annotations += [(e.name.split("#")[0], e.start_ns, e.start_ns + e.duration_ns)
                            for line in plane.lines for e in line.events
                            if e.name.startswith(prefixes)]
    return devices, annotations


def _idle_before(gaps):
    """``f(t)``: the idle nanoseconds of the sorted, disjoint ``gaps`` before ``t``."""
    starts = [s for s, _ in gaps]
    before = [0.0]
    for s, e in gaps:
        before.append(before[-1] + (e - s))

    def f(t):
        i = bisect.bisect_right(starts, t)
        return before[i - 1] + min(t, gaps[i - 1][1]) - gaps[i - 1][0] if i else 0.0

    return f


def _plane(ops, spans, lo, hi, idle):
    """One device plane's idle nanoseconds into ``idle``, by phase."""
    inside = sorted((max(s, lo), min(e, hi)) for _, s, e in ops if e > lo and s < hi)
    merged = trace_reduce._union(inside)
    edges = [lo] + [t for pair in merged for t in pair] + [hi]
    idle_before = _idle_before([(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s])
    op_starts = [s for s, _ in inside]
    ended = []  # the latest end of the operations up to each one, in start order
    for _, e in inside:
        ended.append(max(e, ended[-1]) if ended else e)

    def device_done(t):  # the end of the last operation that started before t
        i = bisect.bisect_left(op_starts, t)
        return ended[i - 1] if i else lo

    done = {i: device_done(e) for i, (name, _, e) in enumerate(spans) if name in WAITS}
    # ends before starts at one instant; of spans starting together the outer first
    events = sorted([(s, 1, -e, i) for i, (_, s, e) in enumerate(spans)]
                    + [(e, 0, 0, i) for i, (_, _, e) in enumerate(spans)])
    open_, t = [], lo
    for at, starting, _, i in events + [(hi, 0, 0, None)]:
        if at > t:
            gone = idle_before(at) - idle_before(t)
            if gone:
                if not open_:
                    idle[OUTSIDE] += gone
                else:
                    top = open_[-1]
                    name = spans[top][0]
                    if top in done:
                        after = idle_before(at) - idle_before(min(max(t, done[top]), at))
                        idle[name + AFTER] = idle.get(name + AFTER, 0.0) + after
                        gone -= after
                    idle[name] += gone
            t = at
        if i is None:
            break
        if starting:
            open_.append(i)
        else:
            open_.remove(i)


def phase_idle(devices, annotations) -> dict | None:
    """``{phase: seconds}`` of the device's idle time inside ``cb.window``,
    averaged over the device planes: each phase the slice holds (its name
    without ``atpu.serve.``; 0.0 where no idle fell under it), the
    ``<wait>.after_device`` entries and ``outside``. None where the trace
    holds no window or no device operation."""
    windows = [a for a in annotations if a[0] == trace_reduce.WINDOW]
    if not windows or not devices:
        return None
    lo, hi = windows[0][1], windows[0][2]
    spans = [(n[len(PHASE):], max(s, lo), min(e, hi)) for n, s, e in annotations
             if n.startswith(PHASE) and e > lo and s < hi]
    idle = dict.fromkeys([OUTSIDE] + sorted({n for n, _, _ in spans}), 0.0)
    for ops in devices:
        _plane(ops, [span for span in spans if span[2] > span[1]], lo, hi, idle)
    return {k: v / len(devices) / 1e9 for k, v in sorted(idle.items(), key=lambda kv: -kv[1])}


def reduce_planes(devices, annotations) -> dict | None:
    """``trace_reduce.reduce`` over the runner's ``cb.*`` spans alone, as a
    file's reduction has it today, with ``phase_idle`` added."""
    reduced = trace_reduce.reduce(
        devices, [a for a in annotations if a[0].startswith(trace_reduce.PREFIX)])
    if reduced is not None:
        reduced["phase_idle"] = phase_idle(devices, annotations)
    return reduced


def reduce_file(path: str) -> dict | None:
    return reduce_planes(*read_planes(path))


def share(trace, cause: str) -> float | None:
    """The idle of one of ``CAUSES`` as a percentage of the traced slice. None
    without a trace or its ``phase_idle``, and where the slice holds a
    ``prefill`` but no ``prefill_sync``: a program older than that span, whose
    prefill's own time would hold its calls and its sync."""
    idle = (trace or {}).get("phase_idle")
    if not idle or ("prefill" in idle and "prefill_sync" not in idle):
        return None
    return 100.0 * sum(idle.get(name, 0.0) for name in CAUSES[cause]) / trace["window_s"]


def main(paths) -> None:
    for path in paths:
        reduced = reduce_file(path)
        if reduced is None:
            print(json.dumps({"trace": path, "phase_idle": None}))
            continue
        print(json.dumps({
            "trace": path, "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
            "idle_share": 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"]),
            "shares": {cause: share(reduced, cause) for cause in CAUSES},
            "outside_share": 100.0 * reduced["phase_idle"][OUTSIDE] / reduced["window_s"],
            "phase_idle": reduced["phase_idle"], "idle_gaps": reduced["idle_gaps"]}))


if __name__ == "__main__":
    main(sys.argv[1:])
