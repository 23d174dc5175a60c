"""XLA profiler trace windows + a dependency-free XSpace (xplane.pb) parser.

``jax.profiler`` answers the question the JSONL step records cannot: *inside*
one step, which fusions/kernels ate the device time, and did the collectives
overlap compute or serialize it? This module makes that answer programmatic:

- :class:`TraceWindows` — every-Nth-step or one-shot ``jax.profiler`` windows
  driven by :class:`~accelerate_tpu.utils.dataclasses.ProfileConfig`
  (``trace_every`` / ``trace_steps`` / ``trace_at``, env-seeded via
  ``ACCELERATE_TRACE_EVERY`` / ``ACCELERATE_TRACE_STEPS`` /
  ``ACCELERATE_TRACE_AT`` / ``ACCELERATE_TRACE_DIR`` so a launcher can turn
  on tracing with zero code changes). Each closed window is parsed
  immediately and lands as one ``trace`` event in the telemetry stream.
- :func:`parse_xspace` — a ~100-line protobuf *wire-format* decoder for the
  profiler's ``*.xplane.pb`` (the tensorflow ``XSpace`` schema), because this
  environment has no tensorboard/tensorflow to parse it with. Falls back to
  the ``*.trace.json.gz`` Chrome trace when no ``.pb`` is present.
- :func:`summarize_trace` — top-k op/fusion durations, a
  compute / collective / idle device-time split, and the **comms-overlap
  ratio**: what fraction of collective time ran concurrently with compute
  (the number ROADMAP item 3's weight-update sharding must move toward 1.0).

Heuristics, stated: events whose names look like C++ frames (``Foo::Bar``),
python tracing (``$file.py:123``), or runtime plumbing are *infra* and
excluded from op accounting; collective ops match the XLA HLO spellings
(``all-reduce``/``all-gather``/``reduce-scatter``/``all-to-all``/
``collective-permute``/``send``/``recv``). Device planes (``/device:TPU:N``)
are preferred; on the CPU backend the ``/host:CPU`` plane's XLA thunk lines
stand in (the ``python`` line is never op time).
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Iterable, Optional

from . import events as tel

TRACE_EVERY_ENV_VAR = "ACCELERATE_TRACE_EVERY"
TRACE_STEPS_ENV_VAR = "ACCELERATE_TRACE_STEPS"
TRACE_AT_ENV_VAR = "ACCELERATE_TRACE_AT"
TRACE_DIR_ENV_VAR = "ACCELERATE_TRACE_DIR"

_PS = 1e-12  # xplane durations are picoseconds
_US = 1e-6  # chrome-trace durations are microseconds

_COLLECTIVE_RE = re.compile(
    r"(^|[-_.\s])(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|ragged-all-to-all|send|recv)",
    re.IGNORECASE,
)
# runtime plumbing, not ops: C++ frames, python tracing, dispatch machinery
_INFRA_RE = re.compile(
    r"::|^\$|^PjitFunction|^ParseArguments|^ThreadpoolListener|"
    r"^ExecuteTask|^RunReady|^program_interpreter|^<unknown>"
)


# ---------------------------------------------------------------- data model
@dataclass
class XEvent:
    name: str
    start_s: float  # absolute seconds (line timestamp_ns + event offset_ps)
    dur_s: float

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s


@dataclass
class XLine:
    name: str
    events: "list[XEvent]" = field(default_factory=list)


@dataclass
class XPlane:
    name: str
    lines: "list[XLine]" = field(default_factory=list)


# ------------------------------------------------------- protobuf wire parse
def _read_varint(buf: bytes, i: int) -> "tuple[int, int]":
    shift = 0
    val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _fields(buf: bytes) -> "Iterable[tuple[int, int, Any]]":
    """Yield ``(field_number, wire_type, value)`` triples of one message.
    Length-delimited values come back as ``bytes`` (nested messages are
    decoded by the caller that knows the schema)."""
    i = 0
    n = len(buf)
    while i < n:
        key, i = _read_varint(buf, i)
        fnum, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _read_varint(buf, i)
        elif wt == 1:
            v = buf[i : i + 8]
            i += 8
        elif wt == 2:
            ln, i = _read_varint(buf, i)
            v = buf[i : i + ln]
            i += ln
        elif wt == 5:
            v = buf[i : i + 4]
            i += 4
        else:  # groups (3/4) never appear in xplane protos
            raise ValueError(f"unsupported wire type {wt}")
        yield fnum, wt, v


def _parse_event(buf: bytes, metadata: "dict[int, str]", epoch_s: float) -> Optional[XEvent]:
    # XEvent: metadata_id=1, offset_ps=2 (oneof with num_occurrences=4), duration_ps=3
    meta_id = offset_ps = dur_ps = None
    for fnum, wt, v in _fields(buf):
        if wt != 0:
            continue
        if fnum == 1:
            meta_id = v
        elif fnum == 2:
            offset_ps = v
        elif fnum == 3:
            dur_ps = v
    if meta_id is None or not dur_ps:
        return None  # instant/aggregated events carry no duration: not op time
    # proto3 omits zero-valued varints: an event starting AT the line epoch
    # has no offset_ps field on the wire — it is offset 0, not malformed
    return XEvent(
        metadata.get(meta_id, f"#{meta_id}"), epoch_s + (offset_ps or 0) * _PS, dur_ps * _PS
    )


def _parse_line(buf: bytes, metadata: "dict[int, str]") -> XLine:
    # XLine: id=1, name=2, timestamp_ns=3, events=4. Event offsets are
    # RELATIVE to this line's timestamp_ns — lines (streams/queues) of one
    # trace can carry different epochs, and the overlap/idle math intersects
    # intervals ACROSS lines, so events must be rebased to absolute time here.
    name = ""
    timestamp_ns = 0
    event_bufs: "list[bytes]" = []
    for fnum, wt, v in _fields(buf):
        if fnum == 2 and wt == 2:
            name = v.decode("utf-8", "replace")
        elif fnum == 3 and wt == 0:
            timestamp_ns = v
        elif fnum == 4 and wt == 2:
            event_bufs.append(v)
    epoch_s = timestamp_ns * 1e-9
    events = []
    for ev_buf in event_bufs:
        ev = _parse_event(ev_buf, metadata, epoch_s)
        if ev is not None:
            events.append(ev)
    return XLine(name, events)


def _parse_plane(buf: bytes) -> XPlane:
    # XPlane: id=1, name=2, lines=3, event_metadata map=4
    name = ""
    line_bufs: "list[bytes]" = []
    metadata: "dict[int, str]" = {}
    for fnum, wt, v in _fields(buf):
        if fnum == 2 and wt == 2:
            name = v.decode("utf-8", "replace")
        elif fnum == 3 and wt == 2:
            line_bufs.append(v)
        elif fnum == 4 and wt == 2:
            # map<int64, XEventMetadata>: key=1, value=2{id=1, name=2}
            key = None
            meta_name = None
            for f2, w2, v2 in _fields(v):
                if f2 == 1 and w2 == 0:
                    key = v2
                elif f2 == 2 and w2 == 2:
                    for f3, w3, v3 in _fields(v2):
                        if f3 == 2 and w3 == 2:
                            meta_name = v3.decode("utf-8", "replace")
            if key is not None and meta_name is not None:
                metadata[key] = meta_name
    return XPlane(name, [_parse_line(b, metadata) for b in line_bufs])


def parse_xspace(path: str) -> "list[XPlane]":
    """Decode one ``*.xplane.pb`` file into planes → lines → duration events.
    Event names are resolved through the plane's metadata table; durations
    are seconds."""
    with open(path, "rb") as f:
        data = f.read()
    planes = []
    for fnum, wt, v in _fields(data):
        if fnum == 1 and wt == 2:  # XSpace.planes
            planes.append(_parse_plane(v))
    return planes


# ----------------------------------------------------- chrome-trace fallback
def parse_chrome_trace(path: str) -> "list[XPlane]":
    """``*.trace.json.gz`` fallback: reconstruct the same plane/line/event
    shape from the Chrome trace's complete (``ph == "X"``) events."""
    import gzip

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    pid_names: dict = {}
    tid_names: dict = {}
    events_by: "dict[tuple, list[XEvent]]" = {}
    for ev in data.get("traceEvents", []):
        if not isinstance(ev, dict):
            continue
        if ev.get("ph") == "M":
            if ev.get("name") == "process_name":
                pid_names[ev.get("pid")] = (ev.get("args") or {}).get("name", "")
            elif ev.get("name") == "thread_name":
                tid_names[(ev.get("pid"), ev.get("tid"))] = (ev.get("args") or {}).get("name", "")
        elif ev.get("ph") == "X" and ev.get("dur"):
            key = (ev.get("pid"), ev.get("tid"))
            events_by.setdefault(key, []).append(
                XEvent(str(ev.get("name", "")), float(ev["ts"]) * _US, float(ev["dur"]) * _US)
            )
    planes: "dict[Any, XPlane]" = {}
    for (pid, tid), evs in events_by.items():
        plane = planes.setdefault(pid, XPlane(pid_names.get(pid, str(pid))))
        plane.lines.append(XLine(tid_names.get((pid, tid), str(tid)), evs))
    return list(planes.values())


# ------------------------------------------------------------- summarization
def find_trace_files(trace_dir: str) -> "tuple[list[str], list[str]]":
    """``(xplane_pb_files, chrome_json_files)`` under a profiler output dir
    (jax writes ``<dir>/plugins/profile/<timestamp>/<host>.xplane.pb``)."""
    pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    jsons = sorted(glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))
    return pbs, jsons


def _union(intervals: "list[tuple[float, float]]") -> "list[tuple[float, float]]":
    if not intervals:
        return []
    intervals = sorted(intervals)
    merged = [intervals[0]]
    for start, end in intervals[1:]:
        if start <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], end))
        else:
            merged.append((start, end))
    return merged


def _total(intervals: "list[tuple[float, float]]") -> float:
    return sum(end - start for start, end in intervals)


def _intersect(a: "list[tuple[float, float]]", b: "list[tuple[float, float]]") -> float:
    """Total overlap between two already-merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def is_collective_op(name: str) -> bool:
    return bool(_COLLECTIVE_RE.search(name))


def is_infra_event(name: str) -> bool:
    return bool(_INFRA_RE.search(name))


# device-plane lines that wrap whole steps/modules rather than individual
# ops — counting them as compute would cover every collective interval and
# fake a ~1.0 overlap ratio (the exact metric this module exists to guard)
_DEVICE_ENVELOPE_LINES = {
    "Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
    "Framework Ops", "Source code", "Source Code",
}


def _device_op_lines(plane: XPlane) -> "list[XLine]":
    """The op-level lines of a device plane: ``XLA Ops`` (plus async-op
    lines, where in-flight collectives land) when present; otherwise
    everything minus the known step/module envelope lines."""
    ops = [
        ln for ln in plane.lines
        if ln.name == "XLA Ops" or "Async" in ln.name
    ]
    if ops:
        return ops
    return [ln for ln in plane.lines if ln.name not in _DEVICE_ENVELOPE_LINES]


def _op_planes(planes: "list[XPlane]") -> "list[XPlane]":
    """The planes that carry op/kernel time: ``/device:*`` when present (TPU/
    GPU) filtered to their op-level lines, else the ``/host:CPU`` plane minus
    its ``python`` tracing line."""
    devices = []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = [ln for ln in _device_op_lines(plane) if ln.events]
        if lines:
            devices.append(XPlane(plane.name, lines))
    if devices:
        return devices
    hosts = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        lines = [ln for ln in plane.lines if ln.name != "python" and ln.events]
        if lines:
            hosts.append(XPlane(plane.name, lines))
    return hosts


def summarize_planes(planes: "list[XPlane]", top_k: int = 10) -> dict:
    """Op-level accounting over already-parsed planes (see
    :func:`summarize_trace` for the file-level entry point)."""
    by_op: "dict[str, dict]" = {}
    compute_iv: "list[tuple[float, float]]" = []
    collective_iv: "list[tuple[float, float]]" = []
    span_lo, span_hi = None, None
    n_events = 0
    for plane in _op_planes(planes):
        for line in plane.lines:
            for ev in line.events:
                if is_infra_event(ev.name):
                    continue
                n_events += 1
                rec = by_op.setdefault(
                    ev.name, {"op": ev.name, "total_s": 0.0, "count": 0}
                )
                rec["total_s"] += ev.dur_s
                rec["count"] += 1
                span_lo = ev.start_s if span_lo is None else min(span_lo, ev.start_s)
                span_hi = ev.end_s if span_hi is None else max(span_hi, ev.end_s)
                (collective_iv if is_collective_op(ev.name) else compute_iv).append(
                    (ev.start_s, ev.end_s)
                )
    compute_u = _union(compute_iv)
    collective_u = _union(collective_iv)
    busy_u = _union(compute_iv + collective_iv)
    span_s = (span_hi - span_lo) if span_lo is not None else 0.0
    compute_s = _total(compute_u)
    collective_s = _total(collective_u)
    overlap_s = _intersect(compute_u, collective_u)
    op_total = sum(r["total_s"] for r in by_op.values())
    top = sorted(by_op.values(), key=lambda r: -r["total_s"])[:top_k]
    for rec in top:
        rec["total_s"] = round(rec["total_s"], 6)
        rec["share"] = round(rec["total_s"] / op_total, 4) if op_total else 0.0
        rec["collective"] = is_collective_op(rec["op"])
    return {
        "events": n_events,
        "ops": len(by_op),
        "span_s": round(span_s, 6),
        "busy_s": round(_total(busy_u), 6),
        "idle_s": round(max(0.0, span_s - _total(busy_u)), 6),
        "compute_s": round(compute_s, 6),
        "collective_s": round(collective_s, 6),
        "collective_overlap_s": round(overlap_s, 6),
        # the ratio ROADMAP item 3 optimizes: collective time hidden under
        # compute / total collective time. None when the trace has no
        # collectives (single-chip runs) — "perfect overlap" would be a lie.
        "comms_overlap_ratio": round(overlap_s / collective_s, 4) if collective_s else None,
        "top_ops": top,
    }


def summarize_trace(trace_dir: str, top_k: int = 10) -> dict:
    """Parse every trace under ``trace_dir`` (``.xplane.pb`` preferred,
    Chrome ``.trace.json.gz`` fallback) and produce the op-level summary:
    top-k op durations, compute/collective/idle split, comms-overlap ratio."""
    pbs, jsons = find_trace_files(trace_dir)
    planes: "list[XPlane]" = []
    files = []
    for path in pbs:
        try:
            planes.extend(parse_xspace(path))
            files.append(os.path.relpath(path, trace_dir))
        except Exception:
            continue  # torn/foreign pb: the json fallback may still work
    if not planes:
        for path in jsons:
            try:
                planes.extend(parse_chrome_trace(path))
                files.append(os.path.relpath(path, trace_dir))
            except Exception:
                continue
    out = summarize_planes(planes, top_k=top_k)
    out["trace_dir"] = trace_dir
    out["files"] = files
    return out


# ----------------------------------------------------------- window driver --
class TraceWindows:
    """Automatic ``jax.profiler`` windows at step boundaries.

    Driven by :class:`~accelerate_tpu.utils.dataclasses.ProfileConfig`:
    every ``trace_every`` steps (or one-shot at ``trace_at``) a window of
    ``trace_steps`` steps is traced into ``<out_dir>/step<k>``, then parsed
    (:func:`summarize_trace`) into one ``trace`` telemetry event and a
    ``summary.json`` next to the raw trace. The Accelerator calls
    :meth:`on_step_start` / :meth:`on_step_end` around every tracked step;
    both are a couple of integer compares while no window is due.

    A profiler that refuses to start (another trace already active — e.g. a
    user's ``accelerator.profile()`` block) disables the driver for the rest
    of the run rather than erroring every step.

    Async-dispatch caveat: the window brackets the *dispatch* of the traced
    steps; device/thunk execution that completes after ``stop_trace`` is not
    in the file. A loop that wants every kernel of step N inside step N's
    window must wait for it per step (``jax.block_until_ready(metrics)``)."""

    def __init__(self, config, out_dir: str, top_k: int = 10):
        self.config = config
        self.out_dir = out_dir
        self.top_k = top_k
        self.tracing = False
        self.disabled = False
        self.window_dir: Optional[str] = None
        self.window_start: Optional[int] = None
        self.summaries: "list[dict]" = []

    @staticmethod
    def enabled_config(config) -> bool:
        return bool(
            getattr(config, "trace_every", 0) > 0
            or getattr(config, "trace_at", None) is not None
        )

    def _window_due(self, step: int) -> bool:
        # both triggers are honored: an env-seeded one-shot (trace_at) must
        # not silently disable a periodic schedule configured in code
        trace_at = getattr(self.config, "trace_at", None)
        if trace_at is not None and step == trace_at:
            return True
        every = getattr(self.config, "trace_every", 0)
        # step 0 pays compile: the first window lands at step `every`
        return every > 0 and step > 0 and step % every == 0

    def on_step_start(self, step: int) -> None:
        if self.tracing or self.disabled or not self._window_due(step):
            return
        import jax

        self.window_dir = os.path.join(self.out_dir, f"step{step}")
        try:
            if os.path.isdir(self.window_dir):
                # a restarted run reuses the same step index + pinned trace
                # dir: stale profile trees would merge into (and double-count)
                # this window's summary, which globs recursively
                import shutil

                shutil.rmtree(self.window_dir, ignore_errors=True)
            os.makedirs(self.window_dir, exist_ok=True)
            jax.profiler.start_trace(self.window_dir)
        except Exception as e:
            # another trace is active (user profile block / bench trace):
            # stand down for the run instead of failing every window
            self.disabled = True
            tel.emit("trace", step_start=step, error=f"{type(e).__name__}: {e}")
            return
        self.tracing = True
        self.window_start = step

    def on_step_end(self, step: int) -> None:
        if not self.tracing:
            return
        steps_traced = step - self.window_start + 1
        if steps_traced < max(1, getattr(self.config, "trace_steps", 1)):
            return
        self._close(step)

    def _close(self, last_step: Optional[int]) -> None:
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        self.tracing = False
        summary = summarize_trace(self.window_dir, top_k=self.top_k)
        summary["step_start"] = self.window_start
        summary["step_end"] = last_step
        self.summaries.append(summary)
        try:
            with open(os.path.join(self.window_dir, "summary.json"), "w") as f:
                json.dump(summary, f, indent=2)
        except OSError:
            pass
        tel.emit("trace", **summary)

    def close(self) -> None:
        """Stop an open window (end of training mid-window)."""
        if self.tracing:
            self._close(None)
