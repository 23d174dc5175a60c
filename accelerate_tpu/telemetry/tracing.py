"""Request-scoped distributed tracing for the serving path.

"Where did request X spend its 900 ms p99" needs ONE coherent timeline per
request across router → replica → engine: admission/queue wait, each
dispatch (with retry lineage when a replica died mid-decode), every prefill
chunk (with prefix-cache hit / copy-on-write annotations), every batched
decode step, and completion. This module is the dependency-free span model
and context-propagation glue that builds it:

- **spans** are plain dicts — ``trace_id`` / ``span_id`` / ``parent_id``,
  ``name``, monotonic-ns ``t0_ns``/``t1_ns`` (the clock
  :func:`time.monotonic_ns`: the span tree's and the phase ring's clock, and
  the one ``Request``'s stamps are read from. It is NOT the clock a device
  trace stamps — the profiler has its own, and nothing converts between the
  two), plus free-form attributes. :func:`span_open` / :func:`span_close` /
  :func:`make_span` build them; holders (the router request, the engine
  request) accumulate them in a list.
- **phases** — :func:`phase` brackets a stretch of host code (the serving
  engine's ``atpu.serve.*`` step phases). It enters a
  ``jax.profiler.TraceAnnotation`` of the same name, which is the ONE join
  with a device trace (the profiler stamps the annotation on its own clock,
  beside the device's operations), and keeps ``(name, t0_ns, t1_ns, key)``
  in a bounded in-memory ring that :func:`recorded` reads back. Always on:
  a phase costs two clock reads and one tuple.
- **context propagation** — a :class:`TraceContext` is a 3-field JSON-able
  dict (``trace_id``, ``parent_id``, ``sampled``) that rides the existing
  transports verbatim: the router puts it in the submit payload, the
  ``LocalReplica`` queue and the ``ProcessReplica`` JSON-lines pipe carry it
  untouched, and the engine parents its spans under ``parent_id``.
  Engine-side spans ship BACK over the same event stream (inside ``done``
  events) and the router emits the assembled trace — one writer per trace,
  so two processes never interleave one request's records.
- **sampling** — ``ACCELERATE_TRACE_SAMPLE`` arms the module (a rate in
  (0, 1]; unset/0 keeps every hot-path check a single ``is None`` branch).
  The keep/drop decision is per TRACE (deterministic in the trace id) and
  applied at EMIT time: armed code always records spans, and
  :func:`finish_trace` force-emits unsampled traces whose outcome is
  SHED/FAILED/EXPIRED or that survived a failover — the requests an
  operator is guaranteed to ask about.
- **export** — emitted spans are ``span`` telemetry records (they carry
  ``trace_id``, unlike the :meth:`EventLog.span <accelerate_tpu.telemetry.
  events.EventLog.span>` timing records); :func:`chrome_trace` converts a
  span list to a Chrome ``trace.json`` (load it in ``chrome://tracing`` or
  Perfetto; its timestamps are monotonic ns, not a device trace's), and
  :func:`validate_span_tree` is the gap-free-tree oracle the tests and
  ``make doctor`` check 16 assert.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Iterable, Optional

from . import events as _events
from ..utils.environment import _TRUE, parse_optional_float_from_env

TRACE_SAMPLE_ENV_VAR = "ACCELERATE_TRACE_SAMPLE"

#: sample rate when armed; None = disarmed (the one-branch hot path)
_ARMED: Optional[float] = None
_ID_LOCK = threading.Lock()
_ID_COUNTER = 0


def is_armed() -> bool:
    return _ARMED is not None


def sample_rate() -> Optional[float]:
    return _ARMED


def arm(sample: float = 1.0) -> None:
    """Arm tracing at ``sample`` (a keep-fraction in (0, 1])."""
    global _ARMED
    if not (0.0 < sample <= 1.0):
        raise ValueError(f"sample must be in (0, 1], got {sample}")
    _ARMED = float(sample)


def disarm() -> None:
    global _ARMED
    _ARMED = None


def maybe_arm_from_env() -> Optional[float]:
    """Honor ``ACCELERATE_TRACE_SAMPLE``: a float rate, or a plain truthy
    value for 1.0. Unset/0/garbage leaves tracing disarmed."""
    raw = os.environ.get(TRACE_SAMPLE_ENV_VAR, "").strip().lower()
    if not raw:
        return _ARMED
    if raw in _TRUE:
        arm(1.0)
        return _ARMED
    rate = parse_optional_float_from_env(TRACE_SAMPLE_ENV_VAR)
    if rate is not None and 0.0 < rate <= 1.0:
        arm(rate)
    return _ARMED


# ---------------------------------------------------------------------------
# ids + context


def _new_id(bits: int = 64) -> str:
    """Unique hex id: entropy + a process-local counter (collision-proof
    within a process even if the entropy source repeats)."""
    global _ID_COUNTER
    with _ID_LOCK:
        _ID_COUNTER += 1
        n = _ID_COUNTER
    raw = int.from_bytes(os.urandom(bits // 8), "big") ^ (n << 1)
    return f"{raw & ((1 << bits) - 1):0{bits // 4}x}"


def _sampled(trace_id: str, rate: float) -> bool:
    """Deterministic per-trace keep/drop: the id's low 32 bits as a uniform
    draw — every component holding the same ctx agrees without coordination."""
    return (int(trace_id[-8:], 16) / float(1 << 32)) < rate


class TraceContext(dict):
    """The 3 fields that cross a transport: ``trace_id``, ``parent_id`` (the
    span new work should parent under), ``sampled``. It IS a dict so it
    serializes through the JSON-lines replica protocol verbatim."""

    @property
    def trace_id(self) -> str:
        return self["trace_id"]

    @property
    def parent_id(self) -> Optional[str]:
        return self.get("parent_id")

    @property
    def sampled(self) -> bool:
        return bool(self.get("sampled"))

    def child(self, parent_id: str) -> "TraceContext":
        """The ctx to hand the next hop: same trace, new parent span."""
        return TraceContext(self, parent_id=parent_id)

    @classmethod
    def from_wire(cls, payload) -> "Optional[TraceContext]":
        if not isinstance(payload, dict) or "trace_id" not in payload:
            return None
        return cls(payload)


def new_trace(sampled: Optional[bool] = None) -> TraceContext:
    """Root context for one request. ``sampled`` defaults to the armed
    rate's deterministic per-trace draw."""
    trace_id = _new_id()
    if sampled is None:
        sampled = _sampled(trace_id, _ARMED if _ARMED is not None else 0.0)
    return TraceContext(trace_id=trace_id, parent_id=None, sampled=bool(sampled))


# ---------------------------------------------------------------------------
# spans


def now_ns() -> int:
    return time.monotonic_ns()


# ---------------------------------------------------------------------------
# phases: host spans in the profiler's trace and in an in-memory ring

#: records the ring keeps (a serve step writes 7 plus one per prefill; 2^16
#: hold some 8000 steps, a quarter of an hour at 100 ms a step)
RING_MAXLEN = 1 << 16
_RING: "collections.deque[tuple]" = collections.deque(maxlen=RING_MAXLEN)
_TraceAnnotation = None  # jax.profiler.TraceAnnotation, bound by the first phase


def record(name: str, t0_ns: int, t1_ns: int, **key: Any) -> None:
    """Append ``(name, t0_ns, t1_ns, key)`` to the process-wide ring (the
    oldest record falls out when it is full; ``deque.append`` is atomic)."""
    _RING.append((name, t0_ns, t1_ns, key))


@contextlib.contextmanager
def phase(name: str, **key: Any):
    """Bracket a stretch of host code: a ``jax.profiler.TraceAnnotation``
    (free while no profiler is attached; the span a device trace's idle gaps
    are attributed to) and, on exit, one :func:`record` stamped by
    :func:`now_ns`. Yields the start's ``t0_ns``, for a caller that opens a
    request span at the same read. The record is written on an exception too."""
    global _TraceAnnotation
    if _TraceAnnotation is None:  # deferred: the rest of the module is stdlib-only;
        from jax.profiler import TraceAnnotation  # once: the import costs 9 us a time

        _TraceAnnotation = TraceAnnotation
    with _TraceAnnotation(name, **key):
        t0_ns = now_ns()
        try:
            yield t0_ns
        finally:
            _RING.append((name, t0_ns, now_ns(), key))


def recorded(name_prefix: str = "") -> "list[tuple]":
    """The ring's records whose name starts with ``name_prefix``, oldest
    first: what a benchmark reads when its window is over, and what an
    operator reads from a live process."""
    return [r for r in tuple(_RING) if r[0].startswith(name_prefix)]


def span_open(
    ctx: TraceContext, name: str, t0_ns: Optional[int] = None,
    parent_id: Optional[str] = None, **attrs: Any,
) -> dict:
    """Open span dict (no ``t1_ns`` yet); parent defaults to the context's
    ``parent_id`` (None = this is the trace root)."""
    span = {
        "trace_id": ctx["trace_id"],
        "span_id": _new_id(),
        "parent_id": parent_id if parent_id is not None else ctx.get("parent_id"),
        "name": name,
        "t0_ns": now_ns() if t0_ns is None else int(t0_ns),
    }
    if attrs:
        span["attrs"] = dict(attrs)
    return span


def span_close(span: dict, t1_ns: Optional[int] = None, **attrs: Any) -> dict:
    span["t1_ns"] = now_ns() if t1_ns is None else int(t1_ns)
    if span["t1_ns"] < span["t0_ns"]:  # monotone even under clock races
        span["t1_ns"] = span["t0_ns"]
    if attrs:
        span.setdefault("attrs", {}).update(attrs)
    return span


def make_span(
    ctx: TraceContext, name: str, t0_ns: int, t1_ns: int,
    parent_id: Optional[str] = None, **attrs: Any,
) -> dict:
    return span_close(span_open(ctx, name, t0_ns=t0_ns, parent_id=parent_id, **attrs),
                      t1_ns=t1_ns)


def emit_spans(spans: Iterable[dict]) -> int:
    """Write spans as ``span`` telemetry records (no-op while telemetry is
    off). Open spans are closed at emit time — a crash-path trace must not
    lose its last span to a missing ``t1_ns``."""
    n = 0
    for span in spans:
        if "t1_ns" not in span:
            span_close(span)
        _events.emit("span", **span)
        n += 1
    return n


def should_emit(ctx: Optional[TraceContext], forced: bool = False) -> bool:
    """The emit decision for one finished trace: sampled, or forced (bad
    outcome / failover survivor — always kept)."""
    if ctx is None:
        return False
    return forced or ctx.sampled


def finish_trace(ctx: Optional[TraceContext], spans: "list[dict]",
                 forced: bool = False) -> bool:
    """Emit the trace's spans iff sampled-or-forced; True when written."""
    if not should_emit(ctx, forced=forced) or not spans:
        return False
    emit_spans(spans)
    return True


# ---------------------------------------------------------------------------
# analysis / export


def spans_by_trace(events: Iterable[dict]) -> "dict[str, list[dict]]":
    """Group ``span`` telemetry records by trace id (input: the report
    loader's merged event list)."""
    traces: "dict[str, list[dict]]" = {}
    for e in events:
        if e.get("kind") == "span" and e.get("trace_id"):
            traces.setdefault(str(e["trace_id"]), []).append(e)
    for spans in traces.values():
        spans.sort(key=lambda s: int(s.get("t0_ns", 0)))
    return traces


def validate_span_tree(spans: "list[dict]") -> "list[str]":
    """Structural integrity of one trace: exactly one root, every
    ``parent_id`` resolvable, every span closed with ``t1_ns >= t0_ns``, and
    every child inside its parent's [t0, t1] window. Returns the list of
    violations — empty means the tree is gap-free (the doctor-16 oracle)."""
    problems: "list[str]" = []
    if not spans:
        return ["no spans"]
    by_id = {}
    for s in spans:
        sid = s.get("span_id")
        if sid is None:
            problems.append(f"span {s.get('name')} has no span_id")
            continue
        if sid in by_id:
            problems.append(f"duplicate span_id {sid}")
        by_id[sid] = s
    trace_ids = {s.get("trace_id") for s in spans}
    if len(trace_ids) != 1:
        problems.append(f"spans from {len(trace_ids)} traces: {sorted(map(str, trace_ids))}")
    roots = [s for s in spans if not s.get("parent_id")]
    if len(roots) != 1:
        problems.append(f"{len(roots)} root span(s), expected exactly 1")
    for s in spans:
        name = s.get("name", "?")
        if "t1_ns" not in s:
            problems.append(f"span {name} never closed")
            continue
        if int(s["t1_ns"]) < int(s["t0_ns"]):
            problems.append(f"span {name} ends before it starts")
        parent_id = s.get("parent_id")
        if parent_id:
            parent = by_id.get(parent_id)
            if parent is None:
                problems.append(f"span {name} orphaned: parent {parent_id} missing")
            elif "t1_ns" in parent and not (
                int(parent["t0_ns"]) <= int(s["t0_ns"])
                and int(s["t1_ns"]) <= int(parent["t1_ns"])
            ):
                problems.append(
                    f"span {name} escapes its parent {parent.get('name', '?')} window"
                )
    return problems


def span_children(spans: "list[dict]") -> "dict[Optional[str], list[dict]]":
    children: "dict[Optional[str], list[dict]]" = {}
    for s in sorted(spans, key=lambda x: int(x.get("t0_ns", 0))):
        children.setdefault(s.get("parent_id") or None, []).append(s)
    return children


def chrome_trace(spans: Iterable[dict]) -> dict:
    """Spans → Chrome ``trace.json``: complete ("ph": "X") events in
    microseconds of the monotonic clock, one pid/tid lane per emitting
    component (the ``component`` attr; default the span name's prefix). A
    device trace is on the profiler's clock, so the two files do not line up
    by timestamp: the ``atpu.*`` annotations of :func:`phase` are what a
    device trace holds of the host."""
    trace_events = []
    tids: "dict[str, int]" = {}
    for s in spans:
        attrs = dict(s.get("attrs") or {})
        component = str(attrs.pop("component", s.get("name", "?").split(":")[0]))
        tid = tids.setdefault(component, len(tids) + 1)
        t0 = int(s.get("t0_ns", 0))
        t1 = int(s.get("t1_ns", t0))
        args = {
            "trace_id": s.get("trace_id"),
            "span_id": s.get("span_id"),
            "parent_id": s.get("parent_id"),
            **attrs,
        }
        trace_events.append(
            {
                "name": s.get("name", "?"),
                "ph": "X",
                "ts": t0 / 1e3,
                "dur": max(t1 - t0, 0) / 1e3,
                "pid": 1,
                "tid": tid,
                "args": args,
            }
        )
    trace_events.extend(
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
         "args": {"name": component}}
        for component, tid in tids.items()
    )
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def format_timeline(spans: "list[dict]") -> str:
    """Indented one-request timeline (the ``report --request`` rendering):
    offsets/durations in ms relative to the trace root."""
    if not spans:
        return "  (no spans)"
    children = span_children(spans)
    base = min(int(s.get("t0_ns", 0)) for s in spans)
    lines: "list[str]" = []

    def _walk(span: dict, depth: int) -> None:
        t0 = int(span.get("t0_ns", base))
        t1 = int(span.get("t1_ns", t0))
        attrs = span.get("attrs") or {}
        attr_s = ""
        if attrs:
            attr_s = "  [" + ", ".join(f"{k}={v}" for k, v in sorted(attrs.items())) + "]"
        lines.append(
            f"  {'  ' * depth}{span.get('name', '?'):<{max(2, 30 - 2 * depth)}} "
            f"+{(t0 - base) / 1e6:9.3f}ms  {(t1 - t0) / 1e6:9.3f}ms{attr_s}"
        )
        for child in children.get(span.get("span_id"), []):
            _walk(child, depth + 1)

    for root in children.get(None, []):
        _walk(root, 0)
    orphans = [
        s for s in spans
        if s.get("parent_id") and s["parent_id"] not in {x.get("span_id") for x in spans}
    ]
    for s in orphans:
        lines.append(f"  (orphan) {s.get('name', '?')}")
    return "\n".join(lines)
