"""Streaming serving metrics: a typed registry with a Prometheus exporter.

The JSONL event log (:mod:`.events`) answers *post-hoc* questions; a serving
fleet also needs *live* ones — "what is the ttft p99 right now", "how deep is
the queue", "is the block pool about to reject". This module is that plane:

- **typed registry** — :class:`Counter` (monotone), :class:`Gauge` (last
  value), :class:`Histogram` (fixed cumulative buckets + sum/count, the
  Prometheus layout), created through one process-wide
  :class:`MetricsRegistry`. The serving router, admission controller,
  scheduler, engine, block allocator and compile cache all feed it.
- **Prometheus exposition** — :meth:`MetricsRegistry.render` emits the
  standard text format; :func:`serve` runs it from a stdlib ``http.server``
  daemon thread (``GET /metrics``). Armed by ``ACCELERATE_METRICS_PORT``
  (off by default; port 0 picks a free one — read it back from
  :func:`server_port`).
- **snapshots** — :func:`maybe_snapshot` periodically freezes the whole
  registry into one ``metrics`` telemetry record
  (``ACCELERATE_METRICS_SNAPSHOT_S``, default 1s between snapshots), so the
  report CLI and benches consume the same numbers a live scrape would show.
- **THE histogram/percentile implementation** — :func:`percentile` (exact,
  nearest-rank) and :meth:`Histogram.quantile` (bucket-interpolated, the
  ``histogram_quantile`` math) are the repo's single definitions; the report
  CLI and every bench import them instead of carrying private copies
  (``tests/test_observability.py`` ratchets that).

Zero-overhead contract (the :mod:`.events` pattern): the module-level
helpers (:func:`inc`, :func:`set_gauge`, :func:`observe`) are a single
``is None`` check when no registry is active — no allocation, no lock, no
syscall. :func:`enable` / ``ACCELERATE_METRICS_PORT`` / telemetry being on
arm the registry.
"""

from __future__ import annotations

import bisect
import math
import os
import re
import threading
import time
from typing import Any, Iterable, Optional

import warnings

from . import events as _events
from ..utils.environment import parse_optional_int_from_env, parse_seconds_from_env

METRICS_PORT_ENV_VAR = "ACCELERATE_METRICS_PORT"
METRICS_SNAPSHOT_ENV_VAR = "ACCELERATE_METRICS_SNAPSHOT_S"

#: default latency buckets (seconds) — wide enough for CPU toy runs and real
#: TPU serving alike; ttft / request latency / per-token latency share them
#: so cross-metric comparisons line up bucket for bucket
LATENCY_BUCKETS_S = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)
#: queue depth / outstanding counts
DEPTH_BUCKETS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)

#: goodput-plane gauge names, set by the :mod:`.goodput` live meter on each
#: periodic flush: the run's goodput fraction, the serving token goodput
#: fraction, and per-cause badput seconds (labelled ``cause=<taxonomy key>``).
#: Declared here so dashboards and tests share one spelling with the meter.
GOODPUT_FRACTION_GAUGE = "accelerate_goodput_fraction"
TOKEN_GOODPUT_FRACTION_GAUGE = "accelerate_token_goodput_fraction"
BADPUT_SECONDS_GAUGE = "accelerate_badput_seconds"
#: occupancies are fractions in [0, 1]
OCCUPANCY_BUCKETS = (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)


def percentile(values: "list[float]", p: float, presorted: bool = False) -> float:
    """Nearest-rank (ceil-rank) percentile of a list — the repo's ONE exact
    percentile definition (the report CLI and the benches both import it;
    bucketed estimation is :meth:`Histogram.quantile`). ``presorted=True``
    skips the defensive sort for callers that already hold sorted data (the
    report's per-distribution loop)."""
    if not values:
        return 0.0
    if not presorted:
        values = sorted(values)
    idx = min(len(values) - 1, max(0, math.ceil(p / 100.0 * len(values)) - 1))
    return values[idx]


def quantile_from_buckets(
    bounds: "tuple[float, ...]", counts: "list[int]", total: int, q: float,
) -> float:
    """``histogram_quantile`` over cumulative bucket ``counts`` (one per
    finite upper bound in ``bounds``, plus the +Inf bucket implied by
    ``total``): linear interpolation inside the bucket containing rank
    ``q * total``. A rank landing past the last finite bound returns that
    bound (the honest answer a fixed lattice can give). This exact function
    is what makes a live ``/metrics`` scrape and the report CLI agree."""
    if total <= 0:
        return 0.0
    rank = q * total
    prev_count = 0
    prev_bound = 0.0
    for bound, count in zip(bounds, counts):
        if count >= rank:
            in_bucket = count - prev_count
            if in_bucket <= 0:
                return bound
            frac = (rank - prev_count) / in_bucket
            return prev_bound + frac * (bound - prev_bound)
        prev_count = count
        prev_bound = bound
    return bounds[-1] if bounds else 0.0


class Counter:
    """Monotone counter (optionally labeled)."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._values: "dict[tuple, float]" = {}
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + n

    def value(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0.0)

    def total(self) -> float:
        return sum(self._values.values())

    def render(self) -> "list[str]":
        with self._lock:  # a scrape racing a first-label inc must not blow up
            values = dict(self._values)
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        for key, v in sorted(values.items()):
            lines.append(f"{self.name}{_fmt_labels(dict(key))} {_fmt_value(v)}")
        if not values:
            lines.append(f"{self.name} 0")
        return lines

    def to_dict(self) -> dict:
        with self._lock:
            values = dict(self._values)
        if not values or values.keys() == {()}:
            return {"type": "counter", "value": sum(values.values())}
        return {
            "type": "counter",
            "value": sum(values.values()),
            "by_label": {_label_key(dict(k)): v for k, v in sorted(values.items())},
        }


class Gauge:
    """Last-write-wins gauge."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._values: "dict[tuple, float]" = {}
        self._lock = threading.Lock()

    def set(self, v: float, **labels) -> None:
        with self._lock:
            self._values[tuple(sorted(labels.items()))] = float(v)

    def value(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0.0)

    def render(self) -> "list[str]":
        with self._lock:
            values = dict(self._values)
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        for key, v in sorted(values.items()):
            lines.append(f"{self.name}{_fmt_labels(dict(key))} {_fmt_value(v)}")
        if not values:
            lines.append(f"{self.name} 0")
        return lines

    def to_dict(self) -> dict:
        with self._lock:
            values = dict(self._values)
        if not values or values.keys() == {()}:
            return {"type": "gauge", "value": values.get((), 0.0)}
        return {
            "type": "gauge",
            "by_label": {_label_key(dict(k)): v for k, v in sorted(values.items())},
        }


class Histogram:
    """Fixed-bucket histogram in the Prometheus layout: CUMULATIVE counts per
    upper bound plus the implicit +Inf bucket, a running sum, and (beyond
    Prometheus, for the report's dist lines) the exact observed max.

    One instance is a complete, mergeable digest: :meth:`quantile` estimates
    percentiles by linear interpolation inside the covering bucket — the
    same math a ``histogram_quantile`` over the scraped series computes, so
    a live dashboard and the post-hoc report cannot disagree."""

    kind = "histogram"

    def __init__(self, name: str, buckets: "tuple[float, ...]" = LATENCY_BUCKETS_S,
                 help: str = ""):
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError(f"buckets must be non-empty, sorted, unique: {buckets}")
        self.name = name
        self.help = help
        self.bounds = tuple(float(b) for b in buckets)
        # per-bucket (NON-cumulative) counts; +1 slot for the +Inf overflow.
        # Cumulated on read — observe stays O(log buckets).
        self._counts = [0] * (len(self.bounds) + 1)
        self.count = 0
        self.sum = 0.0
        self.max = 0.0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self.count += 1
            self.sum += v
            if v > self.max:
                self.max = v
            self._counts[bisect.bisect_left(self.bounds, v)] += 1

    def observe_many(self, values: Iterable[float]) -> "Histogram":
        for v in values:
            self.observe(v)
        return self

    def _snapshot(self) -> "tuple[list[int], int, float, float]":
        """One consistent locked view of (per-bucket counts, count, sum,
        max) — a scrape racing an observe must never emit a histogram whose
        ``_count`` disagrees with its buckets."""
        with self._lock:
            return list(self._counts), self.count, self.sum, self.max

    @staticmethod
    def _cumulate(counts: "list[int]") -> "list[int]":
        out = []
        running = 0
        for c in counts[:-1]:
            running += c
            out.append(running)
        return out

    def cumulative_counts(self) -> "list[int]":
        """Cumulative count per finite upper bound (the ``_bucket`` series)."""
        return self._cumulate(self._snapshot()[0])

    def quantile(self, q: float) -> float:
        counts, count, _, _ = self._snapshot()
        return quantile_from_buckets(self.bounds, self._cumulate(counts), count, q)

    def dist(self, percentiles: "tuple[int, ...]" = (50, 90, 99)) -> dict:
        """The report CLI's distribution shape (count/mean/max + p<k>),
        estimated from the buckets — identical numbers to a scrape of the
        same observations."""
        counts, count, total_sum, vmax = self._snapshot()
        if not count:
            return {"count": 0}
        cumulative = self._cumulate(counts)
        return {
            "count": count,
            "mean": round(total_sum / count, 6),
            "max": round(vmax, 6),
            **{
                f"p{p}": round(
                    quantile_from_buckets(self.bounds, cumulative, count, p / 100.0), 6
                )
                for p in percentiles
            },
        }

    def render(self) -> "list[str]":
        counts, count, total_sum, _ = self._snapshot()
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        for bound, cum in zip(self.bounds, self._cumulate(counts)):
            lines.append(f'{self.name}_bucket{{le="{_fmt_value(bound)}"}} {cum}')
        lines.append(f'{self.name}_bucket{{le="+Inf"}} {count}')
        lines.append(f"{self.name}_sum {_fmt_value(total_sum)}")
        lines.append(f"{self.name}_count {count}")
        return lines

    def to_dict(self) -> dict:
        # the persisted form carries CUMULATIVE counts (the wire/scrape shape)
        counts, count, total_sum, vmax = self._snapshot()
        return {
            "type": "histogram",
            "buckets": list(self.bounds),
            "counts": self._cumulate(counts),
            "count": count,
            "sum": round(total_sum, 9),
            "max": round(vmax, 9),
        }

    @classmethod
    def from_dict(cls, name: str, payload: dict) -> "Histogram":
        h = cls(name, buckets=tuple(payload["buckets"]))
        h._set_cumulative([int(c) for c in payload["counts"]], int(payload["count"]))
        h.sum = float(payload["sum"])
        h.max = float(payload.get("max", 0.0))
        return h

    def _set_cumulative(self, cumulative: "list[int]", total: int) -> None:
        prev = 0
        for i, c in enumerate(cumulative):
            self._counts[i] = c - prev
            prev = c
        self._counts[-1] = total - prev
        self.count = total


def hist_dist(values: "list[float]", buckets: "tuple[float, ...]" = LATENCY_BUCKETS_S,
              percentiles: "tuple[int, ...]" = (50, 90, 99)) -> dict:
    """Distribution summary of ``values`` through a fixed-bucket
    :class:`Histogram` — the serving/router report sections use this so
    their percentiles are the scrape's percentiles."""
    return Histogram("adhoc", buckets=buckets).observe_many(values).dist(percentiles)


def _fmt_value(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _label_key(labels: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(labels.items())) or ""


def _escape_label_value(v) -> str:
    """Prometheus exposition escaping: backslash, double-quote, newline.
    Label values are user-controlled (replica names) — an unescaped quote
    would invalidate the whole scrape."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{_escape_label_value(v)}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


class MetricsRegistry:
    """One process-wide family table. ``counter``/``gauge``/``histogram``
    create-or-return by name, so instrumentation sites never need to
    coordinate declaration order."""

    def __init__(self):
        self._metrics: "dict[str, Any]" = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(name, Counter, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(name, Gauge, help)

    def histogram(self, name: str, buckets: "tuple[float, ...]" = LATENCY_BUCKETS_S,
                  help: str = "") -> Histogram:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = Histogram(name, buckets=buckets, help=help)
                self._metrics[name] = m
            elif not isinstance(m, Histogram):
                raise TypeError(f"metric {name} already registered as {m.kind}")
            return m

    def _get(self, name: str, cls, help: str):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help=help)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name} already registered as {m.kind}")
            return m

    def get(self, name: str):
        return self._metrics.get(name)

    def names(self) -> "list[str]":
        return sorted(self._metrics)

    def render(self) -> str:
        """The Prometheus text exposition of every registered family."""
        with self._lock:  # a scrape racing a first-time family registration
            metrics = dict(self._metrics)
        lines: "list[str]" = []
        for name in sorted(metrics):
            lines.extend(metrics[name].render())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """JSON-able freeze of the whole registry (the ``metrics`` telemetry
        record payload)."""
        with self._lock:
            metrics = dict(self._metrics)
        return {name: m.to_dict() for name, m in sorted(metrics.items())}


# ---------------------------------------------------------------------------
# module-level singleton + zero-overhead shims (the events.py pattern): every
# helper below costs one attribute load + ``is None`` check when disabled.

_ACTIVE: Optional[MetricsRegistry] = None
_SERVER = None  # (http.server instance, thread)
_SHUTTING_DOWN = False  # /healthz readiness: flipped before the socket dies
# "never": time.monotonic() starts near 0 on a freshly booted machine, where a
# 0.0 here read as "a snapshot was just taken" for the whole first interval
_LAST_SNAPSHOT = float("-inf")
_SNAPSHOT_LOCK = threading.Lock()
#: snapshot throttle, parsed ONCE at enable() (the hot loops call
#: maybe_snapshot every step — no per-step env reads)
_SNAPSHOT_INTERVAL_S = 1.0


def is_enabled() -> bool:
    return _ACTIVE is not None


def get_registry() -> Optional[MetricsRegistry]:
    return _ACTIVE


def enable() -> MetricsRegistry:
    """Arm the registry (idempotent)."""
    global _ACTIVE, _SNAPSHOT_INTERVAL_S
    if _ACTIVE is None:
        _ACTIVE = MetricsRegistry()
        # defensive parse, once (never crash — and never re-read per step)
        _SNAPSHOT_INTERVAL_S = parse_seconds_from_env(METRICS_SNAPSHOT_ENV_VAR, 1.0)
    return _ACTIVE


def disable() -> None:
    """Drop the registry and stop the exporter thread."""
    global _ACTIVE, _LAST_SNAPSHOT
    stop_server()
    _ACTIVE = None
    _LAST_SNAPSHOT = float("-inf")


def maybe_enable_from_env() -> Optional[MetricsRegistry]:
    """Arm iff ``ACCELERATE_METRICS_PORT`` is set (also starts the exporter)
    or telemetry is already on (registry only — snapshots still flow into
    the event log). Off by default: an unconfigured process pays one env
    read here and one ``is None`` per instrumentation site afterwards."""
    if _ACTIVE is not None:
        return _ACTIVE
    port = parse_optional_int_from_env(METRICS_PORT_ENV_VAR)
    if port is not None:
        reg = enable()
        serve(port)
        return reg
    if _events.is_enabled():
        return enable()
    return None


def inc(name: str, n: float = 1.0, **labels) -> None:
    if _ACTIVE is not None:
        _ACTIVE.counter(name).inc(n, **labels)


def set_gauge(name: str, v: float, **labels) -> None:
    if _ACTIVE is not None:
        _ACTIVE.gauge(name).set(v, **labels)


def observe(name: str, v: float, buckets: "tuple[float, ...]" = LATENCY_BUCKETS_S) -> None:
    if _ACTIVE is not None:
        _ACTIVE.histogram(name, buckets=buckets).observe(v)


def snapshot_now() -> None:
    """Freeze the registry into one ``metrics`` telemetry record."""
    if _ACTIVE is not None and _events.is_enabled():
        _events.emit("metrics", metrics=_ACTIVE.snapshot())


def maybe_snapshot(now: Optional[float] = None) -> bool:
    """Throttled :func:`snapshot_now` — at most one record per
    ``ACCELERATE_METRICS_SNAPSHOT_S`` (default 1s). The serving step/poll
    loops call this; True when a record was written."""
    global _LAST_SNAPSHOT
    if _ACTIVE is None or not _events.is_enabled():
        return False
    now = time.monotonic() if now is None else now
    with _SNAPSHOT_LOCK:
        if now - _LAST_SNAPSHOT < _SNAPSHOT_INTERVAL_S:
            return False
        _LAST_SNAPSHOT = now
    snapshot_now()
    return True


# ---------------------------------------------------------------------------
# the exporter: GET /metrics from a stdlib http.server daemon thread


def serve(port: int, host: str = "127.0.0.1"):
    """Start the Prometheus endpoint (idempotent; ``port=0`` binds a free
    port — :func:`server_port` reports the real one).

    Never crashes the caller: a second :func:`serve` keeps the existing
    server (warning when a DIFFERENT fixed port was requested — scrapes of
    the requested port would get connection refused), and a bind failure
    (``EADDRINUSE`` — e.g. a child process inheriting the parent's
    ``ACCELERATE_METRICS_PORT``) degrades to registry-only with a warning
    instead of killing engine construction."""
    global _SERVER, _SHUTTING_DOWN
    _SHUTTING_DOWN = False
    if _SERVER is not None:
        bound = _SERVER[0].server_address[1]
        if int(port) not in (0, bound):
            warnings.warn(
                f"metrics exporter already bound to port {bound}; "
                f"ignoring requested port {port}",
                stacklevel=2,
            )
        return _SERVER[0]
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    enable()

    class _Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (http.server API)
            path = self.path.split("?")[0]
            if path == "/healthz":
                # readiness, not content: external probes (k8s, the fleet
                # supervisor) ask this instead of scraping-and-parsing.
                # 200 while the registry is live, 503 once shutdown began
                # so load balancers stop routing before the socket dies.
                ok = _ACTIVE is not None and not _SHUTTING_DOWN
                body = (b"ok\n" if ok else b"shutting down\n")
                self.send_response(200 if ok else 503)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if path not in ("/metrics", "/"):
                self.send_response(404)
                self.end_headers()
                return
            body = (_ACTIVE.render() if _ACTIVE is not None else "").encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # scrapes must not spam stderr
            pass

    try:
        server = ThreadingHTTPServer((host, int(port)), _Handler)
    except OSError as exc:
        warnings.warn(
            f"metrics exporter could not bind {host}:{port} ({exc}); "
            "serving disabled, registry stays armed",
            stacklevel=2,
        )
        return None
    thread = threading.Thread(
        target=server.serve_forever, name="accelerate-tpu-metrics", daemon=True
    )
    thread.start()
    _SERVER = (server, thread)
    return server


def server_port() -> Optional[int]:
    return _SERVER[0].server_address[1] if _SERVER is not None else None


def stop_server() -> None:
    global _SERVER, _SHUTTING_DOWN
    # flip readiness FIRST: a /healthz probe racing the shutdown sees 503
    # and stops routing before the socket actually closes
    _SHUTTING_DOWN = True
    if _SERVER is None:
        return
    server, thread = _SERVER
    _SERVER = None
    try:
        server.shutdown()
        server.server_close()
    except OSError:
        pass
    thread.join(timeout=5.0)


# ---------------------------------------------------------------------------
# scrape-side parsing (tests + doctor check 16 verify a live scrape against
# the report through this, not through a second ad-hoc parser)


def parse_prometheus_text(text: str) -> dict:
    """Parse the exposition format back into
    ``{name: {"type", "samples": [(labels, value)]}}`` — enough to rebuild a
    histogram (`*_bucket`/`*_sum`/`*_count` samples fold under the family
    name) and check counters/gauges."""
    families: dict = {}
    types: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(None, 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        name, labels, value = _parse_sample(line)
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in types:
                family = name[: -len(suffix)]
                break
        rec = families.setdefault(
            family, {"type": types.get(family, "untyped"), "samples": []}
        )
        rec["samples"].append((name, labels, value))
    return families


# one label pair: key="value" with \\, \" and \n escapes inside the value
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_sample(line: str) -> "tuple[str, dict, float]":
    if "{" in line:
        name, rest = line.split("{", 1)
        labels_s, value_s = rest.rsplit("}", 1)
        labels = {}
        for k, v in _LABEL_RE.findall(labels_s):
            labels[k] = (
                v.replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")
            )
        return name.strip(), labels, float(value_s)
    name, value_s = line.rsplit(None, 1)
    return name.strip(), {}, float(value_s)


def histogram_from_scrape(families: dict, name: str) -> Optional[Histogram]:
    """Rebuild a :class:`Histogram` from parsed scrape samples so its
    :meth:`~Histogram.quantile` can be compared 1:1 with the report's."""
    fam = families.get(name)
    if fam is None or fam["type"] != "histogram":
        return None
    bounds: "list[float]" = []
    counts: "list[int]" = []
    total = 0
    total_sum = 0.0
    for sample_name, labels, value in fam["samples"]:
        if sample_name == f"{name}_bucket":
            le = labels.get("le", "")
            if le == "+Inf":
                total = int(value)
            else:
                bounds.append(float(le))
                counts.append(int(value))
        elif sample_name == f"{name}_count":
            total = int(value)
        elif sample_name == f"{name}_sum":
            total_sum = float(value)
    if not bounds:
        return None
    order = sorted(range(len(bounds)), key=lambda i: bounds[i])
    h = Histogram(name, buckets=tuple(bounds[i] for i in order))
    h._set_cumulative([counts[i] for i in order], total)
    h.sum = total_sum
    return h
