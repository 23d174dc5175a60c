"""What the program-span readers share: the serving engine's own record of its
step phases (``atpu.serve.*``) and of each finished request's stamps
(``atpu.request``), which ``accelerate_tpu.telemetry.tracing`` keeps in a
bounded in-memory ring and hands out through ``tracing.recorded()``.

A run's window comes first: the engine takes no step before it, and the traced
slice and the drain come after it. So the window's part of the ring is what
the engine that wrote last recorded with ``step < record.clocks["steps"]``.
Every function here returns None where there is nothing to read: a program
without the ring (the parent of the PR that brought it), a runner without the
``steps`` clock, or a ring that has already dropped part of the window."""

from __future__ import annotations

PREFIX = "atpu.serve."
REQUEST = "atpu.request"


def _recorded(prefix: str) -> list:
    from accelerate_tpu.telemetry import tracing

    recorded = getattr(tracing, "recorded", None)  # the parent's module has no ring
    return recorded(prefix) if recorded else []


def window_steps(record) -> dict | None:
    """``{step: {phase: [seconds, ...]}}`` over the window's engine steps,
    ``phase`` being the span's name without ``atpu.serve.`` (``step`` itself
    among them). The attributes of the ``prefill`` spans are under
    ``"prefill_tokens"``, in the same order."""
    n_steps = record.clocks.get("steps")
    spans = _recorded(PREFIX)
    if not n_steps or not spans:
        return None
    engine = spans[-1][3]["engine"]
    steps: dict = {}
    for name, t0_ns, t1_ns, key in spans:
        if key["engine"] != engine or key["step"] >= n_steps:
            continue
        phases = steps.setdefault(key["step"], {})
        phases.setdefault(name[len(PREFIX):], []).append((t1_ns - t0_ns) / 1e9)
        if name == PREFIX + "prefill":
            phases.setdefault("prefill_tokens", []).append(key["tokens"])
    if sum("step" in phases for phases in steps.values()) != n_steps:
        return None  # the ring has dropped part of the window
    return steps


def window_requests(record) -> list | None:
    """The stamps (the ``atpu.request`` records' fields) of the finished
    requests that the last engine first admitted in a step of the window."""
    n_steps = record.clocks.get("steps")
    requests = _recorded(REQUEST)
    if not n_steps or not requests:
        return None
    engine = requests[-1][3]["engine"]
    mine = [key for _, _, _, key in requests
            if key["engine"] == engine and key["admit_step"] is not None
            and key["admit_step"] < n_steps]
    return mine or None


def total(phases: dict, *names: str) -> float:
    """Seconds a step spent in the named phases, every occurrence counted."""
    return sum(sum(phases.get(name, ())) for name in names)
