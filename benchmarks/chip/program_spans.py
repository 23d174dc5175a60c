"""What the program-span readers share: the serving engine's own record of its
step phases (``atpu.serve.*``) and of each finished request's stamps
(``atpu.request``), which ``accelerate_tpu.telemetry.tracing`` keeps in a
bounded in-memory ring and hands out through ``tracing.recorded()``.

A run's window comes first: the engine takes no step before it, and the traced
slice and the drain come after it. So the window's part of the ring is what
the engine that wrote last recorded with ``step < record.clocks["steps"]``,
and the traced slice's part (the steps the device trace's ``cb.window``
covers, whose operations ``record.trace`` holds) is what it recorded with
``slice_steps[0] <= step < slice_steps[1]``, both stamped by the runner.
Every function here returns None where there is nothing to read: a program
without the ring (the parent of the PR that brought it), a runner without the
clock, or a ring that has already dropped part of the steps asked for."""

from __future__ import annotations

PREFIX = "atpu.serve."
REQUEST = "atpu.request"


def _recorded(prefix: str) -> list:
    from accelerate_tpu.telemetry import tracing

    recorded = getattr(tracing, "recorded", None)  # the parent's module has no ring
    return recorded(prefix) if recorded else []


def _steps(first: int, last: int) -> dict | None:
    """``{step: {phase: [(seconds, attributes), ...]}}`` over the last
    engine's steps ``first <= step < last``."""
    spans = _recorded(PREFIX)
    if last <= first or not spans:
        return None
    engine = spans[-1][3]["engine"]
    steps: dict = {}
    for name, t0_ns, t1_ns, key in spans:
        if key["engine"] != engine or not first <= key["step"] < last:
            continue
        phases = steps.setdefault(key["step"], {})
        phases.setdefault(name[len(PREFIX):], []).append(((t1_ns - t0_ns) / 1e9, key))
    if sum("step" in phases for phases in steps.values()) != last - first:
        return None  # the ring has dropped part of them
    return steps


def window_steps(record) -> dict | None:
    """``{step: {phase: [(seconds, attributes), ...]}}`` over the window's
    engine steps, ``phase`` being the span's name without ``atpu.serve.``
    (``step`` itself among them) and ``attributes`` what the engine keyed the
    record with: ``live_blocks``, ``batch``, ``slot_bucket`` and
    ``block_bucket`` on a ``build``; ``tokens``, ``cached`` and ``rid`` on a
    ``prefill``; ``engine`` and ``step`` on all."""
    return _steps(0, record.clocks.get("steps") or 0)


def slice_steps(record) -> dict | None:
    """The same over the steps of the traced slice: the whole engine steps
    inside the device trace's ``cb.window``, whose device operations
    ``record.trace`` holds. None in a run without a trace."""
    first, last = record.clocks.get("slice_steps") or (0, 0)
    return _steps(first, last) if record.trace else None


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
    return sum(seconds for name in names for seconds, _ in phases.get(name, ()))


def attributes(steps: dict, phase: str) -> list:
    """The attributes of every ``phase`` record of ``steps``, in step order."""
    return [key for step in sorted(steps) for _, key in steps[step].get(phase, ())]
