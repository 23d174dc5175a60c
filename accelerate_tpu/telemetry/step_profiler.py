"""Per-step profiler: wall time, data-wait, compile-vs-execute split, and
recompile detection.

Two complementary recompile signals, because silent reshape-driven recompiles
are the single most common TPU throughput cliff (every new batch shape costs a
full XLA compile — seconds to minutes — while the step "just runs slower"):

1. **Global compile listener** (``jax.monitoring``): counts every
   ``backend_compile_duration`` event and accumulates compile seconds, so a
   step record can split its wall time into ``compile_s`` + ``execute_s`` even
   for compilations we did not register.
2. **Per-function jit-cache polling**: every compiled step the
   :class:`Accelerator` builds is registered here by name; at each step
   boundary the watcher polls ``fn._cache_size()`` and any growth *after the
   first entry* is a recompile, attributed to the function that suffered it —
   the "which function, which step" answer the global counter cannot give.

Data-wait time is accumulated by ``data_loader.py`` via
:func:`record_data_wait` and drained into each step record, so an input-bound
loop shows up as ``data_wait_s`` ≈ ``dur_s`` instead of a mystery.
"""

from __future__ import annotations

import time
from typing import Optional

from . import events as tel

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compile_count = 0
_compile_secs = 0.0
_listener_installed = False

# data-wait seconds accumulated by the dataloader since the last step boundary
_data_wait_accum = 0.0


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _compile_count, _compile_secs
    if event == _COMPILE_EVENT:
        _compile_count += 1
        _compile_secs += float(duration)


def install_compile_listener() -> None:
    """Idempotently hook ``jax.monitoring`` so XLA backend compiles are counted
    process-wide. Installed lazily on the first telemetry-enabled step."""
    global _listener_installed
    if _listener_installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _listener_installed = True


def compile_snapshot() -> "tuple[int, float]":
    """(total backend compiles, total compile seconds) in this process so
    far, since the listener was installed."""
    return _compile_count, _compile_secs


def record_data_wait(seconds: float) -> None:
    """Called by the dataloader: add input-pipeline wait time to the window the
    next step record drains."""
    global _data_wait_accum
    _data_wait_accum += seconds


def drain_data_wait() -> float:
    global _data_wait_accum
    out = _data_wait_accum
    _data_wait_accum = 0.0
    return out


class RecompileWatcher:
    """Counts jit cache misses per registered compiled function.

    ``register`` snapshots the function's current executable-cache size;
    ``poll`` reports growth since the last poll. The first entry per function
    is the expected initial compile (reported with ``first=True``); any later
    growth means a tracing-cache miss — almost always a silently changed input
    shape/dtype — and is a recompile.
    """

    # registered fns are strongly referenced (their executables stay pollable);
    # bound the registry so fresh-function-per-phase callers cannot leak
    MAX_TRACKED = 64

    def __init__(self):
        self._fns: dict = {}  # name -> [fn, last_size, ever_compiled]

    @staticmethod
    def _size(fn) -> Optional[int]:
        try:
            return int(fn._cache_size())
        except Exception:
            return None

    def register(self, name: str, fn) -> None:
        if not hasattr(fn, "_cache_size"):
            return  # eager (disable_jit) fns have no cache to miss
        size = self._size(fn)
        if size is None:
            return
        if name in self._fns and self._fns[name][0] is fn:
            return
        while len(self._fns) >= self.MAX_TRACKED:
            self._fns.pop(next(iter(self._fns)))  # evict oldest registration
        self._fns[name] = [fn, size, size > 0]

    def poll(self, emit: bool = True) -> "dict[str, int]":
        """``{name: recompile count since last poll}`` — cache growth minus the
        one expected initial compile per function; emits one ``jit_cache_miss``
        record per grown function when ``emit``."""
        out: dict = {}
        for name, rec in self._fns.items():
            fn, last, ever = rec
            size = self._size(fn)
            if size is None or size <= last:
                continue
            grew = size - last
            rec[1] = size
            rec[2] = True
            # growth from an empty cache includes the expected first compile;
            # everything past entry #1 is a recompile
            recompiles = grew - (0 if ever else 1)
            out[name] = recompiles
            if emit:
                tel.emit(
                    "jit_cache_miss",
                    fn=name,
                    count=grew,
                    cache_size=size,
                    recompiles=recompiles,
                    first=not ever,
                )
        return out

    def recompile_total(self) -> int:
        """Total cache entries beyond the first per function (live view)."""
        total = 0
        for name, (fn, last, ever) in self._fns.items():
            size = self._size(fn)
            if size is None:
                size = last
            total += max(0, size - 1)
        return total


class _StepContext:
    __slots__ = ("prof", "enabled", "t0", "c0", "s0")

    def __init__(self, prof: "StepTelemetry"):
        self.prof = prof
        self.enabled = False

    def __enter__(self):
        if not tel.is_enabled():
            return self
        self.enabled = True
        install_compile_listener()
        tel.set_step(self.prof.step_index)
        self.c0, self.s0 = compile_snapshot()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        prof = self.prof
        if not self.enabled:
            prof.step_index += 1
            return False
        wall = time.monotonic() - self.t0
        c1, s1 = compile_snapshot()
        compiles = c1 - self.c0
        compile_s = s1 - self.s0
        recompiles = sum(prof.watcher.poll().values())
        fields: dict = {}
        cost = prof.step_cost
        if cost is not None:
            # roofline attribution (telemetry/perf.py): MFU over the step's
            # EXECUTE time (a compile-carrying step would otherwise read as a
            # utilization collapse), intensity/bucket are compile-time facts
            execute = max(wall - compile_s, 1e-9)
            step_mfu = cost.mfu(execute)
            if step_mfu is not None:
                fields["mfu"] = round(step_mfu, 6)
            if cost.intensity is not None:
                fields["arithmetic_intensity"] = round(cost.intensity, 6)
            if cost.roofline is not None:
                fields["roofline"] = cost.roofline
            fields["perf_fn"] = cost.name
        drained_wait = drain_data_wait()
        execute_s = max(0.0, wall - compile_s)
        tel.emit(
            "step",
            name=prof.name,
            dur_s=round(wall, 6),
            data_wait_s=round(drained_wait, 6),
            compile_s=round(compile_s, 6),
            execute_s=round(execute_s, 6),
            compiles=compiles,
            recompiles=max(0, recompiles),
            **fields,
        )
        from . import goodput as _goodput

        _goodput.note_step(execute_s, compile_s, drained_wait)
        _goodput.maybe_emit()
        if prof.memory_every and prof.step_index % prof.memory_every == 0:
            from .memory import MemoryMonitor

            if prof._memory is None:
                prof._memory = MemoryMonitor()
            prof._memory.sample()
        prof.step_index += 1
        tel.set_step(None)
        return False


class StepTelemetry:
    """Accelerator-integrated per-step telemetry driver.

    Cheap to construct and to carry while disabled: ``step()`` hands out a
    context whose enter/exit is a flag check when telemetry is off. Distinct
    from :class:`accelerate_tpu.accelerator.StepProfiler`, which drives
    ``jax.profiler`` *trace windows*; this records lightweight *metrics* for
    every step.
    """

    def __init__(self, name: str = "train_step", memory_every: int = 10):
        self.name = name
        self.memory_every = memory_every
        self.step_index = 0
        self.watcher = RecompileWatcher()
        self._memory = None
        # the XLA-reported cost of the step function about to run (set by the
        # Accelerator's perf capture); folded into each step record as
        # mfu / arithmetic_intensity / roofline
        self.step_cost = None
        if tel.is_enabled():
            install_compile_listener()

    def register_compiled(self, name: str, fn) -> None:
        """Track a jitted function's executable cache for recompile detection."""
        self.watcher.register(name, fn)

    def set_step_cost(self, cost) -> None:
        """Attach a :class:`~accelerate_tpu.telemetry.perf.CompiledCost` for
        the step function the NEXT :meth:`step` context will run (``None``
        clears it — records stop carrying MFU)."""
        self.step_cost = cost

    def step(self) -> _StepContext:
        """``with step_telemetry.step(): compiled_step(...)`` — one record per step."""
        return _StepContext(self)
