"""Per-step performance attribution: hardware peaks, MFU, and roofline buckets.

ROADMAP item 2 ("raw-speed push to MFU >= 0.7") needs per-step *evidence*:
where device time goes, which functions are compute-bound vs HBM-bound, and
how the measured step compares to the chip's roofline. This module is the
shared substrate:

- **Hardware peak table** — bf16 peak FLOP/s and HBM bandwidth per chip,
  keyed by ``device_kind`` (public TPU specs). A TPU kind that is not in it is
  an error, and a CPU has no peak: MFU and roofline fields are then absent
  (``None``), never nominal. ``bench.py`` and the telemetry layer both read
  THIS table, so they can never disagree on peaks.
- **Compile-time cost capture** — :func:`capture_compiled` lowers a jitted
  step function once (AOT), records XLA's own ``cost_analysis()`` (FLOPs,
  bytes accessed — remat recompute *included*: hardware utilization, not
  model-MFU) and ``memory_analysis()`` (argument/output/temp bytes, checked
  against device capacity by :mod:`.memory`), and emits one ``perf`` record.
  The :class:`~accelerate_tpu.accelerator.Accelerator` runs it automatically
  on the first call of every tracked step function while telemetry is on.
- **Per-step folding** — the captured cost is handed to the step profiler, so
  every ``step`` record carries ``mfu``, ``arithmetic_intensity`` and its
  ``roofline`` bucket (``compute-bound`` vs ``hbm-bound``), and the report
  CLI's "performance" section can plot the MFU trend per function.

The capture compiles the step function ahead of its first call; the jit call
then reuses that executable, so the compile is counted once, as the first
step's. It only runs while telemetry is enabled and can be killed
independently with ``ACCELERATE_PERF_CAPTURE=0``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Optional

from . import events as tel

PERF_CAPTURE_ENV_VAR = "ACCELERATE_PERF_CAPTURE"

# (bf16 peak FLOP/s, HBM bytes/s) of one chip, keyed by the ``device_kind`` JAX
# reports. Source: Google Cloud TPU documentation, the per-generation system
# architecture pages (v5e: 197 TFLOP/s bf16, 819 GB/s). THE peak table —
# bench.py imports it; a kind that is missing is an error, not a default.
DEVICE_PEAKS = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


@dataclass(frozen=True)
class HardwarePeaks:
    """Peak throughput of one chip: ``flops`` (bf16 FLOP/s) and
    ``hbm_bytes_per_s``, as :data:`DEVICE_PEAKS` lists them."""

    device_kind: str
    flops: float
    hbm_bytes_per_s: float

    @property
    def ridge_intensity(self) -> float:
        """FLOP/byte at the roofline ridge: below it a kernel is HBM-bound."""
        return self.flops / self.hbm_bytes_per_s


def peaks_for_device(device: Optional[Any] = None) -> Optional[HardwarePeaks]:
    """:data:`DEVICE_PEAKS` lookup for ``device`` (default:
    ``jax.devices()[0]``). A device that is not a TPU has no peak (``None``):
    a utilization of a dev box's CPU is not a number this package reports. A
    TPU whose ``device_kind`` is not in the table raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if getattr(device, "platform", None) != "tpu":
        return None
    kind = str(device.device_kind)
    try:
        flops, hbm = DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s and HBM bandwidth on record for device_kind {kind!r}: "
            "add it to accelerate_tpu.telemetry.perf.DEVICE_PEAKS with its source"
        ) from None
    return HardwarePeaks(kind, flops, hbm)


def device_peak_flops(device: Optional[Any] = None) -> float:
    """Peak bf16 FLOP/s, or ``0.0`` for a device that is not a TPU."""
    peaks = peaks_for_device(device)
    return peaks.flops if peaks else 0.0


def device_hbm_bandwidth(device: Optional[Any] = None) -> Optional[float]:
    """Peak HBM bytes/s, or ``None`` for a device that is not a TPU."""
    peaks = peaks_for_device(device)
    return peaks.hbm_bytes_per_s if peaks else None


# ------------------------------------------------------------- MFU math ----
def train_flops_per_sample(config: Any, seq_len: int, n_params: int) -> float:
    """Model FLOPs per trained sample: 6*N per token (fwd 2N + bwd 4N) plus
    the attention score/context matmuls 12 * L * d_model * T per token.
    ``config`` needs ``n_layers`` and ``dim`` (any transformer config here)."""
    per_token = 6.0 * n_params + 12.0 * config.n_layers * config.dim * seq_len
    return per_token * seq_len


def lm_train_mfu(
    tokens_per_sec: float, n_params: int, config: Any, seq_len: int
) -> Optional[float]:
    """Model-FLOPs utilization for an LM train config, ``None`` off-TPU —
    the one MFU methodology bench.py and telemetry share (remat recompute is
    NOT counted: model-MFU, comparable across remat policies)."""
    import jax

    peak = device_peak_flops(jax.devices()[0])
    if not peak:
        return None
    per_token = train_flops_per_sample(config, seq_len, n_params) / seq_len
    return round(tokens_per_sec * per_token / peak, 4)


def mfu(flops_per_step: float, step_seconds: float, peak_flops: float) -> Optional[float]:
    """Utilization of one step: achieved FLOP/s over peak (``None`` when
    either side is unknown/zero)."""
    if not flops_per_step or not step_seconds or not peak_flops:
        return None
    return flops_per_step / step_seconds / peak_flops


def arithmetic_intensity(flops: float, bytes_accessed: float) -> Optional[float]:
    """FLOPs per byte of memory traffic — the roofline x-axis."""
    if not flops or not bytes_accessed:
        return None
    return flops / bytes_accessed


def roofline_bucket(
    intensity: Optional[float], peaks: Optional[HardwarePeaks]
) -> Optional[str]:
    """``"compute-bound"`` when the kernel's arithmetic intensity clears the
    chip's ridge point (peak FLOPs / peak HBM bytes), else ``"hbm-bound"``;
    ``None`` where there is no peak to compare with."""
    if intensity is None or peaks is None:
        return None
    return "compute-bound" if intensity >= peaks.ridge_intensity else "hbm-bound"


# -------------------------------------------------------- cost capture ----
@dataclass
class CompiledCost:
    """One step function's XLA-reported cost: what `cost_analysis()` /
    `memory_analysis()` said at compile time, plus the derived roofline
    placement against the chip's peaks (``peaks`` is ``None`` off-TPU, and
    every field derived from it then is too)."""

    name: str
    flops: float
    bytes_accessed: float
    peaks: Optional[HardwarePeaks]
    memory: Optional[dict] = None

    @property
    def intensity(self) -> Optional[float]:
        return arithmetic_intensity(self.flops, self.bytes_accessed)

    @property
    def roofline(self) -> Optional[str]:
        return roofline_bucket(self.intensity, self.peaks)

    def mfu(self, step_seconds: float) -> Optional[float]:
        return mfu(self.flops, step_seconds, self.peaks.flops) if self.peaks else None

    def record(self) -> dict:
        """The ``perf`` event payload (stable field names — schema in
        docs/telemetry.md)."""
        import jax

        out = {
            "fn": self.name,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "arithmetic_intensity": _round(self.intensity),
            "roofline": self.roofline,
            "peak_flops": self.peaks.flops if self.peaks else None,
            "peak_hbm_bytes_per_s": self.peaks.hbm_bytes_per_s if self.peaks else None,
            "device_kind": str(jax.devices()[0].device_kind),
        }
        if self.memory:
            out.update({f"memory_{k}": v for k, v in self.memory.items()})
        return out


def _round(x: Optional[float], digits: int = 6) -> Optional[float]:
    return None if x is None else round(float(x), digits)


def capture_enabled() -> bool:
    """Cost capture runs iff telemetry is on and ``ACCELERATE_PERF_CAPTURE``
    is not explicitly falsy."""
    if not tel.is_enabled():
        return False
    return os.environ.get(PERF_CAPTURE_ENV_VAR, "").strip().lower() not in (
        "0",
        "false",
        "no",
        "off",
    )


def cost_from_compiled(name: str, compiled: Any) -> Optional[CompiledCost]:
    """Extract a :class:`CompiledCost` from an already-compiled executable
    (``jitted.lower(...).compile()``). Returns ``None`` when the backend
    reports no cost data."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and bytes_accessed <= 0.0:
        return None
    from .memory import compiled_memory_analysis

    return CompiledCost(
        name=name,
        flops=flops,
        bytes_accessed=bytes_accessed,
        peaks=peaks_for_device(),
        memory=compiled_memory_analysis(compiled),
    )


def capture_compiled(
    name: str,
    fn: Any,
    args: tuple,
    kwargs: Optional[dict] = None,
    mesh: Optional[Any] = None,
) -> Optional[CompiledCost]:
    """AOT-lower ``fn`` with ``args`` and record its XLA cost + memory
    analysis; emits one ``perf`` event and a capacity check (see
    :func:`~accelerate_tpu.telemetry.memory.check_memory_fit`).

    The compile this triggers is the function's own: the jit call that
    follows with the same arguments reuses the executable and compiles
    nothing, so the step profiler counts it like any other. Since the
    compile is already paid, the executable is also EXPORTED to the
    persistent compile cache (when configured —
    :mod:`accelerate_tpu.compile_cache`), which is what lets the next
    restart generation skip this function's compile entirely. Never raises:
    an uncapturable backend returns ``None`` and training proceeds
    untouched."""
    if not hasattr(fn, "lower"):
        return None  # eager (disable_jit) or already-AOT: nothing to lower
    try:
        lowered = fn.lower(*args, **(kwargs or {}))
        compiled = lowered.compile()
        cost = cost_from_compiled(name, compiled)
    except Exception:
        cost = None
    else:
        try:
            from ..compile_cache import maybe_export

            maybe_export(name, lowered, compiled, mesh=mesh)
        except Exception:
            pass  # an unexportable backend must not cost the capture
    if cost is None:
        return None
    tel.emit("perf", **cost.record())
    if cost.memory:
        from .memory import check_memory_fit

        check_memory_fit(name, cost.memory)
    return cost


def capture_from_executable(name: str, executable: Any) -> Optional[CompiledCost]:
    """The zero-compile twin of :func:`capture_compiled`, for a step
    executable LOADED from the persistent compile cache: the cost analysis is
    read off the deserialized executable, so a warm restart's step records
    still carry mfu/roofline without paying the capture's AOT compile."""
    cost = cost_from_compiled(name, executable)
    if cost is None:
        return None
    tel.emit("perf", **cost.record())
    if cost.memory:
        from .memory import check_memory_fit

        check_memory_fit(name, cost.memory)
    return cost
