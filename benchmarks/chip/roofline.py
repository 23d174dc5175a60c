"""A kernel's share of its roofline, the same way for every kernel: the least
time the chip could take for the call's work over the time its operations took
in the device trace.

The work is the LEAST the kernel must do, counted by the metric's own reader
from the cell's shapes and the slice's records: the bytes an ideal kernel
would move once and the operations the mathematics needs. It is never what the
implementation happens to do (a padded table, a pool copied, a block read
twice): those are what the share is there to show. So a share cannot pass 100,
and one that does has counted too much work or too little time."""

from __future__ import annotations

from benchmarks.chip import flops

ITEMSIZE = {"bf16": 2, "f32": 4}  # bytes an element of a cell's `dtype`


def kernel_time(trace: dict, mark: str) -> tuple[float, float] | None:
    """``(seconds, calls)`` of the slice's device operations whose short name
    holds ``mark`` (the ``name=`` of the kernel's ``pallas_call``); None where
    no operation has the name."""
    named = [(op, seconds) for op, seconds in trace["device_ops"] if mark in op]
    if not named:
        return None
    return sum(s for _, s in named), sum(trace["device_op_calls"][op] for op, _ in named)


def share_percent(seconds: float, bytes_moved: float, operations: float, device_kind: str) -> float:
    """``100 x max(bytes / HBM bytes per second, operations / bf16 operations
    per second) / seconds`` from ``flops.PEAKS``; an unknown device kind is an
    error (``flops.peaks``)."""
    peak = flops.peaks(device_kind)
    least_s = max(bytes_moved / peak["hbm_bytes_per_s"], operations / peak["bf16_flops_per_s"])
    return 100.0 * least_s / seconds
