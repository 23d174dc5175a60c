"""Model FLOP/s utilisation over the window: tokens per second times the
forward-plus-backward matrix-multiply operations a token needs (``flops.py``:
no recomputation, and embedding lookups count nothing) over chips times the
published bf16 peak. An unknown device kind is an error."""

from benchmarks.chip import flops


def read(record):
    c = record.clocks
    return flops.mfu_percent(c["tokens_per_s"], c["train_flops_per_token"],
                             c["device_kind"], c["chips"])
