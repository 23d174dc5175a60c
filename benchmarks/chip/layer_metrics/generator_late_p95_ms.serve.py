"""How late the benchmark's own generator ran: 95th percentile of submit time
minus due time. One thread submits between engine steps, so this is about one
step's length; far more would mean a starved generator, not a slow server."""

from benchmarks.chip import harness


def read(record):
    late = record.clocks["generator_late_ms"]
    return harness.nearest_rank(late, 95) if late else None
