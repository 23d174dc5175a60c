"""95th percentile (nearest rank) of ``admit_t - arrival_t`` over the finished
requests first admitted in a step of the window: the wait for a slot and for
the step in flight, without the request's own prefill. The runner passes the
time a request was DUE as its ``arrival_t``."""

from benchmarks.chip import harness, program_spans


def read(record):
    requests = program_spans.window_requests(record)
    if not requests:
        return None
    return harness.nearest_rank([1e3 * (r["admit_t"] - r["arrival_t"]) for r in requests], 95)
