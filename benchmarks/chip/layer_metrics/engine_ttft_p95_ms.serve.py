"""95th percentile (nearest rank) of the engine's own ``first_token_t -
arrival_t`` over the finished requests first admitted in a step of the window.
``first_token_t`` is read when the first token is on the host, inside the
request's prefill; ``ttft_p95_ms.serve`` stamps it when ``engine.step()``
returns, a decode batch later. The runner passes the DUE time as ``arrival_t``."""

from benchmarks.chip import harness, program_spans


def read(record):
    requests = program_spans.window_requests(record)
    if not requests:
        return None
    return harness.nearest_rank(
        [1e3 * (r["first_token_t"] - r["arrival_t"]) for r in requests], 95)
