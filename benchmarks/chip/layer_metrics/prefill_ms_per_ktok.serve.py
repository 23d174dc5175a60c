"""What admitting costs: the wall time of the window's ``engine.step()`` calls
that prefilled prompt tokens, less a median decode step for each, over the
thousands of prompt tokens they prefilled (``engine.prefill_tokens`` deltas)."""


def read(record):
    c = record.clocks
    if not c["prefill_tokens"]:
        return None
    extra_s = c["prefill_step_s"] - c["prefill_steps"] * (c["decode_step_median_s"] or 0.0)
    return 1e3 * extra_s / (c["prefill_tokens"] / 1e3)
