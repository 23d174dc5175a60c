"""What a prefill costs, from the engine's own spans: the summed durations of
the window's ``atpu.serve.prefill`` phases (one per admitted request, up to
the sampled token's arrival on the host) over the thousands of uncached
prompt tokens they prefilled (the spans' ``tokens``)."""

from benchmarks.chip import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps:
        return None
    seconds = sum(program_spans.total(p, "prefill") for p in steps.values())
    tokens = sum(key["tokens"] for key in program_spans.attributes(steps, "prefill"))
    return 1e3 * seconds / (tokens / 1e3) if tokens else None
