"""One decode step of the batch as the engine itself times it: median, over
every step of the window that decoded, of its ``build`` + ``dispatch`` +
``fetch`` phases. Steps that also prefilled are among them, which
``decode_step_ms.serve`` (the runner's clock round whole steps) cannot see."""

import statistics

from benchmarks.chip import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps:
        return None
    decoded = [program_spans.total(p, "build", "dispatch", "fetch")
               for p in steps.values() if "dispatch" in p]
    return 1e3 * statistics.median(decoded) if decoded else None
