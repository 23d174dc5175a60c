"""Of the entries the window's decode batches attended, the share that are
pooled pairs: from the window's ``atpu.serve.build`` records
(``summary_entries``, one pooled key/value pair for every ``chunk_size``
positions of a row's closed windows; ``window_entries``, the raw keys of its
open window up to the row's own), ``100 x summary / (summary + window)``
summed over the batches. A row at ``n`` positions attends ``(n - 1) // 2048 x
128`` pairs and at most 2048 raw keys, so the share grows with the context:
about 43 % at the cell's mean context of 13 k. None where the builds carry no
``summary_entries`` (a model without pooled rows, or a program older than the
counter)."""

from benchmarks.chip import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps:
        return None
    builds = program_spans.attributes(steps, "build")
    if not builds or any("summary_entries" not in b for b in builds):
        return None
    summary = sum(b["summary_entries"] for b in builds)
    attended = summary + sum(b["window_entries"] for b in builds)
    return 100.0 * summary / attended if attended else None
