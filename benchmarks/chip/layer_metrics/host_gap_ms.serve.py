"""The time per step the engine's host code leaves the device nothing to do:
mean over the window's steps of the ``atpu.serve.step`` span less its
``prefill``, ``dispatch`` and ``fetch`` children (in those the device has work
or the host waits for it). What is left is admission, ``scheduler.grow``, the
table building, the emit loop and the step's own bookkeeping."""

from benchmarks.chip import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps:
        return None
    gaps = [program_spans.total(p, "step") - program_spans.total(p, "prefill", "dispatch", "fetch")
            for p in steps.values()]
    return 1e3 * sum(gaps) / len(gaps)
