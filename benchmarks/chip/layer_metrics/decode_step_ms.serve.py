"""Median wall time of the ``engine.step()`` calls of the window that admitted
nothing (no prompt token prefilled) while requests were running: one decode
step of the batch, host work included."""


def read(record):
    s = record.clocks["decode_step_median_s"]
    return None if s is None else 1e3 * s
