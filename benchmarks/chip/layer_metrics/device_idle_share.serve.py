"""Share of the traced slice in which no operation ran on the device: 1 minus
the union of the device's operation intervals over the slice's length."""


def read(record):
    t = record.trace
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
