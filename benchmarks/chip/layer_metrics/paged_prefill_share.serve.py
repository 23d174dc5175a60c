"""Device time of the paged prefill kernel over the device's busy time: the
operations of the traced slice whose short name holds ``paged_prefill`` (the
``name=`` of the kernel's ``pallas_call``, which the compiled instruction
carries). None without a trace, or where no operation has the name."""

MARK = "paged_prefill"


def read(record):
    t = record.trace
    if not t or not t["busy_s"]:
        return None
    named = [seconds for op, seconds in t["device_ops"] if MARK in op]
    return 100.0 * sum(named) / t["busy_s"] if named else None
