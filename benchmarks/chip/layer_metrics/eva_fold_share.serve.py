"""Device time of the fold over the device's busy time: the operations of the
traced slice whose short name holds ``eva_fold`` (the ``name=`` of the
pooling kernel's ``pallas_call``, ``ops.flash_attention.paged_fold_kernel``,
which turns each completed chunk of 16 keys and values into its pooled pair,
in prefill and in decode). The scatter of the pairs into the pool and the
gathers of the block ids are XLA's fusions, under the named scope
``eva_fold`` in a profile but under no name this reduction keeps, and count
nothing here. None without a trace, or where no operation has the name."""

MARK = "eva_fold"


def read(record):
    t = record.trace
    if not t or not t["busy_s"]:
        return None
    named = [seconds for op, seconds in t["device_ops"] if MARK in op]
    return 100.0 * sum(named) / t["busy_s"] if named else None
