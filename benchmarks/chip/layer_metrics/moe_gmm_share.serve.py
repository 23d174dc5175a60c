"""Device time of the expert layer's grouped matmul over the device's busy
time: the operations of the traced slice whose short name holds ``moe_gmm``
(the ``name=`` of the kernel's ``pallas_call`` in
``accelerate_tpu/ops/grouped_matmul.py``: three calls a layer, the gate, up and
down projections of the routed experts held here). None without a trace, or
where no operation has the name (a model without routed experts)."""

MARK = "moe_gmm"


def read(record):
    t = record.trace
    if not t or not t["busy_s"]:
        return None
    named = [seconds for op, seconds in t["device_ops"] if MARK in op]
    return 100.0 * sum(named) / t["busy_s"] if named else None
