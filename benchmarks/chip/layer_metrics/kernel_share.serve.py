"""Device time of the Pallas custom calls (``tpu_custom_call``: in the serve
programs these are the paged decode and paged prefill kernels, which
``paged_decode_share.serve`` and ``paged_prefill_share.serve`` tell apart by
name) over the device's busy time."""


def read(record):
    t = record.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
