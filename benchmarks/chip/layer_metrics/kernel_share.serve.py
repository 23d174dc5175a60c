"""Device time of the Pallas custom calls (``tpu_custom_call``: in the serve
programs these are the paged decode and paged prefill kernels, which carry no
name of their own and so cannot be told apart) over the device's busy time."""


def read(record):
    t = record.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
