"""Median time for the prepared step's call to return (it returns before the
device has finished: this is the entry point's host cost, not the step time)."""


def read(record):
    return 1e3 * record.clocks["dispatch_median_s"]
