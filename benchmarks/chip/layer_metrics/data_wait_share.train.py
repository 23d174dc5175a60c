"""Share of the window the train loop spent waiting in ``next(loader)``: the
runner's clock around the call, summed, over the window's length."""


def read(record):
    c = record.clocks
    return 100.0 * c["data_wait_s"] / c["window_s"]
