"""The windowed paged decode kernel's share of its roofline over the traced
slice: the least time the chip could take for the slice's decode attention on
the model's sliding-window layers over the device time of the operations named
``paged_decode_win`` (``roofline.py``; the full layers' calls are named
``paged_decode`` and are not read here).

As ``paged_decode_roofline.serve``, but a window layer's least bytes are its
``window_blocks``: the slice's ``atpu.serve.build`` records carry, beside
``live_blocks``, the blocks a window layer has to read (a row's from the one
that holds position ``kv_len - sliding_window``), and the layers counted are
the ``sliding_attention`` ones among the cell's depth of the published
``layer_types`` (three a period). For each such layer:

- bytes: every window block's keys and values read once (``block_size x
  num_key_value_heads x head_dim x 2`` at the pool's item size), plus a row's
  queries read and outputs written (``num_attention_heads x head_dim`` each);
- operations: ``4 x num_attention_heads x head_dim`` for every token of those
  blocks.

A row's first and last window block count whole. None without a trace, without
the slice's records or their ``window_blocks`` (a model without a window, or
the parent of the PR that brought the counter), without the kernel's name in
the trace, or where the trace's calls are not one a window layer for each
record."""

from benchmarks.chip import program_spans, roofline
from benchmarks.chip.windowed import window_layers

MARK = "paged_decode_win"


def read(record):
    steps = program_spans.slice_steps(record)
    if not steps or record.cell is None:
        return None
    builds = program_spans.attributes(steps, "build")
    kernel = roofline.kernel_time(record.trace, MARK)
    if not builds or not kernel or any("window_blocks" not in b for b in builds):
        return None
    seconds, calls = kernel
    c, spec, n_layers = record.cell.config, record.cell.spec, window_layers(record.cell)
    if not n_layers or calls != n_layers * len(builds):
        return None  # the records and the trace are not of the same steps
    tokens = sum(b["window_blocks"] for b in builds) * spec["engine"]["block_size"]
    rows = sum(b["batch"] for b in builds)
    q_width = c["num_attention_heads"] * c["head_dim"]
    kv_width = c["num_key_value_heads"] * c["head_dim"]
    bytes_moved = n_layers * roofline.ITEMSIZE[spec["dtype"]] * (
        tokens * kv_width * 2 + rows * q_width * 2)
    operations = n_layers * 4 * tokens * q_width
    return roofline.share_percent(seconds, bytes_moved, operations, record.clocks["device_kind"])
