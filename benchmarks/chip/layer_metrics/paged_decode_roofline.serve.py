"""The paged decode kernel's share of its roofline over the traced slice: the
least time the chip could take for the slice's decode attention over the
device time of the operations named ``paged_decode`` (``roofline.py``).

The least work, counted here from the slice's ``atpu.serve.build`` records
(one per decode batch: ``live_blocks``, the blocks its rows hold, and
``batch``, its rows) and the cell's published keys, for each of the cell's
layers:

- bytes: every live block's keys and values read once (``block_size x
  num_key_value_heads x head_dim x 2`` at the pool's item size), plus a row's
  queries read and outputs written (``num_attention_heads x head_dim`` each);
- operations: ``4 x num_attention_heads x head_dim`` for every live token (the
  score and the weighted sum, two operations a multiply-add).

A row's last block counts whole, though a token or more of it is not live yet:
half a block a row, about 1 % over at the 45 blocks a row of the chat mix.
The table's padding, the pool's copies and whatever else the implementation
moves count nothing. The bytes bind (an operation a byte against the chip's
240). None without a trace, without the slice's records or their
``live_blocks``, without the kernel's name in the trace, or where the trace's
calls are not one a layer for each record."""

from benchmarks.chip import models, program_spans, roofline

MARK = "paged_decode"


def read(record):
    steps = program_spans.slice_steps(record)
    if not steps or record.cell is None:
        return None
    builds = program_spans.attributes(steps, "build")
    kernel = roofline.kernel_time(record.trace, MARK)
    if not builds or not kernel or any("live_blocks" not in b for b in builds):
        return None
    seconds, calls = kernel
    c, spec, n_layers = record.cell.config, record.cell.spec, models.depth(record.cell)
    if calls != n_layers * len(builds):
        return None  # the records and the trace are not of the same steps
    live_tokens = sum(b["live_blocks"] for b in builds) * spec["engine"]["block_size"]
    rows = sum(b["batch"] for b in builds)
    q_width = c["num_attention_heads"] * c["head_dim"]
    kv_width = c["num_key_value_heads"] * c["head_dim"]
    bytes_moved = n_layers * roofline.ITEMSIZE[spec["dtype"]] * (
        live_tokens * kv_width * 2 + rows * q_width * 2)
    operations = n_layers * 4 * live_tokens * q_width
    return roofline.share_percent(seconds, bytes_moved, operations, record.clocks["device_kind"])
