"""The paged decode kernel's share of its roofline over the traced slice, for
a model whose rows hold pooled pairs ahead of raw keys (EVA attention,
``configs/evabyte-6.5b.json``): a row attends its open window's keys and one
pooled key/value pair for every ``chunk_size`` positions of its closed
windows, and the kernel walks both as one row.

The least work, from the slice's ``atpu.serve.build`` records (one per decode
batch: ``window_entries`` and ``summary_entries``, the raw keys and the
pooled pairs its rows attend, and ``batch``, its rows), for each of the
cell's ``n_layers`` layers (every one attends):

- bytes: every attended entry's key and value read once (``num_key_value_heads
  x head size`` each at the pool's item size), plus a row's query read and
  output written (``num_attention_heads x head size`` each);
- operations: ``4 x num_attention_heads x head size`` for every attended entry.

Entries, not blocks: a row's last block counts as far as it is filled. The
table's padding and whatever else the implementation moves count nothing. The
bytes bind. None without a trace, without the slice's records or their
``summary_entries`` (a model without pooled rows, or a program older than the
counter), without the kernel's name in the trace, or where the trace's calls
are not one a layer for each record."""

from benchmarks.chip import models, program_spans, roofline

MARK = "paged_decode"


def read(record):
    steps = program_spans.slice_steps(record)
    if not steps or record.cell is None:
        return None
    builds = program_spans.attributes(steps, "build")
    kernel = roofline.kernel_time(record.trace, MARK)
    if not builds or not kernel or any("summary_entries" not in b for b in builds):
        return None
    seconds, calls = kernel
    c, spec = record.cell.config, record.cell.spec
    layers = models.depth(record.cell)
    if calls != layers * len(builds):
        return None  # the records and the trace are not of the same steps
    head = c["hidden_size"] // c["num_attention_heads"]
    entries = sum(b["window_entries"] + b["summary_entries"] for b in builds)
    rows = sum(b["batch"] for b in builds)
    q_width, kv_width = c["num_attention_heads"] * head, c["num_key_value_heads"] * head
    bytes_moved = layers * roofline.ITEMSIZE[spec["dtype"]] * (
        entries * kv_width * 2 + rows * q_width * 2)
    operations = layers * 4 * entries * q_width
    return roofline.share_percent(seconds, bytes_moved, operations, record.clocks["device_kind"])
