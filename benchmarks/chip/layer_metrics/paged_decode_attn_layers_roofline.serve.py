"""The paged decode kernel's share of its roofline over the traced slice, for
a model only some of whose layers attend: ``paged_decode_roofline.serve``'s
count with the layers taken as the ``full_attention`` entries among the
cell's first ``n_layers`` of the published ``layer_types`` (that reader takes
the cell's whole depth, and would count ten layers' bytes for two), and the
head size as ``hidden_size / num_attention_heads`` where the configuration
has no ``head_dim``.

The least work, from the slice's ``atpu.serve.build`` records (one per decode
batch: ``live_blocks``, the blocks its rows hold, and ``batch``, its rows),
for each attention layer:

- bytes: every live block's keys and values read once (``block_size x
  num_key_value_heads x head size x 2`` at the pool's item size), plus a row's
  queries read and outputs written (``num_attention_heads x head size`` each);
- operations: ``4 x num_attention_heads x head size`` for every live token.

A row's last block counts whole (half a block a row over). The table's
padding and whatever else the implementation moves count nothing; the layers
that do not attend call no kernel and count nothing. The bytes bind. None
without a trace, without the slice's records or their ``live_blocks``, without
``layer_types``, without the kernel's name in the trace, or where the trace's
calls are not one an attention layer for each record."""

from benchmarks.chip import models, program_spans, roofline

MARK = "paged_decode"


def read(record):
    steps = program_spans.slice_steps(record)
    if not steps or record.cell is None or "layer_types" not in record.cell.config:
        return None
    builds = program_spans.attributes(steps, "build")
    kernel = roofline.kernel_time(record.trace, MARK)
    if not builds or not kernel or any("live_blocks" not in b for b in builds):
        return None
    seconds, calls = kernel
    c, spec = record.cell.config, record.cell.spec
    layers = c["layer_types"][:models.depth(record.cell)].count("full_attention")
    if not layers or calls != layers * len(builds):
        return None  # the records and the trace are not of the same steps
    head = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    live_tokens = sum(b["live_blocks"] for b in builds) * spec["engine"]["block_size"]
    rows = sum(b["batch"] for b in builds)
    q_width, kv_width = c["num_attention_heads"] * head, c["num_key_value_heads"] * head
    bytes_moved = layers * roofline.ITEMSIZE[spec["dtype"]] * (
        live_tokens * kv_width * 2 + rows * q_width * 2)
    operations = layers * 4 * live_tokens * q_width
    return roofline.share_percent(seconds, bytes_moved, operations, record.clocks["device_kind"])
