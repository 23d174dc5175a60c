"""The grouped matmul's share of its roofline over the traced slice, for a
model whose routed experts have a width of their own
(``moe_intermediate_size``; ``moe_gmm_roofline.serve`` reads an expert's width
from ``intermediate_size``, which such a model keeps for a dense FFN no layer
may use): the least time the chip could take for the slice's routed-expert
work over the device time of the operations named ``moe_gmm``
(``roofline.py``).

The least work, from the slice's ``atpu.serve.moe`` records (one per call of
the model, a decode batch or a prefill chunk; per layer ``local_pairs``, the
(token, expert) pairs computed here, and ``experts_hit``, the held experts
that got at least one) and the cell's published keys, for each layer of each
call:

- bytes: each hit expert's three matrices read once (``3 x hidden_size x
  moe_intermediate_size`` at the cell's item size: 12.4 MB at 2304 x 896 in
  bf16), and a pair's row read once and its result written once
  (``hidden_size`` each);
- operations: ``6 x hidden_size x moe_intermediate_size`` a local pair (three
  matrix products, two operations a multiply-add).

The hidden activations between the products, the rows a tile pads a group to,
a weight tile streamed twice for a group that straddles two row tiles, and the
sort, gather and scatter-add round the kernel count nothing. The bytes bind
while an expert gets fewer than some 240 rows. None without a trace, without
the slice's records (a program without the counters), without the key
``moe_intermediate_size``, without the kernel's name in the trace, or where
the trace's calls are not three a layer for each record."""

from benchmarks.chip import program_spans, roofline

MARK = "moe_gmm"


def read(record):
    steps = program_spans.slice_steps(record)
    if not steps or record.cell is None or "moe_intermediate_size" not in record.cell.config:
        return None
    calls_recorded = program_spans.attributes(steps, "moe")
    kernel = roofline.kernel_time(record.trace, MARK)
    if not calls_recorded or not kernel:
        return None
    seconds, calls = kernel
    if calls != 3 * sum(len(r["local_pairs"]) for r in calls_recorded):
        return None  # the records and the trace are not of the same steps
    c, item = record.cell.config, roofline.ITEMSIZE[record.cell.spec["dtype"]]
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    pairs = sum(sum(r["local_pairs"]) for r in calls_recorded)
    experts = sum(sum(r["experts_hit"]) for r in calls_recorded)
    bytes_moved = item * (experts * 3 * d * f + pairs * 2 * d)
    operations = 6 * d * f * pairs
    return roofline.share_percent(seconds, bytes_moved, operations, record.clocks["device_kind"])
