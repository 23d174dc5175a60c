"""The windowed paged prefill kernel's share of its roofline over the traced
slice: the least time the chip could take for the slice's prefill attention on
the model's sliding-window layers over the device time of the operations named
``paged_prefill_win`` (``roofline.py``; the full layers' calls are named
``paged_prefill`` and are not read here).

As ``paged_prefill_roofline.serve`` (a request's ``tokens`` behind ``cached``
cut into chunks of the largest prefill bucket, one call a layer for each
chunk), but a query at position ``p`` sees only ``min(p + 1,
sliding_window)`` keys, and the layers counted are the ``sliding_attention``
ones among the cell's depth of the published ``layer_types``. For each chunk
of ``n`` tokens behind ``s`` live ones, for each such layer:

- bytes: the keys and values of the tokens some query of the chunk sees, read
  once: positions ``max(0, s - sliding_window + 1) .. s + n``
  (``num_key_value_heads x head_dim x 2`` at the pool's item size a token); the
  chunk's queries read and outputs written (``num_attention_heads x
  head_dim`` each a token);
- operations: ``4 x num_attention_heads x head_dim`` for every (query, key)
  pair inside the window: the sum over the chunk's positions ``p`` of ``min(p
  + 1, sliding_window)``.

None without a trace, without the slice's records, without the kernel's name
in the trace, or where the trace's calls are not one a window layer for each
chunk."""

from benchmarks.chip import program_spans, roofline
from benchmarks.chip.windowed import chunks, pairs_in_window, window_layers

MARK = "paged_prefill_win"


def read(record):
    steps = program_spans.slice_steps(record)
    if not steps or record.cell is None:
        return None
    prefills = program_spans.attributes(steps, "prefill")
    kernel = roofline.kernel_time(record.trace, MARK)
    if not prefills or not kernel:
        return None
    seconds, calls = kernel
    c, spec, n_layers = record.cell.config, record.cell.spec, window_layers(record.cell)
    cap = max(spec["engine"]["prefill_buckets"])
    work = [ch for p in prefills for ch in chunks(p["tokens"], p["cached"], cap)]
    if not n_layers or calls != n_layers * len(work):
        return None  # the records and the trace are not of the same steps
    window = c["sliding_window"]
    q_width = c["num_attention_heads"] * c["head_dim"]
    kv_width = c["num_key_value_heads"] * c["head_dim"]
    bytes_moved = n_layers * roofline.ITEMSIZE[spec["dtype"]] * sum(
        (s + n - max(0, s - window + 1)) * kv_width * 2 + n * q_width * 2 for s, n in work)
    operations = n_layers * 4 * q_width * sum(pairs_in_window(s, n, window) for s, n in work)
    return roofline.share_percent(seconds, bytes_moved, operations, record.clocks["device_kind"])
