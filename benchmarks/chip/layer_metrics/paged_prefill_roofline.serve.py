"""The paged prefill kernel's share of its roofline over the traced slice: the
least time the chip could take for the slice's prefill attention over the
device time of the operations named ``paged_prefill`` (``roofline.py``).

The least work, counted here from the slice's ``atpu.serve.prefill`` records
(one per admitted request: ``tokens`` to prefill behind ``cached`` tokens that
are in the pool already) and the cell's published keys. The engine prefills a
request in chunks of at most its largest prefill bucket (the last of the cell's
``prefill_buckets``), one call a layer for each chunk, so the chunks of a
record are ``tokens`` cut into runs of that size with the rest behind them. A
smaller bucket only pads the last chunk, and padding is no work. For each
chunk of ``n`` tokens behind ``s`` live ones, for each of the cell's layers:

- bytes: the keys and values of the ``s + n`` tokens read once
  (``num_key_value_heads x head_dim x 2`` at the pool's item size), the
  chunk's queries read and outputs written (``num_attention_heads x head_dim``
  each a token);
- operations: ``4 x num_attention_heads x head_dim`` for every causal pair, a
  query against what is live before it and itself: ``n x s + n (n + 1) / 2``.

The operations bind at the chat mix's lengths. None without a trace, without
the slice's records, without the kernel's name in the trace, or where the
trace's calls are not one a layer for each chunk."""

from benchmarks.chip import models, program_spans, roofline

MARK = "paged_prefill"


def chunks(tokens: int, cached: int, cap: int):
    """``(live before, tokens)`` of each chunk of one request's prefill."""
    start, end = cached, cached + tokens
    while start < end:
        n = min(cap, end - start)
        yield start, n
        start += n


def read(record):
    steps = program_spans.slice_steps(record)
    if not steps or record.cell is None:
        return None
    prefills = program_spans.attributes(steps, "prefill")
    kernel = roofline.kernel_time(record.trace, MARK)
    if not prefills or not kernel:
        return None
    seconds, calls = kernel
    c, spec, n_layers = record.cell.config, record.cell.spec, models.depth(record.cell)
    cap = max(spec["engine"]["prefill_buckets"])
    work = [ch for p in prefills for ch in chunks(p["tokens"], p["cached"], cap)]
    if calls != n_layers * len(work):
        return None  # the records and the trace are not of the same steps
    q_width = c["num_attention_heads"] * c["head_dim"]
    kv_width = c["num_key_value_heads"] * c["head_dim"]
    bytes_moved = n_layers * roofline.ITEMSIZE[spec["dtype"]] * sum(
        (s + n) * kv_width * 2 + n * q_width * 2 for s, n in work)
    operations = n_layers * 4 * q_width * sum(n * s + n * (n + 1) // 2 for s, n in work)
    return roofline.share_percent(seconds, bytes_moved, operations, record.clocks["device_kind"])
