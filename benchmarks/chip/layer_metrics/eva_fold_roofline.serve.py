"""The fold kernel's share of its roofline over the traced slice
(``ops.flash_attention.paged_fold_kernel``, named ``eva_fold``: each chunk of
``chunk_size`` keys and values whose last position a call wrote, turned into
its pooled pair, ``kbar = sum_j softmax_j(k_j . mu) k_j`` and ``vbar = sum_j
softmax_j(k_j . phi) v_j`` a key head).

The least work, from the slice's ``atpu.serve.eva`` records (one per decode
step and per prefill: ``chunks_folded``), for each of the cell's ``n_layers``
layers and each chunk folded:

- bytes: the chunk's keys and values read once (``chunk_size x
  num_key_value_heads x head size x 2`` at the pool's item size) and its pair
  written (``2 x num_key_value_heads x head size``);
- operations: ``8 x chunk_size x num_key_value_heads x head size``: the two
  pooling logits and the two weighted sums, a multiply and an add an element.

A decode step's call runs over every row of the batch and folds the few whose
chunk is complete (a row's chunk completes every 16th step): the others read
the null block, fetched once, and what they compute counts nothing here, so
the share shows what the call costs beside the chunks that needed it. None
without a trace, without the slice's records, without the kernel's name in
the trace, or where the trace's calls are not one a layer for each decode
batch and each prefill call of the slice."""

from benchmarks.chip import models, program_spans, roofline

MARK = "eva_fold"


def read(record):
    steps = program_spans.slice_steps(record)
    if not steps or record.cell is None:
        return None
    folds = program_spans.attributes(steps, "eva")
    kernel = roofline.kernel_time(record.trace, MARK)
    if not folds or not kernel:
        return None
    seconds, calls = kernel
    c, spec = record.cell.config, record.cell.spec
    layers = models.depth(record.cell)
    programs = len(program_spans.attributes(steps, "build")) + len(
        program_spans.attributes(steps, "prefill_call"))
    if calls != layers * programs:
        return None  # the records and the trace are not of the same steps
    width = c["num_key_value_heads"] * (c["hidden_size"] // c["num_attention_heads"])
    chunks = layers * sum(f["chunks_folded"] for f in folds)
    bytes_moved = chunks * roofline.ITEMSIZE[spec["dtype"]] * width * 2 * (c["chunk_size"] + 1)
    operations = chunks * 8 * c["chunk_size"] * width
    return roofline.share_percent(seconds, bytes_moved, operations, record.clocks["device_kind"])
