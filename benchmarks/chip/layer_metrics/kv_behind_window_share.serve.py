"""The share of the live KV pool that no layer will read again: a window
layer keeps a row's blocks behind its window (one block table and one uniform
pool serve every layer kind), and never walks them. Over the window's decode
batches, from their ``atpu.serve.build`` records (``live_blocks``: the rows'
blocks, which every layer holds; ``window_blocks``: those a window layer
still walks) and the cell's depth of the published ``layer_types``:

    window layers x (live_blocks - window_blocks) / (depth x live_blocks)

summed over the batches before dividing. It is what an allocator that frees a
window layer's blocks behind its window would give back (0 for rows shorter
than the window; 3/4 x (1 - 1024 / context) at three window layers of four
and a window of 1024). None where the builds carry no ``window_blocks`` (a
model without a window, or a program older than the counter)."""

from benchmarks.chip import models, program_spans
from benchmarks.chip.windowed import window_layers


def read(record):
    steps = program_spans.window_steps(record)
    if not steps or record.cell is None:
        return None
    builds = program_spans.attributes(steps, "build")
    if not builds or any("window_blocks" not in b for b in builds):
        return None
    live = sum(b["live_blocks"] for b in builds)
    behind = live - sum(b["window_blocks"] for b in builds)
    if not live:
        return None
    return 100.0 * window_layers(record.cell) * behind / (models.depth(record.cell) * live)
