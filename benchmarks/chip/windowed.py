"""What the readers of the window kernels' rooflines share
(``layer_metrics/paged_decode_win_roofline.serve.py``,
``paged_prefill_win_roofline.serve.py``): which of a cell's layers have a
window, how a request's prefill falls into chunks, and how many (query, key)
pairs a chunk has inside the window. Counted from the cell's published keys
and the engine's records, never from what the kernels do."""

from __future__ import annotations

from benchmarks.chip import models


def window_layers(cell) -> int:
    """The ``sliding_attention`` layers among the cell's depth of the published
    ``layer_types`` (three a period of four); 0 for a model without the key."""
    kinds = cell.config.get("layer_types", [])[:models.depth(cell)]
    return sum(kind == "sliding_attention" for kind in kinds)


def chunks(tokens: int, cached: int, cap: int):
    """``(live before, tokens)`` of each chunk of one request's prefill: the
    engine cuts ``tokens`` behind ``cached`` into runs of its largest prefill
    bucket ``cap``, the rest last."""
    start, end = cached, cached + tokens
    while start < end:
        n = min(cap, end - start)
        yield start, n
        start += n


def pairs_in_window(s: int, n: int, window: int) -> int:
    """Sum over positions ``p`` in ``[s, s + n)`` of ``min(p + 1, window)``."""
    ramp = max(0, min(s + n, window) - s)  # positions whose whole past is inside the window
    return ramp * (2 * s + ramp + 1) // 2 + (n - ramp) * window
