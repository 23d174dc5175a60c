"""What the second kind of cache costs beside the first: the bytes of
per-sequence state the window's decode batches held live, over those plus the
bytes of their live K/V blocks. From the window's ``atpu.serve.build``
records (``state_rows``: the batch's live rows of the state; ``live_blocks``:
the blocks its rows hold) and the cell's published keys, at one item size
(which cancels):

    state = state_rows x conv layers x (conv_L_cache - 1) x hidden_size
    kv    = live_blocks x block_size x attention layers x 2 x
            num_key_value_heads x head size

summed over the batches, ``100 x state / (state + kv)``. The layers are the
``conv`` and ``full_attention`` entries among the cell's first ``n_layers`` of
``layer_types``. A state row costs the same whatever the context, a block
table grows with it: 8 layers x 2 x 2048 against 2 layers x 2 x 8 x 64 a token
is the K/V of 16 tokens, so the share is about 16 / (16 + live context), 1.5 %
at 1000 tokens. None where the builds carry no ``state_rows`` (a model
without such state, or a program older than the counter)."""

from benchmarks.chip import models, program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps or record.cell is None:
        return None
    builds = program_spans.attributes(steps, "build")
    if not builds or any("state_rows" not in b for b in builds):
        return None
    c, spec = record.cell.config, record.cell.spec
    kinds = c["layer_types"][:models.depth(record.cell)]
    head = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    state = sum(b["state_rows"] for b in builds) * (
        kinds.count("conv") * (c["conv_L_cache"] - 1) * c["hidden_size"])
    kv = sum(b["live_blocks"] for b in builds) * spec["engine"]["block_size"] * (
        kinds.count("full_attention") * 2 * c["num_key_value_heads"] * head)
    return 100.0 * state / (state + kv) if state + kv else None
