"""The (token, expert) pairs that landed on the experts held here, a token and
a layer, over the window: the ``local_pairs`` of the window's
``atpu.serve.moe`` records (one per call of the model, per layer) over their
``tokens`` (the real tokens of a decode batch or a prefill chunk, padding left
out) times the layers. With even routing it is ``experts per token x held /
num_experts`` (1.0 at 8 x 16 / 128): more says the router favours the experts
held here, and the grouped matmul has that much more to do. None where the
program counts no routing (a model without routed experts, or the parent of
the PR that brought the counters)."""

from benchmarks.chip import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps:
        return None
    calls = program_spans.attributes(steps, "moe")
    layer_tokens = sum(r["tokens"] * len(r["local_pairs"]) for r in calls)
    if not layer_tokens:
        return None
    return sum(sum(r["local_pairs"]) for r in calls) / layer_tokens
