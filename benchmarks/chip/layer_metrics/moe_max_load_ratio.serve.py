"""How uneven the routing is: the most pairs any one held expert got in a
layer of a model call, over what even routing would have given it
(``local_pairs / held``), averaged over the window's calls and layers. From
the window's ``atpu.serve.moe`` records: per layer ``max_expert_load`` and
``local_pairs``, and ``held``, the experts this chip holds. 1.0 is even
routing; a decode batch of 32 rows x 8 pairs over 64 experts reads 2-3 by
chance alone (the largest of 64 counts whose mean is 4), a 2048-token chunk
under 1.5: the grouped matmul walks every expert's rows in tiles of 128, so
the ratio says how far the longest group is from the mean one, not what the
kernel pays. Layers of a call in which nothing landed here are left out. None where the program counts
no routing, or its records do not say what is held."""

from benchmarks.chip import program_spans


def read(record):
    steps = program_spans.window_steps(record)
    if not steps:
        return None
    ratios = []
    for r in program_spans.attributes(steps, "moe"):
        if not r.get("held"):
            return None
        ratios += [load * r["held"] / pairs
                   for load, pairs in zip(r["max_expert_load"], r["local_pairs"]) if pairs]
    return sum(ratios) / len(ratios) if ratios else None
