"""Controls of ``evabyte-6.5b.bytedoc-sat``'s correctness check: the cell run
as the benchmark runs it, with one part of the ENGINE held to a lower
precision than the cell states (``lfm2_control.py``'s controls and
``lfm2_control.run``, unchanged: the weights, or the pool a step leaves,
rounded to float8's 3 bits of mantissa inside the step programs; the verdict
the runner's own ``_check`` against the float32 reference on the TRUE
weights). A control has to come out NOT correct by at least one of the cell's
limits; a sound run of the same seed has to come out correct. Several seeds
run in one process, one after the other, each a line. Nothing here is
imported by a run of the benchmark.

    python3 benchmarks/chip/evabyte_control.py --control weights_f8 --seed 7 --seed 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.chip import harness, lfm2_control  # noqa: E402

CELL = "evabyte-6.5b.bytedoc-sat"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--control", choices=sorted(lfm2_control.CONTROLS), required=True)
    parser.add_argument("--seed", type=int, action="append", required=True)
    parser.add_argument("--seconds", type=float, default=51.0)
    args = parser.parse_args(argv)
    harness.enable_jax_cache()
    cell = harness.load_cell(CELL)
    for seed in args.seed:
        record = lfm2_control.run(cell, args.control, seed=seed, seconds=args.seconds)
        line = harness.result_line(cell, record, traced=False)
        print(json.dumps({"control": args.control, "seed": seed, "check": record.facts["check"],
                          **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
