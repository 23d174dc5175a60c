"""One run of one cell of the chip benchmark, in the one process that holds the chip.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's file under
``workloads/``, its configuration under ``configs/``, its traffic mix under
``traffic/``, its model kind under ``kinds/``, its runner under ``runners/`` and
one reader per per-layer metric under ``layer_metrics/`` (see ``README.md``
beside this file). Earlier lines of the output are free text; the LAST line is
the result the driver reads."""

import time

_PROCESS_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    harness.require_device(cell.chips)  # exits non-zero, and no result is printed
    print(json.dumps({"cell": cell.name, "seed": args.seed, "seconds": args.seconds,
                      "trace": args.trace, "jax_cache_dir": harness.enable_jax_cache()}),
          flush=True)
    record = harness.runner_of(cell).run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        process_t0=_PROCESS_T0,
    )
    print(json.dumps({"facts": record.facts}), flush=True)
    line = harness.result_line(cell, record, traced=bool(args.trace))
    for name, c in line["compared"].items():  # the last lines of standard error
        print(f"compared {name}: {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
