"""Headline MFU tuning grid (VERDICT r04 item 3: spend measured headroom,
target MFU >= 0.6 on the bert-base headline).

Runs the EXACT headline workload (bert-base, seq 128, bf16, loop-fused train
steps — same methodology as bench.py's run_bench) over a grid of the knobs
that plausibly move MXU utilization: global batch size, scan-vs-unrolled
layers, and steps-per-dispatch. Prints one JSON line per cell as it lands
(kill-safe) and a final summary line with the best cell.

Run on a reachable TPU:  python tools/tune_headline.py
CPU smoke (tiny model):  JAX_PLATFORMS=cpu python tools/tune_headline.py --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "examples"))

from accelerate_tpu.telemetry.perf import (  # noqa: E402
    device_peak_flops,
    train_flops_per_sample,
)


def measure_cell(batch_size: int, unroll: bool, steps_per_call: int, smoke: bool):
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator, DataLoader
    from accelerate_tpu.models import BertConfig, bert_loss, bert_shard_rules, init_bert
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.operations import stack_batches
    from nlp_example import DictDataset, make_synthetic_mrpc

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()

    seq_len = 128
    base = BertConfig.tiny() if smoke else BertConfig.base()
    config = dataclasses.replace(base, max_seq_len=seq_len, unroll_layers=unroll)
    accelerator = Accelerator(mixed_precision="bf16", rng_seed=0)
    n_chips = len(jax.devices())
    data = make_synthetic_mrpc(batch_size * n_chips * 4, seq_len, config.vocab_size, seed=0)
    params = init_bert(config, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    params, opt, dl = accelerator.prepare(
        params, optax.adamw(2e-5),
        DataLoader(DictDataset(data), batch_size=batch_size),
        shard_rules=bert_shard_rules(),
    )
    batches = list(dl)
    # the ASSEMBLED global batch (bench.py:628 does the same): on a dp mesh it
    # is batch_size x dp rows — using the nominal bs would underreport by dp
    global_batch = batches[0]["labels"].shape[0]
    stacked = stack_batches([batches[i % len(batches)] for i in range(steps_per_call)])
    loop = accelerator.prepare_train_loop(lambda p, b: bert_loss(p, b, config), opt)
    opt_state = opt.opt_state
    t0 = time.time()
    params, opt_state, m = loop(params, opt_state, stacked)  # compile
    float(np.asarray(m["loss"][-1]))
    compile_s = time.time() - t0
    params, opt_state, m = loop(params, opt_state, stacked)  # warm
    float(np.asarray(m["loss"][-1]))
    n_calls = 3
    t0 = time.time()
    for _ in range(n_calls):
        params, opt_state, m = loop(params, opt_state, stacked)
    float(np.asarray(m["loss"][-1]))
    elapsed = time.time() - t0
    per_chip = n_calls * steps_per_call * global_batch / elapsed / n_chips
    peak = device_peak_flops(jax.devices()[0])
    mfu = per_chip * train_flops_per_sample(config, seq_len, n_params) / peak if peak else None
    return {
        "batch_size": batch_size, "unroll_layers": unroll,
        "steps_per_call": steps_per_call,
        "samples_per_sec_per_chip": round(per_chip, 2),
        "mfu": round(mfu, 4) if mfu else None,
        "compile_seconds": round(compile_s, 1),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny model (CPU plumbing check)")
    ap.add_argument("--budget", type=int, default=1800, help="wall-clock budget (s)")
    args = ap.parse_args()
    if args.smoke:
        import jax

        jax.config.update("jax_platforms", "cpu")
        grid = [(16, True, 10), (16, False, 10)]
    else:
        from benchmarks._common import detect_backend

        if not detect_backend():  # raises where there is no TPU and no CPU was asked for
            raise SystemExit("tuning needs the chip; JAX_PLATFORMS=cpu only runs --smoke")
        # bs ladder x scan-vs-unroll x dispatch fusion depth; ordered so the
        # most promising cells (unrolled, large batch) land first if the
        # budget runs out
        grid = [
            (256, True, 10), (512, True, 10), (128, True, 10),
            (256, True, 20),
            (256, False, 10), (512, False, 10),
        ]
    t_end = time.time() + args.budget
    results = []
    for bs, unroll, spc in grid:
        if time.time() > t_end - 120:
            print(json.dumps({"skipped": [bs, unroll, spc], "reason": "budget"}), flush=True)
            continue
        try:
            cell = measure_cell(bs, unroll, spc, args.smoke)
        except Exception as e:
            cell = {"batch_size": bs, "unroll_layers": unroll, "steps_per_call": spc,
                    "error": f"{type(e).__name__}: {str(e)[:200]}"}
        print(json.dumps(cell), flush=True)
        results.append(cell)
    ok = [c for c in results if c.get("samples_per_sec_per_chip")]
    if ok:
        best = max(ok, key=lambda c: c["samples_per_sec_per_chip"])
        print(json.dumps({"best": best, "cells_measured": len(ok)}), flush=True)


if __name__ == "__main__":
    main()
