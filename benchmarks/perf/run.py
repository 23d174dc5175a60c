"""Performance-observatory microbench: the bench train step under full
attribution (ISSUE 7 acceptance path, also `make profile`).

Runs the headline bench's BERT train step (same model/loss/prepare path as
``bench.py``) for a handful of steps with telemetry, cost-analysis capture and
an automatic trace window enabled, then prints:

- the telemetry report's **performance** section (per-step MFU, roofline
  bucket, top-k ops, comms-overlap ratio) — human-readable, to stdout;
- one JSON line (bench.py conventions, last line on stdout) with the same
  fields for drivers/tests.

With ``JAX_PLATFORMS=cpu`` this rehearses the whole observatory on the CPU
backend, where there is no peak: MFU and the roofline bucket are null. On a
TPU it is a utilization reading of the bench step.
"""

import argparse
import dataclasses
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _common import detect_backend, emit


def run_bench_perf(
    on_tpu: bool,
    steps: int = 8,
    trace_every: int = 3,
    keep_artifacts: bool = False,
) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator, telemetry
    from accelerate_tpu.models import BertConfig, bert_loss, bert_shard_rules, init_bert
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.telemetry.report import build_report, format_performance_section
    from accelerate_tpu.utils.dataclasses import ProfileConfig

    AcceleratorState._reset_state(); GradientState._reset_state(); PartialState._reset_state()
    if on_tpu:
        config, batch_size, seq_len = BertConfig.base(), 64, 128
    else:
        config, batch_size, seq_len = BertConfig.tiny(), 8, 32
    config = dataclasses.replace(config, max_seq_len=seq_len)

    workdir = tempfile.mkdtemp(prefix="bench_perf_")
    telemetry.enable(os.path.join(workdir, "telemetry"))
    try:
        accelerator = Accelerator(
            mixed_precision="bf16",
            rng_seed=0,
            kwargs_handlers=[
                ProfileConfig(
                    trace_every=trace_every,
                    # 2-step windows: on the CPU backend a 1-step window can
                    # close before the XLA pool threads flush their TraceMe
                    # buffers into the session (observed ~1-in-3 empty); the
                    # second step's events force the first step's to land
                    trace_steps=2,
                    output_trace_dir=os.path.join(workdir, "trace"),
                )
            ],
        )
        params = init_bert(config, jax.random.PRNGKey(0))
        params, opt = accelerator.prepare(
            params, optax.adamw(2e-5), shard_rules=bert_shard_rules()
        )
        step = accelerator.prepare_train_step(lambda p, b: bert_loss(p, b, config), opt)
        rng = np.random.default_rng(0)
        batch = {
            "input_ids": jnp.asarray(
                rng.integers(0, config.vocab_size, (batch_size, seq_len)), jnp.int32
            ),
            "attention_mask": jnp.ones((batch_size, seq_len), jnp.int32),
            "token_type_ids": jnp.zeros((batch_size, seq_len), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, 2, (batch_size,)), jnp.int32),
        }
        opt_state = opt.opt_state
        for _ in range(steps):
            params, opt_state, metrics = step(params, opt_state, batch)
            # force completion INSIDE the step (and inside any open trace
            # window): under async dispatch the thunks would otherwise
            # execute after stop_trace and the window would read empty
            final_loss = float(np.asarray(metrics["loss"]))
        accelerator.end_training()
        telemetry.disable()

        report = build_report([os.path.join(workdir, "telemetry")])
        perf = report.get("performance") or {}
        print(format_performance_section(perf) if perf else "no performance records")
        mfu = perf.get("mfu") or {}
        fn = (perf.get("by_fn") or {}).get("train_step") or {}
        trace = perf.get("trace") or {}
        return {
            "bench": "perf",
            "unit": "mfu(p50)",
            "value": mfu.get("p50"),  # null off-TPU: a CPU has no peak
            "mfu": {k: mfu.get(k) for k in ("p50", "mean", "max") if k in mfu},
            "roofline": fn.get("roofline"),
            "arithmetic_intensity": fn.get("arithmetic_intensity"),
            "flops_per_step": fn.get("flops"),
            "device_kind": fn.get("device_kind"),
            "overlap_ratio": trace.get("comms_overlap_ratio"),
            "trace_windows": trace.get("windows", 0),
            "top_ops": (trace.get("top_ops") or [])[:3],
            "steps": steps,
            "final_loss": round(final_loss, 4),
            "on_tpu": on_tpu,
            **({"artifacts": workdir} if keep_artifacts else {}),
        }
    finally:
        telemetry.disable()
        if not keep_artifacts:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--trace-every", type=int, default=3,
                    help="open a two-step jax.profiler window every N steps")
    ap.add_argument("--keep-artifacts", action="store_true",
                    help="keep the telemetry dir + raw traces instead of deleting")
    args = ap.parse_args()
    emit(
        run_bench_perf(
            on_tpu=detect_backend(),
            steps=args.steps,
            trace_every=args.trace_every,
            keep_artifacts=args.keep_artifacts,
        )
    )
