"""The train runner: one model through ``Accelerator.prepare`` ->
``prepare_train_step``, as ``chip_smoke._bert_setup`` proved it on the chip,
with the program's loader running inside the measured window.

The model kind, the depth, the mesh (``parallelism``), the optimizer and the
batch all come from the cell's files, so a long-sequence causal-LM cell or a
four-chip FSDP cell is two data files and no code."""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmarks.chip import flops, harness, models, reference, traffic

TRACE_STEPS = 6  # the profiler slice of a traced run: this many steady steps
# The loop reads each step's loss this many steps later, as a loop that logs
# does. It bounds how far the host runs ahead of the device (unbounded, it ran
# 32 steps ahead: a 10 s window took 14.6 s, and the step call's time was the
# runtime's back-pressure, 141 ms, not its own cost; my chip run, PR 23) and
# leaves the device two steps of queued work, so it never waits for the host.
LOSS_FETCH_LAG = 2


def _reset_state() -> None:
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def _endless(loader):
    """The loader, epoch after epoch: an epoch's end is part of the pipeline."""
    while True:
        yield from loader


def run(cell, *, seed: int, seconds: float, trace: bool, process_t0: float,
        allow_cpu: bool = False) -> harness.Record:
    """One run of a train cell. ``allow_cpu`` is for the tests alone: the
    command never passes it, and without it anything but the cell's number of
    TPU chips is refused."""
    import jax
    import optax

    from accelerate_tpu import Accelerator, DataLoader, DeepSpeedPlugin, ParallelismConfig

    harness.require_device(cell.chips, allow_cpu=allow_cpu)
    spec, mix, kind = cell.spec, cell.traffic, models.kind_of(cell.config, cell.root)
    n_layers = models.depth(cell)
    cfg = kind["program_config"](cell.config, n_layers=n_layers, max_seq_len=mix["seq_len"])
    key_seed = int(seed) % (2**31 - 1)

    _reset_state()
    parallelism = dict(spec.get("parallelism", {}))
    zero_stage = parallelism.pop("zero_stage", None)
    accelerator = Accelerator(
        mixed_precision=spec["mixed_precision"], rng_seed=key_seed,
        parallelism_config=ParallelismConfig(**parallelism),
        **({"deepspeed_plugin": DeepSpeedPlugin(zero_stage=zero_stage)} if zero_stage else {}),
    )
    dp = accelerator.mesh.shape["dp_replicate"] * accelerator.mesh.shape["dp_shard"]
    global_batch = int(mix["global_batch"])
    if global_batch % dp:
        raise ValueError(f"global batch {global_batch} does not divide over {dp} data rows")
    rows = traffic.train_rows(mix, cfg.vocab_size, seed)
    # the weights: on the device, in one jitted call, from the seed
    params = jax.jit(lambda key: kind["init"](cfg, key))(jax.random.PRNGKey(key_seed))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    if spec["optimizer"]["name"] != "adamw":
        raise ValueError(f"the train runner knows adamw, not {spec['optimizer']['name']!r}")
    params, optimizer, loader = accelerator.prepare(
        params, optax.adamw(spec["optimizer"]["learning_rate"]),
        DataLoader(traffic.Rows(rows), batch_size=global_batch // dp),
        shard_rules=kind["shard_rules"](),
    )
    step = accelerator.prepare_train_step(
        kind["loss"](cfg, **spec.get("loss_kwargs", {})), optimizer, compute_grad_norm=True)

    # correctness, outside the window: the first step's loss and gradient norm
    # on the first global batch against the plain float32 reference on the
    # same rows and weights (computed first: the step donates its params)
    first_rows = {k: v[:global_batch] for k, v in rows.items()}
    ref_loss, ref_norm = reference.loss_and_grad_norm(
        kind["reference_loss"](cell.config), params, first_rows,
        rows_at_a_time=int(spec["check_rows_at_a_time"]))
    batches = _endless(loader)
    opt_state = optimizer.opt_state
    params, opt_state, m = step(params, opt_state, next(batches))
    loss, norm = float(m["loss"]), float(m["grad_norm"])
    tol = spec["tolerances"]
    check = {
        "rows": global_batch, "loss": loss, "reference_loss": ref_loss,
        "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
        "grad_norm": norm, "reference_grad_norm": ref_norm,
        "grad_norm_rel": abs(norm - ref_norm) / abs(ref_norm),
        "tolerances": tol,
    }
    # the norm's error has a floor that does not shrink with the norm (a
    # balanced batch has a small gradient), hence the absolute term
    check["ok"] = bool(
        check["loss_rel"] <= tol["loss"]
        and abs(norm - ref_norm) <= tol["grad_norm"] * abs(ref_norm) + tol["grad_norm_abs"])
    # a second step: its inputs are the first one's outputs, as in the window
    params, opt_state, m = step(params, opt_state, next(batches))
    jax.block_until_ready(m["loss"])
    compiles_before, cache_before = harness.compile_count(), step._cache_size()

    def one_step(losses, waits=None, dispatches=None):
        nonlocal params, opt_state
        t0 = time.perf_counter()
        with harness.annotate("cb.next_batch"):
            batch = next(batches)
        t1 = time.perf_counter()
        with harness.annotate("cb.step_call"):
            params, opt_state, m = step(params, opt_state, batch)
        t2 = time.perf_counter()
        losses.append(m["loss"])
        if len(losses) > LOSS_FETCH_LAG:
            with harness.annotate("cb.fetch_loss"):
                losses[-1 - LOSS_FETCH_LAG] = float(losses[-1 - LOSS_FETCH_LAG])
        if waits is not None:
            waits.append(t1 - t0)
            dispatches.append(t2 - t1)

    def drain(losses):
        with harness.annotate("cb.fetch_loss"):
            jax.block_until_ready(params)
            losses[-LOSS_FETCH_LAG:] = [float(x) for x in losses[-LOSS_FETCH_LAG:]]

    # ------------------------------------------------------------ the window
    waits, dispatches, losses = [], [], []
    t_start = time.perf_counter()
    setup_s = t_start - process_t0
    while time.perf_counter() - t_start < seconds:
        one_step(losses, waits, dispatches)
    drain(losses)  # the window ends when the last step is done and its loss is on the host
    window_s = time.perf_counter() - t_start
    late_compiles = harness.compile_count() - compiles_before
    cache_grew = step._cache_size() != cache_before

    steps = len(losses)
    losses = np.asarray(losses, np.float64)
    tokens_per_step = global_batch * int(mix["seq_len"])
    tokens_per_s = steps * tokens_per_step / window_s

    out = {"trace": None}
    if trace:
        with harness.profiler_slice(out):
            traced = []
            for _ in range(LOSS_FETCH_LAG + 1):  # refill the queue the profiler's start drained
                one_step(traced)
            with harness.annotate("cb.window"):
                for _ in range(TRACE_STEPS):
                    one_step(traced)
                drain(traced)
    accelerator.end_training()

    finite = bool(np.all(np.isfinite(losses)))
    device = harness.device_record()
    return harness.Record(
        correct=bool(check["ok"] and finite and late_compiles == 0 and not cache_grew),
        attempted=steps,
        failed=int(np.sum(~np.isfinite(losses))),
        end_to_end={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        clocks={
            "window_s": window_s, "steps": steps, "tokens_per_s": tokens_per_s,
            "data_wait_s": float(sum(waits)),
            "dispatch_median_s": statistics.median(dispatches),
            "train_flops_per_token": flops.train_flops_per_token(kind["forward_flops_per_token"](
                cell.config, int(mix["seq_len"]), n_layers)),
            "device_kind": device["kind"], "chips": cell.chips,
        },
        facts={
            "n_params": n_params, "n_layers": n_layers, "tokens_per_step": tokens_per_step,
            "steps": steps, "window_s": window_s, "step_ms": 1e3 * window_s / steps,
            "check": check, "late_compiles": late_compiles, "step_cache_grew": cache_grew,
            "losses_finite": finite, "loss_first": float(losses[0]), "loss_last": float(losses[-1]),
            "mesh": {k: int(v) for k, v in accelerator.mesh.shape.items() if v > 1},
        },
        trace=out["trace"],
        compared={
            "loss_rel": (check["loss_rel"], tol["loss"]),
            "grad_norm_gap": (abs(norm - ref_norm), tol["grad_norm"] * abs(ref_norm) + tol["grad_norm_abs"]),
            "nonfinite_losses": (int(np.sum(~np.isfinite(losses))), 0),
            "late_compiles": (late_compiles, 0), "step_cache_grew": (int(cache_grew), 0),
        },
    )
