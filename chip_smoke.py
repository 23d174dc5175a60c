"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip: phases train, train_long, serve
    python chip_smoke.py --chips 4  # four chips: the cross-chip phase only

One process (the process that measures is the one that holds the chip), the
entry points a user would call, the published widths of the models (depth may
be cut), weights and data made from ``--seed``. Every phase checks what came
out by the repo's own means and prints one JSON line; the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. Any platform other than ``tpu``, or any
failed phase, makes it ``"ok": false`` and a non-zero exit: there is no CPU
fallback. The timings it prints are set-up evidence, not benchmark results.

The phase functions take their sizes as arguments so ``tests/test_chip_smoke.py``
can run each at a tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import tempfile
import time
import traceback
from typing import Any, NamedTuple

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "examples"))

import numpy as np  # noqa: E402

from benchmarks._common import device_record, enable_jax_cache  # noqa: E402

# bf16 tolerances, stated before the first chip run (PERF.md "Bring-up"):
# the flash kernels round differently from the einsum path (f32 softmax
# statistics carried across blocks), and a mesh reorders the gradient sum.
LONG_LOSS_RTOL = 5e-3       # train_long: step-0 loss, flash vs xla
LONG_GRAD_NORM_RTOL = 2e-2  # train_long: step-0 grad norm, flash vs xla
MESH_LOSS_RTOL = 1e-2       # --chips 4: each of 8 losses, mesh vs one device
# serve: a reference step whose top-2 logits are closer than this many
# standard deviations of its logits is a near-tie that bf16 rounding may flip
# (on the CPU, interpreted kernels against the einsum reference flipped 2 of
# 101 tokens, at gaps of 0.2% and 0.45% of a deviation). Requests are cut
# before such a step, by the reference alone, and then held to exact equality.
SERVE_TIE_MARGIN = 0.03


def _reset_state() -> None:
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def _compiles() -> int:
    from accelerate_tpu.telemetry import step_profiler

    step_profiler.install_compile_listener()
    return step_profiler.compile_snapshot()[0]


def _peak_bytes():
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}  # None on the CPU
    return stats.get("peak_bytes_in_use")


class Run(NamedTuple):
    """What a few timed steps left behind."""

    params: Any
    opt_state: Any
    metrics: list        # per step, as floats
    first_s: float       # step 0: trace + compile (unless done ahead) + execution
    rest_s: list         # the other steps, each to block_until_ready
    late_compiles: int   # compiles after step 0, of anything

    def losses(self) -> list:
        losses = [m["loss"] for m in self.metrics]
        assert all(np.isfinite(losses)), losses
        assert self.late_compiles == 0, self.late_compiles
        return losses

    def median_step_ms(self) -> float:
        return round(statistics.median(self.rest_s) * 1e3, 3)


def _timed_steps(step, params, opt_state, batches, n_steps) -> Run:
    """Run ``n_steps`` steps, each timed to ``block_until_ready``."""
    import jax

    metrics, seconds = [], []
    compiles_after_first = None
    for i in range(n_steps):
        t0 = time.perf_counter()
        params, opt_state, m = step(params, opt_state, batches[i % len(batches)])
        jax.block_until_ready((params, m))
        seconds.append(time.perf_counter() - t0)
        metrics.append({k: float(v) for k, v in m.items()})
        if i == 0:
            compiles_after_first = _compiles()
    return Run(params, opt_state, metrics, seconds[0], seconds[1:],
               _compiles() - compiles_after_first)


def _describe(config) -> str:
    heads = f"{config.n_heads}/{config.n_kv_heads}" if hasattr(config, "n_kv_heads") else config.n_heads
    return f"L{config.n_layers} d{config.dim} h{heads} v{config.vocab_size}"


def _bert_setup(config, batch_size, seq_len, seed, *, n_batches, accelerator_kwargs):
    """bert through Accelerator -> prepare -> prepare_train_step, exactly as
    ``examples/nlp_example.py`` does, on seeded synthetic MRPC-shaped data.
    ``batch_size`` is the GLOBAL batch; the loader's is per data-parallel row."""
    import jax
    import optax
    from nlp_example import DictDataset, make_synthetic_mrpc

    from accelerate_tpu import Accelerator, DataLoader
    from accelerate_tpu.models import bert_loss, bert_shard_rules, init_bert

    _reset_state()
    accelerator = Accelerator(mixed_precision="bf16", rng_seed=seed, **accelerator_kwargs)
    config = dataclasses.replace(config, max_seq_len=seq_len, num_labels=2)
    dp = accelerator.mesh.shape["dp_replicate"] * accelerator.mesh.shape["dp_shard"]
    if batch_size % dp:
        raise ValueError(f"global batch {batch_size} does not divide over {dp} data-parallel rows")
    data = make_synthetic_mrpc(batch_size * n_batches, seq_len, config.vocab_size, seed=seed)
    params = init_bert(config, jax.random.PRNGKey(seed))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    params, optimizer, loader = accelerator.prepare(
        params,
        # bert-base from a random init has no warm-up to hide behind in 8
        # steps: at 1e-4 the loss on the chip went 0.70 -> 2.28 -> 0.86 -> 1.54
        # (my chip run 1, PR 21), and on the CPU 0.70 -> 1.25 at 2e-5
        optax.adamw(2e-6),
        DataLoader(DictDataset(data), batch_size=batch_size // dp),
        shard_rules=bert_shard_rules(),
    )
    step = accelerator.prepare_train_step(lambda p, b: bert_loss(p, b, config), optimizer)
    batches = list(loader)
    assert batches[0]["labels"].shape[0] == batch_size, batches[0]["labels"].shape
    return accelerator, params, optimizer, step, batches, n_params


def phase_train(config=None, *, batch_size=64, seq_len=128, steps=8, seed=0) -> dict:
    """bert-base at its published width: 1 compile + ``steps`` steps, loss
    finite and falling, no compile after the first step, and a
    save_state/load_state round trip that restores bitwise-equal params.

    The loader holds ONE global batch, seen again every step: whether a loss
    falls in 8 steps is only decidable on the batch it is computed on (over
    four batches the CPU showed 0.698 -> 0.712 on step 0's batch after 4
    steps at 1e-6: the class balance of the batches in between decides)."""
    import jax

    from accelerate_tpu.models import BertConfig

    config = config or BertConfig.base()
    accelerator, params, optimizer, step, batches, n_params = _bert_setup(
        config, batch_size, seq_len, seed, n_batches=1, accelerator_kwargs={}
    )
    run = _timed_steps(step, params, optimizer.opt_state, batches, steps + 1)
    params, opt_state, losses = run.params, run.opt_state, run.losses()
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert step._cache_size() == 1, step._cache_size()

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        saved = jax.tree_util.tree_map(np.asarray, params)
        out_dir = accelerator.save_state(ckpt, params=params, opt_state=opt_state)
        scrambled = jax.tree_util.tree_map(lambda x: x * 0 + 1, params)
        restored, _ = accelerator.load_state(out_dir, params=scrambled, opt_state=opt_state)
        same = jax.tree_util.tree_map(
            lambda a, b: bool(np.array_equal(a, np.asarray(b))), saved, restored
        )
        assert all(jax.tree_util.tree_leaves(same)), "load_state did not restore params bitwise"
    accelerator.end_training()
    return {
        "model": "bert " + _describe(config),
        "n_params": n_params,
        "global_batch": batch_size,
        "seq_len": seq_len,
        "steps": steps,
        # step 0 = trace + compile + first execution
        "compile_plus_first_step_s": round(run.first_s, 3),
        "median_step_ms": run.median_step_ms(),
        "loss_first": losses[0],
        "loss_last": losses[-1],
        "compiles_after_first_step": run.late_compiles,
        "checkpoint_roundtrip_bitwise": True,
        "peak_bytes_in_use": _peak_bytes(),
    }


def long_preset(seq_len: int = 2048, n_layers: int = 8):
    """The repo's 1024-wide Llama-shaped preset (``benchmarks/serving/run.py``
    ``run_bench_serving``): dim 1024, 16 query / 8 kv heads, vocab 32000."""
    from accelerate_tpu.models import LlamaConfig

    return LlamaConfig(vocab_size=32000, dim=1024, n_layers=n_layers, n_heads=16,
                       n_kv_heads=8, max_seq_len=seq_len)


def _llama_leg(config, batch, impl, seed, steps):
    import jax
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import init_llama, llama_loss, llama_shard_rules

    _reset_state()
    accelerator = Accelerator(mixed_precision="bf16", rng_seed=seed)
    params, optimizer = accelerator.prepare(
        init_llama(config, jax.random.PRNGKey(seed)), optax.adamw(1e-4),
        shard_rules=llama_shard_rules(),
    )
    step = accelerator.prepare_train_step(
        # remat: at S=2048 the einsum leg would otherwise keep eight layers of
        # [B, H, S, S] scores for its backward
        lambda p, b: llama_loss(p, b, config, attention_impl=impl, remat=True),
        optimizer, compute_grad_norm=True,
    )
    lowered = step.lower(params, optimizer.opt_state, batch).as_text()
    run = _timed_steps(step, params, optimizer.opt_state, [batch], steps)
    accelerator.end_training()
    return lowered, run


def phase_train_long(config=None, *, batch=4, seq_len=2048, steps=4, seed=0,
                     expect_kernel="tpu_custom_call") -> dict:
    """One causal-LM leg past ``ATTN_CROSSOVER_S``, so ``impl="auto"`` really
    takes the flash fwd+bwd kernels; step 0 agrees with ``impl="xla"`` on the
    same batch and weights. ``expect_kernel`` is what a Mosaic kernel looks
    like in the lowered step (``None``: the interpreted kernels of the CPU
    tests leave no such mark)."""
    import jax.numpy as jnp

    from accelerate_tpu.ops.attention import ATTN_CROSSOVER_S

    config = config or long_preset(seq_len)
    assert seq_len >= max(ATTN_CROSSOVER_S.values()), (seq_len, ATTN_CROSSOVER_S)
    ids = np.random.default_rng(seed).integers(0, config.vocab_size, (batch, seq_len))
    data = {"input_ids": jnp.asarray(ids, jnp.int32)}

    lowered, run = _llama_leg(config, data, "auto", seed, steps + 1)
    ref_lowered, ref = _llama_leg(config, data, "xla", seed, 1)
    if expect_kernel is not None:
        assert expect_kernel in lowered, f"impl='auto' did not lower to {expect_kernel}"
        assert expect_kernel not in ref_lowered, "the reference leg is meant to be the einsum path"

    losses = run.losses()
    first, ref_first = run.metrics[0], ref.metrics[0]
    loss_rel = abs(first["loss"] - ref_first["loss"]) / abs(ref_first["loss"])
    norm_rel = abs(first["grad_norm"] - ref_first["grad_norm"]) / abs(ref_first["grad_norm"])
    assert loss_rel <= LONG_LOSS_RTOL, f"step-0 loss flash vs xla: rel {loss_rel}"
    assert norm_rel <= LONG_GRAD_NORM_RTOL, f"step-0 grad norm flash vs xla: rel {norm_rel}"
    return {
        "model": "llama " + _describe(config),
        "batch": batch,
        "seq_len": seq_len,
        "steps": steps,
        "kernel_in_lowered_step": expect_kernel,
        "compile_plus_first_step_s": round(run.first_s, 3),
        "median_step_ms": run.median_step_ms(),
        "losses": losses,
        "step0_loss_rel_vs_xla": loss_rel,
        "step0_grad_norm_rel_vs_xla": norm_rel,
        "tolerances": {"loss": LONG_LOSS_RTOL, "grad_norm": LONG_GRAD_NORM_RTOL},
        "compiles_after_first_step": run.late_compiles,
        "peak_bytes_in_use": _peak_bytes(),
    }


def _clear_prefix(params, config, prompt, max_new):
    """B=1 ``greedy_generate`` of ``prompt``, and how many of its new tokens
    come before the first near-tie: a teacher-forced forward over the
    reference's own output gives each step's logits, and a step whose top-2
    gap is under ``SERVE_TIE_MARGIN`` deviations ends the clear prefix."""
    import jax.numpy as jnp

    from accelerate_tpu.generation import greedy_generate
    from accelerate_tpu.models import llama_forward

    ref = np.asarray(greedy_generate(params, prompt[None], config, max_new_tokens=max_new)[0])
    logits = llama_forward(params, jnp.asarray(ref[None, :-1]), config, attention_impl="xla")
    logits = np.asarray(logits[0, len(prompt) - 1:].astype(jnp.float32))  # [max_new, V]
    top2 = np.sort(np.partition(logits, -2, axis=-1)[:, -2:], axis=-1)
    gaps = (top2[:, 1] - top2[:, 0]) / logits.std(axis=-1)
    near_ties = np.nonzero(gaps < SERVE_TIE_MARGIN)[0]
    return ref, int(near_ties[0]) if len(near_ties) else max_new, gaps


def phase_serve(config=None, *, seed=0, max_new=12, min_new=3, block_size=16, max_slots=8,
                prefill_buckets=(32, 64), blocks_per_seq=16,
                prompt_lens=(20, 40, 64, 150, 33, 90), shared_prefix=48,
                expect_kernel="tpu_custom_call") -> dict:
    """``ServingEngine`` in its default kernel mode: warm-up, then staggered
    requests — one prompt longer than the largest prefill bucket (multi-chunk
    prefill), one sharing a block-aligned prefix with an earlier one (prefix
    hit), one repeating an earlier block-aligned prompt whole (copy-on-write)
    — every output token-equal to B=1 ``greedy_generate``."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import init_llama
    from accelerate_tpu.serving import BucketLattice, RequestStatus, ServingEngine

    config = config or long_preset(seq_len=512)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), init_llama(config, jax.random.PRNGKey(seed))
    )
    lattice = BucketLattice(
        slot_buckets=(max_slots // 2, max_slots), block_buckets=(blocks_per_seq,),
        prefill_buckets=tuple(prefill_buckets),
    )
    engine = ServingEngine(
        params, config, num_blocks=max_slots * blocks_per_seq + 1, block_size=block_size,
        max_slots=max_slots, lattice=lattice,
    )
    t0 = time.perf_counter()
    warmed = engine.warmup()
    warmup_s = time.perf_counter() - t0
    key = np.zeros((2,), np.uint32)
    Sb, W = lattice.prefill_points()[-1]
    prefill_text = engine.prefill_fn.lower(
        params, engine.pool, np.zeros((1, Sb), np.int32), np.zeros((1, W), np.int32),
        np.int32(0), np.int32(0), key, np.int32(0),
    ).as_text()
    Bb, W = lattice.decode_points()[-1]
    decode_text = engine.decode_fn.lower(
        params, engine.pool, np.zeros((Bb,), np.int32), np.zeros((Bb, W), np.int32),
        np.zeros((Bb,), np.int32), np.zeros((Bb, 2), np.uint32), np.zeros((Bb,), np.int32),
    ).as_text()
    if expect_kernel is not None:
        assert expect_kernel in prefill_text, f"prefill did not lower to {expect_kernel}"
        assert expect_kernel in decode_text, f"decode did not lower to {expect_kernel}"
    assert max(prompt_lens) > prefill_buckets[-1], "no prompt runs a multi-chunk prefill"
    assert shared_prefix % block_size == 0 and prompt_lens[2] % block_size == 0

    # the workload, from the seed: each slot redraws its prompt until the
    # reference has `min_new` clear tokens, and is cut at its first near-tie
    rng = np.random.default_rng(seed)
    workload, redraws = [], 0

    def draw(length, prefix=None):
        nonlocal redraws
        for _ in range(16):
            prompt = rng.integers(0, config.vocab_size, (length,)).astype(np.int32)
            if prefix is not None:
                prompt[: len(prefix)] = prefix
            ref, clear, gaps = _clear_prefix(params, config, prompt, max_new)
            if clear >= min_new:
                workload.append((prompt, clear, ref[: length + clear], gaps))
                return prompt
            redraws += 1
        raise RuntimeError(f"no prompt of length {length} with {min_new} clear tokens in 16 draws")

    drawn = [draw(n) for n in prompt_lens]
    draw(prompt_lens[2], prefix=drawn[2][:shared_prefix])  # shares 3 full blocks
    workload.append(workload[2])  # the same block-aligned prompt again: full hit + COW

    t0 = time.perf_counter()
    requests = []
    for i, (prompt, n_new, _, _) in enumerate(workload):
        requests.append(engine.submit(prompt, n_new, rng_seed=i))
        engine.step()  # staggered: one engine step between arrivals
    engine.run()
    wall_s = time.perf_counter() - t0

    assert all(r.status is RequestStatus.FINISHED for r in requests), [r.status for r in requests]
    assert engine.jit_cache_sizes() == warmed, (engine.jit_cache_sizes(), warmed)
    stats = engine.stats()
    assert stats["prefill_tokens_saved"] >= shared_prefix and stats["cow_copies"] >= 1, stats
    for i, (request, (prompt, n_new, ref, gaps)) in enumerate(zip(requests, workload)):
        out = request.output_ids()
        if not np.array_equal(out, ref):
            at = int(np.nonzero(out != ref)[0][0])
            raise AssertionError(
                f"request {i} (prompt {len(prompt)}, {n_new} new): first differing index {at} "
                f"(engine {out[at]}, reference {ref[at]}); the reference's top-2 logit gap there "
                f"is {gaps[at - len(prompt)]:.4f} deviations (near-tie margin {SERVE_TIE_MARGIN})"
            )
    return {
        "model": "llama " + _describe(config),
        "requests": len(requests),
        "prompt_lens": [len(w[0]) for w in workload],
        "new_tokens": [w[1] for w in workload],
        "tokens_generated": int(sum(len(r.generated) for r in requests)),
        "redraws_for_near_ties": redraws,
        "min_top2_gap_deviations": float(min(w[3][: w[1]].min() for w in workload)),
        "outputs_equal_greedy_generate": True,
        "kernel_in_lowered_prefill_and_decode": expect_kernel,
        "jit_cache_frozen_after_warmup": True,
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(wall_s, 3),
        "prefill_tokens_saved": stats["prefill_tokens_saved"],
        "cow_copies": stats["cow_copies"],
        "prefill_calls": stats["prefill_calls"],
        "peak_bytes_in_use": _peak_bytes(),
    }


def _placement(tree):
    """(distinct devices holding a shard, bytes on the first device / total)."""
    import jax

    leaves = [x for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "addressable_shards")]
    devices = {s.device for x in leaves for s in x.addressable_shards}
    first = min(devices, key=lambda d: d.id)
    on_first = sum(s.data.nbytes for x in leaves for s in x.addressable_shards if s.device == first)
    return len(devices), on_first / max(sum(x.nbytes for x in leaves), 1)


def _mesh_leg(config, batch_size, seq_len, steps, seed, accelerator_kwargs):
    accelerator, params, optimizer, step, batches, _ = _bert_setup(
        config, batch_size, seq_len, seed, n_batches=4, accelerator_kwargs=accelerator_kwargs
    )
    t0 = time.perf_counter()
    compiled = step.lower(params, optimizer.opt_state, batches[0]).compile().as_text()
    compile_s = time.perf_counter() - t0  # the step below reuses this executable
    placed = {"params": _placement(params), "opt_state": _placement(optimizer.opt_state)}
    fused = bool(optimizer.fused_zero1)
    run = _timed_steps(step, params, optimizer.opt_state, batches, steps)
    accelerator.end_training()
    return {
        "losses": run.losses(),
        "collectives": sorted(
            c for c in ("reduce-scatter", "all-gather", "all-reduce") if c in compiled
        ),
        "placed": placed,
        "fused_zero1": fused,
        "compile_s": round(compile_s, 3),
        "first_step_s": round(run.first_s, 3),
        "median_step_ms": run.median_step_ms(),
        "peak_bytes_in_use": _peak_bytes(),
    }


def phase_multichip(config=None, *, n_devices=4, batch_size=64, seq_len=128, steps=8,
                    seed=0) -> dict:
    """The bert step of ``phase_train`` across ``n_devices`` chips, once as
    FSDP and once as DP with fused ZeRO-1, against device 0 alone on the same
    seed and global batch."""
    import jax

    from accelerate_tpu import DeepSpeedPlugin, ParallelismConfig
    from accelerate_tpu.models import BertConfig

    assert len(jax.devices()) >= n_devices, (len(jax.devices()), n_devices)
    config = config or BertConfig.base()
    args = (config, batch_size, seq_len, steps, seed)
    one = _mesh_leg(*args, {"parallelism_config": ParallelismConfig()})  # a 1-device mesh
    assert one["placed"]["params"][0] == 1, one["placed"]
    legs = {
        "fsdp": _mesh_leg(*args, {
            "parallelism_config": ParallelismConfig(dp_shard_size=n_devices)}),
        "dp_zero1": _mesh_leg(*args, {
            "parallelism_config": ParallelismConfig(dp_replicate_size=n_devices),
            "deepspeed_plugin": DeepSpeedPlugin(zero_stage=1)}),
    }
    assert legs["dp_zero1"]["fused_zero1"], "zero_stage=1 on a pure-DP mesh did not fuse"
    # 1/n of the state plus what is too small to shard
    share = (1.0 / n_devices, 1.0 / n_devices + 0.05)
    for name, leg in legs.items():
        devices, fraction = leg["placed"]["opt_state"]
        assert devices == n_devices, f"{name}: opt state on {devices} device(s)"
        assert share[0] <= fraction <= share[1], f"{name}: {fraction:.3f} of opt state on one chip"
        assert leg["placed"]["params"][0] == n_devices, f"{name}: params on one device"
        assert leg["collectives"], f"{name}: no collective in the compiled step"
        rel = [abs(a - b) / abs(b) for a, b in zip(leg["losses"], one["losses"])]
        leg["max_loss_rel_vs_one_device"] = max(rel)
        assert max(rel) <= MESH_LOSS_RTOL, f"{name}: loss diverged from one device: {rel}"
    fraction = legs["fsdp"]["placed"]["params"][1]
    assert share[0] <= fraction <= share[1], f"fsdp: {fraction:.3f} of params on one chip"
    return {
        "model": "bert " + _describe(config),
        "n_devices": n_devices, "global_batch": batch_size, "seq_len": seq_len, "steps": steps,
        "loss_rtol": MESH_LOSS_RTOL, "one_device": one, **legs,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the cross-chip phase and what it is compared with")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    device = device_record()
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(json.dumps({
            "ok": False, "device": device,
            "error": f"needs {args.chips} TPU chip(s); there is no CPU fallback",
        }))
        return 1
    from accelerate_tpu import native

    print(json.dumps({
        "jax_cache_dir": enable_jax_cache(),
        "host_data_path": "native" if native.is_native_available() else "numpy",
    }), flush=True)

    phases = (
        {"multichip": phase_multichip} if args.chips == 4
        else {"train": phase_train, "train_long": phase_train_long, "serve": phase_serve}
    )
    ok = True
    for name, phase in phases.items():
        t0 = time.perf_counter()
        try:
            result = {"phase": name, "ok": True, **phase(seed=args.seed)}
        except Exception as e:  # reported, and the run fails: the others still say what they find
            traceback.print_exc()
            result = {"phase": name, "ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
            ok = False
        result.update(phase_s=round(time.perf_counter() - t0, 1), device=device)
        print(json.dumps(result), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
