"""Attention kernel benchmark grid (`make bench-attn`).

The measurement behind ``ops.attention.ATTN_CROSSOVER_S``: fwd+bwd step time
for every (impl × seq × dtype × sparsity) cell, reported as µs/token and as
achieved FLOP/s against the chip's roofline (``telemetry/perf.py`` peaks).
Sparsity legs (dense / causal / sliding-window) matter because the in-tree
flash kernel's block lattice SKIPS fully-masked tiles — its useful-FLOP rate
holds while the einsum path still materializes (and masks) every score.

A second leg times the fp8-vs-bf16 llama train step (``dtype_recipe="fp8"``
routing QKV/O + MLP through ``ops.fp8.fp8_dot``) — the "kernel-dominated
train step" claim needs both the attention kernel AND the matmul recipe
measured on the same chip. Step-time wins only materialize on fp8-capable
MXUs (v5p+); on CPU/v5e the leg is a parity + plumbing check and the ratio
reads > 1.

Emits one JSON line (bench.py conventions). The ``guarded`` block feeds
``telemetry/regress.py`` (``*attn_kernel*`` / ``*fp8*step*`` lower-is-better,
``*mfu*`` higher-is-better specs).

```bash
python benchmarks/attention/run.py --steps 5
```
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _common import detect_backend, emit


def _band_fraction(s: int, window) -> float:
    """Fraction of the S×S score matrix a mask leaves active."""
    if window is None:
        return 1.0
    w = min(window, s)
    return (w * s - w * (w - 1) / 2) / float(s * s)


def _attention_flops(b, h, s, d, active_fraction: float) -> float:
    """Useful fwd+bwd attention FLOPs per step: fwd = QKᵀ + PV (4·B·H·S²·D),
    bwd re-forms scores and produces dQ/dK/dV (≈2.5× fwd)."""
    return 3.5 * 4.0 * b * h * s * s * d * active_fraction


def _time_loop(fn, args, steps: int) -> float:
    import jax

    out = fn(*args)  # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def run_bench_attention(on_tpu: bool, steps: int = None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.attention import dot_product_attention
    from accelerate_tpu.telemetry.perf import peaks_for_device

    if on_tpu:
        b, h, hkv, d = 8, 12, 6, 64
        seqs = (512, 1024, 2048)
        dtypes = (("bf16", jnp.bfloat16), ("f32", jnp.float32))
        impls = ("xla", "flash")
        steps = steps or 10
    else:
        # CPU-shaped: the xla path only (the Pallas interpreter is a
        # correctness tool, ~1000× off any perf signal) — the grid still
        # exercises every sparsity leg so regressions in the einsum path and
        # the mask plumbing are caught per-environment
        b, h, hkv, d = 2, 4, 2, 64
        seqs = (256, 512)
        dtypes = (("f32", jnp.float32),)
        impls = ("xla",)
        steps = steps or 3

    peaks = peaks_for_device()
    sparsities = lambda s: (
        ("dense", False, None),
        ("causal", True, None),
        ("window", True, max(s // 4, 128)),
    )

    def make_step(impl, causal, window):
        def loss(q, k, v):
            out = dot_product_attention(
                q, k, v, causal=causal, window=window, impl=impl
            )
            return jnp.sum(out.astype(jnp.float32) ** 2)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    grid = []
    for s in seqs:
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        for dname, dtype in dtypes:
            q = jax.random.normal(keys[0], (b, s, h, d), dtype)
            k = jax.random.normal(keys[1], (b, s, hkv, d), dtype)
            v = jax.random.normal(keys[2], (b, s, hkv, d), dtype)
            for sname, causal, window in sparsities(s):
                for impl in impls:
                    entry = {
                        "impl": impl,
                        "seq": s,
                        "dtype": dname,
                        "sparsity": sname,
                    }
                    try:
                        sec = _time_loop(
                            make_step(impl, causal, window), (q, k, v), steps
                        )
                    except Exception as e:
                        entry["error"] = f"{type(e).__name__}: {str(e)[:120]}"
                        grid.append(entry)
                        continue
                    frac = _band_fraction(s, window) * (
                        (s + 1) / (2.0 * s) if causal and window is None else 1.0
                    )
                    flops = _attention_flops(b, h, s, d, frac)
                    entry["us_per_token"] = round(sec / (b * s) * 1e6, 3)
                    entry["achieved_tflops"] = round(flops / sec / 1e12, 4)
                    # no peak off-TPU: the field is absent there, not nominal
                    if peaks is not None:
                        entry["fraction_of_peak"] = round(flops / sec / peaks.flops, 4)
                    grid.append(entry)

    ok = [g for g in grid if "us_per_token" in g]
    if not ok:
        raise RuntimeError(f"every attention grid cell failed: {grid}")
    # the headline cell: best impl at the largest causal leg, bench dtype
    # (bf16 on TPU, f32 on CPU) — the regime training actually runs in
    s_top = max(g["seq"] for g in ok)
    head_pool = [
        g for g in ok
        if g["seq"] == s_top and g["sparsity"] == "causal" and g["dtype"] == dtypes[0][0]
    ] or ok
    best = min(head_pool, key=lambda g: g["us_per_token"])
    best_mfu = max(g["fraction_of_peak"] for g in ok) if peaks is not None else None

    fp8_leg = _fp8_train_step_leg(on_tpu)

    out = {
        "metric": f"attention fwd+bwd µs/token (seq {best['seq']}, {best['impl']})",
        "value": best["us_per_token"],
        "unit": "us/token",
        "best": best,
        "grid": grid,
        "peak_flops": peaks.flops if peaks is not None else None,
        "shape": {"batch": b, "heads": h, "kv_heads": hkv, "head_dim": d},
        "steps": steps,
        "fp8_train_step": fp8_leg,
        # regression-guarded (telemetry/regress.py: *attn_kernel* and
        # *fp8*step* lower-is-better, *mfu* higher-is-better)
        "guarded": {
            "attn_kernel_us_per_token": best["us_per_token"],
            "fp8_step_ms": fp8_leg["fp8_step_ms"],
            "attn_mfu_best_fraction": best_mfu,
        },
    }
    return out


def _fp8_train_step_leg(on_tpu: bool, steps: int = None) -> dict:
    """fp8-vs-bf16 llama train step: the ``dtype_recipe="fp8"`` knob routes
    QKV/O + MLP matmuls through ``fp8_dot``; the bf16 baseline runs the same
    step with bf16-cast params. Reports steady-state ms and final-loss
    relative delta (the parity envelope)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from accelerate_tpu.models.transformer import LlamaConfig, init_llama, llama_loss
    from accelerate_tpu.ops.fp8 import make_fp8_optimizer

    if on_tpu:
        base = LlamaConfig(vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
                           n_kv_heads=8, max_seq_len=1024, unroll_layers=False)
        bs, seq = 4, 1024
        steps = steps or 10
    else:
        base = LlamaConfig.tiny()
        bs, seq = 2, 128
        steps = steps or 3

    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, base.vocab_size, (bs, seq)), jnp.int32
    )
    batch = {"input_ids": ids}

    def run(recipe):
        cfg = dataclasses.replace(base, dtype_recipe=recipe)
        params = init_llama(cfg, jax.random.PRNGKey(0))
        if recipe is None:
            params = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), params
            )
            tx = optax.sgd(1e-3)
        else:
            # meta leaves are replaced, not optimized (the same partition the
            # accelerator installs for mixed_precision="fp8")
            tx = make_fp8_optimizer(optax.sgd(1e-3), params)
        state = tx.init(params)

        @jax.jit
        def step(p, s, b):
            loss, grads = jax.value_and_grad(llama_loss)(p, b, cfg)
            updates, s = tx.update(grads, s, p)
            return optax.apply_updates(p, updates), s, loss

        sec = _time_loop(step, (params, state, batch), steps)
        _, _, loss = step(params, state, batch)
        return sec * 1e3, float(np.asarray(loss))

    bf16_ms, bf16_loss = run(None)
    fp8_ms, fp8_loss = run("fp8")
    return {
        "bf16_step_ms": round(bf16_ms, 3),
        "fp8_step_ms": round(fp8_ms, 3),
        "fp8_over_bf16": round(fp8_ms / bf16_ms, 3),
        "loss_rel_delta": round(abs(fp8_loss - bf16_loss) / max(abs(bf16_loss), 1e-9), 5),
        "seq": seq,
        "batch": bs,
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=None,
                    help="timed iterations per grid cell (default 10 TPU / 3 CPU)")
    args = ap.parse_args()
    emit(run_bench_attention(on_tpu=detect_backend(), steps=args.steps))
