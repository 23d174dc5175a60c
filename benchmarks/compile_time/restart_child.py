"""One process generation of the restart bench: build the real stack, do the
first unit of useful work, report how long that took from process entry.

Two modes, matching the two recovery paths the compile cache exists for:

- ``train``: Accelerator + prepared jitted train step (the elastic
  supervisor's respawn path) — reports ``restart_to_first_step_s``, the
  wall time from entry to the first completed optimizer step;
- ``serve``: a ``ReplicaSpec``-built serving engine (the router's
  replacement-replica path) — reports ``boot_to_first_token_s``, entry to
  the first token of the first request (warmup included: a replica is not
  useful until its lattice is compiled).

The parent (``run.py``) runs each mode twice against the same cache
directory — generation 0 cold (populates), generation 1 warm (loads) — and
reads the ``compile_cache`` telemetry records to prove the warm leg actually
hit instead of quietly recompiling.
"""

import argparse
import json
import os
import sys
import time

_T_ENTRY = time.monotonic()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="restart_child")
    parser.add_argument("--mode", choices=("train", "serve"), required=True)
    parser.add_argument("--cache-dir", default="")
    parser.add_argument("--telemetry-dir", default="")
    parser.add_argument("--generation", type=int, default=0)
    args = parser.parse_args(argv)

    if args.cache_dir:
        os.environ["ACCELERATE_COMPILE_CACHE_DIR"] = args.cache_dir
    if args.generation:
        os.environ["ACCELERATE_RESTART_GENERATION"] = str(args.generation)
    if args.telemetry_dir:
        os.environ["ACCELERATE_TELEMETRY"] = "1"
        os.environ["ACCELERATE_TELEMETRY_DIR"] = args.telemetry_dir

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

    import jax  # noqa: E402  (env must be set before backends init)

    from benchmarks._common import detect_backend, device_record
    from accelerate_tpu.telemetry import events as tel

    detect_backend()  # fails where there is no chip and no CPU was asked for
    # cold must be cold: a hit in JAX's persistent cache would hide the
    # compile the repo's own AOT cache (--cache-dir) is measured against
    jax.config.update("jax_enable_compilation_cache", False)

    # the serve path builds an engine without an Accelerator, which is what
    # normally honors the env kill switch — do it explicitly here so the
    # compile_cache records land in this leg's telemetry dir either way
    tel.maybe_enable_from_env()

    out = {"mode": args.mode, "generation": args.generation, "device": device_record()}
    if args.mode == "train":
        import numpy as np
        import optax

        import jax.numpy as jnp
        from accelerate_tpu import Accelerator

        acc = Accelerator()
        # a few chained matmuls so the step's XLA compile is a real cost the
        # warm leg visibly skips (a 2-matrix toy compiles in noise)
        params = {
            "w1": jnp.zeros((64, 128), jnp.float32),
            "w2": jnp.zeros((128, 128), jnp.float32),
            "w3": jnp.zeros((128, 8), jnp.float32),
        }
        params, opt = acc.prepare(params, optax.adam(1e-2))

        def loss_fn(p, batch):
            h = jnp.tanh(batch["x"] @ p["w1"])
            h = jnp.tanh(h @ p["w2"])
            return jnp.mean((h @ p["w3"]) ** 2)

        step = acc.prepare_train_step(loss_fn, opt)
        batch = {"x": jnp.asarray(np.ones((32, 64), np.float32))}
        params, opt_state, metrics = step(params, opt.opt_state, batch)
        jax.block_until_ready(params)
        out["restart_to_first_step_s"] = round(time.monotonic() - _T_ENTRY, 4)
        out["loss"] = float(metrics["loss"])
        acc.end_training()
    else:
        import numpy as np

        from accelerate_tpu.serving.replica import ReplicaSpec

        spec = ReplicaSpec(
            model=dict(
                vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                ffn_dim=64, max_seq_len=128,
            ),
            num_blocks=17,
            block_size=8,
            max_slots=2,
            max_blocks_per_seq=4,
            slot_buckets=(1, 2),
            block_buckets=(4,),
            prefill_buckets=(16,),
            param_dtype="float32",
            compile_cache_dir=args.cache_dir or None,
        )
        engine = spec.build_engine()
        engine.warmup()
        req = engine.submit(np.arange(1, 9, dtype=np.int32), 3, rng_seed=0)
        while not req.generated:
            engine.step()
        out["boot_to_first_token_s"] = round(time.monotonic() - _T_ENTRY, 4)
        out["first_token"] = int(req.generated[0])
        out["cache_stats"] = engine.cache_stats

    tel.hard_flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
