"""``accelerate-tpu env`` — platform/config diagnostic dump (reference ``commands/env.py``)."""

from __future__ import annotations

import argparse
import os
import platform

from .config import resolve_config_file


def _jax_facts() -> dict:
    """JAX backend facts, read in this process. A backend that fails to start
    becomes one single-line field with the reason: the diagnostic still
    prints everything else."""
    try:
        import jax

        return {
            "JAX version": jax.__version__,
            "JAX backend": jax.default_backend(),
            "JAX device count": str(jax.device_count()),
            "JAX local devices": ", ".join(str(d) for d in jax.local_devices()[:8]),
            "JAX process count": str(jax.process_count()),
        }
    except Exception as e:  # the diagnostic must print whatever else it knows
        detail = str(e).strip().splitlines()
        return {"JAX": f"unavailable ({type(e).__name__}: {detail[-1][:300] if detail else ''})"}


def env_command(args) -> int:
    import numpy as np

    import accelerate_tpu

    lines = {
        "`accelerate-tpu` version": accelerate_tpu.__version__,
        "Platform": platform.platform(),
        "Python version": platform.python_version(),
        "Numpy version": np.__version__,
    }
    lines.update(_jax_facts())
    for mod in ("flax", "optax", "orbax.checkpoint", "torch", "transformers"):
        try:
            import importlib

            m = importlib.import_module(mod)
            lines[f"{mod} version"] = getattr(m, "__version__", "unknown")
        except Exception:
            lines[f"{mod} version"] = "not installed"
    accelerate_env = {k: v for k, v in os.environ.items()
                      if k.startswith(("ACCELERATE_", "PARALLELISM_CONFIG_", "JAX_", "XLA_"))}
    lines["Environment variables"] = ""

    print("\nCopy-and-paste the text below in your GitHub issue\n")
    for k, v in lines.items():
        print(f"- {k}: {v}")
    for k, v in sorted(accelerate_env.items()):
        print(f"  - {k}={v}")
    path = resolve_config_file(getattr(args, "config_file", None))
    print(f"- Config file: {path or 'not found'}")
    if path and os.path.isfile(path):
        with open(path) as f:
            for line in f.read().splitlines():
                print(f"  {line}")
    return 0


def register_parser(subparsers) -> argparse.ArgumentParser:
    p = subparsers.add_parser("env", help="Print environment diagnostics")
    p.add_argument("--config_file", default=None)
    p.set_defaults(func=env_command)
    return p
