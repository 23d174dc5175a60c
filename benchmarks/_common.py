"""Shared plumbing for the measurement scripts (``bench.py``, ``chip_smoke.py``,
``benchmarks/*/run.py``): locate the repo, say which device the run is on and
refuse to measure on the wrong one, place JAX's compile cache, emit one JSON
line."""

from __future__ import annotations

import contextlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def device_record() -> dict:
    """The device this process runs on, as JAX reports it. Every printed
    result carries it, so a CPU run can never be read as a chip number."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def detect_backend() -> bool:
    """True on a TPU. False only where ``JAX_PLATFORMS=cpu`` asked for the CPU
    (tiny shapes that rehearse the plumbing; their timings are not device
    numbers). Any other platform is an error: a measurement script that finds
    no chip fails, it does not carry on somewhere else. Runs in this process —
    the process that measures is the one that holds the chip. Every
    measurement script starts here, so this is also where JAX's compile cache
    is placed (:func:`enable_jax_cache`)."""
    enable_jax_cache()
    platform = device_record()["platform"]
    if platform == "tpu":
        return True
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return False
    raise RuntimeError(
        f"no TPU: JAX's default platform here is {platform!r}. Set JAX_PLATFORMS=cpu "
        "to rehearse on the CPU at tiny shapes; device numbers come only from a chip."
    )


def enable_jax_cache() -> "str | None":
    """Place JAX's persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has taken it from the
    environment and nothing is changed. Otherwise, on a chip, it is the one
    fixed, git-ignored directory of this checkout: the path is part of the
    cache's key, so a directory that moves (a temp dir, a pid, a time) never
    hits. On the CPU no cache is placed and None comes back: a rehearsal
    compiles in seconds, and entries an earlier process left there made the
    serving benchmark's own compile counts (its ``zero_recompiles``) depend
    on what had run before it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def jax_cache_off():
    """JAX's persistent compilation cache switched off (not moved) around a
    compile whose seconds are the metric: a hit would time a file read."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # the decision to use the cache is itself cached
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


_FINGERPRINT = None


def env_fingerprint() -> dict:
    """THE environment fingerprint stamped into every bench payload (key
    ``env``): git sha, host, platform, device kind/count, jax/jaxlib versions,
    python, nproc. The regression sentinel (``telemetry.regress``) groups
    payloads by this and REFUSES cross-environment comparisons — a v5 number
    vs a CPU number is not a regression, it is a different machine. Cached per
    process; device fields stay None in a process that never imported jax (a
    parent that only sequences children must stay off the chip they need)."""
    global _FINGERPRINT
    if _FINGERPRINT is None:
        import platform
        import subprocess

        fp = {
            "git_sha": None,
            "host": platform.node(),
            "python": platform.python_version(),
            "nproc": os.cpu_count(),
            "jax": None,
            "jaxlib": None,
            "platform": None,
            "device_kind": None,
            "device_count": None,
        }
        try:
            out = subprocess.run(
                ["git", "-C", REPO, "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
            )
            fp["git_sha"] = out.stdout.strip() or None
        except Exception:
            pass
        if "jax" in sys.modules:
            import jax
            import jaxlib

            device = device_record()
            fp["jax"] = jax.__version__
            fp["jaxlib"] = getattr(jaxlib, "__version__", None)
            fp["platform"] = device["platform"]
            fp["device_kind"] = device["kind"]
            fp["device_count"] = device["count"]
        _FINGERPRINT = fp
    return dict(_FINGERPRINT)


def emit(entry: dict) -> None:
    entry = dict(entry)
    entry.setdefault("env", env_fingerprint())
    print(json.dumps(entry), flush=True)


# THE percentile implementation lives in telemetry.metrics (nearest-rank,
# shared with the report CLI and the /metrics histogram plane) — the benches
# re-export it instead of carrying a private variant, so a bench's p99 and
# the report's p99 of the same numbers can never disagree.
from accelerate_tpu.telemetry.metrics import percentile  # noqa: E402,F401
