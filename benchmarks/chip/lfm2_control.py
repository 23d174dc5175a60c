"""Controls of ``lfm2-24b.agent-sat``'s correctness check: the cell run as the
benchmark runs it (``runners/serve.run``, the cell's own traffic, engine
settings, limits), with one part of the ENGINE held to a lower precision than
the cell states. The verdict is the runner's own, unchanged: ``serve._check``
teacher-forces the float32 reference on the TRUE weights over what the
degraded engine emitted and holds it to the cell's ``check``. A control has to
come out NOT correct by at least one of the cell's limits; a sound run of the
same seed has to come out correct. Nothing here is imported by a run of the
benchmark.

    python3 benchmarks/chip/lfm2_control.py --control weights_f8 --seed 7

prints the result line of that run (``correct``, ``compared``: each number
beside its limit). ``--control none`` is the sound run.

A control rounds to float8's precision and keeps the exponent:
``jax.lax.reduce_precision(x, 8, 3)``, e4m3's 3 bits of mantissa at bfloat16's
exponent range, which is what float8 under an ideal per-value scale keeps.
Rounded INSIDE the step programs (``Lfm2Config.paged_forward`` wrapped), so the
true weights stay the one copy on the chip and the reference reads them.
``reduce_precision(x, 4, 3)``, e4m3's exponent too, is another fault: it
flushes everything under 2**-6 to zero, more than half of these weights
(drawn at about 0.02), and is what PR 35's first control measured (6.2
deviations, no agreement at all).

- ``weights_f8``: every matrix and scale the engine multiplies by. The nearest
  precision below the one the configuration states, for the whole model.
- ``router_f8``: the routers' kernels and selection biases alone (8 x 2048 x 64
  numbers): only who is chosen and how much they count moves.
- ``cache_f8``: what a step leaves in the pool, the attention layers' keys and
  values and the conv layers' state rows: what a float8 cache would hold.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.chip import harness, models  # noqa: E402
from benchmarks.chip.runners import serve  # noqa: E402

CELL = "lfm2-24b.agent-sat"


def _round8(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jax.lax.reduce_precision(x, 8, 3) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def _routers_rounded(params):
    def layer(lp):
        if "experts" not in lp:
            return lp
        experts = lp["experts"]
        return {**lp, "experts": {**experts, **_round8(
            {"router": experts["router"], "expert_bias": experts["expert_bias"]})}}

    return {**params, "layers": tuple(layer(lp) for lp in params["layers"])}


def _same(tree):
    return tree


#: control -> (what the step programs do to the weights on the way in, to the pool on the way out)
CONTROLS = {
    "none": (_same, _same),
    "weights_f8": (_round8, _same),
    "router_f8": (_routers_rounded, _same),
    "cache_f8": (_same, _round8),
}


def degraded(cfg, control: str):
    """``cfg`` (a frozen dataclass with ``paged_forward``) with its paged
    forward under ``control``; every field, ``state_shape`` and the rest are
    the config's own."""
    weights, pool_out = CONTROLS[control]

    class Control(type(cfg)):
        def paged_forward(self, params, ids, pool, *rest, **named):
            logits, pool, counts = super().paged_forward(weights(params), ids, pool, *rest, **named)
            return logits, pool_out(pool), counts

    Control.__name__ = f"{type(cfg).__name__}_{control}"
    return Control(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)})


@contextlib.contextmanager
def engine_under(control: str):
    """While it lasts, every kind the serve runner looks up builds its
    program's config under ``control``; the reference, the weights and the
    comparison are the kind's and the runner's own."""
    kind_of = models.kind_of

    def controlled(config, root):
        kind = kind_of(config, root)  # a new one a call: changing it changes no other run's
        program_config = kind["program_config"]
        kind["program_config"] = lambda *args, **kwargs: degraded(
            program_config(*args, **kwargs), control)
        return kind

    models.kind_of = controlled
    try:
        yield
    finally:
        models.kind_of = kind_of


def run(cell, control: str, *, seed: int, seconds: float, allow_cpu: bool = False):
    """One run of ``cell`` with the engine under ``control``: the runner's record."""
    with engine_under(control):
        return serve.run(cell, seed=seed, seconds=seconds, trace=False,
                         process_t0=time.perf_counter(), allow_cpu=allow_cpu)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--control", choices=sorted(CONTROLS), required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=51.0)
    args = parser.parse_args(argv)
    harness.enable_jax_cache()
    cell = harness.load_cell(CELL)
    record = run(cell, args.control, seed=args.seed, seconds=args.seconds)
    line = harness.result_line(cell, record, traced=False)
    print(json.dumps({"control": args.control, **line}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
