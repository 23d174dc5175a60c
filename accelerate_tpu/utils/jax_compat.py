"""Host-side wire collectives every caller goes through.

Written for the one installed JAX (0.9): ``jax.shard_map``,
``jax.distributed.is_initialized`` and the gloo flag are called directly at
their call sites. What stays here are the two ``multihost_utils`` wrappers,
because they do something the library calls do not: they feed the per-rank
collective schedule (the jaxlint R4 runtime cross-check) and hand back a host
array of the caller's dtype.
"""

from __future__ import annotations


def broadcast_one_to_all(x, is_source: bool):
    """``multihost_utils.broadcast_one_to_all`` as a host array of the input's
    dtype (raw-bytes broadcasts must come back as bytes), recorded in the
    per-rank collective schedule."""
    import numpy as np
    from jax.experimental import multihost_utils

    from ..telemetry import flight_recorder as _flight

    x = np.asarray(x)
    # every wire collective feeds the per-rank schedule fingerprint (the
    # jaxlint R4 runtime cross-check) — here, not only in operations.py,
    # because data_loader and friends call these wrappers directly. The
    # "wire:" prefix separates leaf-level entries from op-level ones (an
    # operations.py gather logs both; the sequence stays rank-consistent).
    _flight.record_collective("wire:broadcast_one_to_all", f"{x.shape}/{x.dtype}")
    out = np.asarray(multihost_utils.broadcast_one_to_all(x, is_source=is_source))
    if out.dtype != x.dtype:
        out = out.astype(x.dtype)
    return out


def process_allgather(x, tiled: bool = False):
    """``multihost_utils.process_allgather`` as a host array of the input's
    dtype, recorded like :func:`broadcast_one_to_all`."""
    import numpy as np
    from jax.experimental import multihost_utils

    from ..telemetry import flight_recorder as _flight

    in_dtype = np.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype
    _flight.record_collective(
        "wire:process_allgather", f"{getattr(x, 'shape', ())}/{in_dtype}"
    )
    out = np.asarray(multihost_utils.process_allgather(x, tiled=tiled))
    if out.dtype != in_dtype:
        out = out.astype(in_dtype)
    return out
