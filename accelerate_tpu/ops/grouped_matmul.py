"""Grouped matrix multiplication: rows sorted by group, one weight matrix a group.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G])`` multiplies rows
``offsets[g] : offsets[g + 1]`` of ``lhs`` by ``rhs[g]``. It is what an expert
layer runs after it has sorted its (token, expert) pairs by expert
(``parallel/moe.py``): no capacity, no padding to a fixed number of rows an
expert, no one-hot dispatch tensor. ``sum(group_sizes)`` may be less than
``M`` (the pairs that landed on experts held elsewhere sort last); the rows
past the last group come back unspecified, and the caller masks them.

The same split as the paged attention kernels (``ops/flash_attention.py``): on
the TPU a Pallas kernel, named ``moe_gmm`` in a device trace; everywhere else
``jax.lax.ragged_dot``; ``ACCELERATE_PAGED_KERNEL=interpret`` drives the
kernel's dataflow through the Pallas interpreter on any backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import paged_kernel_mode

# Rows of `lhs` a grid step multiplies: the MXU's height. A step's time is the
# DMA of its weight tile whether 2 or 128 of the rows belong to the group.
_TILE_M = 128
# Bytes of a grid step's weight tile `[K, tn]`; it is double buffered beside
# the `[tm, K]` rows, inside the 16 MB of VMEM a kernel may scope on a v5e.
_WEIGHT_TILE_BYTES = 2 * 1024 * 1024


def _tile_n(K: int, N: int, itemsize: int) -> int:
    """Columns of a weight tile: the most multiples of 128 that divide ``N``
    and keep ``[K, tn]`` inside :data:`_WEIGHT_TILE_BYTES`; all of ``N`` where
    it has no such divisor (small test shapes)."""
    best = None
    for tn in range(128, N + 1, 128):
        if N % tn == 0 and K * tn * itemsize <= _WEIGHT_TILE_BYTES:
            best = tn
    return best or N


def _visits(group_sizes, M: int, tm: int):
    """The kernel's walk: ``(offsets [G+1], group_of_visit [V], tile_of_visit
    [V], visits)``. Group ``g`` visits every ``tm``-row tile that holds one of
    its rows, groups in order, so a tile's visits are consecutive (its output
    block stays in VMEM between them); ``V = M/tm + G - 1`` bounds their
    number, ``visits`` is how many there are."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first_tile = offsets[:-1] // tm
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    V = M // tm + G - 1
    group_of_visit = jnp.repeat(jnp.arange(G, dtype=jnp.int32), n_tiles, total_repeat_length=V)
    before = jnp.cumsum(n_tiles) - n_tiles  # visits of earlier groups
    rank = jnp.arange(V, dtype=jnp.int32) - before[group_of_visit]
    tile_of_visit = jnp.minimum(first_tile[group_of_visit] + rank, M // tm - 1)
    # at least one visit: with no row in any group it is an empty group's, and stores zeros
    return offsets, group_of_visit, tile_of_visit.astype(jnp.int32), jnp.maximum(jnp.sum(n_tiles), 1)


def _gmm_kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref, *, tm: int):
    """One visit: the tile's ``[tm, K]`` rows times the group's ``[K, tn]``
    weight tile, f32 accumulate; only the rows of the tile that belong to the
    group are stored, the others keep what earlier visits of the tile stored
    (zero on its first visit)."""
    from jax.experimental import pallas as pl  # deferred with pallas_call's

    i = pl.program_id(1)
    g, t = group_ref[i], tile_ref[i]
    row = t * tm + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    first_visit = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)
    product = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)
    kept = jnp.where(first_visit, jnp.zeros_like(product), out_ref[...])
    out_ref[...] = jnp.where(mine, product, kept)


def grouped_matmul_kernel(lhs, rhs, group_sizes, *, interpret: bool = False):
    """The Pallas kernel (``name="moe_gmm"``): grid ``(N / tn, visits)``, the
    visits innermost and as many as the groups' rows need (a run-time value,
    like the paged decode kernel's grid). A visit streams one ``[K, tn]``
    weight tile; a group that lies inside one row tile streams each of its
    weights once."""
    from jax.experimental import pallas as pl_  # deferred: CPU-only installs
    from jax.experimental.pallas import tpu as pltpu

    M, K = lhs.shape
    G, _, N = rhs.shape
    tm = min(_TILE_M, -(-M // 8) * 8)
    padded = -(-M // tm) * tm
    if padded != M:
        lhs = jnp.pad(lhs, ((0, padded - M), (0, 0)))
    tn = _tile_n(K, N, jnp.dtype(rhs.dtype).itemsize)
    offsets, group_of_visit, tile_of_visit, visits = _visits(group_sizes, padded, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N // tn, visits),
        in_specs=[
            pl_.BlockSpec((tm, K), lambda n, i, offsets, group, tile: (tile[i], 0)),
            pl_.BlockSpec((None, K, tn), lambda n, i, offsets, group, tile: (group[i], 0, n)),
        ],
        out_specs=pl_.BlockSpec((tm, tn), lambda n, i, offsets, group, tile: (tile[i], n)),
    )
    out = pl_.pallas_call(
        partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(offsets, group_of_visit, tile_of_visit, lhs, rhs)
    return out[:M]


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [M, K]`` (rows sorted by group) times ``rhs [G, K, N]``, group
    ``g`` being the next ``group_sizes[g]`` rows; ``[M, N]`` in ``lhs``'s
    dtype, accumulated in float32. Rows past ``sum(group_sizes)`` are
    unspecified. Dispatch as in the module docstring."""
    interpret = paged_kernel_mode() == "interpret"
    if interpret or jax.default_backend() == "tpu":
        return grouped_matmul_kernel(lhs, rhs, group_sizes, interpret=interpret)
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes.astype(jnp.int32), preferred_element_type=jnp.float32
    ).astype(lhs.dtype)
