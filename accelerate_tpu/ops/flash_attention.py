"""Flash attention for TPU: in-tree blocked streaming Pallas kernel.

The reference reaches flash/SDPA CUDA kernels through transformers + torch
(SURVEY.md §2.3 "flash attention / SDPA kernels"). Earlier rounds wrapped the
stock JAX kernel (``jax.experimental.pallas.ops.tpu.flash_attention``); that
wrapper materialized repeated KV in HBM for GQA, supported no sliding-window
or block-sparse masking, and had no interpret mode, so tier-1 never exercised
its dataflow. This module replaces it with an in-tree blocked online-softmax
kernel (fwd + custom_vjp bwd with recompute-from-logsumexp):

- grid ``(B·H, q_blocks, kv_blocks)`` with the kv axis innermost; f32 online
  softmax carried in VMEM scratch across kv steps;
- **in-kernel GQA**: the k/v BlockSpec index maps address the kv-head pool
  directly (``g → b·Hkv + h // groups``), so repeated KV never exists in HBM;
- a **block-sparse mask lattice**: causal, sliding-window and segment/packing
  masks are collapsed into a per-``(q_block, kv_block)`` skip map built at
  trace time (scalar-prefetch, like the paged kernels' block tables). The kv
  index map *clamps* skipped steps onto the previous active block — a repeated
  block index elides the DMA — and ``pl.when`` skips their compute, so fully
  masked blocks are never streamed: long-context cost scales with the lattice
  density, not S².

Dispatch follows the same env contract as the paged serving kernels
(:func:`flash_kernel_mode`, ``ACCELERATE_FLASH_KERNEL``): the kill switch is
the einsum reference (byte-identical to ``impl="xla"``), and interpret mode
drives the exact kernel dataflow through CPU tier-1.
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .attention import masked_attention


def flash_kernel_mode() -> str:
    """Dispatch mode for :func:`flash_attention`, read once per trace (step
    functions bake it in at compile time — flipping the env var mid-run does
    not retrace warm jit entries):

    - ``"on"`` (default): the in-tree Pallas kernel when the backend is TPU
      (a shape it cannot tile raises there), einsum reference off-TPU;
    - ``"off"`` (``ACCELERATE_FLASH_KERNEL=0``): einsum reference always —
      the kill switch, byte-identical to ``impl="xla"``;
    - ``"interpret"`` (``ACCELERATE_FLASH_KERNEL=interpret``): the Pallas
      kernel in interpreter mode on ANY backend — how CPU CI drives the
      kernel's exact dataflow (including the backward) in tier-1. A test
      tool: no backend takes it by default."""
    raw = os.environ.get("ACCELERATE_FLASH_KERNEL", "1").strip().lower()
    if raw in ("0", "off", "false"):
        return "off"
    if raw == "interpret":
        return "interpret"
    return "on"


class _FlashConfig(NamedTuple):
    """Static kernel configuration (hashable: rides custom_vjp nondiff)."""

    scale: float
    causal: bool
    window: Optional[int]
    block_q: int
    block_kv: int
    h: int
    hkv: int
    use_seg: bool
    interpret: bool

    @property
    def groups(self) -> int:
        return self.h // self.hkv


def _block_lattice(seg: jax.Array, cfg: _FlashConfig):
    """Per-``(q_block, kv_block)`` active map → (ids, counts) in both
    orientations.

    ``ids[b, qi, :counts[b, qi]]`` lists the kv blocks q block ``qi`` must
    stream, in ascending order; the transposed pair drives the dk/dv kernel.
    Causal and sliding-window activity are pure block-coordinate bands;
    segment activity is an interval-overlap test on per-block id min/max —
    exact for contiguous packing, never-false-negative in general (a q and kv
    block sharing id ``x`` both bracket ``x``). The diagonal block is active
    under every mask (every token attends itself), so counts ≥ 1 and the
    clamped index maps below always have a real block to land on."""
    B, S = seg.shape
    nq, nkv = S // cfg.block_q, S // cfg.block_kv
    qlo = jnp.arange(nq, dtype=jnp.int32) * cfg.block_q
    qhi = qlo + cfg.block_q - 1
    klo = jnp.arange(nkv, dtype=jnp.int32) * cfg.block_kv
    khi = klo + cfg.block_kv - 1
    active = jnp.ones((B, nq, nkv), bool)
    if cfg.causal:
        active &= klo[None, None, :] <= qhi[None, :, None]
    if cfg.window is not None:
        active &= qlo[None, :, None] - khi[None, None, :] < cfg.window
    if cfg.use_seg:
        sq = seg.reshape(B, nq, cfg.block_q)
        skv = seg.reshape(B, nkv, cfg.block_kv)
        qmin, qmax = sq.min(-1), sq.max(-1)
        kmin, kmax = skv.min(-1), skv.max(-1)
        active &= (qmin[:, :, None] <= kmax[:, None, :]) & (
            kmin[:, None, :] <= qmax[:, :, None]
        )

    def order(act):
        # actives first, each side in ascending block order, no stable-sort
        # dependence: inactive keys are offset past every active key
        n = act.shape[-1]
        pos = jnp.arange(n, dtype=jnp.int32)
        key = jnp.where(act, 0, n).astype(jnp.int32) + pos
        return jnp.argsort(key, axis=-1).astype(jnp.int32)

    activeT = active.transpose(0, 2, 1)
    return (
        order(active),
        active.sum(-1).astype(jnp.int32),
        order(activeT),
        activeT.sum(-1).astype(jnp.int32),
    )


def _allow_mask(cfg: _FlashConfig, shape, qi, blk, segq, segkv):
    """Element mask for one (q_block, kv_block) score tile, or None (dense)."""
    preds = []
    if cfg.causal or cfg.window is not None:
        qpos = qi * cfg.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        kpos = blk * cfg.block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if cfg.causal:
            preds.append(kpos <= qpos)
        if cfg.window is not None:
            preds.append(qpos - kpos < cfg.window)
    if cfg.use_seg:
        preds.append(segq[:, None] == segkv[None, :])
    if not preds:
        return None
    allow = preds[0]
    for p in preds[1:]:
        allow = jnp.logical_and(allow, p)
    return allow


def _dot_nt2(a, b):  # [M, K] × [N, K] → [M, N], f32 accumulate
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_nn2(a, b):  # [M, K] × [K, N] → [M, N], f32 accumulate
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tn2(a, b):  # [K, M] × [K, N] → [M, N], f32 accumulate
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _flash_fwd_kernel(
    ids_ref,     # [B, nq, nkv] int32 scalar-prefetch: active kv blocks per q block
    counts_ref,  # [B, nq]      int32 scalar-prefetch: how many are active
    q_ref,       # [1, bq, D]       this (head, q-block) tile
    k_ref,       # [1, bkv, D]      the kv block the clamped index map selected
    v_ref,       # [1, bkv, D]
    segq_ref,    # [1, 1, bq] int32   per-token vectors ride the row layout
    segkv_ref,   # [1, 1, bkv] int32  of _row_spec
    o_ref,       # [1, bq, D]
    lse_ref,     # [1, 1, bq] f32
    acc_ref,     # VMEM [bq, D] f32   online-softmax accumulators,
    m_ref,       # VMEM [bq, 1] f32   carried across the kv grid steps
    l_ref,       # VMEM [bq, 1] f32
    *,
    cfg: _FlashConfig,
):
    """One (head, q_block, kv_step) grid step of blocked streaming flash.

    The kv axis is innermost; ``t`` walks this q block's *active-block list*
    (``ids[b, qi, t]``), not the raw kv range. Steps past ``counts[b, qi]``
    repeat the last active block (the index map clamps, so the DMA is elided)
    and skip their compute via ``pl.when`` — that is the whole block-sparsity
    mechanism. Within an active block, causal/window/segment masking is
    recomputed per element from positions and the streamed segment-id tiles;
    masked lanes go to ``-inf`` and the running max's shift is clamped so a
    fully masked prefix never turns into NaN (same trick as the paged
    kernels)."""
    from jax.experimental import pallas as pl  # deferred with pallas_call's

    g, qi, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    b = g // cfg.h
    count = counts_ref[b, qi]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(t < count)
    def _step():
        blk = ids_ref[b, qi, t]
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = _dot_nt2(q, k) * cfg.scale  # [bq, bkv] f32
        allow = _allow_mask(cfg, s.shape, qi, blk, segq_ref[0, 0], segkv_ref[0, 0])
        if allow is not None:
            s = jnp.where(allow, s, -jnp.inf)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a fully-masked prefix keeps m at -inf: exp(-inf - -inf) would be
        # NaN, so clamp the shift (everything is 0-weighted anyway)
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(m_prev - shift)
        p = jnp.exp(s - shift)  # [bq, bkv] f32, masked -> 0
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot_nn2(p.astype(v.dtype), v)
        m_ref[...] = m_new

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(l_ref[:, 0])


def _flash_dq_kernel(
    ids_ref, counts_ref,
    q_ref,      # [1, bq, D]
    k_ref,      # [1, bkv, D]
    v_ref,      # [1, bkv, D]
    segq_ref, segkv_ref,
    lse_ref,    # [1, 1, bq] f32
    delta_ref,  # [1, 1, bq] f32: sum(do * o) per row, precomputed
    do_ref,     # [1, bq, D]
    dq_ref,     # [1, bq, D]
    dq_acc_ref,  # VMEM [bq, D] f32
    *,
    cfg: _FlashConfig,
):
    """dq kernel: same grid and lattice walk as the forward, recomputing
    probabilities from the saved logsumexp (``p = exp(s - lse)``) instead of
    re-running the online softmax."""
    from jax.experimental import pallas as pl

    g, qi, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    b = g // cfg.h
    count = counts_ref[b, qi]

    @pl.when(t == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    @pl.when(t < count)
    def _step():
        blk = ids_ref[b, qi, t]
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _dot_nt2(q, k) * cfg.scale
        allow = _allow_mask(cfg, s.shape, qi, blk, segq_ref[0, 0], segkv_ref[0, 0])
        if allow is not None:
            s = jnp.where(allow, s, -jnp.inf)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [bq, bkv] f32, masked -> 0
        dp = _dot_nt2(do, v)                  # [bq, bkv] f32
        ds = p * (dp - delta_ref[0, 0][:, None])
        dq_acc_ref[...] += _dot_nn2(ds.astype(k.dtype), k) * cfg.scale

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _flash_dkdv_kernel(
    idsT_ref,     # [B, nkv, nq] int32: active q blocks per kv block
    countsT_ref,  # [B, nkv]     int32
    q_ref,        # [1, bq, D]   q block of group member r = t % groups
    do_ref,       # [1, bq, D]
    k_ref,        # [1, bkv, D]  this kv head's block
    v_ref,        # [1, bkv, D]
    segq_ref, segkv_ref,
    lse_ref,      # [1, 1, bq] f32
    delta_ref,    # [1, 1, bq] f32
    dk_ref,       # [1, bkv, D]
    dv_ref,       # [1, bkv, D]
    dk_acc_ref,   # VMEM [bkv, D] f32
    dv_acc_ref,   # VMEM [bkv, D] f32
    *,
    cfg: _FlashConfig,
):
    """dk/dv kernel: grid ``(B·Hkv, kv_blocks, q_steps·groups)`` — one program
    per *kv head*, streaming every (active q block × GQA group member) pair
    through the transposed lattice and accumulating the group-summed dk/dv in
    VMEM. The GQA reduction happens here, in-kernel: per-q-head dk/dv and
    repeated KV never exist in HBM."""
    from jax.experimental import pallas as pl

    a, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    b = a // cfg.hkv
    qidx = t // cfg.groups
    count = countsT_ref[b, j]

    @pl.when(t == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    @pl.when(qidx < count)
    def _step():
        qb = idsT_ref[b, j, qidx]
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = _dot_nt2(q, k) * cfg.scale  # [bq, bkv] f32
        allow = _allow_mask(cfg, s.shape, qb, j, segq_ref[0, 0], segkv_ref[0, 0])
        if allow is not None:
            s = jnp.where(allow, s, -jnp.inf)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [bq, bkv] f32
        dv_acc_ref[...] += _dot_tn2(p.astype(do.dtype), do)   # pᵀ do
        dp = _dot_nt2(do, v)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dk_acc_ref[...] += _dot_tn2(ds.astype(q.dtype), q) * cfg.scale

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _clamped_block(ids, counts, b, qi, t):
    """Index-map helper: step t of q block qi, clamped onto the last active
    block once t runs past the active count — the repeated block index is what
    lets Mosaic elide the DMA for skipped steps."""
    return ids[b, qi, jnp.minimum(t, jnp.maximum(counts[b, qi] - 1, 0))]


def _row_spec(width, index_map):
    """BlockSpec of a per-token vector (segment ids, lse, delta). They are kept
    ``[N, 1, S]`` so a ``(1, 1, width)`` block meets Mosaic's tiling rule (the
    second-minor block dim equals the array's, the minor one is a multiple of
    128 or the whole of S); ``index_map`` gives ``(row, block)``."""
    from jax.experimental import pallas as pl

    def index(*args):
        row, blk = index_map(*args)
        return row, 0, blk

    return pl.BlockSpec((1, 1, width), index)


def _flash_pallas_call(kernel, name, cfg, grid, in_specs, out_specs, out_shape, scratch):
    """``name`` is the kernel's stable name: it becomes the compiled
    instruction's name, which is how a device trace tells the kernels apart."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # lattice ids + counts
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=cfg.interpret,
        name=name,
    )


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash_call(q3, k3, v3, seg, cfg):
    out, _ = _flash_call_fwd(q3, k3, v3, seg, cfg)
    return out


def _flash_call_fwd(q3, k3, v3, seg, cfg):
    """q3 [B·H, S, D]; k3/v3 [B·Hkv, S, D]; seg [B, S] int32. The saved lse
    is ``[B·H, 1, S]`` (the row layout of :func:`_row_spec`)."""
    from jax.experimental import pallas as pl

    BH, S, D = q3.shape
    H, Hkv, groups = cfg.h, cfg.hkv, cfg.groups
    bq, bkv = cfg.block_q, cfg.block_kv
    nq, nkv = S // bq, S // bkv
    ids, counts, _, _ = _block_lattice(seg, cfg)

    def kv_batch(g):
        return (g // H) * Hkv + (g % H) // groups

    in_specs = [
        pl.BlockSpec((1, bq, D), lambda g, qi, t, ids, cnt: (g, qi, 0)),
        pl.BlockSpec(
            (1, bkv, D),
            lambda g, qi, t, ids, cnt: (
                kv_batch(g), _clamped_block(ids, cnt, g // H, qi, t), 0),
        ),
        pl.BlockSpec(
            (1, bkv, D),
            lambda g, qi, t, ids, cnt: (
                kv_batch(g), _clamped_block(ids, cnt, g // H, qi, t), 0),
        ),
        _row_spec(bq, lambda g, qi, t, ids, cnt: (g // H, qi)),
        _row_spec(
            bkv,
            lambda g, qi, t, ids, cnt: (
                g // H, _clamped_block(ids, cnt, g // H, qi, t)),
        ),
    ]
    out_specs = [
        pl.BlockSpec((1, bq, D), lambda g, qi, t, ids, cnt: (g, qi, 0)),
        _row_spec(bq, lambda g, qi, t, ids, cnt: (g, qi)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
        jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
    ]
    from jax.experimental.pallas import tpu as pltpu

    scratch = [
        pltpu.VMEM((bq, D), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
    ]
    out, lse = _flash_pallas_call(
        partial(_flash_fwd_kernel, cfg=cfg),
        "flash_fwd",
        cfg, (BH, nq, nkv), in_specs, out_specs, out_shape, scratch,
    )(ids, counts, q3, k3, v3, seg[:, None], seg[:, None])
    return out, (q3, k3, v3, seg, lse, out)


def _flash_call_bwd(cfg, res, do):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q3, k3, v3, seg, lse, out = res
    BH, S, D = q3.shape
    H, Hkv, groups = cfg.h, cfg.hkv, cfg.groups
    bq, bkv = cfg.block_q, cfg.block_kv
    nq, nkv = S // bq, S // bkv
    B = BH // H
    ids, counts, idsT, countsT = _block_lattice(seg, cfg)
    # delta = Σ_d do·o per row: elementwise, O(S·D) — no score-shaped tensor
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None]
    seg = seg[:, None]  # [B, 1, S], the row-vector layout of _row_spec

    def kv_batch(g):
        return (g // H) * Hkv + (g % H) // groups

    q_spec = pl.BlockSpec((1, bq, D), lambda g, qi, t, ids, cnt: (g, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, bkv, D),
        lambda g, qi, t, ids, cnt: (
            kv_batch(g), _clamped_block(ids, cnt, g // H, qi, t), 0),
    )
    row_spec = _row_spec(bq, lambda g, qi, t, ids, cnt: (g, qi))
    dq = _flash_pallas_call(
        partial(_flash_dq_kernel, cfg=cfg),
        "flash_dq",
        cfg,
        (BH, nq, nkv),
        [
            q_spec,
            kv_spec,
            kv_spec,
            _row_spec(bq, lambda g, qi, t, ids, cnt: (g // H, qi)),
            _row_spec(
                bkv,
                lambda g, qi, t, ids, cnt: (
                    g // H, _clamped_block(ids, cnt, g // H, qi, t)),
            ),
            row_spec,
            row_spec,
            q_spec,
        ],
        [q_spec],
        [jax.ShapeDtypeStruct((BH, S, D), q3.dtype)],
        [pltpu.VMEM((bq, D), jnp.float32)],
    )(ids, counts, q3, k3, v3, seg, seg, lse, delta, do)[0]

    # transposed walk: per kv head, stream (active q block × group member)
    # pairs; t enumerates them with the member index fastest
    def q_batch(a, t):
        return (a // Hkv) * H + (a % Hkv) * groups + t % groups

    qT_spec = pl.BlockSpec(
        (1, bq, D),
        lambda a, j, t, ids, cnt: (
            q_batch(a, t),
            _clamped_block(ids, cnt, a // Hkv, j, t // groups),
            0,
        ),
    )
    rowT_spec = _row_spec(
        bq,
        lambda a, j, t, ids, cnt: (
            q_batch(a, t),
            _clamped_block(ids, cnt, a // Hkv, j, t // groups),
        ),
    )
    kvT_spec = pl.BlockSpec((1, bkv, D), lambda a, j, t, ids, cnt: (a, j, 0))
    dk, dv = _flash_pallas_call(
        partial(_flash_dkdv_kernel, cfg=cfg),
        "flash_dkdv",
        cfg,
        (B * Hkv, nkv, nq * groups),
        [
            qT_spec,
            qT_spec,
            kvT_spec,
            kvT_spec,
            _row_spec(
                bq,
                lambda a, j, t, ids, cnt: (
                    a // Hkv,
                    _clamped_block(ids, cnt, a // Hkv, j, t // groups),
                ),
            ),
            _row_spec(bkv, lambda a, j, t, ids, cnt: (a // Hkv, j)),
            rowT_spec,
            rowT_spec,
        ],
        [kvT_spec, kvT_spec],
        [
            jax.ShapeDtypeStruct((B * Hkv, S, D), k3.dtype),
            jax.ShapeDtypeStruct((B * Hkv, S, D), v3.dtype),
        ],
        [pltpu.VMEM((bkv, D), jnp.float32), pltpu.VMEM((bkv, D), jnp.float32)],
    )(idsT, countsT, q3, do, k3, v3, seg, seg, lse, delta)
    return dq, dk, dv, None


_flash_call.defvjp(_flash_call_fwd, _flash_call_bwd)


def _reference_attention(q, k, v, *, causal, scale, segment_ids, window):
    """The einsum reference: the ``"off"`` kill switch and the off-TPU path.
    Byte-identical to ``dot_product_attention(..., impl="xla")`` — both call
    :func:`ops.attention._xla_attention` with the same mask construction."""
    from .attention import _xla_attention, segment_mask

    mask = segment_mask(segment_ids) if segment_ids is not None else None
    return _xla_attention(q, k, v, causal=causal, mask=mask, scale=scale, window=window)


def flash_tileable(q_shape, k_shape, block_q: int = 128, block_kv: int = 128) -> bool:
    """Shapes (BSHD) the blocked kernel takes: self-attention whose sequence
    is a whole number of blocks (a sequence shorter than a block is one block)."""
    Sq, Skv = q_shape[1], k_shape[1]
    return Sq == Skv and Sq % min(block_q, Sq) == 0 and Skv % min(block_kv, Skv) == 0


def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,  # [B, S] int; padding = 0
    window: Optional[int] = None,  # sliding window: attend iff 0 <= i-j < window
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    """Blocked streaming flash attention (BSHD in/out), fwd + bwd.

    ``segment_ids`` gates attention to same-id pairs — the kernel-native form
    of padding/packing masks; ``window`` adds a causal sliding-window band
    (requires ``causal=True``). Both feed the block-skip lattice, so fully
    masked (q_block, kv_block) tiles cost nothing. Dispatch is governed by
    :func:`flash_kernel_mode`. On a TPU a shape the blocked kernel cannot tile
    (:func:`flash_tileable`) raises: this function IS the explicit request for
    the kernel, and only ``dot_product_attention(impl="auto")`` may choose the
    einsum path. Off-TPU there is no Mosaic compiler and the einsum reference
    is the implementation, unless the tests' interpret mode is on."""
    if window is not None:
        if not causal:
            raise ValueError(
                "window requires causal=True (the sliding window is a causal band)"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    sm_scale = 1.0 / math.sqrt(D) if scale is None else float(scale)

    mode = flash_kernel_mode()
    on_tpu = jax.default_backend() == "tpu"
    tileable = flash_tileable(q.shape, k.shape, block_q, block_kv)
    if mode == "on" and on_tpu and not tileable:
        raise ValueError(
            f"flash attention cannot tile q={q.shape} k={k.shape}: it needs "
            f"Sq == Skv and S a multiple of the block ({block_q}/{block_kv}) or "
            "shorter than it. Use impl='xla', or impl='auto' to let the dispatcher choose."
        )
    if not (tileable and (mode == "interpret" or (mode == "on" and on_tpu))):
        return _reference_attention(
            q, k, v, causal=causal, scale=scale, segment_ids=segment_ids, window=window
        )
    return _flash_kernel(
        q, k, v, segment_ids, causal=causal, sm_scale=sm_scale, window=window,
        block_q=block_q, block_kv=block_kv, interpret=mode == "interpret",
    )


def _flash_kernel(q, k, v, segment_ids, *, causal, sm_scale, window,
                  block_q=128, block_kv=128, interpret=False):
    """The kernel path of :func:`flash_attention`, past its dispatch (BSHD
    in/out; the shape is already known to be :func:`flash_tileable`). Also
    what ``tests/test_tpu_compile.py`` hands to the chip's compiler, since
    the dispatch above sees the sandbox's CPU."""
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    cfg = _FlashConfig(
        scale=sm_scale,
        causal=causal,
        window=window,
        block_q=min(block_q, Sq),
        block_kv=min(block_kv, Skv),
        h=H,
        hkv=Hkv,
        use_seg=segment_ids is not None,
        interpret=interpret,
    )
    seg = (
        segment_ids.astype(jnp.int32)
        if segment_ids is not None
        else jnp.zeros((B, Sq), jnp.int32)
    )
    # BSHD → flat [B·H, S, D]; layout-only, no repeated KV
    q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    out = _flash_call(q3, k3, v3, seg, cfg)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def paged_kernel_mode() -> str:
    """Dispatch mode for :func:`paged_attention`, read once per trace (the
    engine's step functions bake it in at compile time — flipping the env var
    mid-run does not retrace warm jit entries):

    - ``"on"`` (default, and any value but the next): the Pallas kernels when
      the backend is TPU, the gather reference everywhere else;
    - ``"interpret"`` (``ACCELERATE_PAGED_KERNEL=interpret``): the Pallas
      kernels in interpreter mode on ANY backend — how CPU CI drives the
      kernels' exact dataflow through the full engine."""
    raw = os.environ.get("ACCELERATE_PAGED_KERNEL", "").strip().lower()
    return "interpret" if raw == "interpret" else "on"


#: physical block index reserved for inactive/padded writes (never allocated)
NULL_BLOCK = 0


#: row of a pool's per-sequence state reserved for idle slots (never handed out)
NULL_STATE_ROW = 0


def init_block_pool(config, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                    state_rows: int = 0) -> dict:
    """Device pool ``{"k","v"}: [L, num_blocks, block_size, Hkv, D]``
    (``num_blocks`` INCLUDES the reserved null block 0, one a layer). ``config``
    is any model description with ``n_layers``, ``n_kv_heads`` and ``head_dim``;
    every layer gets the same blocks, whatever its kind (a window layer keeps
    what lies behind its window: an allocator by layer kind is ROADMAP B-m2's).
    This is the format at the programs' boundary (``engine._cow``,
    ``serving/disagg.py``, a pool's checkpoint). Inside a step program a layer
    addresses the stack flat: block ``b`` of layer ``l`` is block ``l *
    num_blocks + b`` of ``[L * num_blocks, block_size, Hkv, D]``, and the
    layer's null block is ``l * num_blocks`` (:func:`paged_write_attend`).

    The pool holds what the model says it needs. Where only some layers attend
    the model says how many (``config.n_kv_layers``) and ``L`` is that, a
    layer's index its rank among them. Where key heads are narrower than the
    128 lanes of a tile and fill them exactly several at a time
    (:func:`kv_lane_pack`), that many lie side by side in a row: ``[..., Hkv /
    pack, pack * D]``, the same bytes in the same order, and
    :func:`paged_write_attend` alone knows. A model with ``state_shape``
    ``(layers, *row)`` keeps that much a SEQUENCE beside its blocks, whatever
    its length: the pool then also has ``"state": [layers, state_rows, *row]``,
    one row a sequence, row ``NULL_STATE_ROW`` for the slots that hold none.
    A block holds ``block_size`` keys and values of one kind or another: a
    model whose attention pools what lies behind a window (``window_size``,
    ``chunk_size``) keeps in the same blocks the raw keys of a row's open
    window and the pooled pairs of its windows, a row then holding pooled
    entries ahead of raw ones (:func:`pooled_row_view`)."""
    pack = kv_lane_pack(config.n_kv_heads, config.head_dim)
    shape = (getattr(config, "n_kv_layers", config.n_layers), num_blocks, block_size,
             config.n_kv_heads // pack, config.head_dim * pack)
    pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    state_shape = getattr(config, "state_shape", None)
    if state_shape is not None:
        pool["state"] = jnp.zeros((state_shape[0], state_rows, *state_shape[1:]), dtype)
    return pool


def kv_lane_pack(n_kv_heads: int, head_dim: int) -> int:
    """Key heads that share a row of the paged pool: ``128 / head_dim`` where
    that many fill a tile's 128 lanes exactly and divide the key heads, else 1
    (every head of 128 or wider).

    Why: a pool whose rows are 64 wide fills half of the 128 lanes of the
    TPU's tiles. The paged kernels want the pool in the layout that pads such
    a row to 128 (twice the memory), the runtime's default layout for the same
    array is another one without the padding, and every step program would
    copy the whole pool from one to the other and back (compiled for a
    described v5e, PR 35: 3 GB of temporaries for a 2 GB pool). With the rows
    full both layouts are the same, and the kernels run at a shape they
    already serve. A row that packing would still leave short of 128 gains
    nothing and is left alone."""
    pack = max(1, 128 // head_dim)
    return pack if pack * head_dim == 128 and n_kv_heads % pack == 0 else 1


def _lane_segment(H: int, Hkv: int, pack: int) -> np.ndarray:
    """Of each of ``H`` query heads, which of the ``pack`` segments of its
    packed key head holds its own key head ``h // (H / Hkv)``."""
    return (np.arange(H) // (H // Hkv)) % pack


def _pack_heads(q, k, v, pack: int):
    """Attention with ``Hkv`` key heads of ``D`` as attention with ``Hkv /
    pack`` key heads of ``pack * D``, exactly: key heads ``pack * j .. pack * j
    + pack - 1`` lie side by side in packed head ``j`` (a reshape of ``k``, ``v``
    ``[B, S, Hkv, D]``: no data moves), and query head ``h``, whose key head
    ``g = h // (H / Hkv)`` is segment ``g % pack`` of packed head ``g // pack``,
    is widened with zeros everywhere but in that segment, so its product with
    a packed key is its product with its own key head. The scores still want
    ``D``'s ``1 / sqrt(D)``, not the packed width's; the packed output
    carries every segment's values and :func:`_unpack_heads` keeps the query
    head's own. Costs ``pack`` times the score and value products (a few
    MFLOP a step) and nothing in bytes."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    own = jax.nn.one_hot(_lane_segment(H, Hkv, pack), pack, dtype=q.dtype)  # [H, pack]
    widened = q[:, :, :, None, :] * own[:, :, None]
    return (widened.reshape(B, S, H, pack * D), k.reshape(B, S, Hkv // pack, pack * D),
            v.reshape(B, S, Hkv // pack, pack * D))


def _unpack_heads(attn, pack: int, n_kv_heads: int):
    """The other end of :func:`_pack_heads`: of ``attn [B, S, H, pack * D]``
    each query head's own segment, ``[B, S, H, D]``."""
    B, S, H, wide = attn.shape
    own = _lane_segment(H, n_kv_heads, pack)
    return attn.reshape(B, S, H, pack, wide // pack)[:, :, np.arange(H), own]


# Keys a grid step of the decode kernel folds, and the VMEM it may plan for
# them. More keys a step spread its start (0.35 us) and the row's init and
# finalize over more blocks, against a row's last step, which fetches and
# computes on what it masks (half a step a row), and against a process's start:
# each of the ``2 N`` K and V BlockSpecs is an index map traced and lowered for
# every kernel instance of every decode program, compile cache warm or not. On
# the v5e, blocks of 16, bf16 (my chip runs, PR 34: the kernel's ms a call at N
# 4 / 8 / 16): 64 rows of 46 live blocks at 32 / 8 heads 0.546 / 0.456 / 0.419;
# 32 rows of 124 at 128 / 8 heads 0.935 / 0.740 / 0.652; 32 rows of 290 at 32 /
# 4 heads 1.583 / 1.211 / 1.056, inside a window of 1024 (64 blocks a row) 0.371
# / 0.297 / 0.277; 3 live rows and 29 padded slots 0.047 / 0.056 / 0.079; N 32
# level with 16 or behind it. Tracing and lowering 16 instances (one program of
# `mistral-7b`'s six) took 0.95 s at N 8, 1.8 s at 16 and 1.4 s for the body
# this replaced (this sandbox's CPU, for a described v5e). 128 keys is N = 8 at
# blocks of 16: 8-13 % behind 16 in a saturated batch, ahead of it where most
# slots are padding, and some 5 s less of a `mistral-7b` cell's `setup_s`. The
# bytes only bind where a block is far wider than the serve cells' (217 KB of
# VMEM a block at 32 / 8 heads of 128 with its share of the scores, 290 KB at
# 128 / 8).
_DECODE_STEP_KEYS = 128
_DECODE_VMEM_BYTES = 6 * 1024 * 1024


def _decode_group_blocks(block_size, Hkv, D, dtype, groups, W) -> int:
    """Blocks fetched and folded per grid step of the decode walk (``N``),
    read from shapes: :data:`_DECODE_STEP_KEYS` keys a step, no more than fit
    :data:`_DECODE_VMEM_BYTES`, a block costing six pool-dtype copies of its
    ``[block_size * Hkv, D]`` rows (K and V, double buffered, and as the
    step's matmul operands; ``D`` padded to 128 lanes) and its columns of the
    ``groups * Hkv`` query heads' scores (f32) and probabilities (pool dtype).
    Clamped to ``[1, W]``."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = block_size * Hkv
    per_block = 6 * rows * -(-D // 128) * 128 * itemsize + groups * Hkv * rows * (4 + itemsize)
    return max(1, min(W, _DECODE_STEP_KEYS // block_size, _DECODE_VMEM_BYTES // per_block))


def _div_rem(x, n: int):
    """``(x // n, x % n)`` of a non-negative int32 array by a static ``n``:
    shifts where ``n`` is a power of two (every head count the serve cells
    have), the truncating division otherwise."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1), x & (n - 1)
    return jax.lax.div(x, n), jax.lax.rem(x, n)


def _paged_decode_kernel(
    walk_ref,    # [B * table] int32 scalar-prefetch: the block tables, each row
                 # padded to ``table`` entries, every entry past a row's last
                 # live one replaced by that one
    lens_ref,    # [B] int32 scalar-prefetch: per-row live kv length
    row_ref,     # [B*steps] int32 scalar-prefetch: the row of grid step i
    group_ref,   # [B*steps] int32 scalar-prefetch: its group within the row
    base_ref,    # [B*steps] int32 scalar-prefetch: where in ``walk_ref`` the
                 # step's first block stands, ``row * table + entry`` (what the
                 # K and V index maps read)
    q_ref,       # [1, H, D]: the row's query, head ``h`` of key head ``h // G``
    *refs,       # N K slabs, N V slabs [1, block_size * Hkv, D] (a block's
                 # rows are (token, key head), token major); o_ref [1, H, D];
                 # the online-softmax carries in VMEM, f32: acc [H, D], m and
                 # l [H, 1]
    block_size: int,
    group: int,
    table: int,
    scale: float,
    window: Optional[int] = None,
):
    """One grid step of paged flash decode: ``N`` consecutive blocks of one
    row, folded into its online softmax in one pass.

    The grid is one-dimensional and as long as the batch's live blocks need:
    row ``b`` takes ``ceil(live_b / N)`` steps, ``live_b = ceil(kv_len_b /
    block_size)``, one row after the other (``row_ref``/``group_ref`` say
    which step is whose). The pool comes in through ``N`` BlockSpecs a side
    whose index maps fetch physical blocks ``walk[base[i] + j]``, the row's
    table entries ``g*N .. g*N+N-1``; the pipeline fetches the next step's
    blocks, the next row's first ones too, while this step computes. So a row
    costs its live blocks, rounded up to ``N``, whatever the width of the
    bucketed table: the table's padding is neither fetched nor computed on.
    Where a row's last step reaches past its last live block, ``walk`` repeats
    that block (already there, the row's own, never the null block's or
    another row's values), and those positions are masked like the tail of the
    last live block (``pos < kv_len``, the mask of the gather reference). A
    padded slot (``kv_len`` 1, all-null table) is one step on one fetched
    block.

    A step is two matmuls in the pool's dtype, f32 accumulation, with no copy
    of K or V: the step's ``N`` blocks stand as they lie in the pool, one slab
    ``[N * block_size * Hkv, D]`` whose rows are (token, key head), and ALL
    ``H`` query heads meet all of its rows, ``[H, D] x [rows, D]^T``. A query
    head's scores against another key head's rows are masked to -inf like dead
    positions, so their probabilities are exact zeros in the value product
    ``[H, rows] x [rows, D]``. That spends ``Hkv`` times the MXU's work (8.4
    MFLOP a step of 256 keys at 32 heads: 0.04 us at peak) and ``Hkv`` times
    the exponentials to save the relayout of K and V to head-major, which was
    the larger cost at every head ratio measured: on the v5e, bf16, blocks of
    16 (my chip runs, PR 34; the kernel's ms a call for 64 rows / 2.9 k live
    blocks at 32 / 8 heads, 32 / 4.0 k at 128 / 8, 32 / 9.3 k at 32 / 4, every
    form on the walk it then had, three SMEM reads a map and the blocks
    reshaped in the kernel) this form 0.544 / 0.855 / 1.675 at N 8 and 0.504 /
    0.758 / 1.500 at 16; a key head a loop step over a head-major copy (``[G,
    D] x [D, keys]``, the copy a bf16 transpose, N 8) 0.818 / 1.116 / 1.986,
    through an f32 transpose 1.05 / 1.41 / 2.30 (host clock), per-head strided
    reads 1.14 / - / 6.2 (host clock); the scores held keys by queries (N 16)
    0.696 / 0.830 / 1.691; a decode row as a one-query tile of the prefill
    kernel 0.738 / 0.937 / 1.790; the f32 multiply-reduce on the VPU this
    replaced 1.209 / 4.715 / 6.216. The scale is applied to the f32 scores,
    the online softmax (``m``, ``l``, ``acc``) is f32, and the probabilities
    are rounded to the pool's dtype for the value product, as
    ``ops.attention.masked_attention`` rounds them (an f32 pool stays f32 all
    through).

    With a ``window`` a query sees the last ``window`` positions only, itself
    among them (``kv_len - window <= pos < kv_len``): the walk starts at block
    ``first = max(0, kv_len - window) // block_size`` (``base`` points there),
    so the blocks wholly behind the window are never fetched, and the head of
    block ``first`` is masked like the tail of the last."""
    from jax.experimental import pallas as pl  # deferred with pallas_call's

    i = pl.program_id(0)
    k_refs, v_refs = refs[:group], refs[group : 2 * group]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * group :]
    row = row_ref[i]
    kv_len = lens_ref[row]
    # position of the step's first key: its table entry times the block
    start = (base_ref[i] - row * table) * block_size

    @pl.when(group_ref[i] == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    k = jnp.concatenate([r[0] for r in k_refs])          # [rows, D]
    v = jnp.concatenate([r[0] for r in v_refs])
    H, rows = q_ref.shape[1], k.shape[0]
    Hkv = rows // (group * block_size)
    s = _dot_nt2(q_ref[0], k) * scale                    # [H, rows] f32
    token, key_head = _div_rem(jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1), Hkv)
    pos = start + token
    # true somewhere in a row's first step (at its first position without a
    # window, at `kv_len - window` with one): `m_new` is finite
    live = pos < kv_len
    if window is not None:
        live = live & (pos >= kv_len - window)
    own = key_head == _div_rem(jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0), H // Hkv)[0]
    s = jnp.where(own & live, s, -jnp.inf)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))  # [H, 1]
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # masked -> 0
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + _dot_nn2(p.astype(v.dtype), v)
    m_ref[...] = m_new

    @pl.when(start + group * block_size >= kv_len)  # the row's last step
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attention_decode(
    q, k_pool, v_pool, block_tables, kv_lens, scale=None, *, window=None, interpret=False
):
    """Pallas paged flash-attention decode: q ``[B, 1, H, D]`` against
    per-layer pools ``[num_blocks, block_size, Hkv, D]`` through
    ``block_tables [B, W]``, with ragged per-row live lengths ``kv_lens
    [B]``. Each row walks the ``ceil(kv_len / block_size)`` live entries of
    its table, ``N`` blocks a grid step, the next step's blocks in flight
    while this step's are computed (:func:`_paged_decode_kernel`); ``N`` comes
    from the shapes (:func:`_decode_group_blocks`) and the length of the grid
    from ``kv_lens``. The gathered ``[B, W*block_size]`` cache the XLA
    reference materializes per layer never exists, and the padding of a
    bucketed table is neither fetched nor computed on. The kernel reads the
    pool as ``[num_blocks, block_size * Hkv, D]`` (at a head width that is a
    multiple of 128 a bitcast, by the compiled programs) and the queries and
    outputs as ``[B, H, D]``: no transpose stands round the call. One form
    serves every head ratio (the body's docstring has what the others read).
    On one v5e, bf16, blocks of 16 (my chip runs, PR 34; the kernel's device
    time a call, beside the f32 VPU body it replaced and what the bytes
    allow): 64 rows / 2.9 k live blocks of a 144-wide table at 32 / 8 heads of
    128 **0.456 ms** (1.209; 0.235), 0.155 us a block; 32 rows / 4.0 k of 400
    at 128 / 8 **0.740 ms** (4.715; 0.316), 0.187; 32 rows / 9.3 k of 1056 at
    32 / 4 **1.211 ms** (6.216; 0.370), 0.131, and inside a window of 1024
    (2.0 k blocks) **0.297 ms** (1.387; 0.082), 0.145; 3 live rows and 29
    padded slots of a 48-wide table 0.056 ms (0.098). With the matmuls and the
    softmax taken out the same walk took 0.30 / 0.37 / 0.72 / 0.20 ms (N 16):
    fetching through ``2 N`` BlockSpecs is most of what is left.
    The walk's integer arrays are selects over the padded tables and one
    gather of ``B`` entries (an elementwise gather of the whole table, which
    this replaced, took 0.13 ms a layer at 64 x 144 and 0.34 at 32 x 1056).
    A static ``window`` (None: the program as it was, named ``paged_decode``)
    makes a row see its last ``window`` positions only and walk only the blocks
    that hold them, under the name ``paged_decode_win``, so that a trace tells
    a model's window layers from its full ones.
    ``interpret=True`` runs the identical kernel through the Pallas
    interpreter (the CPU parity path in tier-1 CI)."""
    from jax.experimental import pallas as pl_  # deferred: CPU-only installs
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    if S != 1:
        raise ValueError(f"decode kernel wants S=1 queries, got S={S}")
    num_blocks, block_size, Hkv, _ = k_pool.shape
    W = block_tables.shape[1]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    sm_scale = (1.0 / math.sqrt(D)) if scale is None else float(scale)
    N = _decode_group_blocks(block_size, Hkv, D, k_pool.dtype, H // Hkv, W)
    steps = pl_.cdiv(W, N)  # of a row whose table is full
    table = W + N           # a row of the walk: a last step may reach N - 1 past the table

    # The walk, from the lengths (the same small integer arrays for every
    # layer of a step). A longer kv_len than the table holds reads the table.
    kv_lens = jnp.minimum(jnp.asarray(kv_lens, jnp.int32).reshape(B), W * block_size)
    live = jnp.maximum(pl_.cdiv(kv_lens, block_size), 1)  # table entries, >= 1
    tables = block_tables.astype(jnp.int32)
    last = jnp.take_along_axis(tables, live[:, None] - 1, axis=1)
    walk = jnp.where(jnp.arange(table, dtype=jnp.int32) < live[:, None],
                     jnp.pad(tables, ((0, 0), (0, N))), last)
    first = 0  # the table entry a row's walk starts at
    if window is not None:  # the block that holds `kv_len - window`
        first = jnp.maximum(kv_lens - int(window), 0) // block_size
    ends = jnp.cumsum(pl_.cdiv(live - first, N))  # grid steps up to and with row b
    step = jnp.arange(B * steps, dtype=jnp.int32)
    done = step[:, None] >= ends  # [B*steps, B]: row b ends before this step
    # (steps past ends[-1] never run; their entries only have to stay in range)
    step_row = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), B - 1)
    step_group = step - jnp.max(jnp.where(done, ends, 0), axis=1)
    step_entry = step_group * N if window is None else first[step_row] + step_group * N
    step_base = step_row * table + jnp.minimum(step_entry, W - 1)

    def pool_block(j):  # two SMEM reads a map: lowering them is what a process's start pays
        return pl_.BlockSpec(
            (1, block_size * Hkv, D),
            lambda i, walk, lens, row, group, base: (walk[base[i] + j], 0, 0),
        )

    row = pl_.BlockSpec((1, H, D), lambda i, walk, lens, row, group, base: (row[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(ends[-1],),
        in_specs=[row] + 2 * [pool_block(j) for j in range(N)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    kernel = partial(
        _paged_decode_kernel, block_size=block_size, group=N, table=table, scale=sm_scale,
        window=None if window is None else int(window),
    )
    # a block's rows as they lie: (token, key head), token major
    slabs = (num_blocks, block_size * Hkv, D)
    out = pl_.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        name="paged_decode" if window is None else "paged_decode_win",
    )(
        walk.reshape(-1),
        kv_lens,
        step_row,
        step_group,
        step_base,
        q.reshape(B, H, D),
        *(N * [k_pool.reshape(slabs)] + N * [v_pool.reshape(slabs)]),
    )
    return out.reshape(B, 1, H, D)


# VMEM the prefill kernel plans for a grid step's keys and values: its N blocks
# of K and of V, double buffered, and their head-major working copies. At the
# serve cells' shapes (blocks of 16, 8 key heads of 128, bf16) this gives N = 8:
# 128 keys a step, one lane tile of scores a query row.
_PREFILL_VMEM_BYTES = 5 * 512 * 1024


def _prefill_group_blocks(block_size, Hkv, D, dtype, W) -> int:
    """Blocks fetched and folded per grid step of the prefill walk (``N``),
    read from shapes as :func:`_decode_group_blocks` reads the decode
    kernel's: how many fit :data:`_PREFILL_VMEM_BYTES`, a block costing its
    four pool-dtype copies (K and V, double buffered), the f32 upcast its
    relayout to head-major goes through and the head-major copies the MXU
    reads, every ``[Hkv, D]`` tile padded to 8 sublanes and 128 lanes.
    Clamped to ``[1, W]``."""
    tile = block_size * -(-Hkv // 8) * 8 * -(-D // 128) * 128  # elements
    per_block = 6 * tile * jnp.dtype(dtype).itemsize + 2 * tile * 4
    return max(1, min(W, _PREFILL_VMEM_BYTES // per_block))


# Query rows one prefill program holds, over all heads (``H * Sq``): the q and
# o tiles (double buffered, pool dtype) and acc in f32, 10 MB at 4096 rows and
# D = 128, beside the 2.5 MB of keys and values and a key head's score tiles.
# On the v5e a 512-token chunk of 128 query heads behind 1024-5632 tokens took
# 1.16-4.25 ms a call at 2048 rows and 0.83-3.18 ms at 4096 (my chip runs, PR
# 30): a tile twice as tall reads the keys half as often and pays half the
# steps' overhead. Twice that again does not fit the 16 MB of scoped VMEM.
_PREFILL_TILE_ROWS = 4096


def _prefill_query_tile(S: int, H: int, D: int) -> int:
    """Queries per program: all of S when ``H*S`` rows fit the budget above,
    else the largest divisor of S that does and is a multiple of 8."""
    budget = max(1, _PREFILL_TILE_ROWS * 128 // (H * max(D, 128)))
    if S <= budget:
        return S
    for sq in range(budget - budget % 8, 0, -8):
        if S % sq == 0:
            return sq
    raise ValueError(
        f"paged prefill kernel cannot tile S={S} queries of H={H}, D={D}: no "
        f"multiple of 8 up to {budget} divides S"
    )


def prefill_tiling(S: int, H: int, Hkv: int, D: int, block_size: int, dtype, W: int):
    """``(Sq, N)`` of a :func:`paged_attention_prefill` call, from its shapes:
    the queries a tile (:func:`_prefill_query_tile`) and the blocks a grid
    step (:func:`_prefill_group_blocks`). The shapes may be a model's as well
    as the call's: heads that :func:`kv_lane_pack` packs reach the call
    packed, and packed heads pack no further."""
    pack = kv_lane_pack(Hkv, D)
    Hkv, D = Hkv // pack, D * pack
    return _prefill_query_tile(S, H, D), _prefill_group_blocks(block_size, Hkv, D, dtype, W)


def _prefill_walk(lo, hi, block_size: int, W: int, N: int, window: Optional[int]):
    """``(first, last, steps)`` of the walk of a query tile whose positions
    span ``lo..hi`` (integers, or arrays of them, numpy's or traced): the
    table entries ``first..last`` hold every key some query of the tile sees,
    ``last`` the block of position ``hi`` (the table's last entry where a
    padded tail runs past it), ``first`` 0 or, with a window, the block of
    position ``lo - window + 1``; ``steps = ceil((last - first + 1) / N)``."""
    xp = jnp if isinstance(lo, jax.Array) else np
    last = xp.minimum(hi // block_size, W - 1)
    first = 0 * last
    if window is not None:
        first = xp.minimum(xp.maximum(lo - (window - 1), 0) // block_size, last)
    return first, last, -(-(last - first + 1) // N)


def prefill_walk_blocks(start: int, S: int, Sq: int, N: int, W: int, block_size: int,
                        window: Optional[int] = None) -> int:
    """Blocks the grid of one :func:`paged_attention_prefill` call visits for
    a row of ``S`` queries at positions ``start .. start + S - 1`` cut into
    tiles of ``Sq``: ``N`` a grid step, every tile's walk rounded up to whole
    steps (:func:`_prefill_walk`, the wrapper's own arithmetic) and never more
    than the table's ``W`` entries. Against ``S // Sq * W``, the entries of the
    bucketed table the tiles are handed, this is the share of the table the
    kernel fetches and computes on."""
    lo = start + Sq * np.arange(S // Sq)
    steps = _prefill_walk(lo, lo + Sq - 1, block_size, W, N, window)[2]
    return int(np.minimum(N * steps, W).sum())


def _paged_prefill_kernel(
    walk_ref,    # [steps*N] int32 scalar-prefetch: the physical block of grid
                 #   step i's j-th K/V operand, at i*N + j (drives their index maps)
    tile_ref,    # [steps] int32 scalar-prefetch: the query tile of grid step i
                 #   (the q, position and output index maps read it)
    group_ref,   # [steps] int32 scalar-prefetch: its step within the tile's walk
    entry_ref,   # [steps] int32 scalar-prefetch: the step's first table entry
    last_ref,    # [steps] int32 scalar-prefetch: the last entry of its tile's walk
    qpos_ref,    # [1, 1, G*Sq] int32 VMEM: absolute position of each query
                 #   row (SMEM operands only yield scalars)
    q_ref,       # [1, Hkv, G*Sq, D]: the tile's queries, key-head major, a
                 #   key head's G query heads one after the other
    *refs,       # N K blocks, N V blocks [1, block_size, Hkv, D]; o_ref
                 # [1, Hkv, D, G*Sq]; the online-softmax carries in VMEM, f32,
                 # queries along the lanes: acc [Hkv, D, G*Sq], m and l
                 # [Hkv, 1, G*Sq]; the step's keys and values head-major in
                 # VMEM, pool dtype: k and v [Hkv, N*block_size, D]
    block_size: int,
    group: int,
    scale: float,
    window: Optional[int] = None,
):
    """One grid step of paged chunked-prefill attention: ``N`` consecutive
    blocks of one query tile's walk, folded into its online softmax.

    The grid is one-dimensional and as long as the tiles' live blocks need
    (:func:`_paged_decode_kernel`'s walk, a query tile where that has a row):
    tile ``t`` of ``Sq`` queries takes ``ceil((last_t - first_t + 1) / N)``
    steps over the table entries ``first_t..last_t`` that hold the keys its
    queries can see (:func:`_prefill_walk`), one tile after the other. The pool
    comes in through ``N`` BlockSpecs a side whose index maps fetch physical
    blocks ``walk[i*N + j] = tables[b, min(first_t + g*N + j, last_t)]``: what
    lies past a tile's last live block is neither fetched nor computed on, and
    where its last step reaches past ``last_t`` the walk repeats that block
    (already there) and its positions are masked. Everything a step needs is
    laid out by grid step, one SMEM read an index map: lowering those reads
    costs a process's start more than anything else in the kernel (at five a
    map they were 1.0 s of ``command-a-plus.rag-sat``'s 18 s of warm set-up,
    my chip runs, PR 30).

    A step is two matmuls a key head in the pool's dtype, f32 accumulation:
    the ``G`` query heads of a key head are the ``G*Sq`` columns of one
    ``[N*block_size, D] x [D, G*Sq]`` score product and one ``[D,
    N*block_size] x [N*block_size, G*Sq]`` value product, so K and V are never
    copied out to the query heads' width, and the probabilities are rounded to
    the pool's dtype for the value product (what
    ``ops.attention.masked_attention`` computes). Scores are held keys by
    queries: the running max ``m``, the sum ``l`` and the rescaling of ``acc``
    are lane-dense rows over the queries and their reductions run down the
    sublanes, where the other way round every ``[G*Sq, 1]`` column cost as
    much as the score tile itself (a 512-token chunk of 128 heads behind 2560
    tokens: 2.19 ms a call against 3.47, my chip runs, PR 30). ``m``, ``l``
    and ``acc`` stay f32; the output leaves as ``[Hkv, D, G*Sq]`` and the
    wrapper's transpose puts it back.

    Causality inside the chunk and raggedness against previously-landed KV
    collapse into ONE predicate: the engine scatter-writes the chunk's own KV
    into the pool *before* attention, so every KV position — old blocks and
    the chunk's own tokens alike — is live in the walked blocks, and masking
    ``kv_pos <= q_position`` per query reproduces the gather reference
    (null-padded table entries sit at positions past every query; a padded
    tail's queries past the table see the table and no more). With a
    ``window`` a query at position ``p`` sees ``p - window < kv_pos <= p``:
    the walk starts at the block the tile's first query's window starts in,
    and the head of that block and every later query's own edge are masked.
    The mask is built once a step, as 0 / -inf added to the scores, and shared
    by the key heads, which are a ``fori_loop`` over the head-major copy of
    the step's K and V that Mosaic unrolls: the body is traced once, not
    ``Hkv`` times (unrolled in Python, the four layers and two chunk buckets
    of ``command-a-plus.rag-sat`` paid 1.6 s more of tracing at every process
    start, compile cache warm or not: my chip runs, PR 30)."""
    from jax.experimental import pallas as pl  # deferred with pallas_call's

    i = pl.program_id(0)
    k_refs, v_refs = refs[:group], refs[group : 2 * group]
    o_ref, acc_ref, m_ref, l_ref, k_ref, v_ref = refs[2 * group :]
    entry, last = entry_ref[i], last_ref[i]

    @pl.when(group_ref[i] == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos = qpos_ref[0]                                   # [1, G*Sq]
    pos = entry * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (group * block_size, 1), 0)
    # a step past the tile's last block repeats it: those keys are no one's
    pos = jnp.where(pos < (last + 1) * block_size, pos, jnp.iinfo(jnp.int32).max)
    seen = pos <= qpos                                   # [N*bs, G*Sq]
    if window is not None:
        seen = seen & (pos > qpos - window)
    unseen = jnp.where(seen, 0.0, -jnp.inf)              # added to the scores

    def head_major(refs):  # N blocks [bs, Hkv, D] -> [Hkv, N*bs, D]
        x = jnp.concatenate([r[0] for r in refs])
        # (the relayout goes through f32: Mosaic moves 32-bit sublanes)
        return x.astype(jnp.float32).transpose(1, 0, 2).astype(x.dtype)

    k_ref[...] = head_major(k_refs)
    v_ref[...] = head_major(v_refs)

    def fold(h, carry):  # one key head and its G query heads
        s = _dot_nt2(k_ref[h], q_ref[0, h]) * scale + unseen  # [N*bs, G*Sq] f32
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))  # [1, G*Sq]
        # a query whose window starts after this step keeps m at -inf:
        # exp(-inf - -inf) would be NaN, so clamp the shift (all is 0-weighted)
        shift = jnp.where(m_new > -jnp.inf, m_new, 0.0)
        alpha = jnp.exp(m_prev - shift)
        p = jnp.exp(s - shift)                           # masked -> 0
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + _dot_tn2(v_ref[h], p.astype(v_ref.dtype))  # [D, G*Sq]
        m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, k_ref.shape[0], fold, 0, unroll=True)

    @pl.when(entry + group > last)  # the tile's last step
    def _finalize():
        # (a padded query whose whole window lies past the table saw nothing: 0, not 0 / 0)
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_attention_prefill(
    q, k_pool, v_pool, block_tables, q_positions, scale=None, *, window=None, interpret=False
):
    """Pallas paged chunked-prefill attention: q ``[B, S, H, D]`` (``S > 1``)
    against per-layer pools ``[num_blocks, block_size, Hkv, D]`` through
    ``block_tables [B, W]``, with per-query absolute positions
    ``q_positions [B, S]`` (a query's index in its row: its position, or where
    a row holds pooled entries ahead of raw ones, its index in the composed
    row, :func:`pooled_row_view`; the mask is the same). The engine has
    already scatter-written the chunk's own KV into the pool, so one walk
    over a row's block table covers
    both the previously-landed KV and the in-chunk causal part; the per-query
    position mask is what makes the online softmax match the gather
    reference's causal masking. The gathered ``[B, W*block_size]`` cache the
    XLA reference materializes per layer never exists.

    The queries are cut into tiles of ``Sq`` (:func:`_prefill_query_tile`) and
    laid out key-head major, ``[B*S/Sq, Hkv, G*Sq, D]`` (one XLA transpose in,
    one out), so the ``G`` query heads of a key head are one matmul operand.
    Each tile walks the table entries that hold keys its queries can see,
    ``N`` blocks a grid step (:func:`_prefill_group_blocks`), the next step's
    blocks in flight while this step's are computed
    (:func:`_paged_prefill_kernel`); the length of the grid comes from the
    positions at run time, so the padding of a bucketed table and the blocks
    ahead of a tile are neither fetched nor computed on
    (:func:`prefill_walk_blocks` counts the walk on the host).
    A static ``window`` (None: full causal attention, named ``paged_prefill``)
    makes a query see its last ``window`` positions only, itself among them;
    a tile's walk then starts at the block its first query's window starts
    in, under the name ``paged_prefill_win``. ``interpret=True`` runs the
    identical kernel through the Pallas interpreter (the CPU parity path in
    tier-1 CI)."""
    from jax.experimental import pallas as pl_  # deferred: CPU-only installs
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    if S < 2:
        raise ValueError(f"prefill kernel wants S>1 queries, got S={S}")
    block_size, Hkv = k_pool.shape[1:3]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    W = block_tables.shape[1]
    G = H // Hkv
    scale = (1.0 / math.sqrt(D)) if scale is None else float(scale)
    window = None if window is None else int(window)
    Sq, N = prefill_tiling(S, H, Hkv, D, block_size, k_pool.dtype, W)
    T = S // Sq  # query tiles a row
    steps = pl_.cdiv(W, N)  # of a tile that walks the whole table

    # The walk, from the positions (the same small integer arrays for every
    # layer of a step): tile t = b*T + i takes grid steps ends[t-1]..ends[t]-1.
    q_positions = jnp.asarray(q_positions, jnp.int32)
    tiled = q_positions.reshape(B * T, Sq)
    first, last, count = _prefill_walk(
        jnp.min(tiled, axis=1), jnp.max(tiled, axis=1), block_size, W, N, window)
    ends = jnp.cumsum(count)
    step = jnp.arange(B * T * steps, dtype=jnp.int32)
    # (steps past ends[-1] never run; their entries only have to stay in range)
    done = step[:, None] >= ends  # [B*T*steps, B*T]: tile t ends before this step
    step_tile = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), B * T - 1)
    step_group = step - jnp.max(jnp.where(done, ends, 0), axis=1)

    step_entry = first[step_tile] + step_group * N
    step_last = last[step_tile]
    # the physical block of step i's j-th operand: its table entry, or the
    # tile's last one where the step reaches past it
    walk = block_tables.astype(jnp.int32)[
        (step_tile // T)[:, None],
        jnp.minimum(step_entry[:, None] + jnp.arange(N, dtype=jnp.int32), step_last[:, None]),
    ].reshape(-1)

    def pool_block(j):
        return pl_.BlockSpec(
            (1, block_size, Hkv, D), lambda i, walk, *_: (walk[i * N + j], 0, 0, 0))

    def of_tile(*block):
        return pl_.BlockSpec(
            (1,) + block, lambda i, walk, tile, *_: (tile[i],) + (0,) * len(block))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(ends[-1],),
        in_specs=[of_tile(1, G * Sq), of_tile(Hkv, G * Sq, D)]
        + 2 * [pool_block(j) for j in range(N)],
        out_specs=of_tile(Hkv, D, G * Sq),
        scratch_shapes=[
            pltpu.VMEM((Hkv, D, G * Sq), jnp.float32),
            pltpu.VMEM((Hkv, 1, G * Sq), jnp.float32),
            pltpu.VMEM((Hkv, 1, G * Sq), jnp.float32),
            pltpu.VMEM((Hkv, N * block_size, D), k_pool.dtype),
            pltpu.VMEM((Hkv, N * block_size, D), v_pool.dtype),
        ],
    )
    kernel = partial(
        _paged_prefill_kernel, block_size=block_size, group=N, scale=scale, window=window)
    out = pl_.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * T, Hkv, D, G * Sq), q.dtype),
        interpret=interpret,
        name="paged_prefill" if window is None else "paged_prefill_win",
    )(
        walk,
        step_tile,
        step_group,
        step_entry,
        step_last,
        # a query row of a key head is (query head g, query s): g*Sq + s
        jnp.broadcast_to(tiled[:, None, :], (B * T, G, Sq)).reshape(B * T, 1, G * Sq),
        # query head h = kv_head * G + g: [B, T, Sq, Hkv, G, D] -> key-head major
        q.reshape(B, T, Sq, Hkv, G, D).transpose(0, 1, 3, 4, 2, 5).reshape(B * T, Hkv, G * Sq, D),
        *(N * [k_pool] + N * [v_pool]),
    )
    # back to [B, S, H, D], the caller's BSHD contract
    return out.reshape(B, T, Hkv, D, G, Sq).transpose(0, 1, 5, 2, 4, 3).reshape(B, S, H, D)


def paged_attention_gather(q, k_pool, v_pool, block_tables, q_positions, scale=None,
                           window=None):
    """The XLA twin of the paged kernels, and their reference semantics.

    q ``[B, S, H, D]``; per-layer pools ``[num_blocks, block_size, Hkv, D]``;
    ``block_tables [B, W]`` (physical block ids, null-padded);
    ``q_positions [B, S]`` per-row absolute positions. Gathers each row's
    blocks into a contiguous ``[B, W*block_size, Hkv, D]`` view and runs the
    shared masked-attention core: a slot at gathered position ``t`` holds
    logical token ``t`` of that sequence, and only slots with ``t <=
    q_position`` are attended, so null/stale slots are masked to an exact
    0 contribution (bitwise parity with the contiguous path,
    ``generation._cached_attention``). With a static ``window`` a query also
    sees nothing at or before ``q_position - window`` (a sliding-window layer:
    the kernels' predicate)."""
    B = q.shape[0]
    k_cache = k_pool[block_tables].reshape(B, -1, k_pool.shape[2], k_pool.shape[3])
    v_cache = v_pool[block_tables].reshape(B, -1, v_pool.shape[2], v_pool.shape[3])
    kv_pos = jnp.arange(k_cache.shape[1])
    allow = kv_pos[None, None, :] <= q_positions[:, :, None]  # [B, S, T]
    if window is not None:
        allow = allow & (kv_pos[None, None, :] > q_positions[:, :, None] - window)
    return masked_attention(q, k_cache, v_cache, allow[:, None], scale)


def paged_attention(q, k_pool, v_pool, block_tables, q_positions, scale=None, window=None):
    """Paged attention for the serving engine (kernel dispatch point).

    q ``[B, S, H, D]``; per-layer pools ``[num_blocks, block_size, Hkv, D]``;
    ``block_tables [B, W]`` (physical block ids, null-padded); ``q_positions
    [B, S]``. On the TPU backend BOTH serving shapes dispatch to Pallas
    paged kernels: single-token decode (``S == 1``) to
    :func:`paged_attention_decode` and chunked prefill / multi-token verify
    (``S > 1``) to :func:`paged_attention_prefill` — block-table walk + VMEM
    block streaming + online softmax, no materialized gathered KV per layer.
    Every other backend runs :func:`paged_attention_gather` (gather blocks by
    table, shared masked-attention core — bitwise-identical to contiguous
    decode), exactly like :func:`flash_attention`'s pallas-vs-xla split.
    ``ACCELERATE_PAGED_KERNEL=interpret`` forces the kernels (interpreter
    mode) on any backend so CPU CI can drive the kernel dataflow through
    the full engine. A static ``window`` (a sliding-window layer: a query at
    position ``p`` sees ``p - window < kv_pos <= p``) is the same predicate on
    all three paths; None is full causal attention, every program as it was."""
    interpret = paged_kernel_mode() == "interpret"
    if interpret or jax.default_backend() == "tpu":
        windowed = {} if window is None else {"window": window}  # None: the call as it was
        if q.shape[1] == 1:
            return paged_attention_decode(
                q, k_pool, v_pool, block_tables, q_positions[:, 0] + 1,
                scale, interpret=interpret, **windowed,
            )
        return paged_attention_prefill(
            q, k_pool, v_pool, block_tables, q_positions,
            scale, interpret=interpret, **windowed,
        )
    return paged_attention_gather(q, k_pool, v_pool, block_tables, q_positions, scale, window)


def paged_write_attend(q, k, v, k_pool, v_pool, layer, block_tables, positions,
                       block_size: int, window=None):
    """One layer's step against the whole pool, the one place that knows
    where a position lives. ``k_pool``, ``v_pool`` are the stacks of ALL
    layers, ``[L, num_blocks, block_size, Hkv, D]``, and ``layer`` (a Python
    int or a traced scalar) says whose step this is: token ``positions[b, s]``
    of row ``b`` is slot ``pos % block_size`` of block ``layer * num_blocks +
    block_tables[b, pos // block_size]`` of the flat view ``[L * num_blocks,
    block_size, Hkv, D]`` (a reshape of the leading two axes: a bitcast).
    Writes ``k``, ``v`` ``[B, S, Hkv, D]`` there (in the pool's dtype), then
    attends ``q [B, S, H, D]`` over the row's blocks (:func:`paged_attention`
    on the flat view and the offset tables, with ``window`` or none). A
    position past the table (a padded prefill tail) and every position of an
    idle slot (its table is all null) write to the LAYER'S null block, ``layer
    * num_blocks + NULL_BLOCK`` — a pad write may never land in a live block,
    nor in another layer. The stack is never taken apart, so a program that
    donates the pool updates it in place. Where the pool's rows hold several
    key heads (:func:`init_block_pool`: its last axis is that many times
    ``D``), the layer's heads are packed to them on the way in and each query
    head's own segment taken on the way out (:func:`_pack_heads`): the caller
    sees neither. Returns ``(attn [B, S, H, D], k_pool, v_pool)``, the stacks
    in the shape they came in."""
    shape = k_pool.shape
    Hkv, D = k.shape[2:]
    pack = shape[-1] // D  # key heads to a row of the pool
    scale = None
    if pack > 1:
        q, k, v = _pack_heads(q, k, v, pack)
        scale = D ** -0.5
    base = layer * shape[1]
    W = block_tables.shape[1]
    logical = positions // block_size
    phys = jnp.take_along_axis(block_tables, jnp.minimum(logical, W - 1), axis=1)
    phys = base + jnp.where(logical < W, phys, NULL_BLOCK)
    off = positions % block_size
    k_flat = k_pool.reshape(-1, *shape[2:]).at[phys, off].set(k.astype(k_pool.dtype))
    v_flat = v_pool.reshape(-1, *shape[2:]).at[phys, off].set(v.astype(v_pool.dtype))
    attn = paged_attention(
        q, k_flat, v_flat, base + block_tables, positions, scale, window=window)
    if pack > 1:
        attn = _unpack_heads(attn, pack, Hkv)
    return attn, k_flat.reshape(shape), v_flat.reshape(shape)


# ------------------------------------------------------------ pooled rows
#
# A model whose attention reads exact keys inside a window and ONE pooled key /
# value pair for every `chunk` positions behind it (EVA attention,
# ``models/evabyte.py``) keeps a row of two kinds in the same pool, the same
# blocks, under the same allocator. The stored row (``serving/kv_pager.py``
# writes it) is ``[raw slots][summary blocks of window 0][of window 1]...``:
# ``window / block_size`` raw slots hold the open window's keys and values (the
# same slots for every window: a window's blocks are handed back when it
# closes), and behind them every window up to the open one has ``window /
# (chunk * block_size)`` summary blocks, one pooled pair a slot. A chunk is one
# block (``chunk == block_size``), so the chunk a pooled pair stands for is one
# raw slot. What the paged kernels walk is the COMPOSED row,
# ``[summary blocks of the closed windows][raw slots]``, which a step program
# builds from the stored one (:func:`pooled_row_view`): a query's index in it
# is what the kernels mask by, so the closed windows' pairs and the open
# window's keys up to the query's own form ONE softmax, and neither kernel
# knows there are two kinds.


def pooled_row_layout(window: int, chunk: int, block_size: int) -> "tuple[int, int]":
    """``(raw, per_window)`` of a pooled row: its raw slots (one window's
    positions, ``window / block_size`` blocks) and the summary blocks a window
    adds behind them (``window / chunk`` pooled pairs, ``block_size`` a block).
    Refuses a chunk that is not one block and a window that is not whole
    summary blocks."""
    if chunk != block_size or window % (chunk * block_size):
        raise ValueError(
            f"a pooled row wants chunk == block_size and whole summary blocks a window; "
            f"got window {window}, chunk {chunk}, block_size {block_size}")
    return window // block_size, window // (chunk * block_size)


def pooled_row_index(positions, window: int, chunk: int):
    """Where each of ``positions`` (integers or arrays, numpy's or traced)
    stands in the composed row of its window ``w = position // window``: the
    ``window / chunk`` pooled pairs of each closed window, then its place in
    its own window."""
    w = positions // window
    return window // chunk * w + positions - window * w


def pooled_row_view(block_tables, positions, window: int, chunk: int, block_size: int):
    """``(composed [B, W], row_positions [B, S])`` from stored pooled rows
    ``block_tables [B, W]`` and the queries' true ``positions [B, S]`` (a
    call's queries lie in one window, ``w = positions[:, 0] // window``): the
    row the paged kernels walk, ``[summary blocks of windows 0..w-1][raw
    slots]`` and null past them, and each query's index in it, ``(window /
    chunk) * w + position - window * w`` (:func:`pooled_row_index`). A query
    sees every pooled pair of the closed windows and its own window's keys up
    to itself; the open window's summary blocks are not in the composed
    row."""
    raw, per_window = pooled_row_layout(window, chunk, block_size)
    width = block_tables.shape[1]
    w = positions[:, :1] // window
    closed = per_window * w                                  # [B, 1] summary blocks before the raw
    j = jnp.arange(width, dtype=jnp.int32)[None]
    src = jnp.where(j < closed, raw + j, j - closed)
    keep = (j < closed) | (j - closed < raw)
    composed = jnp.where(
        keep, jnp.take_along_axis(block_tables, jnp.minimum(src, width - 1), axis=1), NULL_BLOCK)
    return composed.astype(block_tables.dtype), pooled_row_index(positions, window, chunk)


def _pooled_fold_kernel(blocks_ref, k_ref, v_ref, mu_ref, phi_ref, kbar_ref, vbar_ref):
    """One chunk: ``k, v [chunk, Hkv, D]`` as they lie in the pool, folded
    into ``kbar = sum_j softmax_j(k_j . mu) k_j`` and ``vbar = sum_j
    softmax_j(k_j . phi) v_j`` per key head, float32 all through."""
    del blocks_ref  # read by the index maps
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    def pool(weights, x):
        s = jnp.sum(k * weights, axis=-1, keepdims=True)          # [chunk, Hkv, 1]
        p = jnp.exp(s - jnp.max(s, axis=0, keepdims=True))
        return jnp.sum(p * x, axis=0) / jnp.sum(p, axis=0)         # [Hkv, D]

    kbar_ref[0] = pool(mu_ref[...].astype(jnp.float32), k).astype(kbar_ref.dtype)
    vbar_ref[0] = pool(phi_ref[...].astype(jnp.float32), v).astype(vbar_ref.dtype)


def paged_fold_kernel(k_flat, v_flat, blocks, mu, phi, *, interpret=False):
    """Pallas fold of the chunks in physical blocks ``blocks [M]`` of the flat
    pool ``[blocks, block_size, Hkv, D]`` into their pooled pairs ``(kbar,
    vbar) [M, Hkv, D]`` in the pool's dtype (:func:`_pooled_fold_kernel`),
    one chunk a grid step, fetched through a BlockSpec whose index map reads
    ``blocks``; a run of equal ids (the null block of the rows that fold
    nothing) is fetched once. Named ``eva_fold`` in a device trace."""
    from jax.experimental import pallas as pl_  # deferred: CPU-only installs
    from jax.experimental.pallas import tpu as pltpu

    _, block_size, Hkv, D = k_flat.shape
    M = blocks.shape[0]
    chunk = pl_.BlockSpec((1, block_size, Hkv, D), lambda i, b: (b[i], 0, 0, 0))
    head = pl_.BlockSpec((Hkv, D), lambda i, b: (0, 0))
    out = pl_.BlockSpec((1, Hkv, D), lambda i, b: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(M,), in_specs=[chunk, chunk, head, head],
        out_specs=[out, out])
    shape = jax.ShapeDtypeStruct((M, Hkv, D), k_flat.dtype)
    return pl_.pallas_call(
        _pooled_fold_kernel, grid_spec=grid_spec, out_shape=[shape, shape],
        interpret=interpret, name="eva_fold",
    )(blocks.astype(jnp.int32), k_flat, v_flat, mu, phi)


def paged_fold_gather(k_flat, v_flat, blocks, mu, phi):
    """The XLA twin of :func:`paged_fold_kernel`, and its reference
    semantics: gather the chunks, the same float32 softmax pooling."""
    k = k_flat[blocks].astype(jnp.float32)                        # [M, chunk, Hkv, D]
    v = v_flat[blocks].astype(jnp.float32)

    def pool(weights, x):
        p = jax.nn.softmax(jnp.sum(k * weights.astype(jnp.float32), axis=-1), axis=1)
        return jnp.einsum("mjh,mjhd->mhd", p, x)

    return pool(mu, k).astype(k_flat.dtype), pool(phi, v).astype(v_flat.dtype)


def paged_write_fold_attend(q, k, v, k_pool, v_pool, layer, block_tables, positions, valid,
                            mu, phi, *, window: int, chunk: int, block_size: int):
    """One layer's step of a model with pooled rows against the whole pool:
    :func:`paged_write_attend` over the composed row (:func:`pooled_row_view`:
    the keys and values written into the open window's raw slots, the queries
    attending the closed windows' pooled pairs and their own window's keys),
    then every chunk whose last position a query of this call holds (``valid``
    and ``position % chunk == chunk - 1``) folded from its raw block into its
    pair in the open window's summary blocks, under the named scope
    ``eva_fold`` (the kernel on the TPU or under
    ``ACCELERATE_PAGED_KERNEL=interpret``, :func:`paged_fold_gather`
    elsewhere). A call of ``S`` queries that is a whole number of chunks
    starts at a chunk's first position (the engine cuts a prefill so), and
    only its chunks' last queries are looked at; a decode step (``S = 1``)
    looks at every row. Where a row folds nothing the kernel reads and the
    scatter writes the layer's null block. ``mu``, ``phi [Hkv, D]``: the
    layer's pooling vectors. Returns ``(attn, k_pool, v_pool)``."""
    composed, row_pos = pooled_row_view(block_tables, positions, window, chunk, block_size)
    attn, k_pool, v_pool = paged_write_attend(
        q, k, v, k_pool, v_pool, layer, composed, row_pos, block_size)
    raw, per_window = pooled_row_layout(window, chunk, block_size)
    shape = k_pool.shape
    if shape[-1] != k.shape[-1]:
        raise ValueError("a pooled row wants one key head a row of the pool (kv_lane_pack 1)")
    with jax.named_scope("eva_fold"):
        S = positions.shape[1]
        at = np.arange(chunk - 1, S, chunk) if S % chunk == 0 else np.arange(S)
        pos, ok = positions[:, at], valid[:, at] & (positions[:, at] % chunk == chunk - 1)
        w = pos // window
        s = (pos - window * w) // chunk                              # the chunk's index in its window
        src = jnp.take_along_axis(block_tables, s, axis=1)          # its raw slot
        dst = jnp.take_along_axis(block_tables, raw + per_window * w + s // block_size, axis=1)
        base = layer * shape[1]
        src = base + jnp.where(ok, src, NULL_BLOCK).reshape(-1)
        dst = base + jnp.where(ok, dst, NULL_BLOCK).reshape(-1)
        off = jnp.where(ok, s % block_size, 0).reshape(-1)
        k_flat, v_flat = k_pool.reshape(-1, *shape[2:]), v_pool.reshape(-1, *shape[2:])
        interpret = paged_kernel_mode() == "interpret"
        if interpret or jax.default_backend() == "tpu":
            kbar, vbar = paged_fold_kernel(k_flat, v_flat, src, mu, phi, interpret=interpret)
        else:
            kbar, vbar = paged_fold_gather(k_flat, v_flat, src, mu, phi)
        k_flat = k_flat.at[dst, off].set(kbar)
        v_flat = v_flat.at[dst, off].set(vbar)
    return attn, k_flat.reshape(shape), v_flat.reshape(shape)
