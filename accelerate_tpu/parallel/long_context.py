"""Long-context attention parallelism: ring attention (CP) and Ulysses (SP).

TPU-native counterpart of the reference's two mutually-exclusive long-context
backends (SURVEY.md §5 "Long-context / sequence parallelism"):

- **CP / ring attention** — reference ``_prepare_cp`` (``accelerator.py:1628``) +
  ``maybe_context_parallel`` (``:4056-4120``) wrap torch's experimental
  ``context_parallel`` with allgather/alltoall KV rotation. Here: the sequence
  dim is sharded over the ``cp`` mesh axis; K/V blocks rotate around the ICI
  ring with ``lax.ppermute`` inside ``shard_map`` while a flash-style online
  softmax accumulates — O(S/cp) memory per chip, fully overlapped
  compute/communication, differentiable end-to-end. ``rotate="allgather"``
  instead gathers KV once (better for short rings).
- **SP / Ulysses** — reference DeepSpeed ALST path (``accelerator.py:2344-2456``):
  head-sharded attention via all-to-all. Here: ``lax.all_to_all`` reshards
  seq-sharded QKV to head-sharded, runs full-sequence attention locally, and
  reshards back.

Both produce an ``attention_fn(q, k, v, causal=...)`` over GLOBAL [B, S, H, D]
arrays, drop-in for ``models``' pluggable attention hook.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallelism_config import DP_AXES


NEG_INF = -1e30


def _block_attn(q, k, v, mask, scale):
    """One flash block: returns (unnormalized out, row max, row sumexp).

    q: [B, Hq, Sq, D]; k,v: [B, Hq, Skv, D]; mask: [Sq, Skv] bool or None.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return o, m, l


def _merge_blocks(o, m, l, o_new, m_new, l_new):
    """Online-softmax merge of two partial attention results."""
    m_tot = jnp.maximum(m, m_new)
    c_old = jnp.exp(m - m_tot)
    c_new = jnp.exp(m_new - m_tot)
    o = o * c_old[..., None].astype(o.dtype) + o_new * c_new[..., None].astype(o.dtype)
    l = l * c_old + l_new * c_new
    return o, m_tot, l


def _local_ring_attention(q, k, v, *, axis_name: str, axis_size: int, causal: bool, scale: float):
    """Runs INSIDE shard_map: q,k,v are the local seq shards [B, S_loc, H, D]."""
    cp = axis_size
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    # head-major layout for the block kernel
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    if kh.shape[1] != qh.shape[1]:  # GQA: replicate kv heads
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)

    perm = [(i, (i + 1) % cp) for i in range(cp)]
    rows = jnp.arange(S)

    def _mask_for(src):
        if not causal:
            return None
        # global positions: q at idx*S + row, kv at src*S + col
        q_pos = idx * S + rows[:, None]
        k_pos = src * S + rows[None, :]
        return q_pos >= k_pos

    # step 0 is the resident (diagonal) block: no rotation needed, and doing it
    # first means the scan issues exactly cp-1 ppermutes — the final rotation
    # would only restore the starting layout, which nobody reads.
    o, m, l = _block_attn(qh, kh, vh, _mask_for(idx), scale)

    def body(carry, step):
        o, m, l, k_cur, v_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (idx - step) % cp  # global chunk index held after `step` rotations
        o_new, m_new, l_new = _block_attn(qh, k_cur, v_cur, _mask_for(src), scale)
        o, m, l = _merge_blocks(o, m, l, o_new, m_new, l_new)
        return (o, m, l, k_cur, v_cur), None

    if cp > 1:
        (o, m, l, _, _), _ = jax.lax.scan(body, (o, m, l, kh, vh), jnp.arange(1, cp))
    out = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _zigzag_perms(cp: int):
    """Static lane permutations for the zig-zag exchange.

    Global HALF-chunks are numbered 0..2cp-1; contiguous layout puts chunks
    (2i, 2i+1) on rank i, zig-zag layout puts (i, 2cp-1-i) on rank i. Chunk c's
    zig-zag home is ``c if c < cp else 2cp-1-c``. Routing lane A (each rank's
    first half, chunk 2i) and lane B (second half, 2i+1) separately makes each
    lane's routing a bijection on ranks → one ``ppermute`` per lane."""
    home = lambda c: c if c < cp else 2 * cp - 1 - c
    perm_a = [(i, home(2 * i)) for i in range(cp)]
    perm_b = [(i, home(2 * i + 1)) for i in range(cp)]
    inv_a = [(dst, src) for src, dst in perm_a]
    inv_b = [(dst, src) for src, dst in perm_b]
    # chunk id arriving in each lane at rank r (for low/high normalization)
    lane_a_chunk = [0] * cp
    lane_b_chunk = [0] * cp
    for i in range(cp):
        lane_a_chunk[home(2 * i)] = 2 * i
        lane_b_chunk[home(2 * i + 1)] = 2 * i + 1
    return perm_a, perm_b, inv_a, inv_b, lane_a_chunk, lane_b_chunk


def _local_zigzag_attention(q, k, v, *, axis_name: str, axis_size: int, causal: bool, scale: float):
    """Load-balanced causal ring attention (zig-zag chunk placement).

    The contiguous ring computes every (q-shard × kv-shard) block and masks the
    upper-triangle half away — wasted MXU work that also skews per-rank useful
    FLOPs (SURVEY §7 hard part: "load-balancing zig-zag order"; same trick as
    llama3/ring-flash-attention's striped layout). Re-placing half-chunks so
    rank i holds global half-chunks ``(i, 2cp-1-i)`` makes every rotation step
    need exactly TWO half-blocks of UNMASKED attention on every rank —
    half the block-FLOPs of the contiguous schedule, perfectly balanced.

    Data stays contiguous outside: the exchange (2 ppermutes in, 2 out) is
    internal. The rotation loop is unrolled (cp is static, the per-step
    operand selection is a cheap ``where``); fully-masked blocks are simply
    never computed.
    """
    cp = axis_size
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    if S % 2 != 0:
        raise ValueError(f"zigzag CP needs an even local sequence shard, got {S}")
    half = S // 2
    perm_a, perm_b, inv_a, inv_b, lane_a_chunk, lane_b_chunk = _zigzag_perms(cp)
    lane_a_chunk = jnp.asarray(lane_a_chunk)
    lane_b_chunk = jnp.asarray(lane_b_chunk)

    def heads_major(x):
        return x.transpose(0, 2, 1, 3)  # [B, H, S, D]

    qh, kh, vh = heads_major(q), heads_major(k), heads_major(v)
    if kh.shape[1] != qh.shape[1]:  # GQA
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)

    def exchange(x):  # contiguous halves → zigzag lanes
        a = jax.lax.ppermute(x[:, :, :half], axis_name, perm_a)
        b = jax.lax.ppermute(x[:, :, half:], axis_name, perm_b)
        return a, b

    qa, qb = exchange(qh)
    ka, kb = exchange(kh)
    va, vb = exchange(vh)
    # normalize lanes to (low chunk = idx, high chunk = 2cp-1-idx)
    a_is_low = (lane_a_chunk[idx] < lane_b_chunk[idx])[None, None, None, None]

    def pick(low_first, a, b):
        cond = a_is_low if low_first else ~a_is_low
        return jnp.where(cond, a, b)

    q_lo, q_hi = pick(True, qa, qb), pick(False, qa, qb)
    k_lo, k_hi = pick(True, ka, kb), pick(False, ka, kb)
    v_lo, v_hi = pick(True, va, vb), pick(False, va, vb)

    tril = jnp.tril(jnp.ones((half, half), dtype=bool))
    # resident step: q_lo×kv_lo and q_hi×kv_hi are causal diagonals;
    # q_hi×kv_lo is a full block (high chunk id > low chunk id always)
    o_lo, m_lo, l_lo = _block_attn(q_lo, k_lo, v_lo, tril, scale)
    o_hi, m_hi, l_hi = _block_attn(q_hi, k_hi, v_hi, tril, scale)
    o_hi, m_hi, l_hi = _merge_blocks(o_hi, m_hi, l_hi, *_block_attn(q_hi, k_lo, v_lo, None, scale))

    shift = [(i, (i + 1) % cp) for i in range(cp)]

    def body(carry, step):
        (o_lo, m_lo, l_lo, o_hi, m_hi, l_hi, k_lo_c, k_hi_c, v_lo_c, v_hi_c) = carry
        k_lo_c = jax.lax.ppermute(k_lo_c, axis_name, shift)
        k_hi_c = jax.lax.ppermute(k_hi_c, axis_name, shift)
        v_lo_c = jax.lax.ppermute(v_lo_c, axis_name, shift)
        v_hi_c = jax.lax.ppermute(v_hi_c, axis_name, shift)
        j = (idx - step) % cp  # low chunk id of the kv pair now held
        pred = (j < idx)[None, None, None, None]
        # j < idx: needed blocks are (q_lo, kv_lo) and (q_hi, kv_lo)
        # j > idx: needed blocks are (q_hi, kv_lo) and (q_hi, kv_hi)
        # — always two FULL (unmasked) half-blocks; see _zigzag_perms docstring
        qa_sel = jnp.where(pred, q_lo, q_hi)
        ob_a, mb_a, lb_a = _block_attn(qa_sel, k_lo_c, v_lo_c, None, scale)
        kv_sel_k = jnp.where(pred, k_lo_c, k_hi_c)
        kv_sel_v = jnp.where(pred, v_lo_c, v_hi_c)
        ob_b, mb_b, lb_b = _block_attn(q_hi, kv_sel_k, kv_sel_v, None, scale)
        # block A merges into acc_lo when j<idx, else into acc_hi
        pm = pred[..., 0]  # [1,1,1] broadcast over [B,H,Sq]
        n_lo = _merge_blocks(o_lo, m_lo, l_lo, ob_a, mb_a, lb_a)
        n_hi = _merge_blocks(o_hi, m_hi, l_hi, ob_a, mb_a, lb_a)
        o_lo = jnp.where(pred, n_lo[0], o_lo)
        m_lo = jnp.where(pm, n_lo[1], m_lo)
        l_lo = jnp.where(pm, n_lo[2], l_lo)
        o_hi = jnp.where(pred, o_hi, n_hi[0])
        m_hi = jnp.where(pm, m_hi, n_hi[1])
        l_hi = jnp.where(pm, l_hi, n_hi[2])
        # block B always belongs to acc_hi
        o_hi, m_hi, l_hi = _merge_blocks(o_hi, m_hi, l_hi, ob_b, mb_b, lb_b)
        return (o_lo, m_lo, l_lo, o_hi, m_hi, l_hi, k_lo_c, k_hi_c, v_lo_c, v_hi_c), None

    if cp > 1:
        (o_lo, m_lo, l_lo, o_hi, m_hi, l_hi, *_), _ = jax.lax.scan(
            body,
            (o_lo, m_lo, l_lo, o_hi, m_hi, l_hi, k_lo, k_hi, v_lo, v_hi),
            jnp.arange(1, cp),
        )

    out_lo = o_lo / jnp.maximum(l_lo, 1e-30)[..., None].astype(o_lo.dtype)
    out_hi = o_hi / jnp.maximum(l_hi, 1e-30)[..., None].astype(o_hi.dtype)
    # restore lanes, then un-exchange back to the contiguous layout
    lane_a = jnp.where(a_is_low, out_lo, out_hi)
    lane_b = jnp.where(a_is_low, out_hi, out_lo)
    first = jax.lax.ppermute(lane_a, axis_name, inv_a)
    second = jax.lax.ppermute(lane_b, axis_name, inv_b)
    out = jnp.concatenate([first, second], axis=2)  # [B, H, S, D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _local_allgather_attention(q, k, v, *, axis_name: str, axis_size: int, causal: bool, scale: float):
    """CP with one-shot KV allgather (reference rotate_method='allgather')."""
    cp = axis_size
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    k_full = jax.lax.all_gather(k, axis_name, axis=1, tiled=True)  # [B, S*cp, Hkv, D]
    v_full = jax.lax.all_gather(v, axis_name, axis=1, tiled=True)
    qh = q.transpose(0, 2, 1, 3)
    kh = k_full.transpose(0, 2, 1, 3)
    vh = v_full.transpose(0, 2, 1, 3)
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    mask = None
    if causal:
        q_pos = idx * S + jnp.arange(S)[:, None]
        k_pos = jnp.arange(S * cp)[None, :]
        mask = q_pos >= k_pos
    o, m, l = _block_attn(qh, kh, vh, mask, scale)
    out = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _local_ulysses_attention(q, k, v, *, axis_name: str, axis_size: int, causal: bool, scale: float):
    """Runs INSIDE shard_map over the sp axis: local [B, S_loc, H, D] →
    all-to-all → [B, S, H_loc, D] → full-seq attention → all-to-all back
    (reference UlyssesSPAttentionHF head-sharding, accelerator.py:2344-2390)."""
    from ..ops.attention import _xla_attention

    def seq_to_head(x):
        # split heads across the axis, concat sequence
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def head_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    q_h, k_h, v_h = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    out = _xla_attention(q_h, k_h, v_h, causal=causal, mask=None, scale=scale)
    return head_to_seq(out)


def make_context_parallel_attention(
    mesh,
    strategy: str = "ring",  # "ring" | "zigzag" | "allgather" | "ulysses"
    axis_name: Optional[str] = None,
    batch_axes: tuple = DP_AXES,
    head_axis: str = "tp",
):
    """Build an attention_fn over GLOBAL [B, S, H, D] arrays that parallelizes the
    sequence dim over ``cp`` (ring/allgather) or ``sp`` (ulysses).

    The returned function is jit-compatible and differentiable; it is the
    ``attention_fn`` hook of the model family (the moral twin of the reference's
    ``maybe_context_parallel`` buffer-sharding context, ``accelerator.py:4056``).
    """
    from jax import shard_map

    if axis_name is None:
        axis_name = "sp" if strategy == "ulysses" else "cp"
    axis_size = mesh.shape.get(axis_name, 1)
    head_axis_in_mesh = head_axis if mesh.shape.get(head_axis, 1) > 1 else None

    local_fn = {
        "ring": _local_ring_attention,
        "zigzag": _local_zigzag_attention,
        "allgather": _local_allgather_attention,
        "ulysses": _local_ulysses_attention,
    }[strategy]

    def attention_fn(q, k, v, causal: bool = True, scale: Optional[float] = None):
        if axis_size <= 1:
            from ..ops.attention import dot_product_attention

            return dot_product_attention(q, k, v, causal=causal, scale=scale)
        fn_local = local_fn
        if strategy == "zigzag" and not causal:
            # without causal masking every block is needed — the balanced
            # placement buys nothing; use the plain ring
            fn_local = _local_ring_attention
        scale_v = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
        if strategy == "ulysses" and (
            q.shape[2] % axis_size != 0 or k.shape[2] % axis_size != 0
        ):
            raise ValueError(
                f"Ulysses SP needs q heads ({q.shape[2]}) and kv heads ({k.shape[2]}) "
                f"divisible by sp size ({axis_size}); use ring CP for more chips than heads"
            )
        spec = P(batch_axes, axis_name, head_axis_in_mesh, None)
        fn = shard_map(
            partial(
                fn_local, axis_name=axis_name, axis_size=axis_size, causal=causal, scale=scale_v
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)

    return attention_fn


def sequence_parallel_attention(mesh, **kwargs):
    """Ulysses attention_fn (reference ALST/UlyssesSP path)."""
    return make_context_parallel_attention(mesh, strategy="ulysses", **kwargs)
