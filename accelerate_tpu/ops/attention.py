"""Attention ops with pluggable implementations.

The compute core every model routes through — and the swap point for
long-context parallelism (ring attention over ``cp``, Ulysses over ``sp``) and
Pallas flash kernels. The reference reaches flash/SDPA kernels through
transformers (SURVEY.md §2.3); here the kernel boundary is explicit.

Layouts: ``q,k,v: [batch, seq, heads, head_dim]`` (BSHD). GQA supported via
``num_kv_heads <= num_heads`` with head repetition folded into the einsum.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..logging import get_logger

logger = get_logger(__name__)


def segment_mask(segment_ids: jax.Array) -> jax.Array:
    """[B, S] ids → [B, 1, Sq, Skv] bool allow-mask: attend iff same id.

    The single definition of segment semantics — the xla path and the flash
    kernel's off-TPU reference both build their masks here so they cannot
    drift."""
    return segment_ids[:, None, :, None] == segment_ids[:, None, None, :]


def _repeat_kv(hidden: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] (GQA broadcast)."""
    if n_rep == 1:
        return hidden
    b, s, h, d = hidden.shape
    return jnp.broadcast_to(hidden[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,  # [B, 1|H, Sq, Skv] additive or bool
    segment_ids: Optional[jax.Array] = None,  # [B, S] int; padding = 0
    scale: Optional[float] = None,
    window: Optional[int] = None,  # sliding window: attend iff 0 <= i-j < window
    impl: str = "auto",
) -> jax.Array:
    """Standard softmax attention, BSHD layout.

    ``impl``:

    - "xla" — einsum, fused by XLA on the MXU.
    - "flash" — the in-tree blocked streaming kernel (``ops.flash_attention``):
      online softmax, in-kernel GQA, block-sparse causal/window/segment
      skipping. On a TPU a shape the kernel cannot tile raises; it never gives
      way to the einsum path.
    - "auto" — picks flash vs xla from the crossover table
      (``ATTN_CROSSOVER_S``), keyed by dtype and mask sparsity. The only impl
      that may choose the einsum path itself; each choice is logged once per
      shape (:func:`_log_auto_choice`).

    Masking comes in three forms:

    - ``segment_ids`` — per-token ids for self-attention; position *i* attends
      *j* iff ``segment_ids[b, i] == segment_ids[b, j]``. Encode padding as id
      0 and real tokens as id 1 (or document ids for packed sequences). All
      impls support this form — padded models (BERT + attention_mask) keep
      kernel paths available.
    - ``window`` — causal sliding-window band (attend iff ``0 <= i-j <
      window``; requires ``causal=True``). Supported by the xla and flash
      paths; the flash kernel skips out-of-band blocks entirely.
    - ``mask`` — arbitrary [B, 1|H, Sq, Skv] bool/additive mask; forces the
      XLA einsum path (kernels cannot consult a full score-shaped mask).
    """
    if window is not None and not causal:
        raise ValueError(
            "window requires causal=True (the sliding window is a causal band)"
        )
    if impl == "auto":
        impl = (
            "flash"
            if mask is None and _flash_supported(q, k, causal=causal, window=window)
            else "xla"
        )
        _log_auto_choice(
            impl, tuple(q.shape), tuple(k.shape), str(q.dtype), causal, window,
            mask is not None,
        )
    if impl == "flash":
        if mask is not None:
            raise ValueError(
                "impl='flash' does not support an arbitrary mask (causal and "
                "segment_ids only); use impl='xla', or express padding/packing "
                "as segment_ids"
            )
        from .flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, scale=scale, segment_ids=segment_ids, window=window
        )
    if segment_ids is not None:
        seg_mask = segment_mask(segment_ids)
        if mask is None:
            mask = seg_mask
        elif mask.dtype == bool:
            mask = jnp.logical_and(mask, seg_mask)
        else:  # additive mask: fold the segment constraint in as -inf
            mask = mask + jnp.where(seg_mask, 0.0, jnp.finfo(jnp.float32).min)
    return _xla_attention(q, k, v, causal=causal, mask=mask, scale=scale, window=window)


# Flash-vs-xla crossover: "auto" takes the einsum path below the listed S and
# the streaming kernel at/after it. NOT measured on the current kernel (the
# table predates it; benchmarks/attention/run.py is the grid that would
# calibrate it on a chip). The ordering is by argument: sparser masks move the
# crossover EARLIER — the block-skip lattice drops whole tiles while the
# einsum path still materializes (and masks) every score — and f32 crosses
# earlier than bf16 because the f32 score tensor doubles the einsum path's
# HBM traffic while the kernel's VMEM accumulators are f32 either way.
ATTN_CROSSOVER_S = {
    ("bf16", "dense"): 512,
    ("bf16", "causal"): 384,
    ("bf16", "window"): 256,
    ("f32", "dense"): 384,
    ("f32", "causal"): 256,
    ("f32", "window"): 256,
}


@functools.lru_cache(maxsize=None)
def _log_auto_choice(impl, q_shape, k_shape, dtype, causal, window, has_mask) -> None:
    """One INFO line per distinct (choice, shape): ``impl="auto"`` is the one
    place a kernel may give way to the einsum path, so what it chose is on
    record. Cached on its arguments, i.e. once per shape per process (tracing
    calls this, not the compiled step)."""
    logger.info(
        f"attention impl=auto chose {impl!r}: q={q_shape} k={k_shape} {dtype} "
        f"causal={causal} window={window} mask={has_mask} on {jax.default_backend()}"
    )


def _flash_supported(q, k, *, causal: bool = False, window: Optional[int] = None) -> bool:
    from .flash_attention import flash_kernel_mode, flash_tileable

    # the kernel exists on a TPU, and anywhere under the tests' interpret mode
    mode = flash_kernel_mode()
    if mode == "off" or (mode == "on" and jax.default_backend() != "tpu"):
        return False
    if not (flash_tileable(q.shape, k.shape) and q.shape[-1] in (64, 128, 256)):
        return False
    # …and "auto" only takes it past the crossover for this dtype × sparsity
    sparsity = "window" if window is not None else ("causal" if causal else "dense")
    dkey = "bf16" if q.dtype == jnp.bfloat16 else "f32"
    return k.shape[1] >= ATTN_CROSSOVER_S[(dkey, sparsity)]


def _xla_attention(q, k, v, *, causal, mask, scale, window=None):
    *_, sq, hq, d = q.shape
    skv = k.shape[1]
    hkv = k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = _repeat_kv(k, rep)
        v = _repeat_kv(v, rep)
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    # compute logits in f32 for stability, inputs may be bf16
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((sq, skv), dtype=bool), k=skv - sq)
        logits = jnp.where(causal_mask[None, None], logits, jnp.finfo(jnp.float32).min)
    if window is not None:
        # query i sits at absolute position i + (skv - sq); band: 0 <= i-j < w
        qpos = jnp.arange(sq)[:, None] + (skv - sq)
        kpos = jnp.arange(skv)[None, :]
        band = qpos - kpos < window
        logits = jnp.where(band[None, None], logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        if mask.dtype == bool:
            logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def masked_attention(q, k_cache, v_cache, allow, scale=None):
    """The decode attention core shared by the contiguous cache
    (``generation._cached_attention``) and the paged pool's gather path
    (``ops.flash_attention.paged_attention_gather``): q ``[B, S, H, D]``
    against caches ``[B, T, Hkv, D]`` under a boolean ``allow`` mask
    broadcastable to ``[B, H, S, T]``. One implementation so the two paths
    cannot drift — masked slots contribute EXACTLY 0 to the softmax (the
    ``finfo.min`` fill underflows to 0.0 after the max-subtraction), which is
    what makes paged decode bitwise-identical to contiguous decode even
    though the gathered ``T`` differs."""
    B, S, H, D = q.shape
    hkv = k_cache.shape[2]
    # GQA head-repeat: the H/Hkv ratio is fixed per model config, so this
    # shape branch specializes exactly once — not a per-step recompile
    if hkv != H:  # jaxlint: disable=R2
        rep = H // hkv
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(allow, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)


def make_padding_mask(attention_mask: jax.Array, sq: int) -> jax.Array:
    """[B, Skv] 1/0 padding mask -> [B, 1, Sq, Skv] bool mask."""
    return jnp.broadcast_to(
        attention_mask[:, None, None, :].astype(bool),
        (attention_mask.shape[0], 1, sq, attention_mask.shape[1]),
    )
