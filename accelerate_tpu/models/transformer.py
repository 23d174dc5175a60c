"""Transformer model family, pure-JAX, TPU-first.

These are the acceptance workloads for the framework (reference examples:
``examples/nlp_example.py`` BERT-base MRPC — the north star —,
``examples/cv_example.py``, LM fine-tunes in ``benchmarks/fsdp2``; SURVEY.md §2.5).
They are intentionally *plain pytrees + pure functions*, not a module framework:

- params are nested dicts → sharding rules are path regexes, checkpoints are
  flat path→array maps, and every parallelism axis composes;
- per-layer params are **stacked on a leading axis and iterated with
  ``lax.scan``** → compile time is O(1) in depth and FSDP sharding of the stack
  is one spec (a deliberate TPU-first departure from the reference's per-module
  python structure);
- attention routes through ``ops.attention`` so CP/SP/flash kernels swap in
  without touching model code.

``LlamaModel`` (decoder, RoPE/RMSNorm/SwiGLU/GQA) is the flagship;
``BertClassifier`` (encoder + pooled classification head) is the MRPC
north-star workload.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import dot_product_attention
from ..ops.flash_attention import paged_write_attend
from ..ops.fp8 import META_KEY, fp8_dot, init_fp8_meta


# ---------------------------------------------------------------------------
# init helpers


def _dense_init(key, in_dim, out_dim, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(jnp.float32)


def _proj(entry: dict, x: jax.Array) -> jax.Array:
    """``x @ entry["kernel"]``, through :func:`ops.fp8.fp8_dot` when the entry
    carries fp8 meta (``dtype_recipe="fp8"`` threads the delayed-scaling state
    into the param tree at init; its cotangent is the updated meta — see
    ``ops/fp8.py``)."""
    if META_KEY in entry:  # dict-key membership: static at trace time  # jaxlint: disable=R1
        return fp8_dot(x, entry["kernel"], entry[META_KEY])
    return x @ entry["kernel"]


def pin_qkv(q, k, v):
    """The ``wq`` / ``wk`` / ``wv`` projections' outputs ``[B, S, out]``, held
    together as the dots made them (one ``optimization_barrier``; no array, no
    number and no gradient changes). Without it the layout that the reshape to
    heads and the rotary turn prefer travels back through the dot, and the
    chip's compiler meets it by copying the WEIGHT into a transposed layout
    in every step program (805 MB a ``mistral-7b`` decode or prefill program,
    604 MB a ``command-a-plus`` one: PERF.md, PR 36) where the activation is
    a hundredth of it. ``tests/test_tpu_compile.py`` holds the step programs'
    temporaries under one layer's ``wq``; ``tests/test_qkv_pin.py`` holds the
    pin invisible to outputs and gradients."""
    return jax.lax.optimization_barrier((q, k, v))


def _stacked_fp8_meta(n_layers: int):
    """Per-layer fp8 meta stacked on the layer axis, so it rides the same
    ``lax.scan`` as the stacked projection kernels (the test_fp8
    meta-under-scan pattern)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[init_fp8_meta() for _ in range(n_layers)]
    )


def _check_dtype_recipe(recipe):
    if recipe not in (None, "fp8"):
        raise ValueError(f"dtype_recipe must be None or 'fp8', got {recipe!r}")


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def layer_norm(x, scale, bias, eps=1e-6):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out.astype(x.dtype) * scale) + bias


# ---------------------------------------------------------------------------
# RoPE


def _rope_table(inv, max_seq: int, scale: float = 1.0):
    """``(cos, sin) [max_seq, len(inv)]`` of the angles ``position * inv``, times ``scale``."""
    freqs = np.outer(np.arange(max_seq), inv)
    return (np.cos(freqs) * scale).astype(np.float32), (np.sin(freqs) * scale).astype(np.float32)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0):
    return _rope_table(1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim)), max_seq)


def yarn_rope_frequencies(head_dim: int, max_seq: int, theta: float, *, factor: float,
                          original_max_seq: int, beta_fast: float = 32.0, beta_slow: float = 1.0,
                          attention_factor: Optional[float] = None):
    """YaRN's table in :func:`rope_frequencies`' shape (``[max_seq, head_dim /
    2]`` cos and sin), so :func:`apply_rope` takes it unchanged. Pair ``i``
    turns ``r`` times over ``original_max_seq`` positions at ``i = corr(r) =
    head_dim * ln(original_max_seq / (2 pi r)) / (2 ln theta)``: pairs below
    ``floor(corr(beta_fast))`` keep their frequency, pairs above
    ``ceil(corr(beta_slow))`` are interpolated by ``factor``, those between
    are blended linearly. The frequencies hold at every position, not only
    past ``original_max_seq``. ``attention_factor`` (``0.1 ln factor + 1``
    where None) multiplies cos AND sin: a layer's scores grow by its square."""
    def corr(rotations):
        return head_dim * np.log(original_max_seq / (2 * np.pi * rotations)) / (2 * np.log(theta))

    low = max(int(np.floor(corr(beta_fast))), 0)
    high = min(int(np.ceil(corr(beta_slow))), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low) / ((high if high != low else high + 0.001) - low),
                   0, 1)
    plain = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    if attention_factor is None:
        attention_factor = 0.1 * np.log(factor) + 1.0
    return _rope_table((1 - ramp) * plain + ramp * plain / factor, max_seq, attention_factor)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions=None) -> jax.Array:
    """x: [B, S, H, D]; cos/sin: [max_seq, D/2]."""
    seq = x.shape[1]
    if positions is None:
        cos_s = cos[:seq][None, :, None, :]
        sin_s = sin[:seq][None, :, None, :]
    else:
        cos_s = cos[positions][:, :, None, :]
        sin_s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos_s = cos_s.astype(x.dtype)
    sin_s = sin_s.astype(x.dtype)
    return jnp.concatenate([x1 * cos_s - x2 * sin_s, x2 * cos_s + x1 * sin_s], axis=-1)


# ---------------------------------------------------------------------------
# Llama-style decoder (flagship)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_kv_heads: int = 8
    ffn_dim: Optional[int] = None  # default 8/3 * dim rounded to 256
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # moe_experts > 0 swaps the dense SwiGLU FFN for a top-k expert-parallel
    # MoE (parallel/moe.py) in every layer; experts shard over the ``ep`` axis
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # True → layer loop fully unrolled (scan(..., unroll)): XLA fuses across
    # layer boundaries and skips the stacked-residual dynamic-slices; measured
    # 1.5× fwd+bwd on v5e for BERT-base. False → O(1)-in-depth compile time.
    unroll_layers: bool = True
    # default attention implementation for forwards that don't pass one
    # explicitly: "auto" | "xla" | "flash" (ops.attention impls)
    attn_impl: str = "auto"
    # None → matmuls in the param dtype; "fp8" → QKV/O and MLP projections run
    # through ops.fp8.fp8_dot (delayed scaling, e4m3 fwd / e5m2 bwd) with the
    # per-site amax histories living IN the param tree (embeddings and the lm
    # head stay high-precision — the standard first/last-layer exclusion)
    dtype_recipe: Optional[str] = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def hidden_dim(self) -> int:
        if self.ffn_dim is not None:
            return self.ffn_dim
        return int(np.ceil(self.dim * 8 / 3 / 256) * 256)

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2, max_seq_len=256)

    def paged_forward(self, params, ids, pool, block_tables, positions, valid, block_size: int):
        """What ``ServingEngine`` calls: ``(logits, pool, counts)``. The model
        takes no notice of ``valid`` and counts nothing (None)."""
        logits, pool = llama_paged_forward(
            params, ids, pool, block_tables, positions, self, block_size)
        return logits, pool, None


def init_llama(config: LlamaConfig, key) -> dict:
    """Stacked-layer param pytree: every per-layer tensor has leading dim L.
    ``dtype_recipe="fp8"`` adds a stacked ``fp8_meta`` subtree to every
    projection entry (QKV/O + SwiGLU) — state the forward reads and whose
    gradient-side cotangent is the rolled amax histories."""
    _check_dtype_recipe(config.dtype_recipe)
    if config.dtype_recipe == "fp8" and config.moe_experts > 0:
        raise ValueError("dtype_recipe='fp8' does not support MoE layers yet")
    keys = jax.random.split(key, 9)
    L, D, H = config.n_layers, config.dim, config.hidden_dim
    Dq = config.n_heads * config.head_dim
    Dkv = config.n_kv_heads * config.head_dim

    def stack(k, in_dim, out_dim):
        ks = jax.random.split(k, L)
        return jnp.stack([_dense_init(ks[i], in_dim, out_dim) for i in range(L)])

    if config.moe_experts > 0:
        from ..parallel.moe import init_moe_ffn

        moe_keys = jax.random.split(keys[5], L)
        per_layer = [
            init_moe_ffn(moe_keys[i], D, H, config.moe_experts) for i in range(L)
        ]
        ffn = {"moe": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_layer)}
    else:
        ffn = {
            "w1": {"kernel": stack(keys[5], D, H)},
            "w3": {"kernel": stack(keys[6], D, H)},
            "w2": {"kernel": stack(keys[7], H, D)},
        }
    params = {
        "embed_tokens": {"embedding": _dense_init(keys[0], config.vocab_size, D, scale=0.02)},
        "layers": {
            "attn_norm": {"scale": jnp.ones((L, D))},
            "wq": {"kernel": stack(keys[1], D, Dq)},
            "wk": {"kernel": stack(keys[2], D, Dkv)},
            "wv": {"kernel": stack(keys[3], D, Dkv)},
            "wo": {"kernel": stack(keys[4], Dq, D)},
            "mlp_norm": {"scale": jnp.ones((L, D))},
            **ffn,
        },
        "final_norm": {"scale": jnp.ones(D)},
    }
    if config.dtype_recipe == "fp8":
        for name in ("wq", "wk", "wv", "wo", "w1", "w3", "w2"):
            params["layers"][name][META_KEY] = _stacked_fp8_meta(L)
    if not config.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[8], D, config.vocab_size, scale=0.02)}
    return params


def _activation_spec(mesh, *logical):
    """PartitionSpec from logical dim names, dropping axes absent from the mesh.
    ``logical`` entries: None, an axis name, or a tuple of axis names."""
    from jax.sharding import PartitionSpec

    def _present(axis):
        if axis is None:
            return None
        if isinstance(axis, (tuple, list)):
            kept = tuple(a for a in axis if mesh.shape.get(a, 1) > 1)
            return kept if kept else None
        return axis if mesh.shape.get(axis, 1) > 1 else None

    return PartitionSpec(*(_present(ax) for ax in logical))


def _constrain(x, mesh, *logical):
    """Explicit activation sharding (maxtext-style): without these annotations
    GSPMD may pick conflicting intermediate shardings around the embedding
    gather / layer scan and fall back to replicate-then-reshard ("involuntary
    full rematerialization")."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _activation_spec(mesh, *logical))
    )


def _remat_policy(remat: bool | str):
    """Map the ``remat`` knob to a ``jax.checkpoint`` policy (None = save
    nothing, i.e. full recompute). ``"offload_dots"`` saves the
    weight-stationary matmul outputs to HOST memory instead of HBM
    (activation offloading — compose with optimizer host offload to fit the
    largest models). Unlike top-level program I/O placement, offload
    annotations inside remat are compiler hints that every backend accepts
    (the CPU mesh runs them too); only on TPU do they actually move bytes to
    host RAM."""
    if remat is True or remat == "nothing":
        return None
    if remat == "offload_dots":
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host"
        )
    policies = {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    try:
        return policies[remat]
    except KeyError:
        raise ValueError(
            f"remat must be bool, 'nothing', 'dots', 'dots_no_batch' or "
            f"'offload_dots'; got {remat!r}"
        ) from None


def llama_ffn(layer_params: dict, x: jax.Array, config: LlamaConfig, mesh=None):
    """The per-layer FFN block — dense SwiGLU or expert-parallel MoE — of
    :func:`llama_layer`. Returns ``(y, aux)``.

    MoE capacity: a DECODE step (``x [B, 1, D]``) routes only the B new tokens
    as one tiny group, where the training-time capacity ceil(top_k*cf*g/E)
    would drop tokens the full-sequence forward keeps (silent divergence) —
    floor the factor at E/top_k there so per-step routing is drop-free
    (Switch/GShard-style raised eval capacity; cost is bounded by the tiny
    group). A longer sequence (a prefill, a training batch) keeps the
    training factor: its routing group equals the full forward's at that
    length, and the floor would blow dispatch memory up to O(g^2·E) on long
    prompts."""
    if config.moe_experts > 0:
        from ..parallel.moe import moe_ffn

        capacity_factor = config.moe_capacity_factor
        # S == 1 is the decode-vs-prefill split: exactly the two-program shape
        # bucketing the decode paths are built around, not an accidental retrace
        if x.shape[1] == 1:  # jaxlint: disable=R2
            capacity_factor = max(capacity_factor, config.moe_experts / config.moe_top_k)
        return moe_ffn(
            layer_params["moe"], x,
            top_k=config.moe_top_k,
            capacity_factor=capacity_factor,
            mesh=mesh,  # ep-axis dispatch/expert activation constraints
        )
    gate = jax.nn.silu(_proj(layer_params["w1"], x))
    up = _proj(layer_params["w3"], x)
    return _proj(layer_params["w2"], gate * up), jnp.float32(0.0)


def llama_layer(layer_params: dict, h: jax.Array, positions, cos, sin, config: LlamaConfig,
                attend, mesh=None, pin=lambda h: h, ffn=None):
    """One decoder layer over ``h [B, S, D]``, the one statement of its math:
    norm, QKV projection, RoPE at ``positions`` (``[B, S]``, or None for
    ``0..S-1``), attention, output projection, norm, FFN, the two residuals.
    Returns ``(h, aux)`` (``aux``: the MoE load-balance loss, 0.0 when dense).

    ``attend(q, k, v) -> [B, S, H, D]`` is how attention reaches the keys and
    values of earlier positions (and stores these): none
    (:func:`llama_forward`), a contiguous cache (``generation.py``), the
    serving engine's paged pool (:func:`llama_paged_forward`). ``mesh`` pins
    the expert layer's activations (:func:`llama_ffn`); ``pin`` is applied to
    the residual stream after each residual (:func:`llama_forward` pins it
    over the batch and sequence axes of its mesh; the decode paths place
    their batch themselves, ``generation.generation_shardings``).
    ``ffn(layer_params, x) -> (y, aux)`` is the layer's second half where it
    is not :func:`llama_ffn` (``models/mellum.py``: routed experts, ``aux``
    their counts); ``config`` then needs only ``n_heads``, ``n_kv_heads``,
    ``head_dim`` and ``norm_eps``. A layer whose parameters hold ``q_norm`` and
    ``k_norm`` (one scale over ``head_dim`` each) passes every query and key
    head through that RMSNorm before the rotary turn (``models/lfm2.py``).
    The three projections' outputs are pinned (:func:`pin_qkv`) before they
    are reshaped to heads."""
    B, S, _ = h.shape
    x = rms_norm(h, layer_params["attn_norm"]["scale"], config.norm_eps)
    q, k, v = pin_qkv(
        _proj(layer_params["wq"], x), _proj(layer_params["wk"], x), _proj(layer_params["wv"], x))
    q = q.reshape(B, S, config.n_heads, config.head_dim)
    k = k.reshape(B, S, config.n_kv_heads, config.head_dim)
    v = v.reshape(B, S, config.n_kv_heads, config.head_dim)
    if "q_norm" in layer_params:  # the tree's structure, static under jit  # jaxlint: disable=R1
        q = rms_norm(q, layer_params["q_norm"]["scale"], config.norm_eps)
        k = rms_norm(k, layer_params["k_norm"]["scale"], config.norm_eps)
    q = apply_rope(q, cos, sin, positions=positions)
    k = apply_rope(k, cos, sin, positions=positions)
    h = pin(h + _proj(layer_params["wo"], attend(q, k, v).reshape(B, S, -1)))
    x = rms_norm(h, layer_params["mlp_norm"]["scale"], config.norm_eps)
    y, aux = ffn(layer_params, x) if ffn else llama_ffn(layer_params, x, config, mesh=mesh)
    return pin(h + y), aux


def llama_rope(config: LlamaConfig):
    """The ``(cos, sin)`` tables ``[max_seq_len, head_dim / 2]`` of :func:`llama_layer`."""
    cos, sin = rope_frequencies(config.head_dim, config.max_seq_len, config.rope_theta)
    return jnp.asarray(cos), jnp.asarray(sin)


def llama_head(params: dict, h: jax.Array, config: LlamaConfig) -> jax.Array:
    """Final norm and the tied or untied head: ``h [B, S, D] -> logits [B, S, vocab]``."""
    h = rms_norm(h, params["final_norm"]["scale"], config.norm_eps)
    if config.tie_embeddings:
        return h @ params["embed_tokens"]["embedding"].T
    return h @ params["lm_head"]["kernel"]


def llama_forward(
    params: dict,
    input_ids: jax.Array,  # [B, S]
    config: LlamaConfig,
    attention_impl: Optional[str] = None,  # default: config.attn_impl
    attention_fn=None,
    remat: bool | str = False,
    mesh=None,
    with_aux: bool = False,
    segment_ids=None,  # [B, S] int — packed sequences (0 = padding)
    positions=None,  # [B, S] int — rope positions (default: per-segment index)
) -> jax.Array:
    """Return logits [B, S, vocab] (``with_aux=True`` → (logits, aux) where aux
    is the mean MoE load-balance loss, 0.0 for dense configs). ``attention_fn``
    overrides the attention op (ring attention for CP plugs in here); ``mesh``
    enables explicit activation sharding constraints (batch over dp axes, seq
    over cp).

    ``remat``: ``False`` (save all), ``True`` (recompute all — min memory), or
    a policy name trading memory for recompute FLOPs (the knob behind the
    reference's FSDP ``activation_checkpointing``): ``"dots"`` saves matmul
    outputs, ``"dots_no_batch"`` saves only weight-stationary matmuls (the
    usual transformer sweet spot), ``"offload_dots"`` saves them to host RAM
    instead of HBM (activation offloading), ``"nothing"`` ≡ ``True``.

    ``segment_ids`` enables PACKED sequences (``utils/packing.py``): tokens
    attend only within their segment (still causally), rope positions restart
    per segment, and id 0 marks padding. Not combinable with ``attention_fn``
    (the CP/SP rings don't carry segment info)."""
    if attention_impl is None:
        attention_impl = config.attn_impl
    cos, sin = llama_rope(config)
    if segment_ids is not None:
        if attention_fn is not None:
            raise ValueError("segment_ids (packing) cannot combine with attention_fn (CP/SP)")
        if positions is None:
            # per-segment position: index minus the running segment-start index
            # (roll-based start detection keeps the sequence extent unchanged)
            seq_idx = jnp.arange(segment_ids.shape[1])[None, :]
            is_start = jnp.roll(segment_ids, 1, axis=1) != segment_ids
            is_start = is_start.at[:, 0].set(True)
            positions = seq_idx - jax.lax.cummax(jnp.where(is_start, seq_idx, 0), axis=1)
    _batch_axes = ("dp_replicate", "dp_shard")
    # FSDP shards the table's embedding dim at rest; gather it for compute
    # (classic FSDP all-gather-on-use) or the lookup output inherits a D-dim
    # sharding that conflicts with the (batch, seq) activation layout and
    # GSPMD falls back to full rematerialization
    table = _constrain(params["embed_tokens"]["embedding"], mesh, "tp", None)

    def pin(h):
        return _constrain(h, mesh, _batch_axes, "cp", None)

    def attend(q, k, v):
        if attention_fn is not None:
            return attention_fn(q, k, v, causal=True)
        return dot_product_attention(
            q, k, v, causal=True, segment_ids=segment_ids, impl=attention_impl
        )

    def layer(h, layer_params):
        return llama_layer(
            layer_params, h, positions, cos, sin, config, attend, mesh=mesh, pin=pin)

    if remat:
        layer = jax.checkpoint(layer, policy=_remat_policy(remat))
    h, aux_per_layer = jax.lax.scan(
        layer, pin(table[input_ids]), params["layers"], unroll=config.unroll_layers)
    logits = _constrain(llama_head(params, h, config), mesh, _batch_axes, "cp", "tp")
    if with_aux:
        return logits, jnp.mean(aux_per_layer)
    return logits


def llama_paged_forward(params, ids, pool, block_tables, positions, config: LlamaConfig,
                        block_size: int):
    """Forward ``ids [B, S]`` at per-row ``positions [B, S]`` against the
    serving engine's paged pool ``{"k", "v"}: [L, num_blocks, block_size, Hkv,
    D]`` (``ops.flash_attention`` owns its format): each layer writes its keys
    and values through the block tables into its part of the whole stack and
    attends over the row's blocks. The stack rides the layer loop as carry and
    is never sliced or restacked, so a program that donates the pool writes in
    place and holds no second pool among its temporaries. The pool may have
    more layers than ``config`` (a truncated draft): layer ``i`` of the model
    is layer ``i`` of the pool and the rest are left as they were.
    Returns ``(logits [B, S, vocab], new_pool)`` — the paged counterpart of
    ``generation._forward_cached``."""
    cos, sin = llama_rope(config)

    def layer(carry, xs):
        h, k_pool, v_pool = carry
        layer_params, index = xs

        def attend(q, k, v):
            nonlocal k_pool, v_pool
            attn, k_pool, v_pool = paged_write_attend(
                q, k, v, k_pool, v_pool, index, block_tables, positions, block_size)
            return attn

        h, _ = llama_layer(layer_params, h, positions, cos, sin, config, attend)
        return (h, k_pool, v_pool), None

    (h, k_pool, v_pool), _ = jax.lax.scan(
        layer, (params["embed_tokens"]["embedding"][ids], pool["k"], pool["v"]),
        (params["layers"], jnp.arange(config.n_layers)), unroll=config.unroll_layers,
    )
    return llama_head(params, h, config), {"k": k_pool, "v": v_pool}


def llama_loss(params: dict, batch: dict, config: LlamaConfig, **fwd_kwargs) -> jax.Array:
    """Next-token cross entropy. ``batch``: input_ids [B, S] (labels shifted
    internally), optional loss_mask [B, S].

    The forward runs on the FULL sequence and targets come from a
    shape-preserving ``roll`` (a cheap ppermute along cp on the ICI) with the
    final position masked out — a ``[:, :-1]``/``[:, 1:]`` slice pair would
    change the sequence extent and force GSPMD to replicate-then-reshard every
    activation crossing the shift ("involuntary full rematerialization")."""
    ids = batch["input_ids"]
    seq_len = ids.shape[1]
    # packing: segment ids may arrive in the batch OR as a forward kwarg —
    # both must engage the boundary/padding loss masking below
    segment_ids = batch.get("segment_ids")
    if segment_ids is None:
        segment_ids = fwd_kwargs.get("segment_ids")
    elif "segment_ids" not in fwd_kwargs:
        fwd_kwargs = {**fwd_kwargs, "segment_ids": segment_ids}
    if config.moe_experts > 0:
        logits, moe_aux = llama_forward(params, ids, config, with_aux=True, **fwd_kwargs)
    else:
        logits, moe_aux = llama_forward(params, ids, config, **fwd_kwargs), 0.0
    targets = jnp.roll(ids, shift=-1, axis=1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]  # [B, S]
    # position S-1 has no next token; its rolled target is position 0 — mask it
    valid = jnp.broadcast_to(
        (jnp.arange(seq_len) < seq_len - 1).astype(jnp.float32)[None, :], nll.shape
    )
    if segment_ids is not None:
        # packed: a position's target must be the NEXT token of the SAME
        # segment — segment boundaries and padding (id 0) don't contribute
        same_seg = jnp.roll(segment_ids, shift=-1, axis=1) == segment_ids
        valid = valid * same_seg.astype(jnp.float32) * (segment_ids > 0).astype(jnp.float32)
    mask = batch.get("loss_mask")
    if mask is not None:
        valid = valid * jnp.roll(mask, shift=-1, axis=1).astype(jnp.float32)
    nll_mean = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
    return nll_mean + config.moe_aux_weight * moe_aux


def llama_shard_rules():
    """TP rules for the stacked-layer layout: dim 0 is the layer-stack axis, so TP
    shards dim 1 (in) / dim 2 (out). Embeddings/head are 2-D."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import ShardingRules

    return ShardingRules(
        [
            # fp8 scaling metadata: tiny f32 history buffers, always replicated
            (r"fp8_meta", P()),
            (r"layers/(wq|wk|wv|w1|w3)/kernel", P(None, None, "tp")),  # column-parallel
            (r"layers/(wo|w2)/kernel", P(None, "tp", None)),  # row-parallel
            # MoE: leading dims are [layer, expert]; experts over ep, the
            # expert matmul dims over tp like their dense counterparts
            (r"layers/moe/router/kernel", P()),
            (r"layers/moe/wi/kernel", P(None, "ep", None, "tp")),
            (r"layers/moe/wo/kernel", P(None, "ep", "tp", None)),
            (r"embed_tokens/embedding", P("tp", None)),  # vocab-parallel
            (r"lm_head/kernel", P(None, "tp")),
            (r"norm", P()),
        ]
    )


# ---------------------------------------------------------------------------
# Self-draft construction (speculative decoding)


def draft_config(config: LlamaConfig, n_layers: int) -> LlamaConfig:
    """Config for a truncated-layer self-draft: the verifier's config with
    only its first ``n_layers`` decoder layers (``serving/engine.py``'s
    speculative-decoding draft). Everything else — vocab, dims, heads, rope —
    is inherited, so the draft reads/writes the SAME paged KV layout as the
    verifier's first ``n_layers`` layers."""
    if not (0 < n_layers <= config.n_layers):
        raise ValueError(
            f"draft_layers must be in 1..{config.n_layers}, got {n_layers}"
        )
    return replace(config, n_layers=n_layers)


def draft_params(params: dict, n_layers: int) -> dict:
    """Truncated-layer self-draft params: slice the stacked-layer pytree to
    the first ``n_layers`` layers and SHARE embeddings / final norm / lm head
    with the verifier (no copy — the stacked-layer layout makes the slice a
    view-cheap ``x[:n]`` per leaf). Because draft layer i *is* verifier layer
    i, KV the verifier's prefill/verify steps land in the paged pool is
    byte-valid for the draft — the draft needs no pool, no prefill, and no
    extra memory of its own."""
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = jax.tree_util.tree_map(lambda x: x[:n_layers], params["layers"])
    return out


# ---------------------------------------------------------------------------
# BERT-style encoder + classifier (north-star MRPC workload)


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    ffn_dim: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2
    num_labels: int = 2
    norm_eps: float = 1e-12
    # see LlamaConfig.unroll_layers — same measured win applies here
    unroll_layers: bool = True
    # see LlamaConfig.attn_impl — the config-level attention knob
    attn_impl: str = "auto"
    # see LlamaConfig.dtype_recipe — None (native) or "fp8" (delayed-scaling
    # projections + MLP matmuls through ops.fp8.fp8_dot)
    dtype_recipe: Optional[str] = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @classmethod
    def base(cls) -> "BertConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "BertConfig":
        return cls(vocab_size=1024, dim=128, n_layers=2, n_heads=4, ffn_dim=256, max_seq_len=128)


def init_bert(config: BertConfig, key) -> dict:
    _check_dtype_recipe(config.dtype_recipe)
    keys = jax.random.split(key, 12)
    L, D, F = config.n_layers, config.dim, config.ffn_dim

    def stack(k, a, b):
        ks = jax.random.split(k, L)
        return jnp.stack([_dense_init(ks[i], a, b, scale=0.02) for i in range(L)])

    params = {
        "embeddings": {
            "word": {"embedding": _dense_init(keys[0], config.vocab_size, D, 0.02)},
            "position": {"embedding": _dense_init(keys[1], config.max_seq_len, D, 0.02)},
            "token_type": {"embedding": _dense_init(keys[2], config.type_vocab_size, D, 0.02)},
            "norm": {"scale": jnp.ones(D), "bias": jnp.zeros(D)},
        },
        "layers": {
            "wq": {"kernel": stack(keys[3], D, D), "bias": jnp.zeros((L, D))},
            "wk": {"kernel": stack(keys[4], D, D), "bias": jnp.zeros((L, D))},
            "wv": {"kernel": stack(keys[5], D, D), "bias": jnp.zeros((L, D))},
            "wo": {"kernel": stack(keys[6], D, D), "bias": jnp.zeros((L, D))},
            "attn_norm": {"scale": jnp.ones((L, D)), "bias": jnp.zeros((L, D))},
            "fc1": {"kernel": stack(keys[7], D, F), "bias": jnp.zeros((L, F))},
            "fc2": {"kernel": stack(keys[8], F, D), "bias": jnp.zeros((L, D))},
            "mlp_norm": {"scale": jnp.ones((L, D)), "bias": jnp.zeros((L, D))},
        },
        "pooler": {"kernel": _dense_init(keys[9], D, D, 0.02), "bias": jnp.zeros(D)},
        "classifier": {"kernel": _dense_init(keys[10], D, config.num_labels, 0.02), "bias": jnp.zeros(config.num_labels)},
    }
    if config.dtype_recipe == "fp8":
        # per-layer delayed-scaling state for every projection that routes
        # through fp8_dot in bert_forward (pooler/classifier stay native —
        # first/last-matmul exclusion, same as llama's embed/lm_head)
        for name in ("wq", "wk", "wv", "wo", "fc1", "fc2"):
            params["layers"][name][META_KEY] = _stacked_fp8_meta(L)
    return params


def bert_forward(
    params: dict, batch: dict, config: BertConfig, attention_impl: Optional[str] = None
) -> jax.Array:
    """Return classification logits [B, num_labels]. batch: input_ids,
    attention_mask, token_type_ids (all [B, S]). ``attention_impl`` defaults
    to ``config.attn_impl`` (the config-level knob)."""
    if attention_impl is None:
        attention_impl = config.attn_impl
    ids = batch["input_ids"]
    B, S = ids.shape
    emb = params["embeddings"]
    h = (
        emb["word"]["embedding"][ids]
        + emb["position"]["embedding"][jnp.arange(S)][None]
        + emb["token_type"]["embedding"][batch.get("token_type_ids", jnp.zeros_like(ids))]
    )
    h = layer_norm(h, emb["norm"]["scale"], emb["norm"]["bias"], config.norm_eps)
    # padding expressed as segment ids (pad=0, real=1) so the Pallas flash
    # kernel stays engaged under masking (round-2 verdict: the einsum fallback
    # with an explicit [B,1,S,S] mask was the top unplugged perf lever)
    attn_mask = batch.get("attention_mask")
    seg_ids = attn_mask.astype(jnp.int32) if attn_mask is not None else None

    def layer(h, lp):
        # bias adds stay outside _proj — fp8_dot quantizes the matmul only
        q = (_proj(lp["wq"], h) + lp["wq"]["bias"]).reshape(B, S, config.n_heads, config.head_dim)
        k = (_proj(lp["wk"], h) + lp["wk"]["bias"]).reshape(B, S, config.n_heads, config.head_dim)
        v = (_proj(lp["wv"], h) + lp["wv"]["bias"]).reshape(B, S, config.n_heads, config.head_dim)
        attn = dot_product_attention(q, k, v, segment_ids=seg_ids, impl=attention_impl).reshape(B, S, -1)
        h = layer_norm(
            h + _proj(lp["wo"], attn) + lp["wo"]["bias"],
            lp["attn_norm"]["scale"],
            lp["attn_norm"]["bias"],
            config.norm_eps,
        )
        x = jax.nn.gelu(_proj(lp["fc1"], h) + lp["fc1"]["bias"])
        h = layer_norm(
            h + _proj(lp["fc2"], x) + lp["fc2"]["bias"],
            lp["mlp_norm"]["scale"],
            lp["mlp_norm"]["bias"],
            config.norm_eps,
        )
        return h, None

    h, _ = jax.lax.scan(layer, h, params["layers"], unroll=config.unroll_layers)
    pooled = jnp.tanh(h[:, 0] @ params["pooler"]["kernel"] + params["pooler"]["bias"])
    return pooled @ params["classifier"]["kernel"] + params["classifier"]["bias"]


def bert_loss(params: dict, batch: dict, config: BertConfig, **kwargs) -> jax.Array:
    logits = bert_forward(params, batch, config, **kwargs)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def bert_shard_rules():
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import ShardingRules

    return ShardingRules(
        [
            # fp8 scaling metadata: tiny f32 history buffers, always replicated
            (r"fp8_meta", P()),
            (r"layers/(wq|wk|wv|fc1)/kernel", P(None, None, "tp")),
            (r"layers/(wo|fc2)/kernel", P(None, "tp", None)),
            (r"embeddings/word/embedding", P("tp", None)),
            (r"(norm|bias|pooler|classifier)", P()),
        ]
    )
