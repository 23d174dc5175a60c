"""Expert parallelism: capacity-based MoE routing over the ``ep`` mesh axis.

TPU-native counterpart of the reference's expert-parallel reach-through
(SURVEY.md §2.2 EP row: Megatron ``expert_model_parallel_size`` /
``utils/launch.py:367-378`` and DeepSpeed MoE leaf modules
``accelerator.py:2244-2245`` — the reference has no in-repo MoE math; both
engines do CUDA all-to-all token routing).

Here routing is the Switch/GShard einsum formulation: top-k gating with a fixed
per-expert capacity, dispatch/combine as one-hot einsums, and expert weights
carrying a leading ``[E, ...]`` axis sharded over ``ep``
(``P('ep', None, ...)``). With tokens sharded over dp and experts over ep, XLA
lowers the dispatch einsum to the same all-to-all the engines hand-code — but
fused, overlapped, and differentiable. Static capacity keeps every shape fixed
(jit-friendly); dropped tokens pass through the residual, and the standard
load-balance auxiliary loss keeps the router honest.

Routing is *grouped* (GShard "groups"): tokens are split into fixed-size blocks
that each get their own capacity and intra-group cumsum, so dispatch memory is
``O(N · E · capacity_per_group)`` — linear in N — and the position-assignment
cumsum vectorizes over groups instead of serializing across the global batch.
"""

from __future__ import annotations

from functools import partial

import numpy as np


def init_moe_ffn(key, d_model: int, d_ff: int, num_experts: int, dtype=None):
    """Params for an expert-parallel FFN: router + per-expert MLP stacks."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    k_r, k_i, k_o = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d_model)
    scale_out = 1.0 / np.sqrt(d_ff)
    return {
        "router": {"kernel": (jax.random.normal(k_r, (d_model, num_experts)) * scale_in).astype(dtype)},
        "wi": {"kernel": (jax.random.normal(k_i, (num_experts, d_model, d_ff)) * scale_in).astype(dtype)},
        "wo": {"kernel": (jax.random.normal(k_o, (num_experts, d_ff, d_model)) * scale_out).astype(dtype)},
    }


def moe_shard_rules():
    """Sharding rules for MoE params: experts over ``ep``, router replicated.
    Compose with the model family's base rules (first match wins)."""
    from jax.sharding import PartitionSpec as P

    from .sharding import ShardingRules

    return ShardingRules(
        [
            (r"router/kernel", P()),
            (r"wi/kernel", P("ep", None, "tp")),
            (r"wo/kernel", P("ep", "tp", None)),
        ]
    )


def moe_ffn(
    params,
    x,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    mesh=None,
    ep_axis: str = "ep",
    activation=None,
    group_size: int = 4096,
):
    """Mixture-of-experts FFN on ``x: [B, S, D]`` → ``(y, aux_loss)``.

    Tokens are routed in groups of ``group_size`` (each group has its own
    capacity ``ceil(top_k · cf · g / E)``), keeping dispatch memory linear in
    the token count. ``aux_loss`` is the GShard/Switch load-balance term
    ``E * Σ_e fraction_tokens(e) · mean_prob(e)`` — add it (scaled ~1e-2) to the
    training loss.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if activation is None:
        activation = jax.nn.gelu

    B, S, D = x.shape
    E = params["router"]["kernel"].shape[-1]
    N = B * S
    g = min(group_size, N)
    # shape-specialization is intended here: the divisor search runs at trace
    # time and the program is compiled per (B, S) bucket anyway
    while N % g != 0:  # shrink to a divisor; worst case g=1 never happens for 2^k shapes  # jaxlint: disable=R2
        g -= 1
    G = N // g
    capacity = max(int(np.ceil(top_k * capacity_factor * g / E)), 1)

    x_grp = x.reshape(G, g, D)
    router_logits = jnp.einsum(
        "gnd,de->gne", x_grp.astype(jnp.float32), params["router"]["kernel"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(router_logits, axis=-1)  # [G, g, E]

    # --- top-k assignment with per-group, per-expert capacity ---------------
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # [G, g, k]
    # renormalize the chosen gates (standard top-2 practice)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((G, g, E, capacity), dtype=x.dtype)
    combine = jnp.zeros((G, g, E, capacity), dtype=jnp.float32)
    # running count of tokens already admitted per expert, built choice-major so
    # the 1st choice wins capacity over 2nd choices (GShard ordering)
    expert_fill = jnp.zeros((G, E), dtype=jnp.int32)
    for k in range(top_k):
        e_k = gate_idx[..., k]  # [G, g]
        onehot = jax.nn.one_hot(e_k, E, dtype=jnp.int32)  # [G, g, E]
        pos_within = jnp.cumsum(onehot, axis=1) - 1 + expert_fill[:, None, :]  # [G, g, E]
        pos = jnp.take_along_axis(pos_within, e_k[..., None], axis=2)[..., 0]  # [G, g]
        keep = pos < capacity
        pos_onehot = jax.nn.one_hot(pos, capacity, dtype=x.dtype) * keep[..., None]
        contrib = onehot[..., None].astype(x.dtype) * pos_onehot[:, :, None, :]  # [G, g, E, C]
        dispatch = dispatch + contrib
        combine = combine + contrib.astype(jnp.float32) * gate_vals[..., k][..., None, None]
        expert_fill = expert_fill + onehot.sum(axis=1)

    # --- expert compute (ep-sharded) ---------------------------------------
    expert_in = jnp.einsum("gnec,gnd->egcd", dispatch, x_grp)  # [E, G, C, D]
    if mesh is not None and mesh.shape.get(ep_axis, 1) > 1:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(ep_axis, None, None, None))
        )
    h = activation(jnp.einsum("egcd,edf->egcf", expert_in, params["wi"]["kernel"]))
    expert_out = jnp.einsum("egcf,efd->egcd", h, params["wo"]["kernel"])  # [E, G, C, D]
    if mesh is not None and mesh.shape.get(ep_axis, 1) > 1:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(ep_axis, None, None, None))
        )
    y_grp = jnp.einsum("gnec,egcd->gnd", combine.astype(expert_out.dtype), expert_out)

    # --- load-balance auxiliary loss ---------------------------------------
    # fraction of tokens whose FIRST choice is e, and mean router prob for e
    first_choice = jax.nn.one_hot(gate_idx[..., 0].reshape(-1), E, dtype=jnp.float32)
    fraction = first_choice.mean(axis=0)
    mean_prob = probs.reshape(-1, E).mean(axis=0)
    aux_loss = E * jnp.sum(fraction * mean_prob)

    return y_grp.reshape(B, S, D).astype(x.dtype), aux_loss


# ---------------------------------------------------------------------------
# Routing without dropped tokens over the experts held here


def init_held_experts(key, d_model: int, d_ff: int, num_experts: int, held: int):
    """Params of :func:`held_expert_ffn` (float32): a router over all
    ``num_experts`` and the gated MLP stacks of the ``held`` experts that live
    here."""
    import jax

    k_r, k_g, k_u, k_d = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape) / np.sqrt(fan_in)

    return {
        "router": {"kernel": normal(k_r, (d_model, num_experts), d_model)},
        "w_gate": {"kernel": normal(k_g, (held, d_model, d_ff), d_model)},
        "w_up": {"kernel": normal(k_u, (held, d_model, d_ff), d_model)},
        "w_down": {"kernel": normal(k_d, (held, d_ff, d_model), d_ff)},
    }


def route_top_k(router_kernel, x, top_k: int, scoring: str = "sigmoid", *,
                select_bias=None, weight_eps: float = 0.0):
    """Scores over every expert (``scoring``: ``sigmoid``, each expert's own,
    or ``softmax`` over them all), the ``top_k`` largest, their weights
    normalised to sum to 1: ``(expert ids [N, k], weights [N, k])``. The
    arithmetic is float32 (a TPU's default matmul precision is not): the gap
    between the k-th and the next score is small against bf16 rounding.

    ``select_bias [E]`` sets selection apart from weighting: the experts are
    the ``top_k`` largest of ``score + select_bias``, their weights come from
    ``score`` alone (a load-balancing bias moves who is chosen, never how much
    a chosen expert counts). ``weight_eps`` is added to the weights' sum before
    the division. None and 0.0 are the function as it was, bit for bit."""
    import jax
    import jax.numpy as jnp

    score = {"sigmoid": jax.nn.sigmoid, "softmax": partial(jax.nn.softmax, axis=-1)}[scoring]
    logits = jnp.dot(x.astype(jnp.float32), router_kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if select_bias is None:
        scores, experts = jax.lax.top_k(score(logits), top_k)
    else:
        every = score(logits)
        _, experts = jax.lax.top_k(every + select_bias.astype(jnp.float32), top_k)
        scores = jnp.take_along_axis(every, experts, axis=-1)
    total = jnp.sum(scores, axis=-1, keepdims=True)
    return experts, scores / (total + weight_eps if weight_eps else total)


def held_expert_ffn(params, x, *, top_k: int, first_expert: int = 0, valid=None,
                    scoring: str = "sigmoid", select_bias=None, weight_eps: float = 0.0):
    """The routed experts' part of an expert layer that this chip computes:
    ``x [..., D] -> (y [..., D], counts [3])``.

    The router scores all ``E = router.shape[-1]`` experts by ``scoring`` and
    picks ``top_k`` a token (:func:`route_top_k`, which also takes
    ``select_bias`` and ``weight_eps``); the ``held = w_gate.shape[0]`` experts
    ``first_expert .. first_expert + held`` live here. The (token, expert)
    pairs that land on them are sorted by expert and go through one grouped
    matmul a weight stack (:func:`accelerate_tpu.ops.grouped_matmul.
    grouped_matmul`): ``E_i(x) = (silu(x Wg_i) * (x Wu_i)) Wd_i``, and ``y =
    sum of w_i E_i(x)`` over the chosen experts held here. Nothing is dropped
    and nothing stands in for the experts held elsewhere: with every expert
    held (``first_expert`` 0, ``held == E``) ``y`` is the whole layer, else
    its share, and the shares of all the chips add up to the whole.

    ``valid [...]`` (bool) marks the real tokens of a padded batch: the others
    are routed nowhere. ``counts`` is int32 ``(local_pairs, experts_hit,
    max_expert_load)``: the pairs that landed here, the held experts that got
    at least one, the most any of them got."""
    import jax
    import jax.numpy as jnp

    from ..ops.grouped_matmul import grouped_matmul

    lead, D = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, D)
    held = params["w_gate"]["kernel"].shape[0]
    experts, weights = route_top_k(params["router"]["kernel"], x2, top_k, scoring,
                                   select_bias=select_bias, weight_eps=weight_eps)
    local = (experts >= first_expert) & (experts < first_expert + held)
    if valid is not None:
        local = local & valid.reshape(-1, 1)
    # pairs by expert, those of experts held elsewhere behind them all
    key = jnp.where(local, experts - first_expert, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    token_of = order // top_k
    group_sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    rows = x2[token_of]
    hidden = jax.nn.silu(grouped_matmul(rows, params["w_gate"]["kernel"], group_sizes)) * (
        grouped_matmul(rows, params["w_up"]["kernel"], group_sizes))
    out = grouped_matmul(hidden, params["w_down"]["kernel"], group_sizes)
    # the rows behind the groups hold nothing defined: they take weight 0, and are masked
    weight = jnp.where(local, weights, 0.0).reshape(-1)[order]
    out = jnp.where(weight[:, None] > 0, out.astype(jnp.float32) * weight[:, None], 0.0)
    y = jnp.zeros((x2.shape[0], D), jnp.float32).at[token_of].add(out)
    counts = jnp.stack([jnp.sum(group_sizes), jnp.sum(group_sizes > 0), jnp.max(group_sizes)])
    return y.astype(x.dtype).reshape(*lead, D), counts.astype(jnp.int32)
