"""Pipeline parallelism: GPipe microbatch schedule over the ``pp`` mesh axis.

TPU-native counterpart of the reference's PiPPy integration
(``/root/reference/src/accelerate/inference.py`` — ``prepare_pippy:126``,
``build_pipeline:75`` auto-splitting by balanced size, ``pippy_forward:101``
with ``ScheduleGPipe`` microbatching) and of Megatron's training-side PP.

Architecture shift: PiPPy traces an ``nn.Module`` into per-rank graph stages
and moves microbatches over NCCL P2P. Here the model is ALREADY a stack of
homogeneous stage params (leading ``pp``-sharded axis); the schedule is a
``lax.scan`` inside ``shard_map`` whose per-tick communication is one
``lax.ppermute`` shifting activations to the next stage over ICI. The whole
schedule is one compiled function — differentiable end to end, so unlike the
reference (inference-only without Megatron) the same code trains: ``jax.grad``
through ``ppermute`` yields the reverse (backward) pipeline automatically.

Composition: ``shard_map`` is manual over ``pp`` only (``axis_names={'pp'}``);
inside a stage, arrays keep their GSPMD shardings, so tp/dp/cp compose with
pipelining the usual way.
"""

from __future__ import annotations

from typing import Any, Callable


def split_into_stages(layer_params: list, pp: int) -> Any:
    """Stack per-layer param trees ``[L entries] → leaves [pp, L//pp, ...]``
    (the analogue of reference ``build_pipeline``'s balanced split points,
    ``inference.py:75-99`` — homogeneous decoder layers split evenly)."""
    import jax
    import jax.numpy as jnp

    L = len(layer_params)
    if L % pp != 0:
        raise ValueError(f"{L} layers not divisible into {pp} pipeline stages")
    per = L // pp

    def _stack(*leaves):
        stacked = jnp.stack([jnp.asarray(x) for x in leaves], axis=0)  # [L, ...]
        return stacked.reshape((pp, per) + stacked.shape[1:])

    return jax.tree_util.tree_map(_stack, *layer_params)


def split_microbatches(batch, num_microbatches: int):
    """[B, ...] → [M, B//M, ...] on every leaf (reference GPipe ``chunks`` arg,
    ``inference.py:141``)."""
    import jax
    import jax.numpy as jnp

    def _split(x):
        B = x.shape[0]
        if B % num_microbatches != 0:
            raise ValueError(f"batch {B} not divisible into {num_microbatches} microbatches")
        return jnp.reshape(x, (num_microbatches, B // num_microbatches) + x.shape[1:])

    return jax.tree_util.tree_map(_split, batch)


def merge_microbatches(batch):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jnp.reshape(x, (x.shape[0] * x.shape[1],) + x.shape[2:]), batch
    )


def make_pipeline_forward(
    stage_fn: Callable,
    mesh,
    num_microbatches: int,
    axis_name: str = "pp",
):
    """Build ``forward(stage_params_stack, x) -> y`` running a GPipe schedule.

    ``stage_fn(stage_params, x) -> y`` is one stage's compute (e.g. a
    ``lax.scan`` over its layer slice); activations must have the same
    shape/dtype as inputs (transformer trunk). ``stage_params_stack`` leaves
    carry a leading ``[pp, ...]`` axis sharded over ``pp``; ``x`` is the global
    ``[B, ...]`` activation batch (already embedded).

    The schedule runs ``M + pp - 1`` ticks; tick ``t`` has stage ``s`` compute
    microbatch ``t - s`` (the classic GPipe trapezoid), with one ``ppermute``
    per tick moving activations down the ring.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    pp = int(mesh.shape[axis_name])
    M = num_microbatches
    if pp <= 1:
        def forward_trivial(stage_params_stack, x):
            sp = jax.tree_util.tree_map(lambda a: a[0], stage_params_stack)
            return stage_fn(sp, x)

        return forward_trivial

    fwd_perm = [(i, i + 1) for i in range(pp - 1)]

    def _local(stage_params, x_micro):
        # stage_params leaves [1, ...]; x_micro [M, Bm, ...] (replicated over pp)
        params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        idx = jax.lax.axis_index(axis_name)
        out_buf = jnp.zeros_like(x_micro)

        def tick(carry, t):
            cur, out_buf = carry
            # stage 0 injects microbatch t (clamped; masked-out beyond M-1)
            inject = x_micro[jnp.minimum(t, M - 1)]
            stage_in = jnp.where(idx == 0, inject, cur)
            y = stage_fn(params, stage_in)
            # last stage records microbatch t-(pp-1) once the trapezoid fills
            write_idx = jnp.clip(t - (pp - 1), 0, M - 1)
            do_write = jnp.logical_and(idx == pp - 1, t >= pp - 1)
            out_buf = jax.lax.cond(
                do_write,
                lambda b: jax.lax.dynamic_update_index_in_dim(b, y, write_idx, 0),
                lambda b: b,
                out_buf,
            )
            # shift activations to the next stage (stage pp-1 sends nowhere)
            nxt = jax.lax.ppermute(y, axis_name, fwd_perm)
            return (nxt, out_buf), None

        cur0 = jnp.zeros_like(x_micro[0])
        (cur, out_buf), _ = jax.lax.scan(tick, (cur0, out_buf), jnp.arange(M + pp - 1))
        # every stage returns its buffer; only the last stage's holds the result
        # — the caller slices [-1], which fetches just that stage's shard
        return out_buf[None]  # [1, M, Bm, ...]

    sm = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(axis_name),
        axis_names={axis_name},
        check_vma=False,
    )

    def forward(stage_params_stack, x):
        x_micro = split_microbatches(x, M)
        stacked = sm(stage_params_stack, x_micro)  # [pp, M, Bm, ...]
        return merge_microbatches(stacked[-1])

    return forward


def prepare_pipeline(
    layer_params: list,
    stage_fn: Callable,
    mesh,
    num_microbatches: int | None = None,
    axis_name: str = "pp",
):
    """One-call pipeline prep (the reference's user entry ``prepare_pippy:126``:
    auto-split into balanced stages + a GPipe-scheduled forward). Balances the
    homogeneous layer stack over the ``pp`` mesh axis and returns
    ``(stage_params_stack, forward)`` with ``forward(stage_params_stack, x)``
    running the microbatched schedule. ``num_microbatches`` defaults to the
    pipeline degree (enough to fill the trapezoid)."""
    pp = int(mesh.shape[axis_name])
    if num_microbatches is None:
        num_microbatches = max(pp, 1)
    stacked = split_into_stages(layer_params, pp)
    forward = make_pipeline_forward(stage_fn, mesh, num_microbatches, axis_name)
    return stacked, forward


def make_pipeline_train_step_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh,
    num_microbatches: int,
    axis_name: str = "pp",
):
    """1F1B training schedule: backward for a microbatch starts as soon as its
    forward clears the last stage, so each stage holds at most
    ``2·(pp-1-s)+1`` in-flight microbatch inputs instead of GPipe's all-``M``
    residuals (reference precedent to beat: ScheduleGPipe,
    ``/root/reference/src/accelerate/inference.py:101-125`` — inference-only;
    Megatron's 1F1B is the training-side shape this matches).

    Mechanics (one ``lax.scan`` inside ``shard_map``, lockstep across stages):

    - tick ``k``: stage ``s`` FORWARDS microbatch ``m_f = k - s`` (the GPipe
      trapezoid) and BACKWARDS microbatch ``m_b = k - (2·pp - 2 - s)`` — on
      the last stage these coincide (loss vjp starts immediately), upstream
      stages run ``2·(pp-1-s)`` ticks behind, which is exactly the 1F1B
      interleave.
    - residuals: only each microbatch's stage INPUT is kept, in a ring buffer
      of depth ``min(M, 2·pp-1)``; the backward recomputes the stage forward
      inside ``jax.vjp`` (remat — the standard memory/flops trade of 1F1B
      implementations).
    - per-tick comms: one fwd ``ppermute`` (activations down) and one bwd
      ``ppermute`` (input-grads up) on the ICI ring.

    ``stage_fn(stage_params, x) -> y`` as in :func:`make_pipeline_forward`;
    ``loss_fn(y, target) -> scalar`` is applied per microbatch on the last
    stage (mean over microbatches is returned). Returns
    ``step(stage_params_stack, x, targets) -> (loss, grads_stack)`` with
    ``grads_stack`` sharded ``[pp, ...]`` like the params.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    pp = int(mesh.shape[axis_name])
    M = num_microbatches
    if pp <= 1:
        def step_trivial(stage_params_stack, x, targets):
            sp = jax.tree_util.tree_map(lambda a: a[0], stage_params_stack)

            def whole(p, x, t):
                return loss_fn(stage_fn(p, x), t)

            loss, grads = jax.value_and_grad(whole)(sp, x, targets)
            return loss, jax.tree_util.tree_map(lambda g: g[None], grads)

        return step_trivial

    R = min(M, 2 * pp - 1)  # ring depth ≥ max in-flight microbatches per stage
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]
    bwd_perm = [(i + 1, i) for i in range(pp - 1)]
    T = M + 2 * pp - 2  # last tick: stage 0's backward of microbatch M-1

    def _local(stage_params, x_micro, tgt_micro):
        params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        idx = jax.lax.axis_index(axis_name)
        is_last = idx == pp - 1
        zero_x = jnp.zeros_like(x_micro[0])

        def fwd_only(p, x):
            return stage_fn(p, x)

        def tick(carry, k):
            cur_fwd, cur_bwd, ring, grads_acc, loss_acc = carry

            # ---- forward slot: microbatch m_f = k - idx --------------------
            m_f = k - idx
            fwd_valid = jnp.logical_and(m_f >= 0, m_f < M)
            x_in = jnp.where(idx == 0, x_micro[jnp.clip(m_f, 0, M - 1)], cur_fwd)
            y = stage_fn(params, x_in)
            slot_f = jnp.clip(m_f, 0, M - 1) % R
            ring = jax.lax.cond(
                fwd_valid,
                lambda r: jax.lax.dynamic_update_index_in_dim(r, x_in, slot_f, 0),
                lambda r: r,
                ring,
            )

            # ---- backward slot: microbatch m_b = k - (2pp - 2 - idx) -------
            m_b = k - (2 * pp - 2 - idx)
            bwd_valid = jnp.logical_and(m_b >= 0, m_b < M)
            slot_b = jnp.clip(m_b, 0, M - 1) % R
            x_saved = ring[slot_b]
            target = tgt_micro[jnp.clip(m_b, 0, M - 1)]

            # ONE stage vjp per tick: the cotangent is the loss grad wrt this
            # stage's OWN recomputed output on the last stage, or the grad
            # received from downstream elsewhere (lockstep SPMD — the cheap
            # loss-only grad runs masked everywhere, the expensive stage
            # backward runs once)
            y_saved, vjp = jax.vjp(fwd_only, params, x_saved)
            loss_m, dy_last = jax.value_and_grad(loss_fn)(y_saved, target)
            cot = jnp.where(is_last, dy_last, cur_bwd)
            dp, dx = vjp(cot.astype(y_saved.dtype))
            grads_acc = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(bwd_valid, g, jnp.zeros_like(g)),
                grads_acc,
                dp,
            )
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(bwd_valid, is_last), loss_m, 0.0
            )

            nxt_fwd = jax.lax.ppermute(y, axis_name, fwd_perm)
            nxt_bwd = jax.lax.ppermute(
                jnp.where(bwd_valid, dx, jnp.zeros_like(dx)), axis_name, bwd_perm
            )
            return (nxt_fwd, nxt_bwd, ring, grads_acc, loss_acc), None

        ring0 = jnp.zeros((R,) + x_micro.shape[1:], x_micro.dtype)
        grads0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        carry0 = (zero_x, jnp.zeros_like(zero_x), ring0, grads0, jnp.float32(0.0))
        (_, _, _, grads_acc, loss_acc), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T)
        )
        grads_acc = jax.tree_util.tree_map(lambda g: (g / M)[None], grads_acc)
        # only the last stage accumulated a nonzero loss; psum shares it, and
        # each stage emits one slot of a [pp] vector (partial-manual shard_map
        # requires outputs to carry the manual axis)
        loss = jax.lax.psum(loss_acc / M, axis_name)
        return loss[None], grads_acc

    sm = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(axis_name), P(), P()),
        out_specs=(P(axis_name), P(axis_name)),
        axis_names={axis_name},
        check_vma=False,
    )

    import functools

    @functools.partial(jax.jit)  # partial-manual shard_map requires jit context
    def step(stage_params_stack, x, targets):
        x_micro = split_microbatches(x, M)
        tgt_micro = split_microbatches(targets, M)
        loss_stack, grads = sm(stage_params_stack, x_micro, tgt_micro)
        return loss_stack[0], grads

    return step
