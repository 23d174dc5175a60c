"""LocalSGD: k-step local updates, then cross-replica parameter averaging.

TPU-native counterpart of the reference's ``local_sgd.py``
(``/root/reference/src/accelerate/local_sgd.py`` — ``LocalSGD:19``,
``_sync_and_avg_model_params:97-106`` which calls ``reduce(params, "mean")``).
Communication drops from every-step gradient allreduce to a parameter average
every ``local_sgd_steps`` — useful when dp replicas sit across DCN.

Two surfaces:

- :class:`LocalSGD` — imperative context manager with the reference's API
  (``with LocalSGD(...) as ls: ... ls.step()``).
- :func:`make_local_sgd_train_step` — the compiled path: each ``dp`` group keeps
  its OWN param copy (leaves carry a leading ``dp`` axis, sharded over the mesh
  so HBM cost equals the replicated baseline), updates locally with zero
  cross-replica traffic, and a traced ``lax.cond`` averages params only on
  boundary steps. The reference cannot express this (DDP syncs in backward);
  under ``shard_map`` it is one scan-friendly jitted function.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import numpy as np

from .utils import operations as ops


class LocalSGD:
    """Imperative parity surface (reference ``LocalSGD:19``).

    ``step()`` counts micro-steps; every ``local_sgd_steps`` the registered
    params are averaged across replicas via ``reduce(..., "mean")`` exactly like
    the reference's ``_sync_and_avg_model_params``.
    """

    def __init__(self, accelerator, model=None, local_sgd_steps: int = 8, enabled: bool = True):
        if accelerator.parallelism_config is not None and accelerator.parallelism_config.tp_enabled:
            raise NotImplementedError("LocalSGD is not supported with tensor parallelism")
        self.enabled = enabled and accelerator.use_distributed
        self.accelerator = accelerator
        self.local_sgd_steps = local_sgd_steps
        self.num_steps = 0
        self._params = model

    def __enter__(self):
        if self.enabled:
            # local phase: suppress grad sync bookkeeping (reference __enter__
            # enters model.no_sync())
            self.accelerator.gradient_state._set_sync_gradients(False)
        return self

    def __exit__(self, *exc):
        if self.enabled:
            self._sync_and_avg()
            self.accelerator.gradient_state._set_sync_gradients(True)

    def step(self, params=None):
        """Call after every optimizer step; averages on the k-step boundary."""
        if params is not None:
            self._params = params
        self.num_steps += 1
        if not self.enabled:
            return self._params
        if self.num_steps % self.local_sgd_steps == 0:
            self._params = self._sync_and_avg()
        return self._params

    def _sync_and_avg(self):
        if self._params is not None:
            self._params = ops.reduce_(self._params, reduction="mean")
        return self._params


def make_local_sgd_train_step(
    loss_fn: Callable,
    optimizer,
    mesh,
    local_sgd_steps: int = 8,
    dp_axis: str = "dp_shard",
    jit: bool = True,
) -> Callable:
    """Compiled local-SGD: ``step(params_stack, opt_state_stack, batch, step_idx)``.

    ``params_stack`` leaves have a leading axis of size ``mesh.shape[dp_axis]``,
    sharded over ``dp_axis`` — each dp group trains its own replica. Gradients
    never cross replicas; on steps where ``(step_idx+1) % local_sgd_steps == 0``
    a ``lax.pmean`` over ``dp_axis`` averages params (and resets nothing else).

    Build the stack with :func:`replicate_for_local_sgd`.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_rep = int(mesh.shape[dp_axis])

    def _local_step(params, opt_state, batch, step_idx):
        # params leaves arrive as [1, ...] local slices inside shard_map
        squeeze = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
        unsqueeze = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        p, s = squeeze(params), squeeze(opt_state)
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        updates, new_s = optimizer.update(grads, s, p)
        new_p = optax.apply_updates(p, updates)
        do_avg = (step_idx + 1) % local_sgd_steps == 0
        new_p = jax.lax.cond(
            do_avg,
            lambda t: jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, dp_axis), t),
            lambda t: t,
            new_p,
        )
        # loss averaged for reporting only
        loss = jax.lax.pmean(loss, dp_axis)
        return unsqueeze(new_p), unsqueeze(new_s), loss

    def _specs_like(tree, leading):
        return jax.tree_util.tree_map(lambda _: P(*leading), tree, is_leaf=lambda x: x is None)

    def step(params_stack, opt_state_stack, batch, step_idx):
        stack_spec = jax.tree_util.tree_map(lambda _: P(dp_axis), params_stack)
        opt_spec = jax.tree_util.tree_map(lambda _: P(dp_axis), opt_state_stack)
        batch_spec = jax.tree_util.tree_map(lambda _: P(dp_axis), batch)
        fn = shard_map(
            _local_step,
            mesh=mesh,
            in_specs=(stack_spec, opt_spec, batch_spec, P()),
            out_specs=(stack_spec, opt_spec, P()),
            check_vma=False,
        )
        return fn(params_stack, opt_state_stack, batch, step_idx)

    return jax.jit(step) if jit else step


def replicate_for_local_sgd(tree, mesh, dp_axis: str = "dp_shard"):
    """Stack a param/opt-state tree ``n_rep`` times along a new leading axis and
    shard it over ``dp_axis`` (each dp group gets one resident copy)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_rep = int(mesh.shape[dp_axis])

    def _stack(x):
        stacked = jnp.stack([jnp.asarray(x)] * n_rep, axis=0)
        return jax.device_put(stacked, NamedSharding(mesh, P(dp_axis)))

    return jax.tree_util.tree_map(_stack, tree)


def unstack_local_sgd(tree_stack, index: int = 0):
    """Take one replica back out of the stack (they are equal right after an
    averaging boundary)."""
    import jax

    return jax.tree_util.tree_map(lambda x: x[index], tree_stack)
