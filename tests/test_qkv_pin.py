"""The pin on the q/k/v projections' outputs (``transformer.pin_qkv``) is
invisible to the numbers: with ``jax.lax.optimization_barrier`` stood in by
the identity, both layer functions and the training loss's gradient come out
bit for bit the same. What the pin is FOR is a compiled TPU program's layout,
which ``tests/test_tpu_compile.py`` holds; the served tokens are held to the
references by the engines' parity tests."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import LlamaConfig, init_llama, llama_loss
from accelerate_tpu.models import cohere2_moe as cm
from accelerate_tpu.models import transformer as tr

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _cast(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def _plain_attend(q, k, v, *window):
    """Causal attention over the call's own keys; the pin sits before it."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    S = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s / np.sqrt(q.shape[-1]), -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1).astype(q.dtype), v)


def _llama_layer_call(dtype):
    config = LlamaConfig.tiny()
    params = _cast(init_llama(config, jax.random.PRNGKey(0)), dtype)
    lp = jax.tree.map(lambda x: x[1], params["layers"])
    cos, sin = tr.llama_rope(config)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 24, config.dim), dtype)
    return lambda lp, h: tr.llama_layer(lp, h, None, cos, sin, config, _plain_attend)[0], (lp, h)


def _cohere_layer_call(dtype):
    config = cm.Cohere2MoeConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=8, n_kv_heads=2, head_dim=16, expert_dim=64,
        num_experts=16, experts_per_token=4, num_shared_experts=2, sliding_window=32,
        max_seq_len=128)
    params = _cast(cm.init_cohere2_moe(config, jax.random.PRNGKey(0)), dtype)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 24, config.dim), dtype)
    positions = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    # layer 0 turns its queries and keys (a window layer), layer 3 does not
    return lambda layer: lambda params, h: cm._layer(
        params["layers"][layer], h, positions, None, config, layer, _plain_attend)[0], (params, h)


def _with_and_without_the_pin(monkeypatch, fn, args):
    """``fn(*args)`` traced and run with the barrier, then with the identity
    in its place; the first trace must hold the barrier and the second must
    not. The arrays are arguments, not constants the compiler could fold (it
    folds a dot with another summation order than it runs one)."""
    # a fresh function each time: jit and make_jaxpr key their traces on it
    pinned_jaxpr = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    pinned = jax.jit(lambda *a: fn(*a))(*args)
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    plain_jaxpr = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    plain = jax.jit(lambda *a: fn(*a))(*args)
    assert "optimization_barrier" in pinned_jaxpr and "optimization_barrier" not in plain_jaxpr
    return pinned, plain


def _assert_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(
            np.asarray(x.astype(jnp.float32)), np.asarray(y.astype(jnp.float32)))


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_llama_layer_is_bitwise_the_same_without_the_pin(monkeypatch, dtype):
    call, args = _llama_layer_call(DTYPES[dtype])
    _assert_bitwise(*_with_and_without_the_pin(monkeypatch, call, args))


@pytest.mark.parametrize("layer", [0, 3], ids=["window-layer", "full-layer"])
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_cohere2_moe_layer_is_bitwise_the_same_without_the_pin(monkeypatch, dtype, layer):
    call, args = _cohere_layer_call(DTYPES[dtype])
    _assert_bitwise(*_with_and_without_the_pin(monkeypatch, call(layer), args))


@pytest.mark.parametrize("unroll_layers", [True, False], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_llama_loss_and_its_gradient_are_bitwise_the_same_without_the_pin(
        monkeypatch, dtype, unroll_layers):
    config = dataclasses.replace(LlamaConfig.tiny(), unroll_layers=unroll_layers)
    params = _cast(init_llama(config, jax.random.PRNGKey(0)), DTYPES[dtype])
    batch = {"input_ids": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, config.vocab_size)}
    grad = lambda params, batch: jax.value_and_grad(llama_loss)(params, batch, config)
    (loss, g), (loss_plain, g_plain) = _with_and_without_the_pin(monkeypatch, grad, (params, batch))
    assert np.isfinite(float(loss)) and float(loss) == float(loss_plain)
    _assert_bitwise(g, g_plain)
    assert any(float(jnp.abs(x.astype(jnp.float32)).max()) > 0 for x in jax.tree.leaves(g["layers"]["wq"]))


def test_the_pin_passes_through_vmap():
    """``generation.py`` and the engine's sampling ``vmap`` over rows; the
    barrier has a batching rule."""
    q = jnp.arange(24.0).reshape(2, 3, 4)
    out = jax.vmap(lambda x: tr.pin_qkv(x, 2 * x, 3 * x))(q)
    _assert_bitwise(out, (q, 2 * q, 3 * q))
