"""Attention masking: segment-id semantics vs explicit padding masks, and
flash-vs-xla parity (the TPU-gated case pins the Pallas kernel against the
einsum reference under a padding mask — round-2 verdict item 2)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.attention import (
    dot_product_attention,
    make_padding_mask,
)
from accelerate_tpu.ops.flash_attention import flash_attention
from accelerate_tpu.test_utils.testing import require_tpu


def _qkv(b=2, s=32, h=4, d=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d)) for k in keys)


class TestSegmentIds:
    def test_segment_ids_match_padding_mask_on_valid_rows(self):
        """At valid query positions, segment-id masking must equal the
        key-padding-mask einsum path (padded queries differ by design: they
        attend only other pads under segment semantics)."""
        q, k, v = _qkv()
        valid = 20
        attn_mask = np.zeros((2, 32), np.int32)
        attn_mask[:, :valid] = 1

        out_seg = dot_product_attention(
            q, k, v, segment_ids=jnp.asarray(attn_mask), impl="xla"
        )
        out_mask = dot_product_attention(
            q, k, v, mask=make_padding_mask(jnp.asarray(attn_mask), 32), impl="xla"
        )
        np.testing.assert_allclose(
            np.asarray(out_seg[:, :valid]), np.asarray(out_mask[:, :valid]), atol=1e-6
        )

    def test_packed_segments_do_not_cross_attend(self):
        """Two packed documents: tokens of doc A must be unaffected by doc B's
        content (the packing use case of segment ids)."""
        q, k, v = _qkv()
        seg = np.ones((2, 32), np.int32)
        seg[:, 16:] = 2
        out = dot_product_attention(q, k, v, segment_ids=jnp.asarray(seg), impl="xla")

        k2 = k.at[:, 16:].set(jax.random.normal(jax.random.PRNGKey(9), (2, 16, 4, 16)))
        v2 = v.at[:, 16:].set(jax.random.normal(jax.random.PRNGKey(10), (2, 16, 4, 16)))
        out2 = dot_product_attention(q, k2, v2, segment_ids=jnp.asarray(seg), impl="xla")
        np.testing.assert_allclose(
            np.asarray(out[:, :16]), np.asarray(out2[:, :16]), atol=1e-6
        )

    def test_segment_ids_with_causal(self):
        q, k, v = _qkv()
        seg = np.ones((2, 32), np.int32)
        seg[:, 24:] = 0
        out = dot_product_attention(
            q, k, v, causal=True, segment_ids=jnp.asarray(seg), impl="xla"
        )
        assert np.all(np.isfinite(np.asarray(out)))

    def test_flash_wrapper_falls_back_with_segments_off_tpu(self):
        q, k, v = _qkv()
        seg = np.ones((2, 32), np.int32)
        seg[:, 24:] = 0
        out_flash = flash_attention(q, k, v, segment_ids=jnp.asarray(seg))
        out_xla = dot_product_attention(q, k, v, segment_ids=jnp.asarray(seg), impl="xla")
        np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_xla), atol=1e-5)

    def test_arbitrary_mask_rejects_flash(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError):
            dot_product_attention(
                q, k, v, mask=jnp.ones((2, 1, 32, 32), bool), impl="flash"
            )


@require_tpu
class TestFlashParityTPU:
    """Pallas kernel vs einsum reference on real TPU hardware."""

    def test_flash_matches_xla_under_padding(self):
        b, s, h, d = 2, 256, 4, 64
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16) for kk in keys)
        seg = np.ones((b, s), np.int32)
        seg[:, 200:] = 0
        seg = jnp.asarray(seg)
        out_flash = dot_product_attention(q, k, v, segment_ids=seg, impl="flash")
        out_xla = dot_product_attention(q, k, v, segment_ids=seg, impl="xla")
        np.testing.assert_allclose(
            np.asarray(out_flash[:, :200], dtype=np.float32),
            np.asarray(out_xla[:, :200], dtype=np.float32),
            atol=2e-2,
        )

    def test_flash_grads_match_xla_under_padding(self):
        b, s, h, d = 2, 256, 4, 64
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.float32) for kk in keys)
        seg = np.ones((b, s), np.int32)
        seg[:, 192:] = 0
        seg = jnp.asarray(seg)

        def loss(impl, q, k, v):
            out = dot_product_attention(q, k, v, segment_ids=seg, impl=impl)
            return jnp.sum(out[:, :192] ** 2)

        gf = jax.grad(lambda *a: loss("flash", *a), argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(lambda *a: loss("xla", *a), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-2, rtol=1e-2)


class TestAttnImplConfigKnob:
    """`LlamaConfig.attn_impl` (ISSUE 18 satellite): the config knob feeds
    `llama_forward`'s default attention implementation, and an explicit
    `attention_impl=` argument still wins over the config."""

    def _setup(self):
        from dataclasses import replace

        from accelerate_tpu.models import LlamaConfig, init_llama, llama_forward

        cfg = LlamaConfig.tiny()
        params = init_llama(cfg, jax.random.PRNGKey(0))
        ids = jnp.asarray(np.arange(2 * 16).reshape(2, 16) % cfg.vocab_size)
        return replace, cfg, params, ids, llama_forward

    def test_config_default_is_auto_and_round_trips(self):
        replace, cfg, _, _, _ = self._setup()
        assert cfg.attn_impl == "auto"
        assert replace(cfg, attn_impl="flash").attn_impl == "flash"
        assert cfg.attn_impl == "auto"  # frozen original untouched

    def test_flash_knob_matches_xla_off_tpu(self):
        """Off TPU the flash impl IS the einsum reference, so wiring the knob
        through the config must reproduce attn_impl='xla' exactly."""
        replace, cfg, params, ids, llama_forward = self._setup()
        out_flash = llama_forward(params, ids, replace(cfg, attn_impl="flash"))
        out_xla = llama_forward(params, ids, replace(cfg, attn_impl="xla"))
        np.testing.assert_allclose(
            np.asarray(out_flash), np.asarray(out_xla), atol=1e-6
        )

    def test_explicit_argument_overrides_config(self):
        replace, cfg, params, ids, llama_forward = self._setup()
        out_arg = llama_forward(
            params, ids, replace(cfg, attn_impl="flash"), attention_impl="xla"
        )
        out_xla = llama_forward(params, ids, replace(cfg, attn_impl="xla"))
        assert np.array_equal(np.asarray(out_arg), np.asarray(out_xla))
