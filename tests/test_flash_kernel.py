"""In-tree blocked flash attention: interpret-mode parity matrix on CPU tier-1.

The kernel (``ops.flash_attention``) streams KV blocks through VMEM with f32
online softmax over a ``(B·H, q_blocks, kv_blocks)`` grid, broadcasts GQA
heads in-kernel via the k/v index maps, and skips fully-masked
(q_block, kv_block) tiles through a scalar-prefetch block lattice.
``ACCELERATE_FLASH_KERNEL=interpret`` runs the IDENTICAL kernel through the
Pallas interpreter, so these tests drive the exact TPU dataflow — including
the custom_vjp backward — in CPU CI:

- fwd parity vs the einsum reference at dtype-appropriate tolerance
  (f32 near machine-eps, bf16 within the documented envelope);
- bwd grads vs ``jax.grad`` of the reference;
- four GQA ratios (the kv index maps, not an HBM repeat, do the broadcast);
- sliding-window + packed-segment block-skip correctness: NaN-poison a
  skipped block and the unaffected rows must come out bitwise unchanged
  (a streamed-but-masked block would still poison the online max);
- the ``ACCELERATE_FLASH_KERNEL=0`` kill switch is byte-identical to the
  einsum reference;
- the fwd+bwd HLO materializes neither an [B,H,S,S] score tensor nor a
  repeated-KV broadcast.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.attention import (
    _xla_attention,
    dot_product_attention,
    segment_mask,
)
from accelerate_tpu.ops.flash_attention import (
    _block_lattice,
    _FlashConfig,
    flash_attention,
    flash_kernel_mode,
)

BQ = BKV = 32  # small blocks: several grid steps per axis even at S=128


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setenv("ACCELERATE_FLASH_KERNEL", "interpret")


def _qkv(b=2, s=128, h=4, hkv=None, d=16, dtype=jnp.float32, seed=0):
    hkv = h if hkv is None else hkv
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, s, h, d), dtype)
    k = jax.random.normal(keys[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(keys[2], (b, s, hkv, d), dtype)
    return q, k, v


def _packed_seg(b=2, s=128):
    # two packed documents + a padded tail, block-aligned at 32
    return jnp.asarray(np.repeat([[1] * 64 + [2] * 40 + [0] * 24], b, 0), jnp.int32)


def _reference(q, k, v, *, causal=False, segment_ids=None, window=None):
    mask = segment_mask(segment_ids) if segment_ids is not None else None
    return _xla_attention(q, k, v, causal=causal, mask=mask, scale=None, window=window)


MASK_CASES = [
    ("dense", {}),
    ("causal", dict(causal=True)),
    ("window", dict(causal=True, window=40)),
    ("packed", dict(segment_ids="packed")),
    ("all", dict(causal=True, window=50, segment_ids="packed")),
]


def _resolve(kw, b=2, s=128):
    kw = dict(kw)
    if kw.get("segment_ids") == "packed":
        kw["segment_ids"] = _packed_seg(b, s)
    return kw


class TestForwardParity:
    @pytest.mark.parametrize("name,kw", MASK_CASES)
    def test_f32_parity_tight(self, interpret_mode, name, kw):
        """f32: the kernel's online softmax reorders the reduction, so exact
        bitwise equality vs the two-pass einsum is not defined — but both
        accumulate in f32, so parity holds to a few ulps of the row sums.
        (Bitwise equality is the KILL SWITCH's contract, tested below.)"""
        q, k, v = _qkv()
        kw = _resolve(kw)
        out = flash_attention(q, k, v, block_q=BQ, block_kv=BKV, **kw)
        ref = _reference(q, k, v, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6, rtol=0)

    @pytest.mark.parametrize("name,kw", MASK_CASES)
    def test_bf16_parity_envelope(self, interpret_mode, name, kw):
        """bf16: inputs and the PV operands are bf16 (f32 accumulate), same
        as the reference einsum — the documented envelope is 2e-2."""
        q, k, v = _qkv(dtype=jnp.bfloat16)
        kw = _resolve(kw)
        out = flash_attention(q, k, v, block_q=BQ, block_kv=BKV, **kw)
        ref = _reference(q, k, v, **kw)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )

    def test_rectangular_blocks(self, interpret_mode):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=64)
        ref = _reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


class TestBackwardParity:
    @pytest.mark.parametrize("name,kw", MASK_CASES)
    def test_grads_match_reference(self, interpret_mode, name, kw):
        q, k, v = _qkv()
        kw = _resolve(kw)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=BQ, block_kv=BKV, **kw) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_reference(q, k, v, **kw) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name_, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name_} ({name})"
            )


class TestGQA:
    @pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 2), (8, 1)])
    def test_gqa_ratios_fwd_and_bwd(self, interpret_mode, h, hkv):
        """The GQA broadcast lives in the kv BlockSpec index maps (fwd/dq) and
        the group-member walk of the dk/dv kernel — every ratio must match
        the reference's explicit head repetition."""
        q, k, v = _qkv(h=h, hkv=hkv)
        out = flash_attention(q, k, v, causal=True, block_q=BQ, block_kv=BKV)
        ref = _reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)

        gf = jax.grad(
            lambda a, b, c: jnp.sum(
                flash_attention(a, b, c, causal=True, block_q=BQ, block_kv=BKV) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        gr = jax.grad(
            lambda a, b, c: jnp.sum(_reference(a, b, c, causal=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                       err_msg=f"d{name} H={h} Hkv={hkv}")


class TestBlockSkip:
    """Skipped blocks are never streamed: NaN-poisoning one must leave every
    row that does not attend into it bitwise unchanged. A kernel that streamed
    the block and merely masked it would propagate the NaN through the online
    max/exp."""

    def test_sliding_window_skips_out_of_band_blocks(self, interpret_mode):
        q, k, v = _qkv(b=1, h=2, hkv=2)
        # window=32, blocks of 32: query rows >= 64 never touch kv block 0
        kbad = k.at[:, :32].set(jnp.nan)
        vbad = v.at[:, :32].set(jnp.nan)
        out = flash_attention(q, k, v, causal=True, window=32, block_q=BQ, block_kv=BKV)
        outbad = flash_attention(
            q, kbad, vbad, causal=True, window=32, block_q=BQ, block_kv=BKV
        )
        assert bool(jnp.all(out[:, 64:] == outbad[:, 64:]))
        assert bool(jnp.all(jnp.isfinite(outbad[:, 64:])))

    def test_packed_segments_skip_cross_document_blocks(self, interpret_mode):
        q, k, v = _qkv(b=1, h=2, hkv=2)
        seg = jnp.asarray([[1] * 64 + [2] * 64], jnp.int32)
        kbad = k.at[:, :64].set(jnp.nan)
        out = flash_attention(q, k, v, segment_ids=seg, block_q=BQ, block_kv=BKV)
        outbad = flash_attention(q, kbad, v, segment_ids=seg, block_q=BQ, block_kv=BKV)
        assert bool(jnp.all(out[:, 64:] == outbad[:, 64:]))

    def test_backward_also_skips(self, interpret_mode):
        """dq of in-band rows must ignore poisoned out-of-band KV blocks —
        the dq kernel walks the same lattice as the forward."""
        q, k, v = _qkv(b=1, h=2, hkv=2)
        kbad = k.at[:, :32].set(jnp.nan)
        vbad = v.at[:, :32].set(jnp.nan)

        def dq_of(kk, vv):
            return jax.grad(
                lambda a: jnp.sum(
                    flash_attention(
                        a, kk, vv, causal=True, window=32, block_q=BQ, block_kv=BKV
                    )[:, 64:]
                    ** 2
                )
            )(q)

        assert bool(jnp.all(dq_of(k, v)[:, 64:] == dq_of(kbad, vbad)[:, 64:]))

    def test_lattice_counts_scale_with_sparsity(self):
        """The lattice itself: causal halves the active tiles, a window
        caps them per row, and padding tails drop out entirely."""
        seg = jnp.ones((1, 128), jnp.int32)
        base = dict(scale=1.0, block_q=32, block_kv=32, h=1, hkv=1,
                    use_seg=False, interpret=True)
        dense = _block_lattice(seg, _FlashConfig(causal=False, window=None, **base))
        causal = _block_lattice(seg, _FlashConfig(causal=True, window=None, **base))
        window = _block_lattice(seg, _FlashConfig(causal=True, window=32, **base))
        assert int(dense[1].sum()) == 16  # 4x4 all active
        assert int(causal[1].sum()) == 10  # lower triangle of 4x4
        assert int(window[1].sum()) == 7  # diagonal + one band below
        # packed docs: block-aligned documents never cross
        seg2 = jnp.asarray([[1] * 64 + [2] * 64], jnp.int32)
        packed = _block_lattice(
            seg2,
            _FlashConfig(causal=False, window=None, scale=1.0, block_q=32,
                         block_kv=32, h=1, hkv=1, use_seg=True, interpret=True),
        )
        assert int(packed[1].sum()) == 8  # two 2x2 diagonal blocks


class TestKillSwitch:
    def test_off_mode_is_byte_identical_to_einsum(self, monkeypatch):
        monkeypatch.setenv("ACCELERATE_FLASH_KERNEL", "0")
        assert flash_kernel_mode() == "off"
        q, k, v = _qkv()
        seg = _packed_seg()
        out = flash_attention(q, k, v, causal=True, segment_ids=seg)
        ref = _reference(q, k, v, causal=True, segment_ids=seg)
        assert bool(jnp.all(out == ref))

    def test_mode_parsing(self, monkeypatch):
        for raw, want in [("1", "on"), ("0", "off"), ("off", "off"),
                          ("false", "off"), ("interpret", "interpret")]:
            monkeypatch.setenv("ACCELERATE_FLASH_KERNEL", raw)
            assert flash_kernel_mode() == want
        monkeypatch.delenv("ACCELERATE_FLASH_KERNEL", raising=False)
        assert flash_kernel_mode() == "on"

    def test_untileable_shapes_fall_back(self, interpret_mode):
        # cross-attention (Sq != Skv) is reference territory
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 4, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 4, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 4, 16))
        out = flash_attention(q, k, v, causal=True)
        ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
        assert bool(jnp.all(out == ref))


def _broadcast_blowups(hlo: str):
    """(operand_numel, result_numel) for every non-scalar broadcast in the
    lowered text — a repeated-KV materialization shows up as numel × groups."""
    out = []
    for line in hlo.splitlines():
        if "broadcast" not in line:
            continue
        shapes = re.findall(r"tensor<([0-9x]+)x[a-z0-9]+>", line)
        if len(shapes) >= 2:
            nums = [int(np.prod([int(d) for d in s.split("x")])) for s in shapes]
            out.append((nums[0], nums[-1]))
    return out


class TestHLO:
    B, S, H, HKV, D = 2, 256, 8, 2, 64

    def _grad_hlo(self, fn):
        q, k, v = _qkv(b=self.B, s=self.S, h=self.H, hkv=self.HKV, d=self.D)
        grad = jax.grad(lambda a, b, c: jnp.sum(fn(a, b, c) ** 2), argnums=(0, 1, 2))
        return jax.jit(grad).lower(q, k, v).as_text()

    def test_no_score_tensor_and_no_repeated_kv(self, interpret_mode):
        hlo = self._grad_hlo(
            lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
        )
        # no [.., S, S] score tensor anywhere in fwd+bwd
        assert f"x{self.S}x{self.S}x" not in hlo
        # no broadcast inflating a KV-sized tensor to q-head size
        kv_numel = self.B * self.S * self.HKV * self.D
        q_numel = self.B * self.S * self.H * self.D
        blowups = [p for p in _broadcast_blowups(hlo) if p == (kv_numel, q_numel)]
        assert not blowups, blowups

    def test_reference_does_materialize_both(self):
        """Sanity: the detector fires on the einsum reference, which builds
        the [B,H,S,S] scores and repeats KV across the GQA groups."""
        hlo = self._grad_hlo(
            lambda q, k, v: _xla_attention(q, k, v, causal=True, mask=None, scale=None)
        )
        assert f"x{self.S}x{self.S}x" in hlo
        kv_numel = self.B * self.S * self.HKV * self.D
        q_numel = self.B * self.S * self.H * self.D
        assert any(p == (kv_numel, q_numel) for p in _broadcast_blowups(hlo))


class TestDispatch:
    def test_window_requires_causal(self):
        q, k, v = _qkv(s=32)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, window=8)
        with pytest.raises(ValueError, match="causal"):
            dot_product_attention(q, k, v, window=8, impl="xla")

    def test_explicit_flash_never_gives_way_on_tpu(self, monkeypatch):
        """On a TPU an explicitly requested kernel that cannot take the shape
        raises and names it; only impl="auto" may choose the einsum path (and
        off-TPU the einsum reference is the implementation)."""
        q, k, v = _qkv(s=32)
        kx = jnp.concatenate([k, k], axis=1)  # cross-attention: Sq != Skv
        vx = jnp.concatenate([v, v], axis=1)
        ref = dot_product_attention(q, kx, vx, impl="xla")
        assert bool(jnp.all(flash_attention(q, kx, vx) == ref))  # CPU: reference
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match=r"cannot tile q=\(2, 32, "):
            dot_product_attention(q, kx, vx, impl="flash")
        assert bool(jnp.all(dot_product_attention(q, kx, vx, impl="auto") == ref))

    def test_auto_choice_is_logged_once_per_shape(self, monkeypatch):
        from accelerate_tpu.ops import attention as attn

        lines = []
        monkeypatch.setattr(attn.logger, "info", lambda msg, *a, **k: lines.append(msg))
        attn._log_auto_choice.cache_clear()
        q, k, v = _qkv(s=32)
        for _ in range(2):
            dot_product_attention(q, k, v, causal=True, impl="auto")
        assert len(lines) == 1
        assert "chose 'xla'" in lines[0] and "q=(2, 32," in lines[0]

    def test_xla_window_band(self):
        """The xla path's band mask equals an explicit additive window mask."""
        q, k, v = _qkv(s=32)
        out = dot_product_attention(q, k, v, causal=True, window=8, impl="xla")
        i = np.arange(32)[:, None]
        j = np.arange(32)[None, :]
        allow = (j <= i) & (i - j < 8)
        ref = dot_product_attention(
            q, k, v, mask=jnp.asarray(allow)[None, None], impl="xla"
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_auto_crossover_consults_table_off_tpu(self):
        """Off-TPU auto must stay on the einsum path regardless of S — the
        crossover table only applies where the kernel can run natively."""
        from accelerate_tpu.ops.attention import _flash_supported

        q, k, v = _qkv(s=512, d=64)
        assert not _flash_supported(q, k, causal=True)
        out = dot_product_attention(q, k, v, causal=True, impl="auto")
        ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
        assert bool(jnp.all(out == ref))

    def test_crossover_table_orders_sparsity(self):
        """Sparser masks cross over earlier: the block lattice drops tiles, so
        the kernel's streamed work shrinks while the einsum path does not."""
        from accelerate_tpu.ops.attention import ATTN_CROSSOVER_S

        for dkey in ("bf16", "f32"):
            assert (
                ATTN_CROSSOVER_S[(dkey, "window")]
                <= ATTN_CROSSOVER_S[(dkey, "causal")]
                <= ATTN_CROSSOVER_S[(dkey, "dense")]
            )

    def test_dot_product_attention_window_through_flash(self, interpret_mode):
        q, k, v = _qkv()
        out = dot_product_attention(q, k, v, causal=True, window=40, impl="flash")
        ref = _reference(q, k, v, causal=True, window=40)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


# ---------------------------------------------------------------------------
# FP8 end-to-end: dtype_recipe="fp8" must keep the fused ZeRO-1 path ENGAGED
# (meta leaves ride as passthrough slots in the bucket plan instead of
# demoting the whole optimizer to the annotation path).


class TestFp8FusedZero1:
    def _reset(self):
        from accelerate_tpu.state import (
            AcceleratorState,
            GradientState,
            PartialState,
        )

        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()

    def _params(self):
        from accelerate_tpu.ops.fp8 import fp8_dense_init

        k = jax.random.split(jax.random.PRNGKey(0), 2)
        return {"l1": fp8_dense_init(k[0], 16, 32), "l2": fp8_dense_init(k[1], 32, 1)}

    @staticmethod
    def _loss(p, b):
        from accelerate_tpu.ops.fp8 import fp8_dense_apply

        h = jax.nn.relu(fp8_dense_apply(p["l1"], b["x"]))
        return jnp.mean((fp8_dense_apply(p["l2"], h) - b["y"]) ** 2)

    def _run(self, stage, steps=3, accum=1):
        import optax

        from accelerate_tpu import Accelerator, DeepSpeedPlugin

        self._reset()
        acc = Accelerator(
            cpu=True,
            mixed_precision="fp8",
            gradient_accumulation_steps=accum,
            deepspeed_plugin=DeepSpeedPlugin(zero_stage=stage),
            rng_seed=0,
        )
        params, opt = acc.prepare(self._params(), optax.adam(1e-2))
        step = acc.prepare_train_step(self._loss, opt)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 16)).astype(np.float32)
        batch = {
            "x": jnp.asarray(X),
            "y": jnp.asarray((X @ rng.normal(size=(16, 1))).astype(np.float32)),
        }
        s = opt.opt_state
        losses = []
        for _ in range(steps):
            params, s, m = step(params, s, batch)
            losses.append(float(m["loss"]))
        opt.opt_state = s
        return acc, opt, params, losses

    def test_plan_not_demoted_and_advertises_collectives(self):
        """The acceptance bar: fp8 meta must NOT clear the fused path. The
        plan keeps its bucket layout (meta leaves as passthrough slots) and
        still reports per-step collective bytes for telemetry."""
        acc, opt, params, _ = self._run(stage=1, steps=1)
        assert opt.fused_zero1
        plan = acc._sharding_plan
        assert plan.fused_zero1
        assert plan.zero1_collective_bytes() is not None
        assert plan.zero1.passthrough_indices  # the 6 meta history leaves
        assert len(plan.zero1.passthrough_indices) == 6

    def test_opt_state_is_one_over_n(self):
        acc, opt, _, _ = self._run(stage=1, steps=1)
        n = acc.mesh.shape["dp_replicate"]
        assert n == 8
        bucket_leaves = [
            x
            for x in jax.tree_util.tree_leaves(opt.opt_state)
            if hasattr(x, "addressable_shards")
            and getattr(x, "ndim", 0) == 1
            and any(ax is not None for ax in tuple(x.sharding.spec))
        ]
        assert bucket_leaves  # adam mu/nu buckets
        for leaf in bucket_leaves:
            shard = next(iter(leaf.addressable_shards))
            assert shard.data.size == leaf.size // n

    def test_parity_vs_unfused_baseline_and_meta_replacement(self):
        """Fused fp8 step vs the stage-0 (replicated, label-partitioned)
        baseline: same losses, params within the multichip tolerance, meta
        histories BITWISE equal (both sides install the same cotangent)."""
        from accelerate_tpu.ops.fp8 import META_KEY

        _, opt0, p0, l0 = self._run(stage=0)
        assert not opt0.fused_zero1
        _, opt1, p1, l1 = self._run(stage=1)
        assert opt1.fused_zero1
        for a, b in zip(l0, l1):
            assert abs(a - b) / max(abs(a), 1e-12) < 1.5e-7, (l0, l1)
        for name in ("l1", "l2"):
            np.testing.assert_allclose(
                np.asarray(p1[name]["kernel"]),
                np.asarray(p0[name]["kernel"]),
                atol=1e-7,
            )
            for hist in ("x_hist", "w_hist", "g_hist"):
                np.testing.assert_array_equal(
                    np.asarray(p1[name][META_KEY][hist]),
                    np.asarray(p0[name][META_KEY][hist]),
                )
            # histories actually rolled (replace-with-cotangent, not zeros)
            assert float(jnp.max(p1[name][META_KEY]["x_hist"])) > 0

    def test_accumulation_boundaries_under_fused_fp8(self):
        """MultiSteps wraps the BUCKETED inner tx: 4 micro-steps / accum 2 →
        2 optimizer steps, meta still rolling every micro-step."""
        from accelerate_tpu.optimizer import _find_multisteps_state
        from accelerate_tpu.ops.fp8 import META_KEY

        _, opt, params, _ = self._run(stage=1, steps=4, accum=2)
        assert opt.fused_zero1
        ms = _find_multisteps_state(opt.opt_state)
        assert ms is not None and int(ms.gradient_step) == 2
        assert float(jnp.max(params["l1"][META_KEY]["x_hist"])) > 0

    def test_llama_dtype_recipe_plan(self):
        """Model-level knob: a dtype_recipe='fp8' llama tree plans fused
        ZeRO-1 with every fp8_meta leaf passthrough, none bucketed."""
        from dataclasses import replace

        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from accelerate_tpu.models.transformer import LlamaConfig, init_llama
        from accelerate_tpu.ops.fp8 import META_KEY
        from accelerate_tpu.parallel.sharding import make_sharding_plan

        cfg = replace(LlamaConfig.tiny(), dtype_recipe="fp8")
        params = init_llama(cfg, jax.random.PRNGKey(0))
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp_replicate",))
        params = jax.device_put(params, NamedSharding(mesh, P()))
        plan = make_sharding_plan(params, mesh, zero1_axis="dp_replicate")
        assert plan.fused_zero1
        # 7 fp8 projections × 3 histories = 21 passthrough leaves
        assert len(plan.zero1.passthrough_indices) == 21
        paths, _ = jax.tree_util.tree_flatten_with_path(params)
        for i in plan.zero1.passthrough_indices:
            assert any(getattr(p, "key", None) == META_KEY for p in paths[i][0])
        bucketed = {s.leaf_index for s in plan.zero1.slots}
        assert not bucketed & set(plan.zero1.passthrough_indices)
