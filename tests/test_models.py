"""Model family tests: shapes, init statistics, learning, TP rule coverage."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from accelerate_tpu.models import (
    BertConfig,
    LlamaConfig,
    bert_forward,
    bert_loss,
    bert_shard_rules,
    init_bert,
    init_llama,
    llama_forward,
    llama_loss,
    llama_shard_rules,
)


@pytest.mark.smoke
def test_llama_forward_shapes_and_init_loss():
    cfg = LlamaConfig.tiny()
    params = init_llama(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    logits = llama_forward(params, ids, cfg)
    assert logits.shape == (2, 32, cfg.vocab_size)
    loss = float(llama_loss(params, {"input_ids": ids}, cfg))
    assert abs(loss - np.log(cfg.vocab_size)) < 0.5  # ~uniform at init


def test_llama_remat_policies_same_loss_and_grads():
    """remat=False/True/'dots'/'dots_no_batch' are pure memory/recompute
    trades — loss AND grads must match bit-for-bit-ish."""
    cfg = LlamaConfig.tiny()
    params = init_llama(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    batch = {"input_ids": ids}

    def lg(remat):
        return jax.value_and_grad(lambda p: llama_loss(p, batch, cfg, remat=remat))(params)

    ref_loss, ref_grads = lg(False)
    for remat in (True, "nothing", "dots", "dots_no_batch", "offload_dots"):
        loss, grads = lg(remat)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            grads, ref_grads,
        )
    import pytest

    with pytest.raises(ValueError):
        llama_loss(params, batch, cfg, remat="bogus")


def test_llama_loss_mask():
    cfg = LlamaConfig.tiny()
    params = init_llama(cfg, jax.random.PRNGKey(0))
    ids = np.ones((2, 16), np.int32)
    mask = np.zeros((2, 16), np.float32)
    loss = float(llama_loss(params, {"input_ids": ids, "loss_mask": mask}, cfg))
    assert loss == 0.0


def test_llama_overfits_single_batch():
    cfg = LlamaConfig.tiny()
    params = init_llama(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)
    opt = optax.adam(1e-2)
    st = opt.init(params)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(lambda p: llama_loss(p, {"input_ids": ids}, cfg))(p)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    for _ in range(30):
        params, st, loss = step(params, st)
    assert float(loss) < 1.0


def test_llama_tp_rules_cover_params():
    cfg = LlamaConfig.tiny()
    params = init_llama(cfg, jax.random.PRNGKey(0))
    rules = llama_shard_rules()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    from accelerate_tpu.parallel.sharding import _path_str

    for path, leaf in flat:
        spec = rules.match(_path_str(path))
        if leaf.ndim >= 2:
            assert spec is not None, f"no TP rule for {_path_str(path)}"


def test_llama_gqa_heads():
    cfg = LlamaConfig(vocab_size=128, dim=64, n_layers=1, n_heads=4, n_kv_heads=2, max_seq_len=64)
    params = init_llama(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["wk"]["kernel"].shape == (1, 64, 2 * 16)
    ids = np.zeros((1, 8), np.int32)
    assert llama_forward(params, ids, cfg).shape == (1, 8, 128)


@pytest.mark.parametrize("ffn", ["dense", "moe"])
@pytest.mark.parametrize("attend", ["whole", "contiguous", "paged"])
def test_the_one_llama_layer_under_its_three_attends(attend, ffn):
    """``llama_layer`` is the one statement of the decoder layer; what differs
    between its callers is ``attend``: the whole sequence at once, a contiguous
    cache (``generation._forward_cached``), the serving engine's paged pool
    (``LlamaConfig.paged_forward``, its blocks scrambled, a table one block
    wider than the sequence). Float32, the cached paths in two chunks (5 tokens,
    then 3 behind them): the logits are ``llama_forward``'s. The expert layer's
    capacity is high enough that no chunk drops a token."""
    from accelerate_tpu.generation import _forward_cached, init_kv_cache
    from accelerate_tpu.models.transformer import llama_head, llama_layer, llama_rope
    from accelerate_tpu.ops.attention import dot_product_attention
    from accelerate_tpu.serving import NULL_BLOCK, init_block_pool

    moe = dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=8.0) if ffn == "moe" else {}
    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                      max_seq_len=64, **moe)
    params = init_llama(cfg, jax.random.PRNGKey(0))
    B, S, cut, bs = 2, 8, 5, 4
    ids = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    want = np.asarray(llama_forward(params, ids, cfg, attention_impl="xla"))

    if attend == "whole":
        cos, sin = llama_rope(cfg)
        h = params["embed_tokens"]["embedding"][ids]
        for l in range(cfg.n_layers):
            h, _ = llama_layer(
                jax.tree_util.tree_map(lambda x: x[l], params["layers"]), h, None, cos, sin,
                cfg, lambda q, k, v: dot_product_attention(q, k, v, causal=True, impl="xla"))
        got = llama_head(params, h, cfg)
    elif attend == "contiguous":
        cache = init_kv_cache(cfg, B, S, jnp.float32)
        head, cache = _forward_cached(params, ids[:, :cut], cache, jnp.int32(0), cfg)
        tail, cache = _forward_cached(params, ids[:, cut:], cache, jnp.int32(cut), cfg)
        got = jnp.concatenate([head, tail], axis=1)
    else:
        pool = init_block_pool(cfg, 8, bs, jnp.float32)
        tables = jnp.asarray([[5, 2, NULL_BLOCK], [1, 6, NULL_BLOCK]], jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        head, pool, counts = cfg.paged_forward(
            params, ids[:, :cut], pool, tables, positions[:, :cut], None, bs)
        tail, pool, _ = cfg.paged_forward(
            params, ids[:, cut:], pool, tables, positions[:, cut:], None, bs)
        assert counts is None
        got = jnp.concatenate([head, tail], axis=1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_bert_forward_and_padding_mask():
    cfg = BertConfig.tiny()
    params = init_bert(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    full = {"input_ids": ids, "attention_mask": np.ones((2, 32), np.int32)}
    # padding tokens must not change the [CLS] logits
    padded_ids = ids.copy()
    padded_ids[:, 16:] = 0
    mask = np.ones((2, 32), np.int32)
    mask[:, 16:] = 0
    out_a = bert_forward(params, {"input_ids": padded_ids, "attention_mask": mask}, cfg)
    padded_ids2 = padded_ids.copy()
    padded_ids2[:, 16:] = 7  # different garbage in masked region
    out_b = bert_forward(params, {"input_ids": padded_ids2, "attention_mask": mask}, cfg)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), atol=1e-5)


def test_bert_loss_finite():
    cfg = BertConfig.tiny()
    params = init_bert(cfg, jax.random.PRNGKey(0))
    batch = {
        "input_ids": np.ones((4, 16), np.int32),
        "attention_mask": np.ones((4, 16), np.int32),
        "labels": np.array([0, 1, 0, 1], np.int32),
    }
    loss = float(bert_loss(params, batch, cfg))
    assert np.isfinite(loss) and abs(loss - np.log(2)) < 0.3


def test_graft_entry_contract():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[1].shape[0]


@pytest.mark.slow
def test_graft_dryrun_multichip():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


class TestResNet:
    def test_forward_shapes_and_loss(self):
        from accelerate_tpu.models import ResNetConfig, init_resnet, resnet_forward, resnet_loss

        cfg = ResNetConfig.tiny()
        params = init_resnet(cfg, jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 32, 32, 3)), jnp.float32)
        logits = resnet_forward(params, x, cfg)
        assert logits.shape == (2, cfg.num_classes)
        loss = resnet_loss(params, {"pixels": x, "labels": jnp.asarray([0, 1])}, cfg)
        assert np.isfinite(float(loss))

    def test_resnet50_param_count_matches_torch(self):
        """25.56M — the torchvision ResNet-50 count (structure parity)."""
        from accelerate_tpu.models import ResNetConfig, init_resnet

        params = init_resnet(ResNetConfig.resnet50(), jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
        assert abs(n - 25_557_032) < 60_000, n

    def test_overfits_single_batch(self):
        import optax

        from accelerate_tpu.models import ResNetConfig, init_resnet, resnet_loss

        cfg = ResNetConfig.tiny()
        params = init_resnet(cfg, jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 32, 32, 3)), jnp.float32)
        batch = {"pixels": x, "labels": jnp.asarray(np.arange(8) % cfg.num_classes)}
        opt = optax.adam(1e-3)
        state = opt.init(params)

        @jax.jit
        def step(p, s):
            loss, g = jax.value_and_grad(lambda p: resnet_loss(p, batch, cfg))(p)
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s, loss

        first = None
        for _ in range(30):
            params, state, loss = step(params, state)
            if first is None:
                first = float(loss)
        assert float(loss) < first * 0.5, (first, float(loss))

    def test_shards_under_fsdp_tp(self):
        from accelerate_tpu import Accelerator, ParallelismConfig
        from accelerate_tpu.models import (
            ResNetConfig, init_resnet, resnet_loss, resnet_shard_rules,
        )
        import optax

        acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=4, tp_size=2))
        cfg = ResNetConfig.tiny()
        params = init_resnet(cfg, jax.random.PRNGKey(0))
        params, opt = acc.prepare(params, optax.sgd(0.1), shard_rules=resnet_shard_rules())
        step = acc.prepare_train_step(lambda p, b: resnet_loss(p, b, cfg), opt)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 32, 32, 3)), jnp.float32)
        batch = {"pixels": x, "labels": jnp.asarray(np.zeros(8, np.int32))}
        params, opt_state, m = step(params, opt.opt_state, batch)
        assert np.isfinite(float(np.asarray(m["loss"])))
