"""Model zoo smoke tests: forward shapes, grad step, compressed-DP training."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torch_cgx_tpu.models import (
    GPT2,
    Bert,
    BertConfig,
    GPT2Config,
    ResNet18,
    ResNet50,
    ViT,
    ViTConfig,
    lm_loss,
    mlm_loss,
)


def test_resnet18_forward_and_grad():
    model = ResNet18(num_classes=10, cifar_stem=True)
    x = jnp.zeros((4, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (4, 10)

    def loss_fn(params):
        out, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x,
            train=True,
            mutable=["batch_stats"],
        )
        return jnp.mean(out**2)

    g = jax.grad(loss_fn)(variables["params"])
    assert jax.tree_util.tree_structure(g) == jax.tree_util.tree_structure(
        variables["params"]
    )


# Slow tier: depth-scaling rerun of the resnet18 coverage above.
@pytest.mark.slow
def test_resnet50_forward():
    model = ResNet50(num_classes=100, cifar_stem=False)
    x = jnp.zeros((2, 64, 64, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert model.apply(variables, x, train=False).shape == (2, 100)


def test_gpt2_forward_loss_grad():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32))
    )
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    logits = model.apply({"params": params}, toks)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert logits.dtype == jnp.float32

    def loss(p):
        return lm_loss(model.apply({"params": p}, toks), toks)

    l0 = float(loss(params))
    assert np.isfinite(l0) and l0 < 2 * np.log(cfg.vocab_size)
    g = jax.grad(loss)(params)
    assert jnp.isfinite(g["wte"]["embedding"]).all()


def test_bert_mlm():
    cfg = BertConfig.tiny()
    model = Bert(cfg)
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 24))
    )
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    logits = model.apply({"params": params}, toks)
    assert logits.shape == (2, 24, cfg.vocab_size)
    mask = jnp.zeros((2, 24)).at[:, :4].set(1.0)
    l = mlm_loss(logits, toks, mask)
    assert np.isfinite(float(l))


def test_vit_forward():
    cfg = ViTConfig.tiny()
    model = ViT(cfg)
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    assert model.apply({"params": params}, x).shape == (2, 10)


@pytest.mark.slow
def test_gpt2_compressed_dp_training(monkeypatch):
    """End-to-end: tiny GPT-2, 8 devices, 4-bit grads, loss decreases."""
    import os

    from torch_cgx_tpu import config as cgx_config
    from torch_cgx_tpu.parallel import (
        flat_mesh,
        make_train_step,
        replicate,
        shard_batch,
    )

    monkeypatch.setenv(cgx_config.COMPRESSION_QUANTIZATION_BITS, "4")
    monkeypatch.setenv(cgx_config.COMPRESSION_BUCKET_SIZE, "512")
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    rng = np.random.default_rng(0)
    # learnable data: repeated pattern
    data = np.tile(np.arange(32) % 64, (64, 1)).astype(np.int32)
    mesh = flat_mesh()
    params = replicate(
        model.init(jax.random.PRNGKey(0), jnp.asarray(data[:2]))["params"], mesh
    )
    opt = optax.adam(1e-2)
    opt_state = replicate(opt.init(params), mesh)

    def loss_fn(p, batch):
        return lm_loss(model.apply({"params": p}, batch), batch)

    step = make_train_step(loss_fn, opt, mesh, donate=False)
    losses = []
    for i in range(12):
        batch = shard_batch(jnp.asarray(data), mesh)
        params, opt_state, loss = step(params, opt_state, batch, jnp.int32(i))
        losses.append(float(loss))
    assert losses[-1] < 0.6 * losses[0], losses


def test_bert_compressed_dp_training(monkeypatch):
    """BASELINE.md config row: BERT fine-tune DDP at 8-bit with the
    layer_min_size filter keeping LN/bias raw — loss must fall."""
    from torch_cgx_tpu import config as cgx_config
    from torch_cgx_tpu.parallel import (
        flat_mesh,
        make_train_step,
        replicate,
        shard_batch,
    )

    monkeypatch.setenv(cgx_config.COMPRESSION_QUANTIZATION_BITS, "8")
    monkeypatch.setenv(cgx_config.COMPRESSION_BUCKET_SIZE, "512")
    cfg = BertConfig.tiny()
    model = Bert(cfg)
    # learnable MLM data: predictable token pattern, mask every 4th position
    tokens = np.tile(np.arange(32) % 50, (16, 1)).astype(np.int32)
    mask = np.zeros_like(tokens)
    mask[:, ::4] = 1
    inputs = np.where(mask == 1, 3, tokens).astype(np.int32)  # 3 = [MASK]
    mesh = flat_mesh()
    params = replicate(
        model.init(jax.random.PRNGKey(0), jnp.asarray(inputs[:2]))["params"],
        mesh,
    )
    opt = optax.adam(2e-2)
    opt_state = replicate(opt.init(params), mesh)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        return mlm_loss(logits, batch["y"], batch["m"])

    step = make_train_step(loss_fn, opt, mesh, donate=False)
    batch = {
        "x": jnp.asarray(inputs),
        "y": jnp.asarray(tokens),
        "m": jnp.asarray(mask.astype(np.float32)),
    }
    losses = []
    for i in range(10):
        params, opt_state, loss = step(
            params, opt_state, shard_batch(batch, mesh), jnp.int32(i)
        )
        losses.append(float(loss))
    assert losses[-1] < 0.6 * losses[0], losses


def test_vit_hierarchical_compressed_training(monkeypatch):
    """BASELINE.md config row: ViT with the INTRA_BROADCAST hierarchical
    allreduce (2x4 cross x intra mesh), 4-bit — loss must fall and replicas
    stay in sync."""
    from torch_cgx_tpu import config as cgx_config
    from torch_cgx_tpu.parallel import (
        CROSS_AXIS,
        INTRA_AXIS,
        hierarchical_mesh,
        make_train_step,
        replicate,
        shard_batch,
    )

    monkeypatch.setenv(cgx_config.COMPRESSION_QUANTIZATION_BITS, "4")
    monkeypatch.setenv(cgx_config.INTRA_BROADCAST, "1")
    cfg = ViTConfig.tiny()
    model = ViT(cfg)
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=32).astype(np.int32)
    templates = rng.normal(size=(10, 32, 32, 3)).astype(np.float32)
    images = templates[labels] + 0.1 * rng.normal(
        size=(32, 32, 32, 3)
    ).astype(np.float32)
    mesh = hierarchical_mesh(intra_size=4)
    axes = (CROSS_AXIS, INTRA_AXIS)
    params = replicate(
        model.init(jax.random.PRNGKey(0), jnp.asarray(images[:2]))["params"],
        mesh,
    )
    opt = optax.adam(2e-3)
    opt_state = replicate(opt.init(params), mesh)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        onehot = jax.nn.one_hot(batch["y"], 10)
        return optax.softmax_cross_entropy(logits, onehot).mean()

    step = make_train_step(loss_fn, opt, mesh, axes=axes, donate=False)
    batch = {"x": jnp.asarray(images), "y": jnp.asarray(labels)}
    losses = []
    for i in range(10):
        params, opt_state, loss = step(
            params, opt_state, shard_batch(batch, mesh, axes), jnp.int32(i)
        )
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], losses
    # Error symmetry: replicated params identical on every device.
    leaf = jax.tree.leaves(params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(s, shards[0])


def test_tp_sharding_survives_train_step(monkeypatch):
    """make_train_step leaves non-sync mesh axes to GSPMD: tensor-parallel
    parameter shardings must SURVIVE the step (review r3: in_specs=P() on a
    fully-manual shard_map silently gathered tp-sharded params to
    replicated, so tp did duplicate work forever after)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torch_cgx_tpu import config as cgx_config
    from torch_cgx_tpu.models import GPT2, GPT2Config, lm_loss
    from torch_cgx_tpu.models.gpt2 import tp_param_spec
    from torch_cgx_tpu.parallel import make_train_step, shard_batch
    from torch_cgx_tpu.utils.tree import path_str

    monkeypatch.setenv(cgx_config.COMPRESSION_QUANTIZATION_BITS, "4")
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(8, 32)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [tp_param_spec(path_str(p), l) for p, l in flat]
    params = jax.tree_util.tree_unflatten(
        treedef,
        [
            jax.device_put(l, NamedSharding(mesh, s))
            for (p, l), s in zip(flat, specs)
        ],
    )
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def loss_fn(p, batch):
        return lm_loss(model.apply({"params": p}, batch), batch)

    step = make_train_step(loss_fn, opt, mesh, axes=("dp",), donate=False)
    p2, opt_state, loss = step(
        params, opt_state, shard_batch(tokens, mesh, ("dp",)), jnp.int32(0)
    )
    assert np.isfinite(float(loss))

    # Every tp-sharded leaf must still be sharded over tp afterwards.
    flat2 = jax.tree_util.tree_flatten_with_path(p2)[0]
    checked = 0
    for ((path, leaf), spec) in zip(flat2, specs):
        if spec and any(ax == "tp" for ax in jax.tree.leaves(tuple(spec))):
            got = leaf.sharding.spec
            assert "tp" in str(got), (path_str(path), got)
            checked += 1
    assert checked >= 4, f"only {checked} tp-sharded leaves found"


# -- the hybrid state-space / attention decoder (ISSUE 31) -------------------


def _published_granite():
    import json
    from pathlib import Path

    path = (Path(__file__).resolve().parent.parent / "benchmark" / "configs"
            / "granite-4.0-h-micro-serve-kv8.json")
    return json.loads(path.read_text())


def test_hybrid_config_counts_what_the_published_widths_weigh():
    """``HybridConfig.from_hf`` over granite-4.0-h-micro's published keys:
    36 Mamba-2 and 4 attention layers, and the two weights the serve plan
    is told apart: a token's K and V over the attention layers alone, a
    lane's recurrent state over the Mamba layers whatever its length (77.4
    MB: 4.95 GB at 64 lanes)."""
    from torch_cgx_tpu.models.granite_hybrid import HybridConfig

    cfg = HybridConfig.from_hf(_published_granite())
    assert cfg.n_layer == 40 and cfg.attention_layers == (5, 15, 25, 35)
    assert (cfg.n_head, cfg.n_kv_head, cfg.d_head) == (32, 8, 64)
    assert (cfg.d_inner, cfg.d_xbc, cfg.d_state, cfg.chunk) == (
        4096, 4352, 128, 256)
    assert cfg.kv_bytes_per_token() == 4 * 2 * 512 * 4
    assert cfg.state_bytes_per_lane() == 36 * (3 * 4352 + 128 * 4096) * 4
    assert round(cfg.state_bytes_per_lane() * 64 / 1e9, 2) == 4.95


@pytest.mark.parametrize("key,value", [
    ("mamba_n_groups", 8), ("attention_bias", True),
    ("num_local_experts", 4), ("position_embedding_type", "rope"),
])
def test_hybrid_config_refuses_what_it_has_no_equations_for(key, value):
    from torch_cgx_tpu.models.granite_hybrid import HybridConfig

    with pytest.raises(ValueError, match=key):
        HybridConfig.from_hf(dict(_published_granite(), **{key: value}))


# -- the hybrid gated delta-rule / attention decoder (ISSUE 33) --------------


def _published_olmo_hybrid():
    import json
    from pathlib import Path

    path = (Path(__file__).resolve().parent.parent / "benchmark" / "configs"
            / "olmo-hybrid-7b-serve-kv8.json")
    return json.loads(path.read_text())


def test_olmo_hybrid_config_counts_what_the_published_widths_weigh():
    """``OlmoHybridConfig.from_hf`` over Olmo-Hybrid-7B's published keys
    (its first 16 layers): 12 gated delta-rule and 4 full-attention layers,
    and the two weights the serve plan is told apart: a token's K and V
    over the attention layers alone (30,720 values), a lane's recurrent
    state over the delta-rule layers whatever its length (28.2 MB: 2.71 GB
    at 96 lanes)."""
    from torch_cgx_tpu.models.olmo_hybrid import OlmoHybridConfig

    cfg = OlmoHybridConfig.from_hf(_published_olmo_hybrid())
    assert cfg.n_layer == 16 and cfg.attention_layers == (3, 7, 11, 15)
    assert (cfg.n_head, cfg.n_kv_head, cfg.d_head) == (30, 30, 128)
    assert (cfg.g_heads, cfg.d_k, cfg.d_v, cfg.d_conv) == (30, 96, 192, 4)
    assert (cfg.d_qkv, cfg.d_value, cfg.chunk) == (11520, 5760, 64)
    assert cfg.allow_neg_eigval and cfg.eps == 1e-6
    assert cfg.kv_bytes_per_token() == 30720 * 4
    assert cfg.state_bytes_per_lane() == 12 * (3 * 11520 + 96 * 5760) * 4
    assert round(cfg.state_bytes_per_lane() * 96 / 1e9, 2) == 2.71


@pytest.mark.parametrize("key,value", [
    ("rope_theta", 500000.0), ("rope_parameters", {"rope_theta": 10000.0}),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("linear_num_key_heads", 15),
])
def test_olmo_hybrid_config_refuses_what_it_has_no_equations_for(key, value):
    """A stated rotary base (either spelling) is refused rather than
    guessed, as are biases, a tied head, another activation and fewer key
    heads than value heads."""
    from torch_cgx_tpu.models.olmo_hybrid import OlmoHybridConfig

    with pytest.raises(ValueError, match=key.replace("rope_parameters",
                                                     "rope_theta")):
        OlmoHybridConfig.from_hf(dict(_published_olmo_hybrid(),
                                      **{key: value}))


def test_granite_and_olmo_share_one_convolution():
    """The depthwise causal convolution both recurrent mixers use
    (``granite_hybrid.conv_prefill`` / ``conv_state_at`` / ``conv_step``),
    with and without a bias: a prompt's convolution equals its positions
    taken a step at a time from a zero state, and the state cut at
    ``last_idx`` is the state those steps leave."""
    from torch_cgx_tpu.models import granite_hybrid as gh

    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 9, 6)), jnp.float32)
    for b in (None, jnp.asarray(rng.standard_normal((6,)), jnp.float32)):
        conv, padded = gh.conv_prefill(w, b, x)
        state = jnp.zeros((2, 3, 6), jnp.float32)
        for t in range(9):
            step, window = gh.conv_step(w, b, state, x[:, t])
            state = window[:, 1:]
            np.testing.assert_allclose(step, conv[:, t], atol=1e-5)
            np.testing.assert_allclose(
                state, gh.conv_state_at(padded, t, 4), atol=0)


@pytest.mark.parametrize("h,hk", [(8, 8), (8, 2), (4, 1)])
def test_decode_attention_with_grouped_queries(h, hk):
    """``decode_attention`` over rows of ``hk`` K/V heads for ``h`` query
    heads, scores divided by a stated divisor, against the attention
    written out a head at a time (query head ``i`` reads K/V head ``i //
    (h / hk)``); float32, limit 1e-5 of the output's largest value."""
    from torch_cgx_tpu.models.attention import decode_attention

    rng = np.random.default_rng(h * 10 + hk)
    b, t, tt, d = 2, 12, 4, 8
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    k, v = (rng.standard_normal((b, t, hk * d)).astype(np.float32)
            for _ in range(2))
    kt, vt = (rng.standard_normal((b, tt, hk * d)).astype(np.float32)
              for _ in range(2))
    mask = np.arange(t)[None, :] < np.asarray([[9], [12]])
    tail_mask = np.arange(tt)[None, :] <= np.asarray([[1], [3]])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decode_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(kt),
            jnp.asarray(vt), mask=jnp.asarray(mask),
            tail_mask=jnp.asarray(tail_mask), score_divisor=5.0,
        )).reshape(b, h, d)
    keys = np.concatenate([k, kt], axis=1).reshape(b, t + tt, hk, d)
    vals = np.concatenate([v, vt], axis=1).reshape(b, t + tt, hk, d)
    live = np.concatenate([mask, tail_mask], axis=1)
    for i in range(h):
        g = i // (h // hk)
        s = np.einsum("bd,btd->bt", q[:, i], keys[:, :, g]) / 5.0
        s = np.where(live, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("bt,btd->bd", p, vals[:, :, g])
        assert np.max(np.abs(got[:, i] - want)) < 1e-5 * np.max(np.abs(want))
