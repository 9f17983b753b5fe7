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
