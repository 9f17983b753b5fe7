"""Producer-fused gradient quantization: knob-off jaxpr/value inertness,
fused-kernel wire-byte parity vs the compose path, consumption plumbing
bit-equality through the staged allreduce (monolithic and pipelined),
and the fallback ladder (guard/EF/misaligned shapes never consume)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import flax.linen as nn
from jax.sharding import Mesh

from torch_cgx_tpu.config import CompressionConfig
from torch_cgx_tpu.models.layers import CgxDense
from torch_cgx_tpu.ops import dispatch, fused_producer as fp
from torch_cgx_tpu.parallel import grad_sync, reducers
from torch_cgx_tpu.utils.logging import metrics


@pytest.fixture(autouse=True)
def _deconfigure():
    fp.deconfigure()
    yield
    fp.deconfigure()


def _mesh(ws=2):
    return Mesh(np.array(jax.devices()[:ws]).reshape(ws), ("dp",))


# ---------------------------------------------------------------------------
# Knob-off inertness.
# ---------------------------------------------------------------------------


def test_knob_off_matmul_jaxpr_is_plain_dot(monkeypatch):
    """CGX_PRODUCER_FUSE unset on CPU (auto => off): the wrapper lowers to
    exactly the cast + dot_general an unwrapped dense layer stages."""
    x = jnp.zeros((4, 8, 16), jnp.bfloat16)
    w = jnp.zeros((16, 32), jnp.float32)

    def wrapped(x, w):
        return fp.matmul(x, w, name="t/kernel", compute_dtype=jnp.bfloat16)

    def plain(x, w):
        return jax.lax.dot_general(
            x, w.astype(jnp.bfloat16), (((2,), (0,)), ((), ()))
        )

    assert str(jax.make_jaxpr(wrapped)(x, w)) == str(
        jax.make_jaxpr(plain)(x, w)
    )


def test_engaged_backward_stages_payload(monkeypatch):
    """With the knob on, inside the configured sync axis's shard_map, the
    backward stashes the layer's wire payload (one entry per layer)."""
    from jax.sharding import PartitionSpec as P

    from torch_cgx_tpu.utils.compat import shard_map

    monkeypatch.setenv("CGX_PRODUCER_FUSE", "on")
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_STANDALONE_LAYER_ELEMS", "32768")
    mesh = _mesh(2)
    fp.configure(mesh, ("dp",), divisor=2, active=True)
    x = jnp.zeros((4, 256), jnp.float32)
    w = jnp.zeros((256, 512), jnp.float32)

    def body(x, w):
        fp.begin_step()
        return jax.grad(
            lambda w: jnp.sum(
                fp.matmul(x, w, name="big/kernel",
                          compute_dtype=jnp.float32)
            )
        )(w)

    jax.make_jaxpr(
        shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                  out_specs=P(), check_vma=False)
    )(x, w)
    assert fp.stash_size() == 1


def test_grad_outside_shard_map_falls_back(monkeypatch):
    """A bare jax.grad over a wrapped layer (no sync axis bound) must
    produce the plain cotangent, not crash on axis_index."""
    monkeypatch.setenv("CGX_PRODUCER_FUSE", "on")
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_STANDALONE_LAYER_ELEMS", "32768")
    fp.configure(_mesh(2), ("dp",), divisor=2, active=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 512))
    g = jax.grad(
        lambda w: jnp.sum(
            fp.matmul(x, w, name="big/kernel", compute_dtype=jnp.float32)
        )
    )(w)
    ref = jax.grad(lambda w: jnp.sum(x @ w))(w)
    assert bool(jnp.allclose(g, ref, atol=1e-5))


def test_cgx_dense_matches_nn_dense_values_and_grads():
    """CgxDense is a bit-exact nn.Dense twin with the knob off — same
    params, same outputs, same gradients."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 16)).astype(
        jnp.bfloat16
    )
    cgx = CgxDense(8, dtype=jnp.bfloat16)
    ref = nn.Dense(8, dtype=jnp.bfloat16)
    params = cgx.init(jax.random.PRNGKey(1), x)
    out_c = cgx.apply(params, x)
    out_r = ref.apply(params, x)  # identical param structure by design
    assert bool(jnp.array_equal(out_c, out_r))

    def loss_c(p):
        return jnp.sum(cgx.apply(p, x).astype(jnp.float32) ** 2)

    def loss_r(p):
        return jnp.sum(ref.apply(p, x).astype(jnp.float32) ** 2)

    g_c = jax.grad(loss_c)(params)
    g_r = jax.grad(loss_r)(params)
    for a, b in zip(jax.tree.leaves(g_c), jax.tree.leaves(g_r)):
        assert bool(jnp.array_equal(a, b))


# ---------------------------------------------------------------------------
# The fused matmul+quantize kernel.
# ---------------------------------------------------------------------------


def test_kernel_geometry_gates():
    cc = CompressionConfig(bits=4, bucket_size=512)
    # aligned: 256x512 over ws=2 -> chunk 65536, whole chunks, o%128==0
    assert fp._kernel_geometry(64, 256, 512, 2, 65536, cc) is not None
    # misaligned lane width
    assert fp._kernel_geometry(64, 256, 96, 2, 24576, cc) is None
    # bucket not lane-aligned
    cc2 = CompressionConfig(bits=4, bucket_size=96)
    assert fp._kernel_geometry(64, 256, 512, 2, 65536, cc2) is None


def test_kernel_bytes_match_compose_reference():
    """The fused matmul+quantize kernel's wire bytes equal a quantize of
    the same dw values (decode-exact contract on agreeing matmuls)."""
    cc = CompressionConfig(bits=4, bucket_size=512)
    K, din, o, ws = 64, 256, 512, 2
    rng = np.random.default_rng(0)
    x2 = jnp.asarray(rng.standard_normal((K, din)), jnp.float32)
    g2 = jnp.asarray(rng.standard_normal((K, o)), jnp.float32)
    chunk = din * o // ws
    tm, tk = fp._kernel_geometry(K, din, o, ws, chunk, cc)
    q_k = fp._matmul_quantize_q(
        x2, g2, cc, ws=ws, chunk=chunk, div=ws, tm=tm, tk=tk, interpret=True
    )
    dw = (
        jax.lax.dot_general(x2, g2, (((0,), (0,)), ((), ()))) / ws
    ).reshape(ws, chunk)
    q_ref = reducers._quantize_rows(dw, cc, None)
    assert bool(jnp.array_equal(q_k.packed, q_ref.packed))
    # meta rides the wire in the tensor dtype; envelope parity on decode
    d_k = dispatch.dequantize_batch(q_k)
    d_r = dispatch.dequantize_batch(q_ref)
    assert bool(jnp.array_equal(d_k, d_r))


# ---------------------------------------------------------------------------
# End-to-end consumption through the staged allreduce.
# ---------------------------------------------------------------------------


class _OneDense(nn.Module):
    @nn.compact
    def __call__(self, x):
        return CgxDense(512, dtype=jnp.float32, name="big")(x)


def _train(monkeypatch, fuse, steps=2, guard=None, **env):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("CGX_PRODUCER_FUSE", fuse)
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_STANDALONE_LAYER_ELEMS", "32768")
    mesh = _mesh(2)
    model = _OneDense()
    xb = jax.random.normal(jax.random.PRNGKey(1), (8, 256))
    yb = jax.random.normal(jax.random.PRNGKey(2), (8, 512))
    params = model.init(jax.random.PRNGKey(0), xb)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((model.apply(p, x) - y) ** 2)

    step = grad_sync.make_train_step(
        loss_fn, optax.sgd(0.1), mesh, axes=("dp",), nonfinite_guard=guard
    )
    p = grad_sync.replicate(jax.tree.map(jnp.array, params), mesh)
    s = grad_sync.replicate(optax.sgd(0.1).init(p), mesh)
    for i in range(steps):
        batch = grad_sync.shard_batch((xb, yb), mesh, axes=("dp",))
        p, s, loss = step(p, s, batch, i)
    return jax.tree.map(np.asarray, p)


def _consumed():
    return metrics.get("cgx.codec.producer_consumed_slices") or 0.0


def test_consumed_payload_bit_equal_monolithic(monkeypatch):
    p_off = _train(monkeypatch, "off")
    before = _consumed()
    p_on = _train(monkeypatch, "on")
    assert _consumed() > before, "producer payload was not consumed"
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        assert bool(np.array_equal(a, b))


def test_consumed_payload_bit_equal_pipelined(monkeypatch):
    env = dict(CGX_SCHEDULE="on", CGX_SCHED_CHUNKS="2",
               CGX_XLA_ALLREDUCE="on")
    p_off = _train(monkeypatch, "off", **env)
    before = _consumed()
    p_on = _train(monkeypatch, "on", **env)
    assert _consumed() > before
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        assert bool(np.array_equal(a, b))


def test_guard_disables_consumption_but_not_training(monkeypatch):
    """The nonfinite guard rewrites the gradient tree (where-selects), so
    the cotangent-identity match must fail closed: no consumption, and
    results equal the unfused guarded run bit for bit."""
    before = _consumed()
    p_on = _train(monkeypatch, "on", guard="skip")
    assert _consumed() == before
    p_off = _train(monkeypatch, "off", guard="skip")
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        assert bool(np.array_equal(a, b))


def test_error_feedback_never_consumes(monkeypatch):
    """EF adds residuals before the sync — identity match fails closed."""
    monkeypatch.setenv("CGX_PRODUCER_FUSE", "on")
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_STANDALONE_LAYER_ELEMS", "32768")
    mesh = _mesh(2)
    model = _OneDense()
    xb = jax.random.normal(jax.random.PRNGKey(1), (8, 256))
    yb = jax.random.normal(jax.random.PRNGKey(2), (8, 512))
    params = model.init(jax.random.PRNGKey(0), xb)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((model.apply(p, x) - y) ** 2)

    step = grad_sync.make_train_step(
        loss_fn, optax.sgd(0.1), mesh, axes=("dp",), error_feedback=True
    )
    p = grad_sync.replicate(jax.tree.map(jnp.array, params), mesh)
    s = grad_sync.replicate(optax.sgd(0.1).init(p), mesh)
    ef = grad_sync.init_error_feedback(p, mesh, axes=("dp",))
    before = _consumed()
    batch = grad_sync.shard_batch((xb, yb), mesh, axes=("dp",))
    p, s, ef, loss = step(p, s, ef, batch, 0)
    assert np.isfinite(float(loss))
    assert _consumed() == before


def test_stash_epoch_and_claim():
    """lookup() honors identity + epoch; claim() prevents double-spend."""
    fp.configure(_mesh(2), ("dp",), divisor=2, active=True)
    leaf = jnp.zeros((4,))
    ent = fp.Produced(
        cotangent=leaf, q=None, q_blocks=None, table=None,
        raw_row=jnp.zeros((2,)), cc=CompressionConfig(bits=4),
        ws=2, n=4, divisor=2, epoch=fp._CFG["epoch"], name="t",
    )
    fp._STASH[id(leaf)] = ent
    assert fp.lookup(leaf) is ent
    assert fp.lookup(jnp.zeros((4,))) is None  # identity, not equality
    fp.claim(leaf)
    assert fp.lookup(leaf) is None
    fp._STASH[id(leaf)] = ent
    fp.begin_step()  # stale epoch entries unclaimable
    assert fp.lookup(leaf) is None


@pytest.mark.tpu  # compiled Mosaic lowering of the producer kernel
def test_kernel_bytes_match_compose_tpu():
    """Hardware validation of `_matmul_quantize_impl` (`CGX_TEST_TPU=1
    pytest -m tpu` on the chip): compiled-kernel wire bytes vs the compose
    reference — envelope on decode (matmul association may differ between
    the MXU grid and XLA's lowering), bit-equal when it doesn't."""
    cc = CompressionConfig(bits=4, bucket_size=512)
    K, din, o, ws = 256, 1024, 1024, 4
    rng = np.random.default_rng(5)
    x2 = jnp.asarray(rng.standard_normal((K, din)), jnp.float32)
    g2 = jnp.asarray(rng.standard_normal((K, o)), jnp.float32)
    chunk = din * o // ws
    tm, tk = fp._kernel_geometry(K, din, o, ws, chunk, cc)
    q_k = fp._matmul_quantize_q(
        x2, g2, cc, ws=ws, chunk=chunk, div=ws, tm=tm, tk=tk,
        interpret=False,
    )
    dw = (
        jax.lax.dot_general(x2, g2, (((0,), (0,)), ((), ()))) / ws
    ).reshape(ws, chunk)
    q_ref = reducers._quantize_rows(dw, cc, None)
    d_k = np.asarray(dispatch.dequantize_batch(q_k))
    d_r = np.asarray(dispatch.dequantize_batch(q_ref))
    unit = np.abs(np.asarray(dw)).max() / ((1 << cc.bits) - 1)
    assert np.max(np.abs(d_k - d_r)) <= 2 * unit + 1e-6


def test_kernel_geometry_refuses_blocks_mosaic_refuses():
    """The (tk, tm) block of x needs tm % 128 == 0 (or all of din): GPT-2's
    qkv / mlp_in over 4 ranks give tm=64 and must take the compose path
    (libtpu 0.0.34 refuses the block); mlp_out aligns."""
    cc = CompressionConfig(bits=4, bucket_size=512)
    assert fp._kernel_geometry(4096, 768, 2304, 4, 768 * 2304 // 4, cc) is None
    assert fp._kernel_geometry(4096, 768, 3072, 4, 768 * 3072 // 4, cc) is None
    tm, tk = fp._kernel_geometry(4096, 3072, 768, 4, 3072 * 768 // 4, cc)
    assert tm % 128 == 0 and tk % 16 == 0
    # an odd contraction length is one whole block, never a 1-row block
    assert fp._kernel_geometry(37, 1024, 1024, 4, 1024 * 1024 // 4, cc)[1] == 37
