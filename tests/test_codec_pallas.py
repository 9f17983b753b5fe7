"""Pallas codec kernels vs the XLA oracle (interpret mode on CPU).

The wire format must be bit-identical between implementations — payloads are
exchanged between devices that may decode with either path. The chunked-
sublane format was designed so the Pallas kernels use identical float ops to
the XLA codec (same divide, same floor/clip), so deterministic payloads are
asserted byte-equal, not merely close.
"""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_cgx_tpu import config as cgx_config
from torch_cgx_tpu.config import CompressionConfig
from torch_cgx_tpu.ops import codec, codec_pallas, dispatch


@pytest.mark.parametrize("bits", [1, 2, 4, 7, 8])
@pytest.mark.parametrize("bucket_size", [64, 512])
def test_pallas_wire_matches_xla(bits, bucket_size):
    # 4096 values at bucket 64 = 64 buckets (2 full chunks); at bucket 512 =
    # 8 buckets (tail-only region). Both regions must match the XLA bytes.
    rows, m = 2, 4096
    xs = jnp.asarray(
        np.random.default_rng(bits).normal(size=(rows, m)), jnp.float32
    )
    q_p = codec_pallas.quantize_batch(xs, bits, bucket_size, interpret=True)
    q_x = jax.vmap(lambda r: codec.quantize(r, bits, bucket_size))(xs)
    assert q_p.packed.shape == q_x.packed.shape
    np.testing.assert_array_equal(
        np.asarray(q_p.packed), np.asarray(q_x.packed)
    )
    np.testing.assert_array_equal(np.asarray(q_p.meta), np.asarray(q_x.meta))
    # Cross-impl decode of the same payload: equal up to FMA-vs-mul+add
    # codegen (1 ulp).
    for q in (q_p, q_x):
        y_xla = jax.vmap(lambda qq: codec.dequantize(qq))(q)
        y_pls = codec_pallas.dequantize_batch(q, interpret=True, out_dtype=q.dtype)
        np.testing.assert_allclose(
            np.asarray(y_xla), np.asarray(y_pls), rtol=2e-6, atol=5e-7
        )


@pytest.mark.parametrize("m", [1000, 33 * 64, 40 * 64 + 17])
def test_pallas_unaligned_numel(m):
    # m not a multiple of bucket_size: edge-padding must match XLA; sizes
    # straddling the chunk boundary exercise head+tail stitching.
    rows, bits, bucket = 3, 4, 64
    xs = jnp.asarray(np.random.default_rng(0).normal(size=(rows, m)), jnp.float32)
    q_p = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    q_x = jax.vmap(lambda r: codec.quantize(r, bits, bucket))(xs)
    assert q_p.packed.shape == q_x.packed.shape
    np.testing.assert_array_equal(np.asarray(q_p.packed), np.asarray(q_x.packed))
    y = codec_pallas.dequantize_batch(q_p, interpret=True, out_dtype=jnp.float32)
    y_ref = jax.vmap(lambda qq: codec.dequantize(qq, out_dtype=jnp.float32))(q_p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-6, atol=5e-7)


def test_pallas_constant_exact():
    xs = jnp.full((2, 40 * 512), 5.0, jnp.float32)
    q = codec_pallas.quantize_batch(xs, 4, 512, interpret=True)
    y = codec_pallas.dequantize_batch(q, interpret=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(xs))


def test_pallas_bf16():
    xs = jnp.asarray(
        np.linspace(-1, 1, 2 * 64 * 512).reshape(2, -1), jnp.bfloat16
    )
    q_p = codec_pallas.quantize_batch(xs, 8, 512, interpret=True)
    q_x = jax.vmap(lambda r: codec.quantize(r, 8, 512))(xs)
    assert q_p.packed.shape == q_x.packed.shape
    assert q_p.meta.dtype == jnp.bfloat16
    y = codec_pallas.dequantize_batch(q_p, interpret=True)
    err = np.abs(np.asarray(y, np.float32) - np.asarray(xs, np.float32))
    assert err.max() < 0.02


def test_stochastic_falls_back_off_tpu(monkeypatch):
    # pltpu.prng_* has no CPU lowering; dispatch must route stochastic
    # quantization to the XLA path off-TPU (pallas stochastic is exercised on
    # real TPU by bench.py / the verify drive).
    monkeypatch.setenv(cgx_config.CODEC_IMPL, "pallas")
    rows, m, bits, bucket = 2, 8192, 4, 512
    cc = CompressionConfig(bits=bits, bucket_size=bucket, stochastic=True)
    xs = jnp.asarray(np.random.default_rng(1).normal(size=(rows, m)), jnp.float32)
    q = dispatch.quantize_batch(xs, cc, key=jax.random.PRNGKey(3))
    y = np.asarray(dispatch.dequantize_batch(q, out_dtype=jnp.float32))
    xb = np.asarray(xs).reshape(rows, -1, bucket)
    unit = (xb.max(-1) - xb.min(-1)) / ((1 << bits) - 1)
    err = np.abs(y - np.asarray(xs)).reshape(rows, -1, bucket).max(-1)
    assert (err <= unit * 1.001 + 1e-7).all()


@pytest.mark.parametrize("bits,bucket", [(2, 128), (4, 512), (8, 256), (3, 384)])
def test_flat_path_wire_matches_xla(bits, bucket, monkeypatch):
    # The zero-relayout flat kernels (taken whenever nb_r % 32 == 0 and
    # bucket % 128 == 0 — the cleanly-sized buffers real training produces,
    # at the default 512/1024 bucket sizes) must emit the
    # same bytes as the XLA codec. Run under CPU interpret mode so the normal
    # suite covers the path BENCH_r02 shipped broken (VERDICT r2 Weak #1/#4).
    # Poison the block-path impls: if the gate ever stops routing these
    # shapes to the flat path, the test fails loudly instead of silently
    # testing the wrong kernels.
    def _boom(*a, **k):
        raise AssertionError("expected the flat fast path, got the block path")

    monkeypatch.setattr(codec_pallas, "_quantize_chunks_impl", _boom)
    monkeypatch.setattr(codec_pallas, "_dequantize_chunks_impl", _boom)
    m = 64 * bucket
    xs = jnp.asarray(
        np.random.default_rng(bits).normal(size=(2, m)), jnp.float32
    )
    q_p = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    q_x = jax.vmap(lambda r: codec.quantize(r, bits, bucket))(xs)
    np.testing.assert_array_equal(
        np.asarray(q_p.packed), np.asarray(q_x.packed)
    )
    np.testing.assert_array_equal(np.asarray(q_p.meta), np.asarray(q_x.meta))
    y_p = codec_pallas.dequantize_batch(q_p, interpret=True, out_dtype=jnp.float32)
    y_x = jax.vmap(
        lambda qq: codec.dequantize(qq, out_dtype=jnp.float32)
    )(q_x)
    np.testing.assert_allclose(
        np.asarray(y_p), np.asarray(y_x), rtol=2e-6, atol=5e-7
    )


def test_flat_path_unpadded_rows(monkeypatch):
    # Flat path with m not a bucket multiple but nb_r % 32 == 0 after
    # edge-padding: pad + slice-back must round-trip through the flat kernels.
    bits, bucket = 4, 128
    nb_r = 32
    m = nb_r * bucket - 7
    xs = jnp.asarray(np.random.default_rng(11).normal(size=(3, m)), jnp.float32)
    q_p = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    q_x = jax.vmap(lambda r: codec.quantize(r, bits, bucket))(xs)
    np.testing.assert_array_equal(np.asarray(q_p.packed), np.asarray(q_x.packed))
    y = codec_pallas.dequantize_batch(q_p, interpret=True, out_dtype=jnp.float32)
    assert y.shape == (3, m)
    y_ref = jax.vmap(lambda qq: codec.dequantize(qq, out_dtype=jnp.float32))(q_x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-6, atol=5e-7)


@pytest.mark.tpu  # compiled (non-interpret) flat kernels on real hardware
def test_flat_path_wire_matches_xla_tpu():
    for bits, bucket in ((2, 128), (4, 512), (8, 256)):
        m = 64 * bucket
        xs = jnp.asarray(
            np.random.default_rng(bits).normal(size=(2, m)), jnp.float32
        )
        q_p = codec_pallas.quantize_batch(xs, bits, bucket)
        q_x = jax.vmap(lambda r: codec.quantize(r, bits, bucket))(xs)
        np.testing.assert_array_equal(
            np.asarray(q_p.packed), np.asarray(q_x.packed)
        )
        np.testing.assert_array_equal(
            np.asarray(q_p.meta), np.asarray(q_x.meta)
        )
        y_p = codec_pallas.dequantize_batch(q_p, out_dtype=jnp.float32)
        y_x = jax.vmap(
            lambda qq: codec.dequantize(qq, out_dtype=jnp.float32)
        )(q_x)
        np.testing.assert_allclose(
            np.asarray(y_p), np.asarray(y_x), rtol=2e-6, atol=5e-7
        )


@pytest.mark.tpu  # compiled-kernel check of the with_add Mosaic lowering
def test_fused_add_tpu():
    rows, bits, bucket = 2, 4, 512
    m = 64 * bucket
    xs = jnp.asarray(
        np.random.default_rng(21).normal(size=(rows, m)), jnp.float32
    )
    acc = jnp.asarray(
        np.random.default_rng(22).normal(size=(rows, m)), jnp.float32
    )
    q = codec_pallas.quantize_batch(xs, bits, bucket)
    fused = codec_pallas.dequantize_batch(
        q, add_to=acc, out_dtype=jnp.float32
    )
    plain = codec_pallas.dequantize_batch(q, out_dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(fused), np.asarray(acc) + np.asarray(plain)
    )


@pytest.mark.tpu  # pltpu.prng_seed has no CPU-interpret lowering
def test_pallas_stochastic_envelope():
    """Stochastic rounding moves each value to one of its bucket's two
    adjacent levels, so the error bound is PER BUCKET: |err| < that
    bucket's unit (floor(t + r), r in [0,1)). The bound must not be
    collapsed to bucket 0's unit — buckets with a wider min/max range
    have a larger unit, and the 2026-07-31 live-chip session caught
    exactly that (max err 1.036x bucket-0's unit, within its own
    bucket's)."""
    nb, bucket = 64, 512
    xs = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, nb * bucket)), jnp.float32
    )
    q = codec_pallas.quantize_batch(
        xs, 4, bucket, stochastic=True, key=jax.random.PRNGKey(7)
    )
    out = codec_pallas.dequantize_batch(q)
    units = np.asarray(q.meta, np.float32)[0, :, 0]  # (nb,) per-bucket units
    err = np.abs(np.asarray(out) - np.asarray(xs)).reshape(nb, bucket)
    assert (err.max(axis=1) <= units * 1.01).all()
    # And the rounding is genuinely stochastic: strictly inside-the-grid
    # values must land on BOTH adjacent levels somewhere in 32k draws
    # (deterministic rounding would give err <= unit/2 everywhere). The
    # bound is PER BUCKET here too (advisor r5 low #3): the global max
    # error may come from a small-unit bucket, so comparing it against the
    # global max unit can fail spuriously when the widest bucket happens
    # to round near its levels — assert some bucket exceeds its OWN
    # deterministic bound instead.
    assert (err.max(axis=1) > units * 0.5).any()


def test_pallas_add_fusion():
    xs = jnp.asarray(np.random.default_rng(2).normal(size=(2, 64 * 256)), jnp.float32)
    acc = jnp.full_like(xs, 3.0)
    q = codec_pallas.quantize_batch(xs, 8, 256, interpret=True)
    y = codec_pallas.dequantize_batch(q, interpret=True)
    y_add = codec_pallas.dequantize_batch(q, add_to=acc, interpret=True)
    np.testing.assert_allclose(np.asarray(y_add), np.asarray(y) + 3.0, rtol=1e-6)


def test_supports_gating():
    assert codec_pallas.supports(4096, 4, 512, False)
    assert not codec_pallas.supports(4096, 4, 100, False)  # bucket % 32 != 0
    assert codec_pallas.supports(4096, 4, 512, True)  # residual mode rides
    assert codec_pallas.supports(4096 + 17, 4, 512, True)
    # residual mode with < 1 whole bucket left after the slice: XLA path
    assert not codec_pallas.supports(100, 4, 512, True)
    assert not codec_pallas.supports(100, 4, 512, False)  # tiny tensor


@pytest.mark.parametrize("m", [4096 + 17, 33 * 64 + 63])
def test_pallas_skip_incomplete_matches_xla(m):
    # Residual mode (compressor.cc:315-339): incomplete final bucket rides
    # raw; packed/meta/residual must all match the XLA oracle byte-for-byte
    # and the roundtrip must reproduce the tail exactly.
    rows, bits, bucket = 2, 4, 64
    xs = jnp.asarray(
        np.random.default_rng(m).normal(size=(rows, m)), jnp.float32
    )
    q_p = codec_pallas.quantize_batch(
        xs, bits, bucket, interpret=True, skip_incomplete_buckets=True
    )
    q_x = jax.vmap(
        lambda r: codec.quantize(r, bits, bucket, skip_incomplete_buckets=True)
    )(xs)
    assert q_p.packed.shape == q_x.packed.shape
    np.testing.assert_array_equal(np.asarray(q_p.packed), np.asarray(q_x.packed))
    np.testing.assert_array_equal(np.asarray(q_p.meta), np.asarray(q_x.meta))
    np.testing.assert_array_equal(
        np.asarray(q_p.residual), np.asarray(q_x.residual)
    )
    assert q_p.residual.shape == (rows, m % bucket)
    y = codec_pallas.dequantize_batch(q_p, interpret=True, out_dtype=jnp.float32)
    y_ref = jax.vmap(lambda qq: codec.dequantize(qq, out_dtype=jnp.float32))(q_x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=2e-6, atol=5e-7
    )
    # the raw tail is exact
    np.testing.assert_array_equal(
        np.asarray(y)[:, m - m % bucket:], np.asarray(xs)[:, m - m % bucket:]
    )
    # add_to fusion with a residual present
    acc = jnp.ones_like(xs)
    y_acc = codec_pallas.dequantize_batch(q_p, add_to=acc, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y_acc), np.asarray(y) + 1.0, rtol=2e-6, atol=5e-7
    )


def test_fused_add_matches_unfused():
    """The fused decompress-accumulate (UnpackArray<ADD> parity,
    cuda_compression_operations.cu:474-544) must be BIT-identical to
    decode-then-add: same op order (acc + (bmin + unit*lvl)), just one
    fewer HBM round trip. Engages only on the flat fast path with an
    exactly-tiling accumulator; a mismatched accumulator width falls back
    to the unfused add with the same values."""
    rows, bits, bucket = 2, 4, 128
    m = 64 * bucket  # nb_r = 64 full chunks per row -> flat path, no pad
    xs = jnp.asarray(np.random.default_rng(11).normal(size=(rows, m)), jnp.float32)
    acc = jnp.asarray(np.random.default_rng(12).normal(size=(rows, m)), jnp.float32)
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    fused = codec_pallas.dequantize_batch(
        q, add_to=acc, interpret=True, out_dtype=jnp.float32
    )
    plain = codec_pallas.dequantize_batch(
        q, interpret=True, out_dtype=jnp.float32
    )
    np.testing.assert_array_equal(
        np.asarray(fused), np.asarray(acc) + np.asarray(plain)
    )
    # XLA-oracle agreement: equal up to the documented FMA-vs-mul+add
    # codegen delta between decode implementations (1 ulp).
    y_ref = jax.vmap(
        lambda qq, a: codec.dequantize(qq, add_to=a, out_dtype=jnp.float32)
    )(q, acc)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(y_ref), rtol=2e-6, atol=5e-7
    )
    # Unaligned numel (edge-padded flat path): falls back, same values.
    m2 = 64 * bucket - 57
    xs2, acc2 = xs[:, :m2], acc[:, :m2]
    q2 = codec_pallas.quantize_batch(xs2, bits, bucket, interpret=True)
    out2 = codec_pallas.dequantize_batch(
        q2, add_to=acc2, interpret=True, out_dtype=jnp.float32
    )
    want2 = acc2 + codec_pallas.dequantize_batch(
        q2, interpret=True, out_dtype=jnp.float32
    )
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(want2))


def test_dispatch_skip_incomplete_pallas(monkeypatch):
    # Forced-pallas dispatch honors the residual config end-to-end and the
    # flat fast path (bucket % 128 == 0) emits XLA-identical bytes.
    monkeypatch.setenv(cgx_config.CODEC_IMPL, "pallas")
    cc = CompressionConfig(bits=4, bucket_size=128, skip_incomplete_buckets=True)
    m = 32 * 128 + 50
    xs = jnp.asarray(np.random.default_rng(3).normal(size=(2, m)), jnp.float32)
    q = dispatch.quantize_batch(xs, cc)
    assert q.residual.shape == (2, 50)
    q_ref = jax.vmap(
        lambda r: codec.quantize(r, 4, 128, skip_incomplete_buckets=True)
    )(xs)
    np.testing.assert_array_equal(np.asarray(q.packed), np.asarray(q_ref.packed))
    y = dispatch.dequantize_batch(q)
    y_ref = jax.vmap(lambda qq: codec.dequantize(qq))(q_ref)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=2e-6, atol=5e-7
    )


def test_dispatch_forced_pallas_on_cpu(monkeypatch):
    # CGX_CODEC_IMPL=pallas on CPU -> interpret-mode pallas, same wire bytes.
    monkeypatch.setenv(cgx_config.CODEC_IMPL, "pallas")
    cc = CompressionConfig(bits=4, bucket_size=64)
    xs = jnp.asarray(np.random.default_rng(5).normal(size=(2, 4096)), jnp.float32)
    q = dispatch.quantize_batch(xs, cc)
    q_ref = jax.vmap(lambda r: codec.quantize(r, 4, 64))(xs)
    np.testing.assert_array_equal(np.asarray(q.packed), np.asarray(q_ref.packed))
    monkeypatch.setenv(cgx_config.CODEC_IMPL, "xla")
    q2 = dispatch.quantize_batch(xs, cc)
    np.testing.assert_array_equal(np.asarray(q2.packed), np.asarray(q_ref.packed))


def test_host_wire_matches_pallas():
    # numpy/C++ host codec and pallas kernel bytes must agree (the torch
    # bridge encodes on host; JAX-side reducers may decode the same frames).
    from torch_cgx_tpu.ops import codec_host

    rows, m, bits, bucket = 1, 50_000, 3, 128
    x = np.random.default_rng(9).normal(size=m).astype(np.float32)
    q_h = codec_host.quantize(x, bits, bucket)
    q_p = codec_pallas.quantize_batch(
        jnp.asarray(x)[None, :], bits, bucket, interpret=True
    )
    np.testing.assert_array_equal(q_h.packed, np.asarray(q_p.packed)[0])
    np.testing.assert_array_equal(q_h.meta, np.asarray(q_p.meta)[0])


def test_tile_chunks_env_validation(monkeypatch):
    monkeypatch.setenv("CGX_PALLAS_TILE_CHUNKS", "0")
    with pytest.raises(ValueError, match="CGX_PALLAS_TILE_CHUNKS"):
        codec_pallas.quantize_batch(
            jnp.zeros((1, 64 * 512), jnp.float32), 4, 512, interpret=True
        )


def test_tile_chunks_env_override(monkeypatch):
    monkeypatch.setenv("CGX_PALLAS_TILE_CHUNKS", "2")
    xs = jnp.asarray(np.random.default_rng(3).normal(size=(1, 70 * 64)), jnp.float32)
    q = codec_pallas.quantize_batch(xs, 4, 64, interpret=True)
    q_ref = jax.vmap(lambda r: codec.quantize(r, 4, 64))(xs)
    np.testing.assert_array_equal(np.asarray(q.packed), np.asarray(q_ref.packed))


@pytest.mark.parametrize("shape_case", ["flat", "chunks"])
def test_butterfly_pack_byte_identity(monkeypatch, shape_case):
    """CGX_PALLAS_PACK=butterfly must emit exactly the same wire bytes as
    the default sum pack (both quantize kernel families)."""
    from torch_cgx_tpu.ops import codec_pallas

    bits = 4
    if shape_case == "flat":
        b, n = 128, 128 * 32 * 4  # whole chunks, bucket % 128 == 0
    else:
        b, n = 96, 96 * 32 * 2  # 32-aligned but not 128: chunk kernels
    rng = np.random.default_rng(5)
    xs = jnp.asarray(rng.normal(size=(1, n)), jnp.float32)

    monkeypatch.delenv("CGX_PALLAS_PACK", raising=False)
    q_sum = codec_pallas.quantize_batch(xs, bits, b, interpret=True)
    monkeypatch.setenv("CGX_PALLAS_PACK", "butterfly")
    q_bf = codec_pallas.quantize_batch(xs, bits, b, interpret=True)
    np.testing.assert_array_equal(np.asarray(q_sum.packed), np.asarray(q_bf.packed))
    np.testing.assert_array_equal(np.asarray(q_sum.meta), np.asarray(q_bf.meta))

    monkeypatch.setenv("CGX_PALLAS_PACK", "bogus")
    with pytest.raises(ValueError, match="CGX_PALLAS_PACK"):
        codec_pallas.quantize_batch(xs, bits, b, interpret=True)


def test_mul_encode_envelope_and_constant_exact(monkeypatch):
    """CGX_CODEC_ENCODE=mul (reciprocal-multiply level encode): trades
    strict cross-impl byte-identity (last-ulp ties may pick the adjacent
    level) for encode throughput. The error envelope, constant-bucket
    exactness, and decode round trip must all still hold."""
    monkeypatch.setenv("CGX_CODEC_ENCODE", "mul")
    bits, bucket = 4, 512
    rows, m = 2, 64 * bucket
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.normal(size=(rows, m)), jnp.float32)
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    y = codec_pallas.dequantize_batch(q, interpret=True, out_dtype=jnp.float32)
    unit = np.asarray(q.meta, np.float32)[..., 0].max()
    assert np.abs(np.asarray(y) - np.asarray(xs)).max() <= unit / 2 + 1e-6
    # differs from the div encode in at most a tiny fraction of levels, and
    # any differing value is off by exactly one level
    q_div = jax.vmap(lambda r: codec.quantize(r, bits, bucket))(xs)
    y_div = jax.vmap(lambda qq: codec.dequantize(qq, out_dtype=jnp.float32))(q_div)
    diff = np.abs(np.asarray(y) - np.asarray(y_div))
    assert (diff <= unit * 1.01).all()
    # diffs below unit/10 are last-ulp decode arithmetic, not level moves
    moved = np.mean(diff > unit * 0.1)
    assert moved < 1e-3, f"{moved:%} of levels moved"
    # constants stay bit-exact
    const = jnp.full((1, m), 2.75, jnp.float32)
    qc = codec_pallas.quantize_batch(const, bits, bucket, interpret=True)
    yc = codec_pallas.dequantize_batch(qc, interpret=True, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(yc), np.asarray(const))


@pytest.mark.tpu  # compiled Mosaic lowering of the butterfly pack
def test_flat_pack_butterfly_tpu(monkeypatch):
    monkeypatch.setenv("CGX_PALLAS_PACK", "butterfly")
    bits, bucket = 4, 512
    xs = jnp.asarray(
        np.random.default_rng(5).normal(size=(2, 64 * bucket)), jnp.float32
    )
    q_p = codec_pallas.quantize_batch(xs, bits, bucket)
    monkeypatch.delenv("CGX_PALLAS_PACK")
    q_s = codec_pallas.quantize_batch(xs, bits, bucket)
    np.testing.assert_array_equal(np.asarray(q_p.packed), np.asarray(q_s.packed))
    np.testing.assert_array_equal(np.asarray(q_p.meta), np.asarray(q_s.meta))


@pytest.mark.tpu  # compiled Mosaic lowering of the mul encode
def test_mul_encode_tpu(monkeypatch):
    monkeypatch.setenv("CGX_CODEC_ENCODE", "mul")
    bits, bucket = 4, 512
    xs = jnp.asarray(
        np.random.default_rng(6).normal(size=(1, 64 * bucket)), jnp.float32
    )
    q = codec_pallas.quantize_batch(xs, bits, bucket)
    y = codec_pallas.dequantize_batch(q, out_dtype=jnp.float32)
    unit = np.asarray(q.meta, np.float32)[..., 0].max()
    assert np.abs(np.asarray(y) - np.asarray(xs)).max() <= unit / 2 + 1e-6


# Slow tier: the interpret-mode sweep runs ~40 s serially; the
# targeted parity tests above keep both kernel families in tier-1.
@pytest.mark.slow
def test_fuzz_pallas_wire_matches_xla():
    """Seeded fuzz over supported (n, bits, bucket) combos — both kernel
    families (flat whole-chunk rows and chunk-block tails) must stay
    byte-identical to the XLA oracle across odd sizes and value extremes
    (the class of tail bug test_codec_host's fuzz caught in the C++ core).
    Interpret mode; small operands keep it fast."""
    rng = np.random.default_rng(0xCA5)
    # Pinned flat-path combos: nb % 32 == 0 and bucket % 128 == 0 routes
    # the whole-chunk-row kernels; random draws below essentially always
    # carry a chunk tail, which would leave that family unfuzzed.
    combos = [(4096, 4, 128, False), (8192, 2, 128, False)]
    for bits in (1, 2, 3, 4, 5, 6, 7, 8):
        n = int(rng.integers(256, 9000))
        bucket = int(rng.choice([32, 64, 96, 128, 160, 512]))
        skip = bool(rng.integers(0, 2)) and (n % bucket != 0)
        if codec_pallas.supports(n, bits, bucket, skip):
            combos.append((n, bits, bucket, skip))
    assert len(combos) >= 8  # the seed must keep real coverage
    for n, bits, bucket, skip in combos:
        from conftest import fuzz_operand

        kind = rng.integers(0, 3)
        x = fuzz_operand(rng, n, int(kind))
        xs = jnp.asarray(x)[None, :]
        ctx = (n, bits, bucket, skip, int(kind))
        qp = codec_pallas.quantize_batch(
            xs, bits, bucket, interpret=True, skip_incomplete_buckets=skip
        )
        qx = codec.quantize(
            jnp.asarray(x), bits, bucket, skip_incomplete_buckets=skip
        )
        np.testing.assert_array_equal(
            np.asarray(qp.packed[0]), np.asarray(qx.packed), err_msg=str(ctx))
        np.testing.assert_array_equal(
            np.asarray(qp.meta[0], np.float32),
            np.asarray(qx.meta, np.float32), err_msg=str(ctx))
        dp = np.asarray(codec_pallas.dequantize_batch(
            qp, out_dtype=jnp.float32, interpret=True
        )[0])
        dx = np.asarray(codec.dequantize(qx, out_dtype=jnp.float32))
        # Decode parity is NOT bit-exact: min + lvl*unit rounds once per
        # op, and orderings differ between kernels, so the two decodes can
        # differ by a couple of roundings AT THE OPERAND MAGNITUDE — which
        # is many ulps of the RESULT when min and lvl*unit cancel (decoded
        # value near zero inside a wide bucket). Bound per element by the
        # bucket's own magnitude; each implementation stays deterministic
        # (the byte-equal wire above), which is all error symmetry needs,
        # and the quantization envelope (unit/2) dwarfs this bound.
        pad = (-n) % bucket
        xb = np.concatenate([x, np.repeat(x[-1:], pad)]).reshape(-1, bucket)
        bound = np.abs(xb).max(axis=1).repeat(bucket)[:n]
        tol = 4 * np.spacing(np.float32(bound))
        diff = np.abs(dp - dx)
        worst = int(np.argmax(diff - tol))
        assert (diff <= tol).all(), (
            ctx, worst, dp[worst], dx[worst], float(tol[worst]))


# ---------------------------------------------------------------------------
# Fused SRA epilogue (ISSUE 4): K-operand dequantize-accumulate(-requantize)
# vs the staged oracle, in interpret mode on CPU.
# ---------------------------------------------------------------------------


def _staged_epilogue(q, xs, own_idx, bits, bucket, out_dtype=jnp.float32):
    """The staged reference ops, spelled out: decode rows, swap the raw own
    chunk, ordered accumulate, stage-2 quantize — the byte oracle for the
    fused kernel."""
    ws = xs.shape[0]
    vals = codec_pallas.dequantize_batch(q, out_dtype=jnp.float32, interpret=True)
    own = (jnp.arange(ws) == own_idx)[:, None]
    red = dispatch.ordered_rowsum(
        jnp.where(own, xs.astype(jnp.float32), vals)
    )
    return red, codec_pallas.quantize_batch(
        red.astype(out_dtype)[None], bits, bucket, interpret=True
    )


@pytest.mark.parametrize("ws,bits,bucket", [
    (2, 4, 128), (4, 2, 128), (4, 8, 256), (8, 4, 128), (3, 1, 128),
])
def test_fused_epilogue_matches_staged_oracle(ws, bits, bucket):
    """The acceptance oracle: the fused dequant-accumulate-requantize
    kernel must reproduce the staged path's stage-2 wire BYTES (payload
    and per-bucket meta) and reduced values exactly, per bucket, on the
    default deterministic div encode."""
    chunk = 2 * codec.CHUNK_BUCKETS * bucket
    rng = np.random.default_rng(ws * 10 + bits)
    xs = jnp.asarray(rng.normal(size=(ws, chunk)), jnp.float32)
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    assert codec_pallas.supports_reduce(q)
    own_idx = jnp.int32(ws - 1)
    red_ref, q_ref = _staged_epilogue(q, xs, own_idx, bits, bucket)
    red = codec_pallas.reduce_rows_batch(
        q, raw_row=xs[ws - 1], own_idx=own_idx, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(red_ref), np.asarray(red))
    q_f = codec_pallas.sra_epilogue_batch(
        q, raw_row=xs[ws - 1], own_idx=own_idx, interpret=True
    )
    np.testing.assert_array_equal(
        np.asarray(q_ref.packed), np.asarray(q_f.packed)
    )
    # per-bucket meta: (1, nb, 2) (unit, min) pairs must agree bucket by
    # bucket, not just in aggregate
    np.testing.assert_array_equal(
        np.asarray(q_ref.meta, np.float32), np.asarray(q_f.meta, np.float32)
    )
    # both decode to the same allgather-phase values
    y_ref = codec_pallas.dequantize_batch(q_ref, interpret=True)
    y_f = codec_pallas.dequantize_batch(q_f, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_f))


def test_fused_reduce_no_own_swap_matches_staged():
    """The all-to-all form: no raw-row substitution — plain K-operand
    decompress-accumulate."""
    ws, bits, bucket = 4, 4, 128
    chunk = codec.CHUNK_BUCKETS * bucket
    xs = jnp.asarray(
        np.random.default_rng(7).normal(size=(ws, chunk)), jnp.float32
    )
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    vals = codec_pallas.dequantize_batch(q, out_dtype=jnp.float32, interpret=True)
    ref = dispatch.ordered_rowsum(vals)
    got = codec_pallas.reduce_rows_batch(q, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_fused_epilogue_bf16_wire_dtype():
    """bf16 wire: the staged path quantizes reduced.astype(bf16); the
    fused kernel's cast_dtype must round identically."""
    ws, bits, bucket = 4, 4, 128
    chunk = codec.CHUNK_BUCKETS * bucket
    xs = jnp.asarray(
        np.random.default_rng(8).normal(size=(ws, chunk)), jnp.float32
    ).astype(jnp.bfloat16)
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    own_idx = jnp.int32(1)
    _, q_ref = _staged_epilogue(q, xs, own_idx, bits, bucket, jnp.bfloat16)
    q_f = codec_pallas.sra_epilogue_batch(
        q, raw_row=xs[1], own_idx=own_idx, out_dtype=jnp.bfloat16,
        interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(q_ref.packed), np.asarray(q_f.packed)
    )
    np.testing.assert_array_equal(
        np.asarray(q_ref.meta, np.float32), np.asarray(q_f.meta, np.float32)
    )


def test_fused_epilogue_mul_encode_envelope_and_ties(monkeypatch):
    """ISSUE 4 satellite: CGX_CODEC_ENCODE=mul must apply INSIDE the fused
    epilogue's requantize — same one-knob flip criterion as the plain
    quantize kernel (PERF_NOTES.md): error envelope holds, only a tiny
    tie fraction of levels moves vs the div encode, constants stay
    bit-exact."""
    ws, bits, bucket = 4, 4, 512
    chunk = 2 * codec.CHUNK_BUCKETS * bucket
    rng = np.random.default_rng(9)
    xs = jnp.asarray(rng.normal(size=(ws, chunk)), jnp.float32)
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    own_idx = jnp.int32(0)
    red_ref, q_div = _staged_epilogue(q, xs, own_idx, bits, bucket)
    monkeypatch.setenv("CGX_CODEC_ENCODE", "mul")
    q_mul = codec_pallas.sra_epilogue_batch(
        q, raw_row=xs[0], own_idx=own_idx, interpret=True
    )
    monkeypatch.delenv("CGX_CODEC_ENCODE")
    # meta (pure max/min arithmetic) is encode-independent
    np.testing.assert_array_equal(
        np.asarray(q_div.meta, np.float32), np.asarray(q_mul.meta, np.float32)
    )
    y_div = codec_pallas.dequantize_batch(q_div, interpret=True)[0]
    y_mul = codec_pallas.dequantize_batch(q_mul, interpret=True)[0]
    unit = np.asarray(q_mul.meta, np.float32)[..., 0].max()
    # envelope: the mul decode still round-trips the reduced chunk within
    # half a level
    assert np.abs(np.asarray(y_mul) - np.asarray(red_ref)).max() <= (
        unit / 2 + 1e-5
    )
    # tie fraction: differing values are off by at most one level and rare
    diff = np.abs(np.asarray(y_mul) - np.asarray(y_div))
    assert (diff <= unit * 1.01).all()
    assert np.mean(diff > unit * 0.1) < 1e-3
    # constant buckets encode exactly under mul too
    const = jnp.full((ws, chunk), 1.5, jnp.float32)
    qc = codec_pallas.quantize_batch(const, bits, bucket, interpret=True)
    monkeypatch.setenv("CGX_CODEC_ENCODE", "mul")
    qc_f = codec_pallas.sra_epilogue_batch(
        qc, raw_row=const[0], own_idx=jnp.int32(0), interpret=True
    )
    yc = codec_pallas.dequantize_batch(qc_f, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(yc), np.full((1, chunk), ws * 1.5, np.float32)
    )


def test_fused_reduce_unsupported_shapes_fall_back(monkeypatch):
    """Dispatch keeps the staged reference path for shapes outside the
    flat-kernel geometry (tail buckets, non-128-aligned buckets) and on
    CPU auto mode — supports_reduce gates the kernel, values are
    unchanged either way."""
    ws, bits = 4, 4
    # bucket not 128-aligned -> unsupported
    xs = jnp.asarray(
        np.random.default_rng(11).normal(size=(ws, 32 * 64)), jnp.float32
    )
    q = codec_pallas.quantize_batch(xs, bits, 64, interpret=True)
    assert not codec_pallas.supports_reduce(q)
    # chunk tail (nb_r % 32 != 0) -> unsupported
    q2 = codec_pallas.quantize_batch(
        jnp.asarray(np.random.default_rng(12).normal(size=(ws, 8 * 128)),
                    jnp.float32),
        bits, 128, interpret=True,
    )
    assert not codec_pallas.supports_reduce(q2)
    # forced-fused dispatch on a supported shape equals forced-staged
    chunk = codec.CHUNK_BUCKETS * 128
    xs3 = jnp.asarray(
        np.random.default_rng(13).normal(size=(ws, chunk)), jnp.float32
    )
    q3 = codec_pallas.quantize_batch(xs3, bits, 128, interpret=True)
    own_idx = jnp.int32(2)
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    monkeypatch.setenv("CGX_SRA_EPILOGUE", "staged")
    staged = dispatch.reduce_rows(q3, raw_rows=xs3, own_idx=own_idx)
    monkeypatch.setenv("CGX_SRA_EPILOGUE", "fused")
    fused = dispatch.reduce_rows(q3, raw_rows=xs3, own_idx=own_idx)
    np.testing.assert_array_equal(np.asarray(staged), np.asarray(fused))


@pytest.mark.tpu  # compiled Mosaic lowering of the fused epilogue
def test_fused_epilogue_tpu():
    ws, bits, bucket = 8, 4, 512
    chunk = 2 * codec.CHUNK_BUCKETS * bucket
    xs = jnp.asarray(
        np.random.default_rng(14).normal(size=(ws, chunk)), jnp.float32
    )
    q = codec_pallas.quantize_batch(xs, bits, bucket)
    own_idx = jnp.int32(3)
    vals = codec_pallas.dequantize_batch(q, out_dtype=jnp.float32)
    own = (jnp.arange(ws) == own_idx)[:, None]
    red = dispatch.ordered_rowsum(
        jnp.where(own, xs.astype(jnp.float32), vals)
    )
    q_ref = codec_pallas.quantize_batch(red[None], bits, bucket)
    q_f = codec_pallas.sra_epilogue_batch(
        q, raw_row=xs[3], own_idx=own_idx
    )
    np.testing.assert_array_equal(
        np.asarray(q_ref.packed), np.asarray(q_f.packed)
    )
    np.testing.assert_array_equal(
        np.asarray(q_ref.meta, np.float32), np.asarray(q_f.meta, np.float32)
    )


# ---------------------------------------------------------------------------
# int8 epilogue accumulation (CGX_SRA_ACCUM) — codec roofline round 2.
# ---------------------------------------------------------------------------


def test_int8_accum_envelope(monkeypatch):
    """CGX_SRA_ACCUM=int8: the fixed-point peer-row fold stays within the
    documented envelope of the exact f32 fold — per-row unit snap error
    <= U/2^13 * maxlvl, summed over ws rows."""
    ws, bits, bucket = 4, 4, 512
    rng = np.random.default_rng(24)
    xs = jnp.asarray(
        rng.standard_normal((ws, 2 * 32 * bucket)), jnp.float32
    )
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)
    own = jnp.int32(2)
    exact = codec_pallas.reduce_rows_batch(
        q, raw_row=xs[2], own_idx=own, interpret=True
    )
    monkeypatch.setenv("CGX_SRA_ACCUM", "int8")
    fixed = codec_pallas.reduce_rows_batch(
        q, raw_row=xs[2], own_idx=own, interpret=True
    )
    units = np.asarray(q.meta, np.float32)[..., 0]
    bound = ws * units.max() * ((1 << bits) - 1) / (1 << 13) + 1e-6
    err = np.max(np.abs(np.asarray(exact) - np.asarray(fixed)))
    assert err <= bound, (err, bound)
    # and the requantizing epilogue still produces a decodable payload
    q2 = codec_pallas.sra_epilogue_batch(
        q, raw_row=xs[2], own_idx=own, interpret=True
    )
    dec = codec_pallas.dequantize_batch(q2, interpret=True)
    unit2 = np.abs(np.asarray(exact)).max() / ((1 << bits) - 1)
    assert np.max(
        np.abs(np.asarray(dec)[0] - np.asarray(exact))
    ) <= 2 * unit2 + bound


def test_int8_accum_constant_buckets_exact(monkeypatch):
    """Constant buckets (unit 0) decode exactly under the int8 fold too —
    the zero-unit guard must not poison the fixed-point scales."""
    ws, bucket = 4, 512
    xs = jnp.tile(
        jnp.asarray([[1.5]], jnp.float32), (ws, 32 * bucket)
    )
    q = codec_pallas.quantize_batch(xs, 4, bucket, interpret=True)
    monkeypatch.setenv("CGX_SRA_ACCUM", "int8")
    red = codec_pallas.reduce_rows_batch(q, interpret=True)
    np.testing.assert_allclose(np.asarray(red), ws * 1.5, rtol=1e-6)


# ---------------------------------------------------------------------------
# Tile alignment Mosaic enforces (found compiling for a v5e, PR 21).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "bits,bucket,n_chunks",
    [(3, 4096, 40), (4, 16384, 6), (5, 2048, 40), (4, 512, 2355), (1, 8192, 9)],
)
def test_chunk_tile_is_sublane_aligned(bits, bucket, n_chunks, monkeypatch):
    """A multi-block chunk-kernel tile makes the (tc*bits, bucket) word
    block a whole number of 8-sublane tiles — for the heuristic and for a
    forced tile alike (libtpu 0.0.34 refuses anything else)."""
    for forced in (None, "3"):
        if forced:
            monkeypatch.setenv("CGX_PALLAS_TILE_CHUNKS", forced)
        tc = codec_pallas._chunks_tc(n_chunks, bucket, bits)
        assert 1 <= tc <= n_chunks
        assert tc == n_chunks or (tc * bits) % 8 == 0, (tc, bits)


def test_supports_refuses_geometry_without_a_legal_tile():
    # 3 bits at bucket 16384: the aligned quantum is 8 chunks = 16 MB of
    # f32 per block — no kernel path for rows that need the chunk kernels.
    n = (6 * 32 + 5) * 16384
    assert not codec_pallas.supports(n, 3, 16384, False)
    assert codec_pallas.supports(n, 4, 16384, False)  # quantum 2 fits
    # whole-chunk rows of 128-lane buckets take the flat kernels: fine
    assert codec_pallas.supports(6 * 32 * 16384, 3, 16384, False)
    # rows below one chunk never reach a chunk kernel
    assert codec_pallas.supports(5 * 16384, 3, 16384, False)


def test_on_tpu_does_not_swallow_a_dead_backend(monkeypatch):
    """A backend that fails to come up must fail the caller, not answer
    "not a TPU" (which selects the interpret kernels and the XLA codec)."""

    def dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", dead)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        dispatch._on_tpu()


def test_lowering_ledger_names_what_ran():
    """Trace-time ledger (nothing is compiled or run here)."""
    from torch_cgx_tpu.utils.logging import metrics

    metrics.reset()
    cc = CompressionConfig(bits=4, bucket_size=512)

    def row(buckets):
        return jax.ShapeDtypeStruct((1, buckets * 512), jnp.float32)

    jax.eval_shape(
        lambda x: dispatch.dequantize_batch(dispatch.quantize_batch(x, cc)),
        row(40),
    )
    assert metrics.get("cgx.codec.lowering.quantize.xla") == 1  # cpu: auto
    assert metrics.get("cgx.codec.lowering.dequantize.xla") == 1
    pallas = lambda x: codec_pallas.quantize_batch(x, 4, 512, interpret=True)
    jax.eval_shape(pallas, row(40))
    assert metrics.get("cgx.codec.lowering.quantize.pallas_chunks") == 1
    # the 16-token GPT-2 KV page: no whole chunk, the XLA tail does it all
    jax.eval_shape(pallas, row(24))
    assert metrics.get("cgx.codec.lowering.quantize.xla_tail") == 1
    jax.eval_shape(pallas, row(64))
    assert metrics.get("cgx.codec.lowering.quantize.pallas_flat") == 1


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("bucket", [128, 512])
def test_flat_kernel_stores_the_consumers_type_and_rows(bits, bucket):
    """The flat decode kernel's store (ISSUE 28): a ``bfloat16`` store is
    the float32 decode cast to ``bfloat16`` bit for bit, flat or as rows
    of the consumer's width; the default call stays float32; an ``add_to``
    keeps the float32 store (fused add, one cast after it), and the ledger
    says which store ran."""
    from torch_cgx_tpu.utils.logging import metrics

    rng = np.random.default_rng(bits * 1000 + bucket)
    rows, width = 3, 2 * bucket
    numel = 64 * bucket  # two whole chunks a row; 32 rows of ``width``
    xs = jnp.asarray(rng.standard_normal((rows, numel)) * 3.0, jnp.float32)
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=True)

    metrics.reset()
    f32 = codec_pallas.dequantize_batch(q, interpret=True)
    assert f32.dtype == jnp.float32 and f32.shape == (rows, numel)
    assert metrics.get("cgx.codec.lowering.dequantize.pallas_flat") == 1

    def bits_of(a):
        return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))

    cast = f32.astype(jnp.bfloat16)
    bf = codec_pallas.dequantize_batch(
        q, interpret=True, out_dtype=jnp.bfloat16
    )
    assert bf.dtype == jnp.bfloat16 and bf.shape == (rows, numel)
    np.testing.assert_array_equal(bits_of(bf), bits_of(cast))
    assert metrics.get(
        "cgx.codec.lowering.dequantize.pallas_flat.bfloat16") == 1

    as_rows = codec_pallas.dequantize_batch(
        q, interpret=True, out_dtype=jnp.bfloat16, row_width=width
    )
    assert as_rows.shape == (rows, numel // width, width)
    np.testing.assert_array_equal(
        bits_of(as_rows), bits_of(cast).reshape(as_rows.shape)
    )
    assert metrics.get("cgx.codec.lowering.dequantize_rows.pallas_flat") == 1
    # Rows that are not whole 128-lane columns: the same values, reshaped
    # after the kernel.
    narrow = codec_pallas.dequantize_batch(
        q, interpret=True, out_dtype=jnp.bfloat16, row_width=64
    )
    np.testing.assert_array_equal(
        bits_of(narrow), bits_of(cast).reshape(rows, -1, 64)
    )
    assert metrics.get("cgx.codec.lowering.dequantize_rows.xla_reshape") == 1

    acc = jnp.asarray(rng.standard_normal(xs.shape), jnp.float32)
    added = codec_pallas.dequantize_batch(
        q, add_to=acc, out_dtype=jnp.bfloat16, interpret=True
    )
    np.testing.assert_array_equal(
        bits_of(added), bits_of((acc + f32).astype(jnp.bfloat16))
    )
    # ... through the float32 store: no further bfloat16 one was counted.
    assert metrics.get("cgx.codec.lowering.dequantize.pallas_flat") == 2
    assert metrics.get(
        "cgx.codec.lowering.dequantize.pallas_flat.bfloat16") == 3


def _paged_pool(rng, n_pool, numel, bits, bucket, interpret=True):
    """A quantized page pool in the flat kernel's operand layout
    (``ops/paged_kv.py``: words as rows of 128, a page's meta as two
    lane-dense planes) and the wire QTensor it was made from."""
    xs = jnp.asarray(rng.standard_normal((n_pool, numel)) * 3.0, jnp.float32)
    q = codec_pallas.quantize_batch(xs, bits, bucket, interpret=interpret)
    words = jax.lax.bitcast_convert_type(q.packed, jnp.int32).reshape(
        n_pool, -1, 128)
    return q, words, jnp.swapaxes(q.meta, 1, 2)


def _gathered(q, ids):
    return dataclasses.replace(
        q, packed=q.packed[ids], meta=q.meta[ids], residual=q.residual[ids])


def _bits_of(a):
    kind = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    return np.asarray(jax.lax.bitcast_convert_type(a, kind))


@pytest.mark.parametrize("geo", [
    # (page tokens, row width, bits, bucket, store, pages a table)
    pytest.param((64, 1280, 8, 512, jnp.bfloat16, 8), id="gpt2l-kv"),
    pytest.param((64, 1280, 8, 512, jnp.float32, 6), id="gpt2l-kv-f32"),
    pytest.param((256, 512, 8, 512, jnp.bfloat16, 4), id="joyai-c"),
    pytest.param((32, 256, 4, 128, jnp.bfloat16, 12), id="one-chunk-4bit"),
])
def test_paged_decode_is_the_gathered_decode_bit_for_bit(geo):
    """ISSUE 30: the flat decode kernel fed a page POOL and page ids (the
    ids a scalar-prefetch operand, each grid step's word and meta blocks
    picked by id) writes what ``dequantize_batch`` writes over the gathered
    rows, bit for bit, for a permuted table that repeats a row (the clipped
    sentinels of a real table), at the serving cells' page geometries, in
    ``_pages_tc``'s tile (several page operands a step) and one page a
    step."""
    pt, width, bits, bucket, store, n = geo
    rng = np.random.default_rng(pt + width + n)
    n_pool = n + 1
    q, words, meta = _paged_pool(rng, n_pool, pt * width, bits, bucket)
    ids = rng.permutation(n_pool)[:n].astype(np.int32)
    ids[[1, n - 1]] = 0  # sentinel entries, clipped to row 0 by the caller
    ids = jnp.asarray(ids)
    want = codec_pallas.dequantize_batch(
        _gathered(q, ids), interpret=True, out_dtype=store, row_width=width)
    page_chunks = pt * width // (32 * bucket)
    tile = codec_pallas._pages_tc(
        n, page_chunks, bucket, width, np.dtype(store))
    assert tile and tile % page_chunks == 0 and tile > page_chunks
    for tc in (tile, page_chunks):
        got = codec_pallas._dequantize_flat_impl(
            words, meta, None, ids, bits=bits, bucket_size=bucket,
            interpret=True, tc=tc, out_dtype=np.dtype(store),
            row_width=width)
        assert got.shape == want.shape == (n, pt, width)
        assert got.dtype == want.dtype == store
        np.testing.assert_array_equal(_bits_of(got), _bits_of(want))
    # A consumer of float16 rows: float32 store, cast after the kernel.
    f16 = codec_pallas.dequantize_pages(
        words, meta, ids, bits=bits, bucket_size=bucket, tc=tile,
        out_dtype=jnp.float16, row_width=width, interpret=True)
    np.testing.assert_array_equal(
        _bits_of(f16), _bits_of(codec_pallas.dequantize_batch(
            _gathered(q, ids), interpret=True, out_dtype=jnp.float16,
            row_width=width)))


# A K/V or latent page of each of the seven serving adapters (ISSUE 46), at
# its cell's size: (page tokens, row width); 8 bits, buckets of 512.
_ADAPTER_PAGES = {
    "gpt2": (64, 1280),  # 160 buckets: not whole lanes of 128
    "mla_moe": (256, 512),  # the latent ``c``: 256 buckets
    "hybrid_ssm": (256, 512),  # granite: 8 K/V heads x 64
    "hybrid_gdn": (64, 3840),  # Olmo: 480 buckets, fifteen chunks
    "hybrid_kda_mla": (256, 512),  # Ling's one latent layer
    "window_moe": (256, 512),  # SmallThinker: 4 K/V heads x 128, the ring's
    "afmoe": (256, 1024),  # Trinity: 512 buckets, two a token
}


@pytest.mark.parametrize("guarded", [False, True], ids=["bare", "live"])
@pytest.mark.parametrize("adapter", sorted(_ADAPTER_PAGES))
def test_paged_read_of_the_planes_is_the_wire_pages_decode(adapter, guarded):
    """ISSUE 46: the paged read over a pool whose meta lies as planes
    ``(pool rows, 2, buckets)`` writes, bit for bit, what the UNPAGED kernel
    (the parent's program: ``test_unguarded_paged_decode_is_the_parents_
    jaxpr``) decodes from the same pages' wire ``QTensor``, the ``(buckets,
    2)`` pairs: as rows of the adapter's width, in float32 and bfloat16,
    under both kernel names, with and without ``live`` (a dead entry's rows
    are zeros), for a table that repeats a row. ``codec.dequantize`` of
    those pages, the XLA codec, is the same up to its fused multiply-add, as
    it is of the unpaged kernel (``test_pallas_wire_matches_xla``)."""
    pt, width = _ADAPTER_PAGES[adapter]
    rng = np.random.default_rng(46 + pt + width + guarded)
    n = 4
    q, words, meta = _paged_pool(rng, n + 2, pt * width, 8, 512)
    assert meta.shape == (n + 2, 2, pt * width // 512)
    ids = jnp.asarray([5, 0, 5, 2], jnp.int32)
    live = jnp.asarray([1, 0, 1, 1], jnp.int32) if guarded else None
    wire = _gathered(q, ids)
    assert wire.meta.shape == (n, pt * width // 512, 2)
    want = codec_pallas.dequantize_batch(wire, interpret=True)
    np.testing.assert_allclose(
        np.asarray(jax.vmap(codec.dequantize)(wire)), np.asarray(want),
        rtol=2e-6, atol=5e-7)
    want = want.reshape(n, pt, width)
    if guarded:
        want = want.at[1].set(0)
    tile = codec_pallas.pages_tile(n, pt * width, 512, width, jnp.bfloat16)
    assert tile
    for name, store in (("cgx_dequantize_flat", jnp.float32),
                        ("cgx_dequantize_flat", jnp.bfloat16),
                        ("cgx_dequantize_window", jnp.bfloat16)):
        got = codec_pallas.dequantize_pages(
            words, meta, ids, bits=8, bucket_size=512, tc=tile,
            out_dtype=store, row_width=width, interpret=True, name=name,
            live=live)
        assert got.shape == (n, pt, width) and got.dtype == store
        np.testing.assert_array_equal(
            _bits_of(got), _bits_of(want.astype(store)))


@pytest.mark.parametrize("adapter", sorted(_ADAPTER_PAGES))
def test_the_read_and_the_commit_take_the_pools_meta_as_it_lies(
        adapter, monkeypatch):
    """The gain of ISSUE 46 without a chip: at the cells' page shapes, on
    TPU dispatch, ``gather_dequant_pages`` hands the pool's meta to the
    ``pallas_call`` as the very array the pool holds, and ``commit_page_rows``
    hands it to the ``scatter``: no other equation (a ``transpose``, a
    ``reshape``, a ``copy``: what XLA answered with a relay of the whole
    pool) has an operand of the pool's meta's shape. The rows a commit turns
    are the ``K`` it wrote."""
    from torch_cgx_tpu.ops import paged_kv

    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    pt, width = _ADAPTER_PAGES[adapter]
    spec = paged_kv.PageSpec(pt, width // 128, 128, 8, 512)
    pool_rows, lanes, pages, k = 65, 8, 4, 4
    pool = jax.eval_shape(lambda: paged_kv.empty_pool(pool_rows, spec))
    meta_shape = (pool_rows, 2, spec.num_buckets)
    assert pool[1].shape == meta_shape

    def takers(jaxpr, shape=meta_shape):
        found = []

        def walk(jp):
            for eqn in jp.eqns:
                if any(tuple(getattr(v.aval, "shape", ())) == shape
                       for v in eqn.invars):
                    found.append(eqn.primitive.name)
                if eqn.primitive.name != "pallas_call":
                    for sub in jax.core.jaxprs_in_params(eqn.params):
                        walk(sub)

        walk(jaxpr.jaxpr)
        return found

    for window in (False, True):
        read = jax.make_jaxpr(
            lambda pool, table, live: paged_kv.gather_dequant_pages(
                pool, table, spec, jnp.bfloat16, window=window,
                live=live if window else None))(
            pool, jax.ShapeDtypeStruct((lanes, pages), jnp.int32),
            jax.ShapeDtypeStruct((lanes, pages), bool))
        assert sorted(takers(read)) == ["jit", "pallas_call"]
    commit = jax.make_jaxpr(
        lambda pool, ids, rows: paged_kv.commit_page_rows(
            pool, ids, rows, spec))(
        pool, jax.ShapeDtypeStruct((k,), jnp.int32),
        jax.ShapeDtypeStruct((k, spec.flat), jnp.float32))
    assert takers(commit) == ["scatter"]
    assert "transpose" in takers(commit, (k, spec.num_buckets, 2))


def test_pages_tc_is_the_gathered_reads_tile_in_whole_pages():
    """``_pages_tc`` keeps the tile the gathered read of the same table
    takes (``_rows_tc``) where that is whole pages, and refuses (the read
    gathers) rows the kernel cannot store, a page the tile does not hold
    and a forced tile under a page."""
    bf16, f32 = np.dtype(jnp.bfloat16), np.dtype(np.float32)
    assert codec_pallas._pages_tc(512, 5, 512, 1280, bf16) == 10
    assert codec_pallas._pages_tc(512, 5, 512, 1280, f32) == 10
    assert codec_pallas._pages_tc(512, 8, 512, 512, bf16) == 16
    assert codec_pallas._pages_tc(512, 1, 512, 64, bf16) is None
    # 7 pages of five chunks: 35 chunks, whose only tiles of whole
    # 1,280-wide bfloat16 rows are 5 (one page a step) ... and of three
    # chunks a page with 128-wide rows, 15 chunks: tile 15, five pages.
    assert codec_pallas._pages_tc(7, 5, 512, 1280, bf16) == 5
    assert codec_pallas._pages_tc(5, 3, 512, 128, bf16) == 15
    # A page over the VMEM cap (16 chunks): the tile is part of a page.
    assert codec_pallas._rows_tc(2 * 32, 512, 512, bf16) == 16
    assert codec_pallas._pages_tc(2, 32, 512, 512, bf16) is None


@pytest.mark.tpu  # compiled Mosaic lowering of the scalar-prefetch grid
def test_paged_decode_tpu():
    rng = np.random.default_rng(30)
    pt, width, bits, bucket, n = 64, 1280, 8, 512, 16
    q, words, meta = _paged_pool(rng, n + 1, pt * width, bits, bucket,
                                 interpret=False)
    ids = jnp.asarray(rng.permutation(n + 1)[:n], jnp.int32)
    want = codec_pallas.dequantize_batch(
        _gathered(q, ids), out_dtype=jnp.bfloat16, row_width=width)
    got = codec_pallas.dequantize_pages(
        words, meta, ids, bits=bits, bucket_size=bucket, tc=10,
        out_dtype=jnp.bfloat16, row_width=width)
    np.testing.assert_array_equal(_bits_of(got), _bits_of(want))


# The guard (ISSUE 42): one small page geometry (32 tokens of 512, one chunk a
# page, bucket 512), a table of 8 entries over a pool of 9.
_GUARD_CASES = [
    pytest.param(bits, ppb, rows,
                 id=f"{bits}bit-ppb{ppb}-{'rows' if rows else 'flat'}")
    for bits in (4, 8) for ppb in (1, 2) for rows in (False, True)
]
# The first 16 hex digits of the SHA-256 of the UNPAGED call's jaxpr as text
# (``page_ids`` None: the training fabric's decode of the wire's ``(rows,
# buckets, 2)`` pairs), computed on the parent of the PR that turned the
# pools' meta into planes (PR 46's parent, its ``git archive``), by (bits,
# chunks a grid step, rows stored), and of the fused add at 4 bits, two
# chunks a step. (Until PR 46 this table held the unguarded paged call's,
# from PR 42's parent; that call takes the pool's planes now.)
_PARENT_UNPAGED = {
    (4, 1, False): "48a4f28459d4763b", (4, 1, True): "75c0307abbfa682e",
    (4, 2, False): "74411fbf498a5aa7", (4, 2, True): "dd8d9d3978ba4c09",
    (8, 1, False): "722f2dd32f922013", (8, 1, True): "41d8069ff36c8ebd",
    (8, 2, False): "798284d8114e81ba", (8, 2, True): "4292b98f301c83e2",
    "add": "0196c00d2751e41d",
}


# The same of the PAGED call at the plane loop (``unpack`` left alone: every
# table's read), bare and guarded, computed on PR 51's parent.
_PARENT_PAGED = {
    (4, 1, False): ("7a2d4d0598ee31cb", "633e59c91f0f4590"),
    (4, 1, True): ("f11b9241c9434462", "8586d47881d09222"),
    (4, 2, False): ("3f11ed53044aaa74", "c712b24a6a3e4631"),
    (4, 2, True): ("8aaf9222e8ac2c3e", "344c73ddb99301d9"),
    (8, 1, False): ("227a1074abd517df", "d3edccc860015adc"),
    (8, 1, True): ("4b39872041af01f4", "9589ea512395d3d1"),
    (8, 2, False): ("181c47f82b154eee", "9af8aff8e6ef363e"),
    (8, 2, True): ("0de9cda837b03112", "24425aaa153ba195"),
}


def _guard_call(bits, ppb, rows):
    return functools.partial(
        codec_pallas._dequantize_flat_impl, bits=bits, bucket_size=512,
        interpret=True, tc=ppb, out_dtype=np.dtype(jnp.bfloat16),
        row_width=512 if rows else None)


@pytest.mark.parametrize("bits,ppb,rows", _GUARD_CASES)
def test_guarded_paged_decode_zeroes_dead_pages_and_keeps_live_ones(
        bits, ppb, rows):
    """ISSUE 42: with ``live``, a live entry's rows are the unguarded call's
    bit for bit and a dead entry's are all zeros whatever id it carries (a
    sentinel clipped to row 0, a stale page, the first entry of an operand's
    sequence, a run of dead entries that ends the table); ``live`` all ones
    is the call without a guard."""
    rng = np.random.default_rng(42 + bits + ppb)
    n = 8
    _, words, meta = _paged_pool(rng, n + 1, 32 * 512, bits, 512)
    call = _guard_call(bits, ppb, rows)
    ids = jnp.asarray([0, 4, 7, 0, 2, 2, 8, 0], jnp.int32)
    want = _bits_of(call(words, meta, None, ids))
    assert want.any(axis=(1, 2)).all()  # no page decodes to zeros
    for live in ([0, 1, 1, 0, 1, 0, 0, 0], [1, 0, 0, 0, 0, 1, 1, 1],
                 [0] * n):
        got = _bits_of(call(words, meta, None, ids,
                            jnp.asarray(live, jnp.int32)))
        keep = np.asarray(live, bool)
        np.testing.assert_array_equal(got[keep], want[keep])
        assert not got[~keep].any()
    np.testing.assert_array_equal(
        _bits_of(call(words, meta, None, ids, jnp.ones((n,), jnp.int32))),
        want)


def _sha(jaxpr):
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


@pytest.mark.parametrize("bits,ppb,rows", _GUARD_CASES)
def test_unguarded_paged_decode_is_the_parents_jaxpr(bits, ppb, rows):
    """ISSUE 46 changed the PAGED call's meta operand and nothing else: the
    unpaged call (no ``page_ids``: the training codec, the wire's pairs)
    traces the program the parent traced; the paged call takes the pool's
    ``(pool rows, 2, buckets)`` planes a ``(2, buckets)`` block a page, and
    ``live=None`` is the call without a branch (every cell but the ring's)."""
    n = 8
    call = _guard_call(bits, ppb, rows)
    pairs = jax.ShapeDtypeStruct((n, 32, 2), jnp.float32)
    unpaged = jax.make_jaxpr(lambda w, m: call(w, m))(
        jax.ShapeDtypeStruct((n, bits * 512), jnp.int32), pairs)
    assert _sha(unpaged) == _PARENT_UNPAGED[bits, ppb, rows]
    words = jax.ShapeDtypeStruct((n + 1, bits * 4, 128), jnp.int32)
    meta = jax.ShapeDtypeStruct((n + 1, 2, 32), jnp.float32)
    ids = jax.ShapeDtypeStruct((n,), jnp.int32)
    bare = str(jax.make_jaxpr(lambda w, m, i: call(w, m, None, i))(
        words, meta, ids))
    assert "cond[" not in bare
    # The kernel's meta blocks are pages of the pool as it lies ...
    assert bare.count("f32[2,32]") >= ppb and "f32[32,2]" not in bare
    # ... and the wire's pairs in place of the planes are refused, not relaid.
    with pytest.raises(ValueError, match="as planes"):
        jax.make_jaxpr(lambda w, m, i: call(w, m, None, i))(
            words, jax.ShapeDtypeStruct((n + 1, 32, 2), jnp.float32), ids)
    guarded = jax.make_jaxpr(lambda w, m, i, l: call(w, m, None, i, l))(
        words, meta, ids, ids)
    assert str(guarded).count("cond[") >= 2 * ppb  # a branch pair a page
    # ISSUE 51 gave the ring's call a byte unpack and the tables' none.
    assert (_sha(bare), _sha(guarded)) == _PARENT_PAGED[bits, ppb, rows]


def test_fused_add_decode_is_the_parents_jaxpr():
    """The training fabric's decompress-accumulate (``with_add``) traces the
    program the parent traced, as the plain unpaged call does."""
    n = 8
    added = jax.make_jaxpr(functools.partial(
        codec_pallas._dequantize_flat_impl, bits=4, bucket_size=512,
        interpret=True, tc=2, with_add=True))(
        jax.ShapeDtypeStruct((n, 4 * 512), jnp.int32),
        jax.ShapeDtypeStruct((n, 32, 2), jnp.float32),
        jax.ShapeDtypeStruct((n, 32 * 512), jnp.float32))
    assert _sha(added) == _PARENT_UNPAGED["add"]


def _ring_guard(lanes=4, ring=17):
    """A ring table's ``live`` as the window cells give it, flat."""
    live = np.ones((lanes, ring), np.int32)
    live[0, 3] = 0  # a slot that slid out
    live[1, 2:] = 0  # a short lane
    live[3] = 0  # a vacated lane
    return jnp.asarray(live.reshape(-1))


@pytest.mark.tpu  # compiled Mosaic lowering of the guard and the held block
def test_guarded_paged_decode_tpu():
    rng = np.random.default_rng(42)
    live = _ring_guard()
    n = live.size
    _, words, meta = _paged_pool(rng, n + 1, 256 * 512, 8, 512,
                                 interpret=False)
    ids = jnp.asarray(rng.permutation(n + 1)[:n], jnp.int32)
    kw = dict(bits=8, bucket_size=512, tc=16, out_dtype=jnp.bfloat16,
              row_width=512)
    want = _bits_of(codec_pallas.dequantize_pages(words, meta, ids, **kw))
    got = _bits_of(codec_pallas.dequantize_pages(
        words, meta, ids, live=live, **kw))
    keep = np.asarray(live, bool)
    np.testing.assert_array_equal(got[keep], want[keep])
    assert not got[~keep].any()


def _random_pool(rng, n_pool, chunks, bucket):
    """A pool of 8-bit pages of ``chunks`` chunks whose words are drawn
    whole (every level in every plane position, bit 31 set in half of them)
    and whose page 0 decodes to its levels (unit 1, minimum 0)."""
    words = rng.integers(-2**31, 2**31, (n_pool, chunks * 8 * bucket // 128,
                                         128), dtype=np.int32)
    meta = rng.standard_normal((n_pool, 2, chunks * 32)).astype(np.float32)
    meta[0, 0], meta[0, 1] = 1.0, 0.0
    return jnp.asarray(words), jnp.asarray(meta)


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("guarded", [False, True], ids=["bare", "live"])
@pytest.mark.parametrize("ppb", [1, 2])
@pytest.mark.parametrize("rb", [1, 4])
def test_byte_unpack_is_the_plane_loop_bit_for_bit(rb, ppb, guarded, k):
    """ISSUE 51: the paged read asked for ``unpack="bytes"`` (the ring's
    call) stores what the plane loop stores, bit for bit: buckets of 128 and
    512 (``rb`` 1: every swap inside one sublane tile; 4: the cells'), one
    and two pages a grid step, with and without the guard (a dead page is
    zeros), flat rows and rows of 1,024, over pages that hold all 256 levels
    and words whose sign bit is set."""
    bucket, chunks, n = rb * 128, 2, 6
    words, meta = _random_pool(np.random.default_rng(51 + rb), n + 1, chunks,
                               bucket)
    assert (np.asarray(words) < 0).any()
    ids = jnp.asarray([0, 3, 6, 3, 1, 5], jnp.int32)
    live = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.int32) if guarded else None
    call = functools.partial(
        codec_pallas._dequantize_flat_impl, bits=8, bucket_size=bucket,
        interpret=True, tc=ppb * chunks,
        out_dtype=np.dtype(jnp.bfloat16 if k > 1 else np.float32),
        row_width=128 * k if k > 1 else None)
    want = call(words, meta, None, ids, live)
    got = call(words, meta, None, ids, live, unpack="bytes")
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits_of(got), _bits_of(want))
    levels = np.unique(np.asarray(got[0], np.float32))
    np.testing.assert_array_equal(levels, np.arange(256))
    if guarded:
        assert not _bits_of(got)[[1, 4]].any()


def _shifted_values(jaxpr):
    """Values that go through ``shift_right_arithmetic`` in ``jaxpr``, its
    kernels and their branches included."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shift_right_arithmetic":
            total += int(np.prod(eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _shifted_values(sub)
    return total


@pytest.mark.parametrize("guarded", [False, True], ids=["bare", "live"])
@pytest.mark.parametrize("bits", [4, 8])
def test_byte_unpack_is_honoured_at_eight_bits_alone(bits, guarded):
    """The static argument asks; the kernel answers by the width it is
    given. At 8 bits the ring's kernel shifts at most a quarter of the
    values the plane loop shifts (its 8 rounds a value are three rounds of
    swaps over half the words and three byte shifts); at any other width
    the call traces the plane loop's jaxpr, the cells' 4-bit controls'."""
    n = 8
    call = _guard_call(bits, 2, True)
    args = [jax.ShapeDtypeStruct((n + 1, bits * 4, 128), jnp.int32),
            jax.ShapeDtypeStruct((n + 1, 2, 32), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.int32)]
    args += [args[2]] if guarded else []

    def traced(**kw):
        return jax.make_jaxpr(
            lambda w, m, i, *l: call(w, m, None, i, *l, **kw))(*args)

    planes, asked = traced(), traced(unpack="bytes")
    assert codec_pallas.unpack_taken("bytes", bits) == (
        "bytes" if bits == 8 else "planes")
    assert codec_pallas.unpack_taken("planes", bits) == "planes"
    if bits == 8:
        assert 0 < 4 * _shifted_values(asked.jaxpr) <= _shifted_values(
            planes.jaxpr)
    else:
        assert str(asked) == str(planes)


def _table_guard(lanes, pages):
    """A page table's ``live`` as ``adapter.page_live`` gives it, flat: a
    lane's first ``n_pages`` slots; a full lane, a vacated one, the others
    part way."""
    n_pages = np.arange(lanes) * 3 % (pages + 1)
    n_pages[:2] = pages, 0
    return jnp.asarray((np.arange(pages)[None] < n_pages[:, None])
                       .astype(np.int32).reshape(-1))


# The 8-bit reads ``adapter.attend_paged`` asks the byte unpack of, at the
# cells' page shapes: (tokens a page, row width, chunks a grid step, the
# kernel's name, the table's guard).
_BYTE_READS = {
    # The rings of ISSUE 51: a page and two pages a grid step.
    "trinity-ring": (256, 1024, 16, "cgx_dequantize_window", _ring_guard),
    "smallthinker-ring": (256, 512, 16, "cgx_dequantize_window", _ring_guard),
    # The page tables of ISSUE 53. Ouro: four chunks a page, four pages a
    # grid step over the 16 x 14 slots a pass reads; Olmo: fifteen chunks, a
    # page a step; granite: SmallThinker's and the latent ``c``'s geometry,
    # two pages a step, over a (64, 6) table. (The window cells' global
    # tables have their rings' page shapes.)
    "ouro-table": (32, 2048, 16, "cgx_dequantize_flat",
                   lambda: _table_guard(16, 14)),
    "olmoh-table": (64, 3840, 15, "cgx_dequantize_flat",
                    lambda: _table_guard(8, 5)),
    "granite-table": (256, 512, 16, "cgx_dequantize_flat",
                      lambda: _table_guard(64, 6)),
}


@pytest.mark.tpu  # compiled Mosaic lowering of the byte unpack
@pytest.mark.parametrize("guarded", [True, False], ids=["live", "bare"])
@pytest.mark.parametrize("read", sorted(_BYTE_READS))
def test_byte_unpack_tpu(read, guarded):
    """The kernel at the page shapes of the five cells whose reads ask for
    the byte unpack, guarded as the cells guard them and bare: the compiled
    byte unpack against the compiled plane loop, bit for bit."""
    pt, width, tc, name, guard = _BYTE_READS[read]
    rng = np.random.default_rng(51)
    live = guard()
    n = live.size
    words, meta = _random_pool(rng, n + 1, pt * width // (32 * 512), 512)
    ids = jnp.asarray(rng.permutation(n + 1)[:n], jnp.int32)
    kw = dict(bits=8, bucket_size=512, tc=tc, out_dtype=jnp.bfloat16,
              row_width=width, name=name, live=live if guarded else None)
    want = _bits_of(codec_pallas.dequantize_pages(words, meta, ids, **kw))
    got = _bits_of(codec_pallas.dequantize_pages(
        words, meta, ids, unpack="bytes", **kw))
    np.testing.assert_array_equal(got, want)
    keep = np.asarray(live, bool)
    assert want[keep].any()
    assert want[~keep].any() != guarded


# ---------------------------------------------------------------------------
# What the benchmark's cells run (ISSUE 29). The static tile functions are
# the one owner of "which lowering, which tile": each case is one kernel call
# site of a cell (PERF.md section 4, benchmark/configs/*.json), with the
# lowering and the tile the program had at PR 28 with every CGX_* unset. A
# change to a case is a change to a cell's program: say so in PERF.md.
# ---------------------------------------------------------------------------

_GPT2L_PAGE = 64 * 1280  # one layer's K or V page: 64 tokens x 20 heads x 64
_JOYAI_C, _JOYAI_KR = 256 * 512, 256 * 64  # latent / rotated-key pages
_OLMOH_PAGE = 64 * 3840  # a K or V page: 64 tokens x 30 heads x 128
_TRAIN_FLAT = (1048576, 491520, 147456)  # GPT-2 124M fusion slices / ws 4
_TRAIN_CHUNKS = (110592, 314880, 316096)  # ... whose rows end in a chunk tail


def _commit_lanes(max_batch, page_tokens):
    """Rows of a cell's one ``commit`` program: ``ServeConfig.commit_lanes``
    at the cell's lanes and page size (4, and 8 for the 96-lane cell)."""
    from torch_cgx_tpu.serving.adapter import ServeConfig

    return ServeConfig(page_tokens=page_tokens, max_batch=max_batch,
                       max_pages=8, max_seq=page_tokens,
                       ship_depth=1).commit_lanes


def _cell_cases():
    flat, chunks = "pallas_flat", "pallas_chunks"
    read = dict(bits=8, rows=512, out_dtype=jnp.bfloat16)  # 32 lanes x 16 pages
    yield "gpt2l-decode-read", "dequantize", dict(
        read, numel=_GPT2L_PAGE, row_width=1280,
    ), {"dequantize": "pallas_flat.bfloat16", "dequantize_rows": flat}, {
        "_rows_tc": 10}
    # A float16 payload: Mosaic refuses a float16 store, so float32 + a cast.
    yield "gpt2l-decode-read-f16", "dequantize", dict(
        read, numel=_GPT2L_PAGE, row_width=1280, out_dtype=jnp.float16,
    ), {"dequantize": flat, "dequantize_rows": flat}, {"_rows_tc": 10}
    yield "joyai-decode-read-c", "dequantize", dict(
        read, numel=_JOYAI_C, row_width=512,
    ), {"dequantize": "pallas_flat.bfloat16", "dequantize_rows": flat}, {
        "_rows_tc": 16}
    # 64 is not whole lanes: the kernel stores flat rows, XLA reshapes.
    yield "joyai-decode-read-kr", "dequantize", dict(
        read, numel=_JOYAI_KR, row_width=64,
    ), {"dequantize": "pallas_flat.bfloat16",
        "dequantize_rows": "xla_reshape"}, {"_rows_tc": None, "_pipe_tc": 16}
    # ISSUE 30: the same reads as the serving programs make them, through
    # ``paged_kv.gather_dequant_pages`` over a pool of 513 rows and a (32,
    # 16) page table. Where the kernel stores the rows itself it walks the
    # page table in the gathered read's tile (two pages a grid step); the
    # 64-wide ``kr`` keeps the gather and XLA's reshape.
    # The kernel notes the unpack it took beside its lowering: the plane loop
    # where the adapter builds its own read (GPT-2, the latent ``c``) ...
    paged = {"dequantize_pages": "pallas_paged.meta_planes",
             "dequantize_pages.unpack": "planes", "dequantize_rows": flat}
    # ... and bytes where the read is ``adapter.attend_paged``'s (ISSUE 53).
    attend = dict(paged, **{"dequantize_pages.unpack": "bytes"})
    yield "gpt2l-decode-pages", "dequantize_pages", dict(
        read, page=(64, 20, 64),
    ), dict(paged, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 10}
    yield "gpt2l-decode-pages-f16", "dequantize_pages", dict(
        read, page=(64, 20, 64), out_dtype=jnp.float16,
    ), dict(paged, dequantize=flat), {"_pages_tc": 10}
    yield "joyai-decode-pages-c", "dequantize_pages", dict(
        read, page=(256, 1, 512),
    ), dict(paged, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 16}
    yield "joyai-decode-pages-kr", "dequantize_pages", dict(
        read, page=(256, 1, 64),
    ), {"dequantize_pages": "xla_gather", "dequantize": "pallas_flat.bfloat16",
        "dequantize_rows": "xla_reshape"}, {"_pages_tc": None, "_pipe_tc": 16}
    # ISSUE 31: granite-serve-chat64. ``k`` and ``v`` of the four attention
    # layers: a page of 256 tokens x 8 K/V heads x 64 is the latent ``c``'s
    # geometry (256 buckets of 512 in rows of 512), read through a (64, 6)
    # page table over a pool of 385 rows: paged, two pages a grid step.
    for name in ("k", "v"):
        yield f"granite-decode-pages-{name}", "dequantize_pages", dict(
            bits=8, rows=384, out_dtype=jnp.bfloat16, page=(256, 8, 64),
            lanes=64, pool=385, unpack="bytes",
        ), dict(attend, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 16}
    # Its commits: the tails that filled in the decode loop (ISSUE 34: 4 of
    # the 64 lanes a call), a padded prompt's 2 or 4 pages in prefill_pages
    # (512 and 1,024 tokens).
    tails = _commit_lanes(64, 256)
    for tag, rows in ((f"tails-{tails}", tails), ("2", 2), ("4", 4)):
        yield f"granite-kv-commit-{tag}", "quantize", dict(
            bits=8, rows=rows, numel=_JOYAI_C,
        ), {"quantize": flat}, {"_pipe_tc": 16}
    # ISSUE 33: olmoh-serve-chat96. ``k`` and ``v`` of the four full-attention
    # layers: a page of 64 tokens x 30 heads x 128 is 480 buckets of 512,
    # fifteen whole 32-bucket chunks, rows of 3,840, read through a (96, 5)
    # page table over a pool of 481 rows: paged, a page a grid step.
    for name in ("k", "v"):
        yield f"olmoh-decode-pages-{name}", "dequantize_pages", dict(
            bits=8, rows=480, out_dtype=jnp.bfloat16, page=(64, 30, 128),
            lanes=96, pool=481, unpack="bytes",
        ), dict(attend, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 15}
    # Its commits: the tails that filled in the decode loop (ISSUE 34: 8 of
    # the 96 lanes a call), a padded prompt's 2 pages in prefill_pages (128
    # tokens).
    for rows, tc in ((_commit_lanes(96, 64), 15), (2, 15)):
        yield f"olmoh-kv-commit-{rows}", "quantize", dict(
            bits=8, rows=rows, numel=_OLMOH_PAGE,
        ), {"quantize": flat}, {"_pipe_tc": tc}
    # ISSUE 37: ling3-serve-reason128. ``c`` and ``kr`` of the one
    # latent-attention layer at the JoyAI geometry, read through a (128, 7)
    # page table over a pool of 897 rows: ``c`` paged, two pages a grid step;
    # the 64-wide ``kr`` keeps the gather and XLA's reshape.
    ling = dict(bits=8, rows=896, out_dtype=jnp.bfloat16, lanes=128, pool=897)
    yield "ling3-decode-pages-c", "dequantize_pages", dict(
        ling, page=(256, 1, 512),
    ), dict(paged, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 16}
    yield "ling3-decode-pages-kr", "dequantize_pages", dict(
        ling, page=(256, 1, 64),
    ), {"dequantize_pages": "xla_gather", "dequantize": "pallas_flat.bfloat16",
        "dequantize_rows": "xla_reshape"}, {"_pages_tc": None, "_pipe_tc": 16}
    # Its commits: the tails that filled in the decode loop (a few of the
    # 128 lanes a call), a padded prompt's 1 or 2 pages in prefill_pages (256
    # and 512 tokens).
    tails = _commit_lanes(128, 256)
    for name, numel, commits in (
        ("ling3-c", _JOYAI_C, {tails: 16, 1: 8, 2: 16}),
        ("ling3-kr", _JOYAI_KR, {tails: 4, 1: 1, 2: 2}),
    ):
        for rows, tc in commits.items():
            yield f"{name}-commit-{rows}", "quantize", dict(
                bits=8, rows=rows, numel=numel,
            ), {"quantize": flat}, {"_pipe_tc": tc}
    # ISSUE 41: smallthinker-serve-mix8k. ``k`` and ``v`` of every layer, a
    # page of 256 tokens x 4 K/V heads x 128 (the latent ``c``'s geometry
    # again). A global layer reads a (48, 36) page table over a pool of 1,729
    # rows, a window layer its (48, 17) ring over a pool of 817 under a
    # lowering counter (and a kernel name) of its own: both paged, two pages
    # a grid step.
    small = dict(bits=8, out_dtype=jnp.bfloat16, page=(256, 4, 128), lanes=48,
                 unpack="bytes")
    yield "smallthinker-decode-pages-global", "dequantize_pages", dict(
        small, rows=48 * 36, pool=1729,
    ), dict(attend, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 16}
    yield "smallthinker-decode-pages-window", "dequantize_pages", dict(
        small, rows=48 * 17, pool=817, window=True,
    ), {"dequantize_pages.window": "pallas_paged.meta_planes",
        "dequantize_pages.window.unpack": "bytes", "dequantize_rows": flat,
        "dequantize": "pallas_flat.bfloat16"}, {"_pages_tc": 16}
    # Its commits: the tails that filled in the decode loop (4 of the 48
    # lanes a call); a padded prompt's 2 or 32 pages in prefill_pages (512
    # and 8,192 tokens), of which a window layer writes the last 18.
    for rows in (_commit_lanes(48, 256), 2, 18, 32):
        yield f"smallthinker-kv-commit-{rows}", "quantize", dict(
            bits=8, rows=rows, numel=_JOYAI_C,
        ), {"quantize": flat}, {"_pipe_tc": 16}
    # ISSUE 45: trinity-serve-agent64. ``k`` and ``v`` of every layer, a
    # page of 256 tokens x 8 K/V heads x 128: 262,144 values, 512 buckets,
    # sixteen whole chunks, two buckets a token, rows of 1,024. The global
    # layer reads a (64, 19) page table over a pool of 1,217 rows, a window
    # layer its (64, 17) ring over a pool of 1,089: both paged, a page a grid
    # step.
    trinity = dict(bits=8, out_dtype=jnp.bfloat16, page=(256, 8, 128),
                   lanes=64, unpack="bytes")
    yield "trinity-decode-pages-global", "dequantize_pages", dict(
        trinity, rows=64 * 19, pool=1217,
    ), dict(attend, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 16}
    yield "trinity-decode-pages-window", "dequantize_pages", dict(
        trinity, rows=64 * 17, pool=1089, window=True,
    ), {"dequantize_pages.window": "pallas_paged.meta_planes",
        "dequantize_pages.window.unpack": "bytes", "dequantize_rows": flat,
        "dequantize": "pallas_flat.bfloat16"}, {"_pages_tc": 16}
    # Its commits: the tails that filled in the decode loop (4 of the 64
    # lanes a call); a padded prompt's 4 or 16 pages in prefill_pages (1,024
    # and 4,096 tokens), all of them within a ring.
    for rows in (_commit_lanes(64, 256), 16):
        yield f"trinity-kv-commit-{rows}", "quantize", dict(
            bits=8, rows=rows, numel=256 * 8 * 128,
        ), {"quantize": flat}, {"_pipe_tc": 16}
    # ISSUE 48: xing4-serve-doc16k. ``c`` and ``kr`` of all six layers at the
    # JoyAI geometry, read through a (32, 69) page table over a pool of 2,209
    # rows: ``c`` paged, two pages a grid step; the 64-wide ``kr`` keeps the
    # gather and XLA's reshape.
    xing = dict(bits=8, rows=32 * 69, out_dtype=jnp.bfloat16, lanes=32,
                pool=2209)
    yield "xing4-decode-pages-c", "dequantize_pages", dict(
        xing, page=(256, 1, 512),
    ), dict(paged, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 16}
    yield "xing4-decode-pages-kr", "dequantize_pages", dict(
        xing, page=(256, 1, 64),
    ), {"dequantize_pages": "xla_gather", "dequantize": "pallas_flat.bfloat16",
        "dequantize_rows": "xla_reshape"}, {"_pages_tc": None, "_pipe_tc": 16}
    # ISSUE 52, ISSUE 53: ouro-serve-answer16. ``k`` and ``v`` of all 48
    # layers, a page of 32 tokens x 16 heads x 128: 65,536 values, four whole
    # chunks, rows of 2,048, read a pass through a (16, 14) page table over a
    # pool of 4 x 225 rows: paged, four pages a grid step, the byte unpack.
    for name in ("k", "v"):
        yield f"ouro-decode-pages-{name}", "dequantize_pages", dict(
            bits=8, rows=16 * 14, out_dtype=jnp.bfloat16, page=(32, 16, 128),
            lanes=16, pool=900, unpack="bytes",
        ), dict(attend, dequantize="pallas_flat.bfloat16"), {"_pages_tc": 16}
    # Its commits: the tails that filled in the decode loop (4 of the 32
    # lanes a call), a padded prompt's 32 or 64 pages in prefill_pages (8,192
    # and 16,384 tokens).
    tails = _commit_lanes(32, 256)
    for name, numel, commits in (
        ("xing4-c", _JOYAI_C, {tails: 16, 32: 16, 64: 16}),
        ("xing4-kr", _JOYAI_KR, {tails: 4, 32: 16, 64: 16}),
    ):
        for rows, tc in commits.items():
            yield f"{name}-commit-{rows}", "quantize", dict(
                bits=8, rows=rows, numel=numel,
            ), {"quantize": flat}, {"_pipe_tc": tc}
    # Page commits: the tails that filled in the decode loop (ISSUE 34: 4 of
    # the 32 lanes a call, where every lane's 32 rows were quantized), a
    # padded prompt's pages in prefill_pages (704 and 896 tokens; 2,048 and
    # 3,072).
    for name, numel, commits in (
        ("gpt2l", _GPT2L_PAGE,
         {_commit_lanes(32, 64): 10, 11: 11, 14: 14}),
        ("joyai-c", _JOYAI_C, {_commit_lanes(32, 256): 16, 8: 16, 12: 16}),
        ("joyai-kr", _JOYAI_KR, {_commit_lanes(32, 256): 4, 8: 8, 12: 12}),
    ):
        for rows, tc in commits.items():
            yield f"{name}-commit-{rows}", "quantize", dict(
                bits=8, rows=rows, numel=numel,
            ), {"quantize": flat}, {"_pipe_tc": tc}
    # gpt2s-dp4-q4: stage-1 quantize of the (ws, slice / ws) rows, the fused
    # epilogue of the two slices over CGX_SRA_EPILOGUE_MIN_ELEMS (the staged
    # one's stage-2 quantize of one row for the others), the all-gather
    # leg's decode of (ws, chunk).
    for numel, tc, tc_reduce in zip(_TRAIN_FLAT, (16, 15, 12), (8, 6, None)):
        geo = dict(bits=4, rows=4, numel=numel)
        yield f"train-quantize-{numel}", "quantize", geo, {
            "quantize": flat}, {"_pipe_tc": tc}
        yield f"train-allgather-decode-{numel}", "dequantize", dict(
            geo, out_dtype=jnp.float32), {"dequantize": flat}, {"_pipe_tc": tc}
        if tc_reduce:
            for kind in ("sra_epilogue", "sra_epilogue_key"):
                yield f"train-{kind}-{numel}", kind, geo, {
                    "sra_epilogue": "pallas_fused"}, {"_reduce_tc": tc_reduce}
    yield "train-stage2-quantize-147456", "quantize", dict(
        bits=4, rows=1, numel=147456), {"quantize": flat}, {"_pipe_tc": 9}
    for numel, tc1 in zip(_TRAIN_CHUNKS, (6, 16, 16)):
        geo = dict(bits=4, rows=4, numel=numel)
        yield f"train-quantize-{numel}", "quantize", geo, {
            "quantize": chunks}, {"_chunks_tc": 16}
        yield f"train-stage2-quantize-{numel}", "quantize", dict(
            geo, rows=1), {"quantize": chunks}, {"_chunks_tc": tc1}
        yield f"train-allgather-decode-{numel}", "dequantize", dict(
            geo, out_dtype=jnp.float32), {"dequantize": chunks}, {
            "_chunks_tc": 16}


def _trace_cell_call(kind, *, bits, rows, numel=None, bucket=512, **kw):
    """``jax.eval_shape`` of one wrapper call: nothing compiled or run."""
    if kind == "dequantize_pages":
        from torch_cgx_tpu.ops import paged_kv

        spec = paged_kv.PageSpec(*kw["page"], bits, bucket)
        lanes = kw.get("lanes", 32)
        pool = jax.eval_shape(
            lambda: paged_kv.empty_pool(kw.get("pool", 513), spec))
        return jax.eval_shape(
            lambda pool, table: paged_kv.gather_dequant_pages(
                pool, table, spec, kw["out_dtype"],
                window=kw.get("window", False),
                unpack=kw.get("unpack", "planes")),
            pool, jax.ShapeDtypeStruct((lanes, rows // lanes), jnp.int32),
        )
    if kind == "quantize":
        return jax.eval_shape(
            lambda x: codec_pallas.quantize_batch(
                x, bits, bucket, interpret=True),
            jax.ShapeDtypeStruct((rows, numel), jnp.float32),
        )
    nb = codec.num_buckets(numel, bucket)

    def q_of(packed, meta):
        return codec.QTensor(
            packed=packed, meta=meta,
            residual=jnp.zeros((rows, 0), jnp.float32), numel=numel,
            bits=bits, bucket_size=bucket, dtype=np.dtype(np.float32),
        )

    shapes = [
        jax.ShapeDtypeStruct((rows, nb * bucket * bits // 32), jnp.uint32),
        jax.ShapeDtypeStruct((rows, nb, 2), jnp.float32),
    ]
    if kind == "dequantize":
        return jax.eval_shape(
            lambda p, m: codec_pallas.dequantize_batch(
                q_of(p, m), interpret=True, **kw),
            *shapes,
        )
    assert codec_pallas.supports_reduce(q_of(*shapes), rows)
    key = jax.random.PRNGKey(0) if kind == "sra_epilogue_key" else None
    return jax.eval_shape(
        lambda p, m, raw, own: codec_pallas.sra_epilogue_batch(
            q_of(p, m), raw_row=raw, own_idx=own, key=key, interpret=True),
        *shapes, jax.ShapeDtypeStruct((numel,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )


@pytest.mark.parametrize(
    "kind,geo,lowering,tiles",
    [pytest.param(*case[1:], id=case[0]) for case in _cell_cases()],
)
def test_cells_lowering_and_tile(kind, geo, lowering, tiles, monkeypatch):
    from torch_cgx_tpu.utils.logging import metrics

    seen = {}  # conftest clears every CGX_*
    if kind == "dequantize_pages":  # the serving read asks the backend
        monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
        geo = dict(geo, numel=int(np.prod(geo["page"])))
    for name in ("_pipe_tc", "_rows_tc", "_reduce_tc", "_chunks_tc",
                 "_pages_tc"):
        def spy(*a, _fn=getattr(codec_pallas, name), _name=name):
            seen.setdefault(_name, []).append(_fn(*a))
            return seen[_name][-1]

        monkeypatch.setattr(codec_pallas, name, spy)
    assert codec_pallas.supports(geo["numel"], geo["bits"], 512, False)
    metrics.reset()
    out = _trace_cell_call(kind, **geo)
    if kind == "dequantize_pages":
        pt, h, d = geo["page"]
        lanes = geo.get("lanes", 32)
        assert out.shape == (lanes, geo["rows"] // lanes * pt, h * d)
        assert out.dtype == geo["out_dtype"]
    ledger = {
        k[len("cgx.codec.lowering."):]: v
        for k, v in metrics.snapshot("cgx.codec.lowering.").items()
    }
    assert ledger == {f"{site}.{low}": 1 for site, low in lowering.items()}
    assert {k: seen[k] for k in tiles} == {k: [v] for k, v in tiles.items()}


def _cell_geometries():
    """(rows, numel, bits) of every cell case above, once each."""
    return sorted({
        (c[2]["rows"], c[2]["numel"], c[2]["bits"]) for c in _cell_cases()
        if "numel" in c[2]
    })


def test_rows_tc_refuses_what_no_block_can_hold():
    """``_rows_tc`` answers None (the caller lets XLA reshape) for a row
    that is not whole 128-lane columns and for a chunk count none of whose
    divisors makes a block whole rows; any tile it does give divides the
    chunks, holds whole rows, and fills whole sublane tiles of the store."""
    f32, bf16 = np.dtype(np.float32), np.dtype(jnp.bfloat16)
    for width in (64, 192, 1280 + 64):
        assert codec_pallas._rows_tc(2560, 512, width, bf16) is None
    # 1,280-wide bfloat16 rows need blocks of 5 chunks: 7 and 16 chunks
    # have no such divisor under the cap, 10 has.
    assert codec_pallas._rows_tc(7, 512, 1280, bf16) is None
    assert codec_pallas._rows_tc(16, 512, 1280, bf16) is None
    assert codec_pallas._rows_tc(10, 512, 1280, bf16) == 10
    for rows, numel, _ in _cell_geometries():
        n_chunks, tail = divmod(rows * numel, 32 * 512)
        for width in (128, 512, 1280):
            for store in (f32, bf16):
                tc = codec_pallas._rows_tc(n_chunks, 512, width, store)
                if tail or tc is None:
                    continue
                assert n_chunks % tc == 0 and tc <= 16
                assert tc * 32 * 512 % (width * 32 // store.itemsize) == 0


@pytest.mark.parametrize("forced", [None, "4"])
def test_reduce_tc_is_pipe_tc_where_vmem_allows(forced, monkeypatch):
    """The fused epilogue's requantize draws its stochastic rounding per
    grid step, so its grid must be the staged stage-2 quantize's
    (``_pipe_tc`` over the reduced row) wherever the ws-way block fits the
    VMEM budget, and a divisor of the row's chunks within it elsewhere —
    with ``CGX_PALLAS_TILE_CHUNKS`` forced as without."""
    if forced:
        monkeypatch.setenv("CGX_PALLAS_TILE_CHUNKS", forced)
    chunk_rows = {
        numel // (32 * 512) for _, numel, _ in _cell_geometries()
        if numel % (32 * 512) == 0
    }
    assert {64, 30, 9} <= chunk_rows  # the training cell's flat slices
    for c_r in sorted(chunk_rows):
        for ws in (2, 4, 8, 16):
            budget = codec_pallas.MAX_REDUCE_BLOCK_ELEMS // (2 * ws * 32 * 512)
            staged = codec_pallas._pipe_tc(c_r, 512)
            tc = codec_pallas._reduce_tc(c_r, 512, ws)
            if staged <= budget:
                assert tc == staged, (c_r, ws)
            else:
                assert c_r % tc == 0 and 1 <= tc <= max(1, budget), (c_r, ws)
