"""Host codec (numpy + native C++) parity with the JAX codec oracle.

The torch bridge stages DDP buckets through this codec, so its wire bytes
must be byte-identical to what the JAX/Pallas path produces (same format as
the reference's compressor wire, compressor.cc:401-419)."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from torch_cgx_tpu.ops import codec, codec_host
from torch_cgx_tpu.runtime import native

CASES = [
    (16, 2, 64),
    (77, 8, 512),
    (130, 2, 64),
    (1000, 3, 64),
    (4096, 1, 128),
    (10_000, 4, 512),
    (65_536, 6, 2048),
]


def _datasets(n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        np.linspace(-3.0, 5.0, n).astype(np.float32),
        rng.standard_normal(n).astype(np.float32),
        np.full(n, 2.5, np.float32),  # constant buckets — exactness oracle
    ]


def _numpy_quantize(x, bits, bucket, **kw):
    """Force the pure-numpy path regardless of the native build."""
    orig = codec_host._native
    codec_host._native = lambda: None
    try:
        return codec_host.quantize(x, bits, bucket, **kw)
    finally:
        codec_host._native = orig


@pytest.mark.parametrize("n,bits,bucket", CASES)
def test_wire_bytes_match_jax(n, bits, bucket):
    for x in _datasets(n):
        q_np = _numpy_quantize(x, bits, bucket)
        q_jax = codec.quantize(jnp.asarray(x), bits, bucket)
        np.testing.assert_array_equal(q_np.packed, np.asarray(q_jax.packed))
        np.testing.assert_array_equal(q_np.meta, np.asarray(q_jax.meta))


@pytest.mark.parametrize("n,bits,bucket", CASES)
def test_native_matches_numpy(n, bits, bucket):
    if not native.available():
        pytest.skip("native core not built (no g++)")
    for x in _datasets(n, seed=1):
        q_np = _numpy_quantize(x, bits, bucket)
        packed, meta = native.quantize_f32(x, bits, bucket)
        np.testing.assert_array_equal(q_np.packed, packed)
        np.testing.assert_array_equal(q_np.meta, meta)
        d_np = codec_host.dequantize(q_np, out_dtype=np.float32)
        d_nat = native.dequantize_f32(packed, meta, bits, bucket, n)
        np.testing.assert_array_equal(d_np, d_nat)


def test_decode_within_one_ulp_of_xla():
    n, bits, bucket = 10_000, 4, 512
    x = np.linspace(-3, 5, n).astype(np.float32)
    q = _numpy_quantize(x, bits, bucket)
    d_host = codec_host.dequantize(q, out_dtype=np.float32)
    d_jax = np.asarray(
        codec.dequantize(codec.quantize(jnp.asarray(x), bits, bucket),
                         out_dtype=jnp.float32)
    )
    ulp = np.spacing(np.abs(d_jax).astype(np.float32))
    assert np.all(np.abs(d_host - d_jax) <= ulp)


def test_roundtrip_error_bound():
    n, bits, bucket = 50_000, 4, 512
    x = np.linspace(0.0, 1.0, n).astype(np.float32)
    q = _numpy_quantize(x, bits, bucket)
    out = codec_host.dequantize(q, out_dtype=np.float32)
    # per-bucket range / (2^bits - 1) is the max quantization error
    step = (x[bucket] - x[0]) / ((1 << bits) - 1)
    assert np.abs(out - x).max() <= step


def test_constant_buckets_exact():
    x = np.full(2048, -1.25, np.float32)
    for bits in (1, 2, 4, 8):
        q = _numpy_quantize(x, bits, 512)
        np.testing.assert_array_equal(
            codec_host.dequantize(q, out_dtype=np.float32), x
        )


def test_serialization_roundtrip():
    n, bits, bucket = 1000, 3, 64
    x = np.random.default_rng(2).standard_normal(n).astype(np.float32)
    q = _numpy_quantize(x, bits, bucket)
    buf = q.to_bytes()
    _, _, _, total = codec_host.wire_layout(n, bits, bucket, np.float32)
    assert buf.nbytes == total == q.wire_bytes()
    q2 = codec_host.from_bytes(buf, n, bits, bucket, np.float32)
    np.testing.assert_array_equal(q2.packed, q.packed)
    np.testing.assert_array_equal(q2.meta, q.meta)
    np.testing.assert_array_equal(
        codec_host.dequantize(q2, out_dtype=np.float32),
        codec_host.dequantize(q, out_dtype=np.float32),
    )


def test_serialization_padding_crosses_group_boundary():
    """Regression: bucket padding that crosses a 32-lane group boundary must
    be framed identically by wire_layout (receiver) and quantize (sender)."""
    n, bits, bucket = 10_000, 4, 512  # padded 10240 vs main 10000
    x = np.linspace(-3, 5, n).astype(np.float32)
    q = _numpy_quantize(x, bits, bucket)
    buf = q.to_bytes()
    assert buf.nbytes == codec_host.wire_layout(n, bits, bucket, np.float32)[3]
    q2 = codec_host.from_bytes(buf, n, bits, bucket, np.float32)
    np.testing.assert_array_equal(
        codec_host.dequantize(q2, out_dtype=np.float32),
        codec_host.dequantize(q, out_dtype=np.float32),
    )


def test_skip_incomplete_buckets_residual():
    n, bits, bucket = 1000, 4, 512  # 488-value tail -> residual
    x = np.random.default_rng(3).standard_normal(n).astype(np.float32)
    q = _numpy_quantize(x, bits, bucket, skip_incomplete_buckets=True)
    assert q.residual.shape[0] == n % bucket
    out = codec_host.dequantize(q, out_dtype=np.float32)
    np.testing.assert_array_equal(out[-(n % bucket):], x[-(n % bucket):])
    buf = q.to_bytes()
    q2 = codec_host.from_bytes(
        buf, n, bits, bucket, np.float32, skip_incomplete=True
    )
    np.testing.assert_array_equal(
        codec_host.dequantize(q2, out_dtype=np.float32), out
    )


def test_add_accumulate():
    n = 5000
    rng = np.random.default_rng(4)
    x = rng.standard_normal(n).astype(np.float32)
    acc = rng.standard_normal(n).astype(np.float32)
    q = _numpy_quantize(x, 4, 512)
    fused = codec_host.dequantize(q, add_to=acc.copy(), out_dtype=np.float32)
    plain = acc + codec_host.dequantize(q, out_dtype=np.float32)
    np.testing.assert_allclose(fused, plain, rtol=0, atol=0)


def test_native_executor_async():
    if not native.available():
        pytest.skip("native core not built (no g++)")
    rng = np.random.default_rng(5)
    ex = native.NativeExecutor(2)
    try:
        xs = [rng.standard_normal(20_000).astype(np.float32) for _ in range(4)]
        jobs = []
        for x in xs:
            packed, meta = native.quantize_f32(x[:1], 4, 512)  # shape probe
            packed = np.empty(codec.packed_words(-(-20_000 // 512) * 512, 4),
                              np.uint32)
            meta = np.empty((-(-20_000 // 512), 2), np.float32)
            jobs.append((ex.submit_quantize(x, 4, 512, packed, meta),
                         x, packed, meta))
        for jid, x, packed, meta in jobs:
            ex.wait(jid)
            ref_p, ref_m = native.quantize_f32(x, 4, 512)
            np.testing.assert_array_equal(packed, ref_p)
            np.testing.assert_array_equal(meta, ref_m)
    finally:
        ex.close()


def test_stochastic_rounding_unbiased():
    n, bits, bucket = 100_000, 2, 512
    x = np.random.default_rng(6).uniform(-1, 1, n).astype(np.float32)
    rng = np.random.default_rng(7)
    acc = np.zeros(n, np.float64)
    reps = 30
    for _ in range(reps):
        q = _numpy_quantize(x, bits, bucket, stochastic=True, rng=rng)
        acc += codec_host.dequantize(q, out_dtype=np.float32)
    mean = (acc / reps).astype(np.float32)
    # unbiased: mean of stochastic decodes approaches x much closer than the
    # deterministic quantization step
    step = 2.0 / ((1 << bits) - 1)
    assert np.abs(mean - x).mean() < step / 4


# Slow tier: exhaustive three-way fuzz (~20 s); the pinned-combo
# byte-identity tests above stay in tier-1.
@pytest.mark.slow
def test_fuzz_three_way_byte_identity():
    """Seeded fuzz over the config space: every (n, bits, bucket) combo
    must produce BYTE-IDENTICAL wire from all three implementations
    (numpy host, native C++, XLA codec) and decode consistently — the
    fixed CASES list can't cover the odd-size / extreme-value corners
    the bridge actually sees (reference sweep: test_cgx.py:69-93)."""
    rng = np.random.default_rng(0xC6)
    combos = []
    for bits in range(1, 9):
        for _ in range(2):
            n = int(rng.integers(1, 50_000))
            bucket = int(rng.choice([1, 32, 100, 512, 1024, 100_000]))
            combos.append((n, bits, bucket))
    for n, bits, bucket in combos:
        from conftest import fuzz_operand

        kind = int(rng.integers(0, 3))
        x = fuzz_operand(rng, n, kind)
        q_np = _numpy_quantize(x, bits, bucket)  # pure-numpy path, forced
        q_jax = codec.quantize(jnp.asarray(x), bits, bucket)
        ctx = (n, bits, bucket, int(kind))
        np.testing.assert_array_equal(
            q_np.packed, np.asarray(q_jax.packed), err_msg=str(ctx))
        np.testing.assert_array_equal(
            np.asarray(q_np.meta, np.float32).reshape(-1),
            np.asarray(q_jax.meta, np.float32).reshape(-1),
            err_msg=str(ctx))
        if native.available():
            p_nat, m_nat = native.quantize_f32(x, bits, bucket)
            np.testing.assert_array_equal(q_np.packed, p_nat, err_msg=str(ctx))
            np.testing.assert_array_equal(
                np.asarray(q_np.meta, np.float32).reshape(-1),
                m_nat.reshape(-1), err_msg=str(ctx))
        # Decode consistency across all three paths (the numpy dequantize
        # is forced off the native core the same way _numpy_quantize is).
        orig = codec_host._native
        codec_host._native = lambda: None
        try:
            d_np = codec_host.dequantize(q_np, out_dtype=np.float32)
        finally:
            codec_host._native = orig
        d_jax = np.asarray(codec.dequantize(q_jax, out_dtype=jnp.float32))
        # Same cross-impl decode contract as test_decode_within_one_ulp_of
        # _xla: an FMA-contracting XLA build may differ by an ulp.
        ulp = np.abs(d_np.view(np.int32) - d_jax.view(np.int32))
        assert ulp.max() <= 1, (ctx, int(ulp.max()))
        if native.available():
            d_nat = native.dequantize_f32(p_nat, m_nat, bits, bucket, n)
            np.testing.assert_array_equal(d_np, d_nat, err_msg=str(ctx))


def test_native_build_is_keyed_on_source_and_flags(monkeypatch):
    """The .so lives in the checkout's cache dir under a name derived from
    the source bytes and the flags — never beside the source, where a
    library built on another machine would ride along in a copied tree."""
    from torch_cgx_tpu.runtime import native
    from torch_cgx_tpu.utils import entry

    path = native._lib_path()
    assert path.parent == entry.cache_root() / "native"
    assert path.parent != native._SRC.parent.parent
    assert "-march=native" not in native._FLAGS
    monkeypatch.setattr(native, "_FLAGS", native._FLAGS + ("-DX=1",))
    assert native._lib_path() != path
    st = native.status()
    assert st["host_codec"] in ("native", "numpy") and len(st) == 2
