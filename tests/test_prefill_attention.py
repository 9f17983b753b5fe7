"""The attention of a whole prompt: ``ops/prefill_attention.py``'s kernel
(interpret mode on the CPU) against the query-block loop it replaces and
against a plain float32 softmax over the full mask, the rule that picks
between kernel and loop, and what the rule leaves of a one-block prompt's
jaxpr."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_cgx_tpu.models import mla_moe, window_moe as wm
from torch_cgx_tpu.models.mla_moe import MlaMoeConfig
from torch_cgx_tpu.models.window_moe import WindowMoeConfig
from torch_cgx_tpu.ops import dispatch, prefill_attention as pfa
from torch_cgx_tpu.utils.logging import metrics


def _operands(seed, s, h, hk, d, dv, dr, dtype):
    """Scores that spread by about 1.5, as both cells' weights draw them."""
    rng = np.random.default_rng(seed)

    def arr(*shape, std=1.0):
        return jnp.asarray(rng.standard_normal(shape) * std, dtype)

    q = arr(1, s, h, d, std=1.5 ** 0.5)
    k, v = arr(1, s, hk, d), arr(1, s, hk, dv)
    if not dr:
        return q, k, v, None, None
    return q, k, v, arr(1, s, h, dr, std=1.5 ** 0.5), arr(1, s, dr)


def _plain(q, k, v, q_rope, k_rope, window, scale):
    """float64 scores over every pair, the full mask, one softmax."""
    f = lambda x: np.asarray(x, np.float64)  # noqa: E731
    _, s, h, _ = q.shape
    g = h // k.shape[2]
    kk, vv = np.repeat(f(k), g, axis=2), np.repeat(f(v), g, axis=2)
    scores = np.einsum("bqhd,bkhd->bhqk", f(q), kk)
    if k_rope is not None:
        scores += np.einsum("bqhr,bkr->bhqk", f(q_rope), f(k_rope))
    scores *= scale
    back = np.arange(s)[:, None] - np.arange(s)[None, :]
    seen = back >= 0
    if window:
        seen &= back < window
    scores = np.where(seen, scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, vv).reshape(1, s, -1)


# name: (S, H, Hk, d, dv, d_rope, window, queries a tile, keys a block,
# dtype). 128-wide heads are the cells'; the narrow ones the rehearsals'.
CASES = {
    "grouped 7 to 1 at head 128, causal": (
        320, 7, 1, 128, 128, 0, 0, 128, 128, jnp.bfloat16),
    "grouped, two K/V heads, float32": (
        96, 4, 2, 16, 16, 0, 0, 32, 16, jnp.float32),
    "a prompt shorter than the window": (
        48, 4, 2, 16, 16, 0, 64, 16, 16, jnp.float32),
    "a prompt as long as the window": (
        64, 4, 2, 16, 16, 0, 64, 16, 16, jnp.float32),
    "a prompt of two windows": (
        128, 4, 2, 16, 16, 0, 64, 32, 16, jnp.float32),
    "a window no multiple of the key block": (
        120, 4, 2, 16, 16, 0, 40, 24, 16, jnp.float32),
    "a window at head 128, bfloat16": (
        384, 2, 1, 128, 128, 0, 160, 128, 128, jnp.bfloat16),
    "two-part keys 128 + 64, values of 128": (
        256, 2, 2, 128, 128, 64, 0, 128, 128, jnp.bfloat16),
    "two-part keys at the rehearsal's widths": (
        80, 4, 4, 16, 16, 8, 0, 32, 16, jnp.float32),
    "tiles longer than the blocks, S no multiple of either": (
        100, 4, 2, 16, 16, 0, 0, 48, 16, jnp.float32),
    "blocks longer than the tiles": (
        100, 4, 2, 16, 16, 0, 24, 16, 40, jnp.float32),
    "one tile, one block": (
        24, 2, 2, 16, 16, 0, 0, 0, 0, jnp.float32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_the_loop_and_a_plain_softmax(case):
    """float32: the kernel, the loop and the plain softmax agree to 1e-5.
    bfloat16 operands: kernel and loop each within 2e-2 of the plain
    softmax over the same rounded operands (the probabilities' rounding),
    and the kernel, which rounds its accumulator once, no farther from it
    than the loop plus a rounding of the output."""
    s, h, hk, d, dv, dr, window, tq, tk, dtype = CASES[case]
    ops = _operands(len(case), s, h, hk, d, dv, dr, dtype)
    scale = 1.0 / np.sqrt(d + dr)
    got = np.asarray(pfa.prefill_attention_pallas(
        *ops, window=window, scale=scale, tq=tq, tk=tk, interpret=True
    ), np.float64)
    loop = np.asarray(pfa.prefill_attention_xla(
        *ops, window=window, scale=np.float32(scale), q_block=16,
        dtype=dtype), np.float64)
    want = _plain(*ops, window, scale)
    assert got.shape == loop.shape == want.shape == (1, s, h * dv)
    if dtype == jnp.float32:
        assert np.abs(got - want).max() < 1e-5
        assert np.abs(loop - want).max() < 1e-5
    else:
        assert np.abs(got - want).max() < 2e-2
        assert np.abs(got - want).mean() <= (
            np.abs(loop - want).mean() + 2.0 ** -9 * np.abs(want).mean())


def test_a_right_padded_prompt():
    """A prompt padded on the right to the program's length: the real rows
    are the unpadded prompt's, the padded rows finite, whatever the padded
    positions hold short of infinity."""
    real, s = 70, 96
    q, k, v, _, _ = _operands(3, s, 4, 2, 16, 16, 0, jnp.float32)
    big = lambda x: x.at[:, real:].multiply(1e3)  # noqa: E731
    kw = dict(window=40, scale=0.25, tq=32, tk=16, interpret=True)
    padded = pfa.prefill_attention_pallas(big(q), big(k), big(v), **kw)
    alone = pfa.prefill_attention_pallas(
        q[:, :real], k[:, :real], v[:, :real], **kw)
    assert np.allclose(np.asarray(padded[:, :real]), np.asarray(alone),
                       atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(padded)))


def test_the_work_list_holds_the_blocks_a_tile_can_see():
    """Every (tile, block) pair with a visible (query, key) pair of the
    prompt in it, once, a tile's blocks in order; ``EDGE`` exactly where a
    pair is masked; ``FIRST`` and ``LAST`` at a tile's ends, and the block
    of a tile's last query (the prompt's, in a tile that ends past it)
    last: no block past the prompt's keys."""
    for s, tq, tk, window in [(8192, 256, 1024, 0), (8192, 256, 1024, 4096),
                              (3072, 1024, 1024, 0), (120, 24, 16, 40),
                              (100, 16, 40, 24), (64, 64, 64, 0),
                              (384, 256, 384, 0), (100, 48, 16, 0)]:
        tile, block, flags = pfa.work_list(s, tq, tk, window)
        nq, nk = -(-s // tq), -(-s // tk)
        back = np.arange(nq * tq)[:, None] - np.arange(nk * tk)[None, :]
        seen = (back >= 0) & ((back < window) if window else True)
        cells = seen.reshape(nq, tq, nk, tk)
        some, every = cells.any((1, 3)), cells.all((1, 3))
        # Rows and keys past the prompt are nobody's: they ask for no block.
        real = (seen & (np.arange(nq * tq)[:, None] < s)
                & (np.arange(nk * tk)[None, :] < s))
        asked = real.reshape(nq, tq, nk, tk).any((1, 3))
        assert sorted(zip(tile, block)) == list(zip(*np.nonzero(asked)))
        assert np.array_equal(asked, some)
        assert list(zip(tile, block)) == sorted(zip(tile, block))
        for t, j, f in zip(tile, block, flags):
            assert bool(f & pfa.EDGE) == (not every[t, j])
            mine = block[tile == t]
            assert bool(f & pfa.FIRST) == (j == mine[0])
            assert bool(f & pfa.LAST) == (j == mine[-1])
            assert mine[-1] == min((t + 1) * tq - 1, s - 1) // tk
    # A long prompt's global layer contracts the causal half and a diagonal
    # of edges; a window layer the band.
    assert len(pfa.work_list(8192, 256, 1024, 0)[0]) == 144
    assert len(pfa.work_list(8192, 256, 1024, 4096)[0]) == 120


def test_takes_kernel_is_the_shapes_alone():
    both = [(28, 4, 128, 128, 0, 4096), (28, 4, 128, 128, 0, 0),
            (32, 32, 128, 128, 64, 0)]
    for widths in both:
        assert pfa.takes_kernel(8192, 512, *widths)
        assert pfa.takes_kernel(513, 512, *widths)
        assert not pfa.takes_kernel(512, 512, *widths)  # one block
        assert not pfa.takes_kernel(193, 512, *widths)
    # Compiled, heads of whole lanes alone; interpreted, any width.
    assert not pfa.takes_kernel(64, 16, 4, 2, 16, 16)
    assert pfa.takes_kernel(64, 16, 4, 2, 16, 16, compiled=False)
    assert not pfa.takes_kernel(64, 16, 4, 4, 16, 16, 8)
    assert pfa.takes_kernel(64, 16, 4, 4, 16, 16, 8, compiled=False)
    # No grouped queries and no window over two-part keys.
    assert not pfa.supports(8, 4, 128, 128, 64, 0)
    assert not pfa.supports(8, 8, 128, 128, 64, 256)
    assert pfa.heads_a_step(28, 4, 0) == 7
    assert pfa.heads_a_step(32, 32, 64) == 2
    assert pfa.tiles(8192, 7) == (256, 1024)
    assert pfa.tiles(3072, 2) == (1024, 1024)
    assert pfa.tiles(600, 2) == (640, 640)


def _sha(x) -> str:
    return hashlib.sha256(str(x).encode()).hexdigest()[:16]


def _window_call(s, window, q_block):
    cfg = WindowMoeConfig(
        vocab_size=64, n_layer=1, d_model=64, n_head=28, n_kv_head=4,
        d_head=128, n_experts=4, top_k=2, d_expert=8, windows=(window,),
        rotated=(False,), q_block=q_block)
    q = jax.ShapeDtypeStruct((1, s, 28, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, s, 4, 128), jnp.float32)
    return (lambda q, k, v: wm.attend_blocks(cfg, q, k, v, window)), (q, k, k)


def _latent_call(s, window, q_block):
    cfg = MlaMoeConfig(
        vocab_size=64, n_layer=1, d_model=64, n_head=32, q_lora_rank=16,
        kv_lora_rank=512, d_nope=128, d_rope=64, d_v=128, d_ff=32,
        n_experts=4, top_k=2, d_expert=8, q_block=q_block)
    bf, f32 = jnp.bfloat16, jnp.float32
    ops = ({"kv_b": jax.ShapeDtypeStruct((512, 32 * 256), bf)},
           jax.ShapeDtypeStruct((1, s, 32, 128), bf),
           jax.ShapeDtypeStruct((1, s, 32, 64), bf),
           jax.ShapeDtypeStruct((1, s, 512), f32),
           jax.ShapeDtypeStruct((1, s, 64), f32))
    return (lambda *a: mla_moe.attend_expanded(cfg, *a)), ops


# The first 16 hex digits of the SHA-256 of the jaxpr, as text, that each
# model's prefill attention traced to at the parent of PR 43 (the loop in the
# model's own file), at the cells' widths. A prompt of one query block has to
# trace to exactly that, on the chip's path too.
PARENT_JAXPR = {
    ("window", 512, 4096): "2dfa0481f62556d7",
    ("window", 512, 0): "a855ee38052dd24a",
    ("latent", 512, 0): "929b4c883ed7fad2",
    # Off the chip a long prompt is the parent's loop as well.
    ("window", 8192, 4096): "f840d1d641d2d93e",
    ("window", 8192, 0): "2475b3355ba562b7",
    ("latent", 3072, 0): "1acb81ae595168cf",
}
CALLS = {"window": _window_call, "latent": _latent_call}


@pytest.mark.parametrize("site,s,window", list(PARENT_JAXPR))
def test_the_rule_keeps_the_parents_program(monkeypatch, site, s, window):
    """On the chip's path (``auto`` on a TPU) a prompt of one query block
    traces the parent's jaxpr and counts ``.xla``; a longer one traces the
    kernel and counts ``.pallas``. Off the chip every prompt is the parent's
    loop. Traced alone: the compiled kernel needs the chip."""
    monkeypatch.setenv("CGX_CODEC_IMPL", "auto")
    fn, ops = CALLS[site](s, window, 512)

    def count(lowering):
        return metrics.get(
            f"cgx.codec.lowering.prefill_attention.{lowering}")

    for on_tpu in (True, False):
        monkeypatch.setattr(dispatch, "_on_tpu", lambda: on_tpu)
        metrics.reset()
        # A function of its own: a trace is cached by the function traced.
        text = str(jax.make_jaxpr(lambda *a: fn(*a))(*ops))
        kernel = on_tpu and s > 512
        assert ("cgx_prefill_attention" in text) == kernel
        assert (count("pallas"), count("xla")) == (
            (1, 0) if kernel else (0, 1))
        if not kernel:
            assert _sha(text) == PARENT_JAXPR[site, s, window]


@pytest.mark.parametrize("impl,lowering", [("pallas", "pallas"),
                                           ("xla", "xla"), ("auto", "xla")])
def test_dispatch_is_the_codecs(monkeypatch, impl, lowering):
    """Off the TPU ``CGX_CODEC_IMPL=pallas`` interprets the kernel at any
    width, for a prompt of several query blocks; the results agree."""
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    metrics.reset()
    q, k, v, _, _ = _operands(5, 72, 4, 2, 16, 16, 0, jnp.float32)
    kw = dict(window=24, scale=0.25, dtype=jnp.float32)
    got = jax.jit(lambda *a: dispatch.prefill_attention(*a, q_block=16, **kw)
                  )(q, k, v)
    assert metrics.get(
        f"cgx.codec.lowering.prefill_attention.{lowering}") == 1
    want = pfa.prefill_attention_xla(q, k, v, q_block=16, **kw)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    metrics.reset()
    jax.jit(lambda *a: dispatch.prefill_attention(*a, q_block=72, **kw)
            )(q, k, v)
    assert metrics.get("cgx.codec.lowering.prefill_attention.xla") == 1


@pytest.mark.tpu  # the compiled Mosaic kernel at the cells' shapes
@pytest.mark.parametrize("s,h,hk,dr,window", [
    (8192, 28, 4, 0, 0), (8192, 28, 4, 0, 4096), (2048, 32, 32, 64, 0),
    (3072, 32, 32, 64, 0), (640, 32, 32, 64, 0),
])
def test_prefill_attention_tpu(s, h, hk, dr, window):
    """A long prompt of either cell: the kernel within the probabilities'
    rounding of a float32 softmax over the same operands, and no farther
    from it than the loop."""
    ops = _operands(43, s, h, hk, 128, 128, dr, jnp.bfloat16)
    scale = 1.0 / np.sqrt(128 + dr)
    got = pfa.prefill_attention_pallas(*ops, window=window, scale=scale)
    loop = pfa.prefill_attention_xla(
        *ops, window=window, scale=np.float32(scale), q_block=512,
        dtype=jnp.bfloat16)
    # The plain softmax a block of rows at a time, in float32 on the chip:
    # at the default precision its second product would round the
    # probabilities as the loop does, and flatter the loop.
    f32 = [None if x is None else x.astype(jnp.float32) for x in ops]
    with jax.default_matmul_precision("highest"):
        want = pfa.prefill_attention_xla(
            *f32, window=window, scale=np.float32(scale), q_block=512,
            dtype=jnp.float32)
    err = lambda x: float(jnp.mean(jnp.abs(  # noqa: E731
        x.astype(jnp.float32) - want)))
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 3e-2
    assert err(got) <= 1.05 * err(loop)
