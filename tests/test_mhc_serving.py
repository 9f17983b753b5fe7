"""Hyper-connected residual streams and YaRN positions in the latent-attention
adapter (``serving/latent.py``, ``models/mhc.py``, ``ops/mhc.py``), against
the plain reference (``benchmark/reference_mhc_mla_moe.py``).

Tiny sizes, seeded weights (``benchmark/weights_mhc_mla_moe.py``), float32
activations at full matmul precision unless a test says otherwise, so that
what a tolerance bounds is the thing it names (a page's rounding, an
iteration left out, a lower precision) and not the CPU's arithmetic.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import reference_mhc_mla_moe as reference  # noqa: E402
from benchmark import weights_mhc_mla_moe as weights  # noqa: E402
from torch_cgx_tpu.models import mhc, mla_moe  # noqa: E402
from torch_cgx_tpu.models.mla_moe import MlaMoeConfig, Yarn  # noqa: E402
from torch_cgx_tpu.ops import mhc as mhc_ops  # noqa: E402
from torch_cgx_tpu.serving.latent import LatentMoEServer  # noqa: E402
from torch_cgx_tpu.wire import edges  # noqa: E402

import test_latent_serving as latent  # noqa: E402

# ``test_latent_serving``'s sizes with the two mechanisms on: four streams,
# and YaRN over 16 original positions, so that a 40-token sequence lies on
# both sides of them.
SCALING = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
               mscale_all_dim=1, original_max_position_embeddings=16,
               type="yarn")
HF = dict(
    latent.HF, routed_scaling_factor=2, rope_theta=10000, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, rope_scaling=SCALING,
)
N, D = HF["hc_mult"], HF["hidden_size"]


@pytest.fixture(autouse=True)
def _clean():
    edges.clear_edges()
    with jax.default_matmul_precision("highest"):
        yield
    edges.clear_edges()


@pytest.fixture(scope="module")
def params():
    return weights.make_params(HF, 3)


def _cfg(**kw):
    return MlaMoeConfig.from_hf(
        HF, **{"dtype": jnp.float32, "q_block": 8, **kw}
    )


def _streams(params, tokens):
    """Streams as a later layer sees them: the embedding repeated, then
    each stream scaled and shifted apart, ``(S, n, D)`` float32."""
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    spread = jnp.stack([x * (1.0 + 0.3 * i) + 0.005 * i for i in range(N)],
                       axis=1)
    return spread


def _program_mixes(cfg, streams, hc):
    """``(u (S, D), H_post (S, n), H_res (S, n, n))`` as the program's
    ``mhc.pre`` computes them."""
    u, h_post, h_res = mhc.pre(cfg, streams[None], hc, mhc.DECODE_KERNEL)
    s = streams.shape[0]
    return (np.asarray(u[0]), np.asarray(h_post).T,
            np.asarray(h_res).reshape(N, N, s).transpose(2, 0, 1))


# -- the served path against the full forward ------------------------------

# The largest logit difference over the reference's spread across the
# vocabulary, prefill and then decode through the pages against the
# reference's full forward. Read here at these sizes over two prompts
# (float32 activations, so that pages are all that differs): raw (float16
# pages) 0.0065, 0.0067; 8-bit 0.118, 0.121; 4-bit 2.54, 2.65.
# ``test_latent_serving``'s limits for the same widths (0.03, 0.2) hold as
# they are: each over its own readings by 1.6 to 4 times, and each width
# reads outside the limit of the width above it.
@pytest.mark.parametrize("bits", ["0", "8"])
def test_prefill_then_decode_matches_reference_logits(params, monkeypatch,
                                                      bits):
    monkeypatch.setenv("CGX_KV_BITS", bits)
    cfg = _cfg()
    worst = 0.0
    for seed, n_prompt in ((0, 21), (1, 13)):
        prompt = latent._prompt(n_prompt, seed=seed)
        tokens, got = latent._served_logits(params, cfg, prompt, 14)
        ref = np.asarray(reference.forward(
            params, jnp.asarray(prompt + tokens[:-1]), HF, q_block=8,
            expert_block=8))
        want = ref[len(prompt):]  # what predicted tokens[1:]
        assert got.shape == want.shape
        worst = max(worst, latent._gap(got, want))
    assert worst < latent.PAGE_LIMITS[bits], worst
    if bits == "8":
        assert worst > latent.PAGE_LIMITS["0"], worst


def test_prefill_logits_match_reference_at_any_last_position(params):
    """The prefill alone, the prompt right-padded: the logits at
    ``last_idx`` are the reference's at that position (the streams are read
    out at one token, the ``jax.numpy`` form's shape). Float32 rounding:
    1e-4 of the logits' spread is a hundred times the reading (4e-6)."""
    cfg = _cfg()
    server = LatentMoEServer(cfg, params, latent._serve())
    tokens = latent._prompt(40, seed=2)
    ref = np.asarray(reference.forward(params, jnp.asarray(tokens), HF,
                                       q_block=8, expert_block=8))
    for last in (39, 17):
        got, cs, krs = jax.jit(server.prefill_forward)(
            jnp.asarray(tokens)[None], jnp.arange(40)[None], last)
        assert latent._gap(np.asarray(got)[0], ref[last]) < 1e-4
        assert len(cs) == len(krs) == cfg.n_layer


# -- the hyper-connection against the reference's loop ----------------------

# ``H_res`` of the program against the reference's loop over (n, n)
# matrices: float32 rounding through twenty iterations reads 1.8e-7 here
# (``H_post`` 1.2e-7); the limit is fifty times that, a hundredth of what
# bfloat16 coefficients read (9.9e-4; ``H_post`` 2.3e-3) and a thousandth of
# what three iterations in place of twenty read (1.8e-2).
H_RES_LIMIT = 1e-5


def _h_res_gap(params, cfg, hc=None):
    tokens = latent._prompt(48, seed=5)
    streams = _streams(params, tokens)
    ref_hc = params["layer_1"]["hc_ffn"]
    _, want_post, want_res = reference.hc_mixes(streams, ref_hc, HF)
    _, got_post, got_res = _program_mixes(cfg, streams, hc or ref_hc)
    return (float(np.max(np.abs(got_res - np.asarray(want_res)))),
            float(np.max(np.abs(got_post - np.asarray(want_post)))),
            got_res)


def test_h_res_matches_the_reference_loop_and_is_doubly_stochastic(params):
    res_gap, post_gap, h_res = _h_res_gap(params, _cfg())
    assert res_gap < H_RES_LIMIT and post_gap < H_RES_LIMIT
    # Under the ``init`` draw twenty iterations leave every row and column
    # within 1e-3 of 1, and the matrix is neither the identity nor uniform.
    assert np.max(np.abs(h_res.sum(-1) - 1)) < 1e-3
    assert np.max(np.abs(h_res.sum(-2) - 1)) < 1e-3
    off_diagonal = 1 - np.trace(h_res, axis1=1, axis2=2) / N
    assert 0.2 < off_diagonal.mean() < 0.8
    moves = np.abs(h_res[1:] - h_res[:-1]).max(axis=(1, 2))
    assert np.median(moves) > 0.1
    assert float(mhc.res_error(
        jnp.asarray(h_res.transpose(1, 2, 0).reshape(N * N, -1)), N)) < 1e-3


def test_three_iterations_fail_the_comparison(params):
    res_gap, _, h_res = _h_res_gap(params, _cfg(hc_sinkhorn_iters=3))
    assert res_gap > 100 * H_RES_LIMIT
    assert np.max(np.abs(h_res.sum(-1) - 1)) > 1e-3  # and need the rest


def test_bfloat16_coefficients_fail_the_comparison(params):
    """``phi``, ``alpha`` and ``base`` rounded to bfloat16, the nearest
    precision below the float32 the configuration states for them."""
    hc = jax.tree.map(
        lambda v: v.astype(jnp.bfloat16).astype(jnp.float32),
        params["layer_1"]["hc_ffn"])
    res_gap, post_gap, _ = _h_res_gap(params, _cfg(), hc)
    assert res_gap > 50 * H_RES_LIMIT and post_gap > 100 * H_RES_LIMIT


def test_mix_and_read_out_match_the_reference(params):
    cfg = _cfg()
    tokens = latent._prompt(24, seed=6)
    streams = _streams(params, tokens)
    hc = params["layer_2"]["hc_attn"]
    y = jax.random.normal(jax.random.key(0), (24, D), jnp.float32) * 0.05
    u, h_post, h_res = reference.hc_read(streams, hc, HF)
    want = reference.hc_write(streams, y, h_post, h_res)
    got_u, got_post, got_res = mhc.pre(cfg, streams[None], hc,
                                       mhc.PREFILL_KERNEL)
    got = mhc.mix(streams[None], y[None], got_post, got_res)[0]
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got_u[0] - u))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale
    head = params["hc_head"]
    h_pre, _, _ = reference.hc_mixes(streams, head, HF)
    want = jnp.einsum("si,sid->sd", h_pre, streams)
    got = mhc.read_out(cfg, streams[None], head, mhc.PREFILL_KERNEL)[0]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale


# -- the kernel against the ``jax.numpy`` form ------------------------------

@pytest.mark.parametrize("shape,mixes", [
    ("decode", True), ("prefill", True), ("decode", False)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_matches_the_jnp_form(shape, mixes, dtype):
    """``cgx_mhc_pre`` interpreted against ``mhc_pre_xla`` over a decode
    step's lanes (one tile of 32 tokens), a prompt (two tiles of 256) and
    the read-out. The kernel's product is three bfloat16 pieces of ``phi``
    (and of float32 streams) accumulated in float32: ``H_post`` and
    ``H_res`` agree to 2e-6 (read: 6e-7), ``u`` to a unit of its type's
    last place."""
    tokens = {"decode": 32, "prefill": 2 * mhc_ops.TILE}[shape]
    width = 128
    hc = weights._hc(jax.random.key(7), weights.HC_DEFAULTS, N, width, mixes)
    x = (0.02 * jax.random.normal(jax.random.key(8), (tokens, N * width))
         ).astype(dtype)
    how = dict(n=N, iters=20, eps=1e-6, clamp=(-30.0, 30.0), rms_eps=1e-6,
               mixes=mixes)
    assert mhc_ops.token_tile(tokens) == min(tokens, mhc_ops.TILE)
    want = mhc_ops.mhc_pre_xla(x, hc["phi"], hc["alpha"], hc["base"], **how)
    phi_t = mhc_ops.kernel_phi(hc["phi"])
    assert phi_t.dtype == jnp.bfloat16 and phi_t.shape == (
        3 * (24 if mixes else 8), N * width)
    got = mhc_ops.mhc_pre_pallas(x, phi_t, hc["alpha"], hc["base"],
                                 name="cgx_mhc_pre_decode", interpret=True,
                                 **how)
    u_tol = 2 ** -8 if dtype == "bfloat16" else 1e-6
    u_want = np.asarray(want[0], np.float32)
    assert np.max(np.abs(np.asarray(got[0], np.float32) - u_want)) <= (
        u_tol * np.max(np.abs(u_want)))
    if not mixes:
        assert got[1] is None and got[2] is None
        return
    assert got[1].shape == (N, tokens) and got[2].shape == (N * N, tokens)
    for a, b in zip(got[1:], want[1:]):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-6


def test_a_call_with_the_kept_pieces_prepares_nothing(params, monkeypatch):
    """The adapter keeps ``phi`` as the kernel reads it beside ``phi``
    (made once, when it takes the tree, the caller's tree left as it was),
    and a call that is handed the pieces is the kernel and nothing in front
    of it; without them the call makes them itself (a transpose in every
    call, which is why the adapter does)."""
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    cfg = _cfg(dtype=jnp.bfloat16)
    server = LatentMoEServer(cfg, params, latent._serve())
    assert "phi_t" not in params["hc_head"]
    kept = server.p["layer_0"]["hc_attn"]
    assert kept["phi_t"].dtype == jnp.bfloat16
    assert kept["phi_t"].shape == (3 * 24, N * D)
    assert server.p["hc_head"]["phi_t"].shape == (3 * 8, N * D)
    assert kept["phi"] is params["layer_0"]["hc_attn"]["phi"]
    # A program builds the adapter again around the tree it is handed
    # (``with_params``): the pieces it holds are taken, not made again.
    again = server.with_params(server.p).p
    assert again["layer_0"]["hc_attn"]["phi_t"] is kept["phi_t"]
    assert again["hc_head"]["phi_t"] is server.p["hc_head"]["phi_t"]
    streams = jnp.zeros((8, 1, N, D), jnp.bfloat16)

    def walk(jaxpr):
        """Every primitive of the trace, a kernel's own body left out."""
        for e in jaxpr.eqns:
            inner = e.params.get("jaxpr")
            if inner is None or e.primitive.name == "pallas_call":
                yield e.primitive.name
            else:
                yield from walk(getattr(inner, "jaxpr", inner))

    def primitives(hc):
        return set(walk(jax.make_jaxpr(
            lambda x, hc: mhc.pre(cfg, x, hc, mhc.DECODE_KERNEL))(
                streams, hc).jaxpr))

    assert primitives(kept) == {"reshape", "pallas_call"}
    bare = {k: v for k, v in kept.items() if k != "phi_t"}
    assert "transpose" in primitives(bare)


def test_a_single_token_is_the_jnp_forms():
    assert mhc_ops.token_tile(1) is None  # the prefill's read-out
    assert mhc_ops.token_tile(7936) == mhc_ops.TILE


def test_split_pieces_add_up_to_float32():
    v = jax.random.normal(jax.random.key(9), (64, 24), jnp.float32)
    pieces = mhc_ops.split_bf16(v)
    assert pieces.dtype == jnp.bfloat16 and pieces.shape == (3, 64, 24)
    total = jnp.sum(pieces.astype(jnp.float32), axis=0)
    assert float(jnp.max(jnp.abs(total - v) / jnp.abs(v))) < 2 ** -22


def test_the_reference_compiles_ahead_from_shapes_alone(params):
    """``reference.compile_ahead`` (the benchmark's driver runs it in a
    thread beside its set-up) is handed shapes and no array, walks both
    kinds of layer, and leaves the call that follows what it was."""
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    how = dict(pad_multiple=16, most_outputs=6, q_block=8, expert_block=8)
    prompt, served = latent._prompt(21, seed=5), [3, 1, 4, 1, 5]
    before, _ = reference.served_token_gaps(
        params, HF, [prompt], [served], lengths=[40], **how)
    reference.compile_ahead(shapes, HF, [40, 56], **how)
    after, agree = reference.served_token_gaps(
        params, HF, [prompt], [served], lengths=[40], **how)
    assert after[0].shape == (5,) and 0.0 <= agree <= 1.0
    np.testing.assert_array_equal(before[0], after[0])


# -- YaRN -------------------------------------------------------------------

def test_yarn_factor_one_is_the_plain_rotation_to_the_bit():
    x = jax.random.normal(jax.random.key(1), (2, 40, 4, 8), jnp.float32)
    positions = jnp.arange(40)[None] + jnp.asarray([[0], [5000]])
    plain = mla_moe.rope(x, positions, 10000.0)
    one = mla_moe.rope(x, positions, 10000.0,
                       Yarn(factor=1.0, original_positions=16,
                            mscale=1.0, mscale_all_dim=1.0))
    assert np.array_equal(np.asarray(plain), np.asarray(one))
    assert Yarn(factor=1.0, original_positions=16).softmax_scale == 1.0


def test_yarn_frequencies_and_scale_match_the_reference():
    """The published numbers: of the 32 pairs of a 64-wide rotary key,
    pairs up to 10 keep their frequency, pairs from 23 on turn 64 times
    slower, a ramp between; the softmax scale doubles."""
    published = dict(SCALING, original_max_position_embeddings=4096)
    yarn = MlaMoeConfig.from_hf(dict(HF, rope_scaling=published)).yarn
    scale = yarn.frequency_scale(64, 10000.0)
    assert np.all(scale[:11] == 1.0) and np.allclose(scale[23:], 1 / 64)
    assert np.all(np.diff(scale[10:24]) < 0)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(
        plain * scale, reference.yarn_frequencies(64, 10000.0, published),
        rtol=1e-12)
    assert yarn.rotation_scale == 1.0
    want = (0.1 * np.log(64) + 1) ** 2
    assert abs(yarn.softmax_scale - want) < 1e-12 and 2.0 < want < 2.01
    cfg = _cfg(yarn=yarn)
    assert mla_moe._softmax_scale(cfg) == pytest.approx(
        reference.softmax_scale(dict(HF, rope_scaling=published)))
    # The rotation itself, at positions on both sides of the 4,096.
    x = jax.random.normal(jax.random.key(2), (6, 64), jnp.float32)
    positions = jnp.asarray([0, 100, 4095, 4096, 9000, 17000])
    got = mla_moe.rope(x[None], positions[None], 10000.0, yarn)[0]
    want = reference.rope(x, positions, 10000.0, published)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    far = mla_moe.rope(x[None], positions[None], 10000.0)[0]
    assert float(jnp.max(jnp.abs(far - want))) > 0.1  # unscaled: another


def test_from_hf_refuses_a_rope_scaling_it_does_not_compute():
    with pytest.raises(ValueError, match="linear"):
        MlaMoeConfig.from_hf(dict(HF, rope_scaling={"type": "linear",
                                                    "factor": 4}))
    assert MlaMoeConfig.from_hf(dict(HF, rope_scaling=None)).yarn is None
    cfg = _cfg()
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_clamp) == (
        4, 20, (-30.0, 30.0))
    assert cfg.yarn == Yarn(factor=64.0, original_positions=16,
                            mscale=1.0, mscale_all_dim=1.0)


# -- a config without the mechanisms ----------------------------------------

def test_a_single_stream_config_has_no_hc_leaf_and_serves_its_tokens(
        monkeypatch):
    """A JoyAI-shaped config: no ``hc_*`` leaf in its tree, no stream axis,
    no ``mhc`` counter, and the tokens the parent of this change served for
    the same seed and prompt (pinned from that tree)."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    params = latent.weights.make_params(latent.HF, 3)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    assert paths and not [p for p in paths if "hc_" in p]
    cfg = latent._cfg()
    assert cfg.hc_mult == 0 and cfg.yarn is None
    server = LatentMoEServer(cfg, params, latent._serve())
    assert server.step_counters == ("moe.assignments", "moe.experts_touched",
                                    "moe.load_max", "moe.dropped")
    tokens, _ = latent._served_logits(params, cfg,
                                      latent._prompt(21, seed=48), 14)
    assert tokens == [159, 187, 209, 449, 97, 159, 476, 298, 209, 240, 159,
                      454, 209, 240]


def test_the_hyper_connected_tree_and_counters(params):
    for layer in range(HF["num_hidden_layers"]):
        pl = params[f"layer_{layer}"]
        for name in ("hc_attn", "hc_ffn"):
            assert pl[name]["phi"].shape == (N * D, 2 * N + N * N)
            assert pl[name]["alpha"].shape == (3,)
            assert pl[name]["base"].shape == (2 * N + N * N,)
            assert pl[name]["phi"].dtype == jnp.float32
    assert params["hc_head"]["phi"].shape == (N * D, N)
    server = LatentMoEServer(_cfg(), params, latent._serve())
    assert server.step_counters[-1] == "mhc.res_err_ppm"
    assert server.with_params(params).step_counters == server.step_counters
