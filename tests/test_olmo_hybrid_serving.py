"""The gated delta-rule adapter (``serving/hybrid.HybridGDNServer``) through
the one scheduler, against the plain reference
(``benchmark/reference_olmo_hybrid.py``, the delta rule a position at a
time).

Tiny sizes, seeded weights (``benchmark/weights_olmo_hybrid.py``), float32
activations at full matmul precision unless a test says otherwise, so that
what a tolerance bounds is the thing it names (a page's rounding, a narrower
state) and not the CPU's arithmetic.
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import reference_olmo_hybrid as reference  # noqa: E402
from benchmark import weights_olmo_hybrid as weights  # noqa: E402
from torch_cgx_tpu.models import olmo_hybrid as oh  # noqa: E402
from torch_cgx_tpu.models.olmo_hybrid import OlmoHybridConfig  # noqa: E402
from torch_cgx_tpu.observability import memledger  # noqa: E402
from torch_cgx_tpu.ops import dispatch as ops_dispatch  # noqa: E402
from torch_cgx_tpu.ops import gdn  # noqa: E402
from torch_cgx_tpu.serving import scheduler as sched_mod  # noqa: E402
from torch_cgx_tpu.serving import hybrid as hybrid_mod  # noqa: E402
from torch_cgx_tpu.serving.hybrid import HybridGDNServer  # noqa: E402
from torch_cgx_tpu.serving.prefill import PrefillWorker  # noqa: E402
from torch_cgx_tpu.serving.adapter import ServeConfig  # noqa: E402
from torch_cgx_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.serving.transport import KvPageReceiver  # noqa: E402
from torch_cgx_tpu.utils.logging import metrics  # noqa: E402
from torch_cgx_tpu.wire import edges  # noqa: E402

from test_faults import FakeStore  # noqa: E402
import serving_guard  # noqa: E402

PAGE = 16  # = the delta rule's chunk: a padded prompt is whole chunks
HF = dict(
    model_type="olmo_hybrid", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_attention_heads=4, num_key_value_heads=4,
    hidden_act="silu", attention_bias=False, rms_norm_eps=1e-6,
    tie_word_embeddings=False,
    layer_types=["linear_attention", "linear_attention", "full_attention",
                 "linear_attention", "full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
    precision={"params": "float32"},
    # Query and key norm weights drawn large: an attention without
    # positions is then peaked and a page's rounding shows in the logits,
    # as on the chip.
    init={"q_norm_mean": 1.5, "k_norm_mean": 1.5},
)
DELTA, ATTENTION = (0, 1, 3), (2, 4)
D_QKV, D_VALUE = 4 * (2 * 8 + 16), 4 * 16


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
    return OlmoHybridConfig.from_hf(
        HF, **{"dtype": jnp.float32, "chunk": PAGE, **kw})


def _serve(**kw):
    base = dict(page_tokens=PAGE, max_batch=3, max_pages=24, max_seq=96,
                ship_depth=4)
    return ServeConfig(**{**base, **kw})


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, HF["vocab_size"], n)]


def _operands(rng, b, s, h, dk, dv):
    """``q``, ``k`` (normalised as the layer normalises them), ``v``, ``log
    alpha`` over slow and fast heads, ``beta`` in 0-2."""
    q, k = (rng.standard_normal((b, s, h, dk)) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, s, h, dv))
    g = -rng.uniform(0.001, 2.0, (b, s, h))
    beta = rng.uniform(0.0, 2.0, (b, s, h))
    return tuple(np.asarray(t, np.float32) for t in (q, k, v, g, beta))


def _sequential(q, k, v, g, beta, state):
    """The recurrence one position at a time, in float64 numpy."""
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    s = np.asarray(state, np.float64).copy()
    out = []
    for t in range(q.shape[1]):
        s = s * np.exp(g[:, t])[..., None, None]
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", s, k[:, t]))
        s = s + k[:, t][..., None] * u[:, :, None, :]
        out.append(np.einsum("bhkv,bhk->bhv", s, q[:, t]))
    return np.stack(out, axis=1), s


@pytest.mark.parametrize("n", [1, 2, 16, 64, 23])
def test_unit_lower_inverse_is_the_inverse(n):
    """``(I + A)^-1`` by doubling against numpy's inverse in float64, for
    sizes that are and are not powers of two, entries as large as the
    chunked form's (``beta k . k``, up to 2): limit 1e-5 of the inverse's
    largest entry (readings under 2e-6)."""
    rng = np.random.default_rng(n)
    a = np.tril(rng.uniform(-1.0, 1.0, (3, n, n)), -1).astype(np.float32)
    got = np.asarray(oh.unit_lower_inverse(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(n) + a.astype(np.float64))
    assert np.max(np.abs(got - want)) < 1e-5 * np.max(np.abs(want))


@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("length", [PAGE, 3 * PAGE, 2 * PAGE + 5, 3])
def test_chunked_delta_rule_equals_the_sequential_recurrence(length, carried):
    """``gdn_chunk_scan`` (within a chunk the triangular system, between
    chunks the carried state) against the recurrence a position at a time,
    for lengths that are and are not whole chunks, from zeros and from a
    state handed in: outputs and final state to float32 rounding (limit 2e-5
    of the largest value; readings under 1e-6)."""
    rng = np.random.default_rng(length)
    b, h, dk, dv = 2, 4, 8, 16
    ops = _operands(rng, b, length, h, dk, dv)
    state = (rng.standard_normal((b, h, dk, dv)).astype(np.float32)
             if carried else np.zeros((b, h, dk, dv), np.float32))
    o, final = oh.gdn_chunk_scan(*(jnp.asarray(t) for t in ops), PAGE,
                                 jnp.asarray(state) if carried else None)
    want_o, want_final = _sequential(*ops, state)
    assert o.shape == (b, length, h, dv)
    for got, want in ((o, want_o), (final, want_final)):
        assert np.max(np.abs(np.asarray(got) - want)) < 2e-5 * np.max(
            np.abs(want))


def test_recurrence_equals_the_published_torch_recurrence():
    """The reference's recurrence, the program's one-step update and its
    chunked form against ``transformers``' plain-torch
    ``torch_recurrent_gated_delta_rule`` (the delta-rule layer's published
    code, under Qwen3-Next's name) on the same arrays: outputs and final
    state to float32 rounding (limit 2e-5 of the largest value)."""
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    rng = np.random.default_rng(7)
    b, s, h, dk, dv = 2, 2 * PAGE + 3, 4, 8, 16
    q, k, v, g, beta = _operands(rng, b, s, h, dk, dv)
    # torch scales the query by 1/sqrt(dk) itself.
    want_o, want_state = modeling.torch_recurrent_gated_delta_rule(
        *(torch.from_numpy(t) for t in (q * np.sqrt(dk), k, v, g, beta)),
        initial_state=None, output_final_state=True)
    want_o, want_state = want_o.numpy(), want_state.numpy()

    def close(got, want):
        return np.max(np.abs(np.asarray(got) - want)) < 2e-5 * np.max(
            np.abs(want))

    for lane in range(b):  # the reference: one sequence
        o, state = reference.delta_recurrence(
            *(jnp.asarray(t[lane]) for t in (q, k, v, np.exp(g), beta)))
        assert close(o, want_o[lane]) and close(state, want_state[lane])
    o, state = oh.gdn_chunk_scan(*(jnp.asarray(t) for t in
                                   (q, k, v, g, beta)), PAGE)
    assert close(o, want_o) and close(state, want_state)
    lanes = jnp.zeros((b, dk, h * dv), jnp.float32)
    for t in range(s):  # the one-step update, in the lanes' layout
        lanes, o_t = gdn.gdn_update_xla(
            lanes, *(jnp.asarray(x[:, t]) for x in (q, k, v, np.exp(g),
                                                    beta)))
        assert close(o_t.reshape(b, h, dv), want_o[:, t])
    assert close(lanes.reshape(b, dk, h, dv).transpose(0, 2, 1, 3),
                 want_state)


@pytest.mark.parametrize("length", [PAGE, 2 * PAGE + 5])
def test_model_forward_equals_the_reference(params, length):
    """The program's full forward (chunked delta rule, grouped attention)
    against the plain reference's (a ``lax.scan`` over positions), float32
    both: logits to 5e-5 of the reference's spread (readings 1e-5)."""
    tokens = jnp.asarray(_prompt(length, seed=length), jnp.int32)
    got = np.asarray(oh.forward(_cfg(), params, tokens[None])[0])
    want = np.asarray(reference.forward(params, tokens, HF))
    assert np.max(np.abs(got - want)) < 5e-5 * np.std(want)


@pytest.mark.parametrize("last_idx", [PAGE - 2, PAGE - 1, PAGE, PAGE + 1,
                                      2 * PAGE - 1, 2 * PAGE, 0, 2])
def test_prefill_state_is_the_state_at_the_last_real_position(params,
                                                              last_idx):
    """A right-padded prompt, its last real position on either side of a
    chunk's edge and inside the convolution's first window: the state
    ``gdn_prefill`` returns is the one after ``last_idx`` (the pad takes
    ``beta = 0`` and ``log alpha = 0``; the convolution's state is its
    inputs ending at ``last_idx``), equal to the unpadded prompt's and to
    ``last_idx + 1`` single steps from zeros."""
    cfg, pg = _cfg(), params["layer_0"]["gdn"]
    rng = np.random.default_rng(last_idx)
    n = last_idx + 1
    y = jnp.asarray(rng.standard_normal((2, n, 64)), jnp.float32)
    junk = jnp.asarray(rng.standard_normal((2, 3 * PAGE - n, 64)),
                       jnp.float32) * 50
    out, conv, state = oh.gdn_prefill(cfg, pg, y, last_idx)
    out_p, conv_p, state_p = oh.gdn_prefill(
        cfg, pg, jnp.concatenate([y, junk], axis=1), last_idx)
    scale = max(float(jnp.max(jnp.abs(state))), 1e-3)
    assert conv.shape == (2, 3, D_QKV) and state.shape == (2, 8, D_VALUE)
    assert float(jnp.max(jnp.abs(conv - conv_p))) < 1e-5
    assert float(jnp.max(jnp.abs(state - state_p))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(out - out_p[:, :n]))) < 1e-5
    c = jnp.zeros((2, cfg.d_conv - 1, cfg.d_qkv))
    s = jnp.zeros((2, cfg.d_k, cfg.d_value))
    for t in range(n):
        o, c, s = oh.gdn_step(cfg, pg, y[:, t], c, s)
    assert float(jnp.max(jnp.abs(c - conv))) < 1e-5
    assert float(jnp.max(jnp.abs(s - state))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(o - out[:, last_idx]))) < 1e-5


def _served_logits(params, cfg, prompt, gen, **server_kw):
    """Serve one request through the scheduler and return ``(tokens, the
    decode steps' logits (gen - 1, V))``: the logits are read by the
    adapter's own ``decode_forward`` on the very state each
    ``decode_step`` call is given."""
    server = HybridGDNServer(cfg, params, _serve(), **server_kw)
    sched = ContinuousBatchScheduler(server)
    prog, seen = sched._prog, []
    probe = jax.jit(lambda p, st: server.with_params(p).decode_forward(
        st, prog.streams)[0])

    def decode_step(p, state):
        seen.append(np.asarray(probe(p, state))[0])
        return prog.decode_step(p, state)

    sched._prog = SimpleNamespace(**{**vars(prog), "decode_step": decode_step})
    req = Request(id="a", tokens=prompt, max_new_tokens=gen)
    sched.submit(req)
    assert sched.run(deadline_s=300.0)
    assert sched.cache.free_pages == sched.cache.max_pages
    return req.output, np.stack(seen)


def _gap(params, prompt, tokens, got):
    """Largest |difference| of the served decode steps' logits and the
    reference's full forward over ``prompt + served tokens``, over the
    reference's spread (its standard deviation over the vocabulary)."""
    ref = np.asarray(reference.forward(
        params, jnp.asarray(prompt + tokens[:-1], jnp.int32), HF))
    assert tokens[0] == int(np.argmax(ref[len(prompt) - 1]))
    ref = ref[len(prompt): len(prompt) + len(got)]
    return float(np.max(np.abs(got - ref)) / np.std(ref))


# What the served path may cost, as the largest logit difference over the
# reference's spread across the vocabulary, float32 activations and state.
# The block norms what every mixer returns, so an attention layer's
# rounding reaches the logits whole: readings here over the four prompts,
# 8-bit pages 0.033-0.060, 4-bit pages 0.66-0.99. PAGES_LIMIT lies 2.5
# times above the largest sound reading and 4.4 times below the smallest
# 4-bit one. At 8 bits the pages' rounding hides a bfloat16 state (0.061-
# 0.092), so the state is held to its own limit over raw (float16) pages:
# readings 0.0013-0.0019 with a float32 state, 0.10-0.16 with a bfloat16
# one (it rounds once a token for the life of the request); STATE_LIMIT lies
# 3 times above the one and 17 below the other.
PAGES_LIMIT = 0.15
STATE_LIMIT = 0.006


@pytest.mark.parametrize("prompt_len", [2 * PAGE + 3, 2 * PAGE, 3, PAGE - 1],
                         ids=["mid_page", "page_edge", "under_the_conv",
                              "fills_its_tail"])
@pytest.mark.parametrize("bits,limit", [("8", PAGES_LIMIT),
                                        ("0", STATE_LIMIT)],
                         ids=["pages_8bit", "pages_raw"])
def test_prefill_then_decode_matches_reference_logits(params, monkeypatch,
                                                      prompt_len, bits,
                                                      limit):
    """Prefill of a right-padded prompt (chunked delta rule, state taken at
    ``last_idx``, K/V pages into the pools), then decode through the
    per-lane state and the pages (tails committing on the way), against the
    plain reference's full forward over ``prompt + served tokens``: logits
    at every decode position, for prompts that end mid-page, on a page edge,
    before the convolution's window is full, and one token short of a
    page."""
    monkeypatch.setenv("CGX_KV_BITS", bits)
    prompt, gen = _prompt(prompt_len, seed=prompt_len), 2 * PAGE + 4
    tokens, got = _served_logits(params, _cfg(), prompt, gen)
    gap = _gap(params, prompt, tokens, got)
    assert gap < limit, gap


@pytest.mark.parametrize("lower", ["pages_4bit", "state_bfloat16"])
def test_a_lower_precision_fails_the_served_limit(params, monkeypatch, lower):
    """4-bit pages in place of 8-bit ones, and a bfloat16 recurrent state in
    place of the float32 one (over raw pages, so that the state is all that
    differs), each leave their limit by a factor of two or more: the
    comparison can see both."""
    pages = lower == "pages_4bit"
    monkeypatch.setenv("CGX_KV_BITS", "4" if pages else "0")
    kw = {} if pages else {"state_dtype": jnp.bfloat16}
    prompt, gen = _prompt(2 * PAGE + 3, seed=2 * PAGE + 3), 2 * PAGE + 4
    tokens, got = _served_logits(params, _cfg(), prompt, gen, **kw)
    gap = _gap(params, prompt, tokens, got)
    assert gap > 2 * (PAGES_LIMIT if pages else STATE_LIMIT), gap


def test_a_lane_does_not_depend_on_what_other_lanes_hold_or_held(
        params, monkeypatch):
    """A request's tokens are the same served alone in a fresh scheduler
    and served in a lane that a longer request has just left (its state
    rows and pages are whatever that request wrote) beside two other busy
    lanes: an admission overwrites the lane's recurrent state whole, and a
    lane's state reaches no other lane. Nothing of the state is reset at
    release; a free lane's state stays finite through the steps it idles."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    cfg = _cfg()
    probe = Request(id="probe", tokens=_prompt(PAGE + 5, seed=9),
                    max_new_tokens=PAGE + 6)
    alone = ContinuousBatchScheduler(HybridGDNServer(cfg, params, _serve()))
    alone.submit(probe)
    assert alone.run(deadline_s=300.0)
    want = list(probe.output)

    sched = ContinuousBatchScheduler(HybridGDNServer(cfg, params, _serve()))
    first = [
        Request(id="long", tokens=_prompt(3 * PAGE + 2, seed=1),
                max_new_tokens=8),
        Request(id="b", tokens=_prompt(PAGE, seed=2), max_new_tokens=60),
        Request(id="c", tokens=_prompt(5, seed=3), max_new_tokens=60),
    ]
    for r in first:
        sched.submit(r)
    while not first[0].done:
        sched.step()
    lane = sched._lanes.index(None)  # the lane the long request left
    held = np.asarray(sched._state["state_gdn"][0])[lane]
    assert np.any(held != 0)  # not reset at release
    for _ in range(3):  # the free lane idles through decode steps
        sched.step()
    for name in ("state_gdn", "state_conv"):
        for per_layer in sched._state[name]:
            assert per_layer is None or bool(jnp.all(jnp.isfinite(per_layer)))
    again = Request(id="probe2", tokens=list(probe.tokens),
                    max_new_tokens=probe.max_new_tokens)
    sched.submit(again)
    sched.step()
    assert sched._lanes[lane] is again
    assert sched.run(deadline_s=300.0)
    assert again.output == want


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads,dv,blocks", [
    (4, 192, (4, 2)),   # Olmo-Hybrid's 192: heads two at a time, one block
    (6, 128, (6, 1)),   # whole vectors a head
    (4, 16, (4, 4)),    # not whole groups (8 of 16): the row one block
    (40, 192, (10, 2)),  # over the block's cap: four grid steps a lane
])
def test_gdn_update_lowerings_agree(heads, dv, blocks, state_dtype):
    """``cgx_gdn_update`` (interpreted here) against its ``jax.numpy`` form,
    to float32 rounding (the kernel adds the ``d_k`` products of a
    contraction in another order; a narrower state may then round one step
    apart), over a state no lane of which is zero, for head widths that are
    one and a half vectors, whole vectors and a fraction of one; the kernel
    writes the state over its operand (``input_output_aliases``) and leaves
    a free lane's state finite."""
    rng = np.random.default_rng(heads * dv)
    b, dk = 3, 24 if heads == 40 else 8
    assert gdn.head_blocks(heads, 96 if heads == 40 else dk, dv) == blocks
    q, k, v, g, beta = (jnp.asarray(t[:, 0])
                        for t in _operands(rng, b, 1, heads, dk, dv))
    state = jnp.asarray(rng.standard_normal((b, dk, heads * dv)), state_dtype)
    args = (state, q, k, v, jnp.exp(g), beta)
    new_k, o_k = gdn.gdn_update_pallas(*args, interpret=True)
    new_x, o_x = gdn.gdn_update_xla(*args)
    assert new_k.dtype == state_dtype and o_k.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(new_k.astype(jnp.float32))))
    step = 2.0 ** -8 if state_dtype == jnp.bfloat16 else 2e-6
    new_k, new_x = (np.asarray(t, np.float32) for t in (new_k, new_x))
    assert np.max(np.abs(new_k - new_x)) <= step * np.max(np.abs(new_x))
    assert float(jnp.max(jnp.abs(o_k - o_x))) < 1e-5 * float(
        jnp.max(jnp.abs(o_x)))
    text = str(jax.make_jaxpr(
        lambda *a: gdn.gdn_update_pallas(*a, interpret=True))(*args))
    assert "input_output_aliases=((0, 0),)" in text
    assert "cgx_gdn_update" in text


@pytest.mark.tpu  # the compiled Mosaic kernel at the published widths
def test_gdn_update_tpu():
    rng = np.random.default_rng(33)
    b, heads, dk, dv = 4, 30, 96, 192
    assert gdn.head_blocks(heads, dk, dv) == (10, 2)
    q, k, v, g, beta = (jnp.asarray(t[:, 0])
                        for t in _operands(rng, b, 1, heads, dk, dv))
    state = jnp.asarray(rng.standard_normal((b, dk, heads * dv)), jnp.float32)
    args = (state, q, k, v, jnp.exp(g), beta)
    new_x, o_x = gdn.gdn_update_xla(*args)
    new_k, o_k = gdn.gdn_update_pallas(*args)
    assert float(jnp.max(jnp.abs(new_k - new_x))) <= 2e-6 * float(
        jnp.max(jnp.abs(new_x)))
    assert float(jnp.max(jnp.abs(o_k - o_x))) < 1e-5 * float(
        jnp.max(jnp.abs(o_x)))


@pytest.mark.parametrize("impl,lowering", [("pallas", "pallas"),
                                           ("xla", "xla"), ("auto", "xla")])
def test_gdn_update_dispatch_counts_its_lowering(monkeypatch, impl, lowering):
    """``ops.dispatch.gdn_update`` is dispatched as the codec is
    (``CGX_CODEC_IMPL``; off the TPU ``auto`` is the ``jax.numpy`` form)
    and counts the call site by lowering."""
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    metrics.reset()
    b, h, dk, dv = 2, 2, 8, 64
    args = (jnp.ones((b, dk, h * dv)), jnp.ones((b, h, dk)),
            jnp.ones((b, h, dk)) / 8, jnp.ones((b, h, dv)),
            jnp.full((b, h), 0.5), jnp.ones((b, h)))
    new, o = ops_dispatch.gdn_update(*args)
    assert new.shape == (b, dk, h * dv) and o.shape == (b, h * dv)
    # S~ = 0.5; S~^T k = 0.5; u = 0.5; S' = 0.5 + 0.125 * 0.5; o = 8 S'
    assert float(o[0, 0]) == pytest.approx(8 * 0.5625)
    assert metrics.snapshot("cgx.codec.lowering.") == {
        f"cgx.codec.lowering.gdn_update.{lowering}": 1.0}


@pytest.mark.parametrize("impl,lowering", [("xla", "xla"),
                                           ("pallas", "pallas")])
def test_gdn_update_call_sites_are_counted_by_lowering(params, monkeypatch,
                                                       impl, lowering):
    """``cgx.codec.lowering.gdn_update.<lowering>`` counts the decode
    program's call sites, one a delta-rule layer, as the codec counts its
    own."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    sched_mod.invalidate_decode_cache("test")
    metrics.reset()
    server = HybridGDNServer(_cfg(), params, _serve())
    sched = ContinuousBatchScheduler(server)
    jax.make_jaxpr(sched._prog.decode_step)(server.p, sched._state)
    assert metrics.snapshot("cgx.codec.lowering.gdn_update.") == {
        f"cgx.codec.lowering.gdn_update.{lowering}": float(len(DELTA))}


def test_the_kernel_serves_what_its_jax_numpy_form_serves(params,
                                                          monkeypatch):
    """The whole served path with ``cgx_gdn_update`` (interpreted here, the
    state donated and aliased through every decode step) against the same
    path with the ``jax.numpy`` form: the same tokens, logits to 1e-4 of
    their spread (float32 rounding in another order, through 36 steps)."""
    monkeypatch.setenv("CGX_KV_BITS", "0")
    prompt, gen = _prompt(PAGE + 3, seed=5), 2 * PAGE + 4
    served = {}
    for impl in ("xla", "pallas"):
        monkeypatch.setenv("CGX_CODEC_IMPL", impl)
        sched_mod.invalidate_decode_cache("test")
        served[impl] = _served_logits(params, _cfg(), prompt, gen)
    assert served["xla"][0] == served["pallas"][0]
    x, k = served["xla"][1], served["pallas"][1]
    assert np.max(np.abs(x - k)) < 1e-4 * np.std(x)


def test_layers_that_name_different_streams_build(params, monkeypatch):
    """The adapter's layers name different streams and the programs build:
    ``k`` and ``v`` pools and tails on the full-attention layers alone,
    ``conv`` and ``gdn`` state rows a lane on the delta-rule layers alone,
    None where a layer has no such stream; the program key holds the state
    streams, so a narrower state is another program; the state's bytes are
    the scheduler's gauge and the memory ledger's ``serve.state`` owner."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    noted = []
    monkeypatch.setattr(
        memledger, "note_alloc",
        lambda owner, n=1, nbytes=0: noted.append((owner, n, nbytes)))
    cfg = _cfg()
    server = HybridGDNServer(cfg, params, _serve())
    sched = ContinuousBatchScheduler(server)
    prog, st = sched._prog, sched._state
    assert prog.names == ("k", "v") and prog.state_names == ("conv", "gdn")
    for layer in range(cfg.n_layer):
        attention = layer in ATTENTION
        assert sorted(st["pools"][layer]) == (["k", "v"] if attention else [])
        assert (prog.specs[layer] is not None) == attention
        for name in ("tail_k", "tail_v"):
            assert (st[name][layer] is not None) == attention
        for name in ("state_conv", "state_gdn"):
            assert (st[name][layer] is None) == attention
    spec = prog.specs[2]
    assert (spec.n_head, spec.d_head, spec.bits) == (4, 16, 8)
    assert st["tail_k"][4].shape == (3, PAGE, 4 * 16)
    assert st["state_conv"][0].shape == (3, 3, D_QKV)
    assert st["state_gdn"][3].shape == (3, 8, D_VALUE)
    assert st["state_gdn"][3].dtype == jnp.float32
    held = 3 * len(DELTA) * (3 * D_QKV + 8 * D_VALUE) * 4
    assert server.state_bytes_per_lane() * 3 == held
    assert metrics.get("cgx.serve.state.bytes") == held
    assert ("serve.state", 3, held) in noted
    key = sched_mod._program_key(server)
    assert key[0] == "hybrid_gdn"
    narrow = HybridGDNServer(cfg, params, _serve(), state_dtype=jnp.bfloat16)
    assert sched_mod._program_key(narrow) != key
    assert narrow.state_bytes_per_lane() * 2 == server.state_bytes_per_lane()
    monkeypatch.setenv("CGX_KV_BITS", "4")
    assert sched_mod._program_key(server) != key


def test_admissions_write_the_lanes_state(params, monkeypatch):
    """``cgx.serve.state.lane_writes`` counts this adapter's admissions (a
    lane's ``conv`` and ``gdn`` rows written from the prefill's device
    arrays), one a request."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    sched = ContinuousBatchScheduler(
        HybridGDNServer(_cfg(), params, _serve()))
    before = metrics.get("cgx.serve.state.lane_writes")
    for i in range(4):
        sched.submit(Request(id=f"r{i}", tokens=_prompt(5 + i, seed=i),
                             max_new_tokens=3))
    assert sched.run(deadline_s=300.0)
    assert metrics.get("cgx.serve.state.lane_writes") - before == 4


def test_the_global_guard_leaves_every_held_lanes_logits_bit_for_bit(
        params, monkeypatch):
    """A step's logits with the attention layers' read guarded by the
    lane's committed pages (``adapter.page_live``) are the logits of the
    read of the whole table on every held lane, finite on a vacated one,
    through a batch of a short request (it finishes first and leaves its
    lane vacant), one that commits its second page on the way and one
    prefilled at four pages of its six."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    sv = _serve()
    seen = serving_guard.steps_with_and_without_the_guard(
        HybridGDNServer(_cfg(), params, sv), hybrid_mod,
        [(_prompt(5, seed=10), 3), (_prompt(PAGE + 9, seed=11), 14),
         (_prompt(4 * PAGE + 5, seed=12), 14)])
    share = serving_guard.assert_held_lanes_bit_for_bit(seen, sv.pages_per_seq)
    assert 0.2 < share < 0.4  # 5-6 of the table's 18 slots


def test_disaggregated_path_refuses_a_recurrent_state(params):
    """The transport's frames are K and V pages of every layer; no frame
    kind ships a lane's recurrent state. The adapter is refused by name and
    in plain words at both ends, before anything is shipped."""
    server = HybridGDNServer(_cfg(), params, _serve())
    store = FakeStore()
    with pytest.raises(ValueError, match="ships K and V page frames") as e:
        ContinuousBatchScheduler(server, receiver=KvPageReceiver(store))
    assert "'hybrid_gdn'" in str(e.value)
    assert "recurrent state ['conv', 'gdn']" in str(e.value)
    with pytest.raises(ValueError, match="local prefill only"):
        PrefillWorker(server, store)


def test_the_serve_plan_is_told_pages_and_state_apart(params, monkeypatch):
    """``ServeConfig.from_env`` tells the serve plan what a token's pages
    weigh over the full-attention layers alone (and that they are that many
    layers' frames) and, apart, what a lane's matrix state weighs whatever
    its length."""
    from torch_cgx_tpu.parallel import planner

    asked = []
    real = planner.solve_serve_plan

    def spy(**kw):
        asked.append((kw["kv_token_bytes"], kw["n_layers"],
                      kw["state_lane_bytes"]))
        return real(**kw)

    monkeypatch.setattr(planner, "solve_serve_plan", spy)
    for name in ("CGX_KV_PAGE_TOKENS", "CGX_KV_SHIP_DEPTH"):
        monkeypatch.delenv(name, raising=False)
    cfg = _cfg()
    state = len(DELTA) * (3 * D_QKV + 8 * D_VALUE) * 4
    assert cfg.kv_bytes_per_token() == 2 * len(ATTENTION) * (4 * 16) * 4
    assert cfg.state_bytes_per_lane() == state
    ServeConfig.from_env(cfg)
    assert asked == [(2 * 2 * 64 * 4, 2, state)]
