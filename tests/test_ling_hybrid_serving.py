"""The KDA / latent-attention adapter (``serving/hybrid.HybridLatentMoEServer``)
through the one scheduler, against the plain reference
(``benchmark/reference_ling_hybrid.py``: the delta rule a position at a time,
expanded attention, the held experts looped).

Tiny sizes, seeded weights (``benchmark/weights_ling_hybrid.py``), float32
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

from benchmark import reference_ling_hybrid as reference  # noqa: E402
from benchmark import weights_ling_hybrid as weights  # noqa: E402
from torch_cgx_tpu.models import ling_hybrid as lh  # noqa: E402
from torch_cgx_tpu.models.ling_hybrid import LingHybridConfig  # noqa: E402
from torch_cgx_tpu.observability import memledger  # noqa: E402
from torch_cgx_tpu.ops import dispatch as ops_dispatch  # noqa: E402
from torch_cgx_tpu.ops import gdn  # noqa: E402
from torch_cgx_tpu.parallel import moe  # noqa: E402
from torch_cgx_tpu.serving import scheduler as sched_mod  # noqa: E402
from torch_cgx_tpu.serving.hybrid import HybridLatentMoEServer  # noqa: E402
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

PAGE = 16  # = the delta rule's chunk and sub-chunk
# The published keys at a tiny size: 7 of 12 layers (layer 0, dense, and a
# whole period, layers 6-11), 16 of 64 experts, four groups of which two stay.
HF = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    num_hidden_layers=7, layers_kept=[0, 6, 7, 8, 9, 10, 11],
    first_k_dense_replace=2, layer_group_size=6, q_lora_rank=None,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rotary_dim=8, rope_theta=6000000, rms_norm_eps=1e-6,
    short_conv_kernel_size=4, kda_safe_gate=True, kda_lower_bound=-5,
    no_kda_lora=True, use_kda_lora=False, linear_silu=True, use_qk_norm=True,
    gated_attention_proj_granularity_type="head_wise", group_norm_size=1,
    num_kv_heads_for_linear_attn=0, use_mla_nope=False,
    num_experts=16, num_experts_published=64, first_expert=0,
    num_experts_per_tok=4, n_group=4, topk_group=2, score_function="sigmoid",
    moe_router_enable_expert_bias=True, norm_topk_prob=True,
    routed_scaling_factor=2.5, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32,
    # A clamp on a layer that is not kept (published layer 3) is no matter.
    expert_swiglu_limit_list=[0, 0, 0, 4] + [0] * 8,
    share_expert_swiglu_limit_list=[0] * 12,
    precision={"params": "float32"},
    # Peaked attention, so that a page's rounding shows in the logits.
    init={"q_std": 0.3, "kv_b_std": 0.3},
)
KDA, LATENT, DENSE = (0, 1, 2, 3, 4, 5), (6,), (0,)
D_QKV, D_INNER = 3 * 4 * 16, 4 * 16


@pytest.fixture(autouse=True)
def _clean():
    edges.clear_edges()
    with jax.default_matmul_precision("highest"):
        yield
    edges.clear_edges()


@pytest.fixture(scope="module")
def params():
    return weights.make_params(HF, 37)


def _cfg(hf=HF, **kw):
    return LingHybridConfig.from_hf(
        hf, **{"dtype": jnp.float32, "chunk": PAGE, "q_block": 32, **kw})


def _serve(**kw):
    base = dict(page_tokens=PAGE, max_batch=3, max_pages=24, max_seq=96,
                ship_depth=4)
    return ServeConfig(**{**base, **kw})


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, HF["vocab_size"], n)]


def _operands(rng, b, s, h, dk, dv, floor_channel=True):
    """``q``, ``k`` (normalised as the layer normalises them), ``v``, ``log
    alpha`` a key channel over slow and fast channels (channel 0 at the safe
    gate's bound, -5, at every position; channel 1 never decaying), ``beta``
    in 0-1."""
    q, k = (rng.standard_normal((b, s, h, dk)) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, s, h, dv))
    g = -5.0 * rng.uniform(0.0, 1.0, (b, s, h, dk)) ** 4
    if floor_channel:
        g[..., 0], g[..., 1] = -5.0, 0.0
    beta = rng.uniform(0.0, 1.0, (b, s, h))
    return tuple(np.asarray(t, np.float32) for t in (q, k, v, g, beta))


def _sequential(q, k, v, g, beta, state):
    """The recurrence one position at a time, in float64 numpy."""
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    s = np.asarray(state, np.float64).copy()
    out = []
    for t in range(q.shape[1]):
        s = s * np.exp(g[:, t])[..., None]
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", s, k[:, t]))
        s = s + k[:, t][..., None] * u[:, :, None, :]
        out.append(np.einsum("bhkv,bhk->bhv", s, q[:, t]))
    return np.stack(out, axis=1), s


# ---------------------------------------------------------------------------
# The configuration.
# ---------------------------------------------------------------------------


def test_config_reads_the_cut_from_the_published_keys():
    cfg = _cfg()
    assert cfg.layer_types == ("kda",) * 6 + ("mla",)
    assert cfg.dense_layers == DENSE and cfg.expert_layers == (1, 2, 3, 4, 5,
                                                               6)
    assert cfg.attention_layers == LATENT
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert) == (64, 16, 0)
    assert (cfg.d_qkv, cfg.d_inner) == (D_QKV, D_INNER)
    whole = _cfg(dict(HF, num_hidden_layers=12, layers_kept=list(range(11))
                      + [11], expert_swiglu_limit_list=[0] * 12,
                      num_experts=64))
    assert whole.attention_layers == (5, 11) and whole.dense_layers == (0, 1)
    assert whole.experts_held is None and whole.n_held == 64


@pytest.mark.parametrize("key,value,says", [
    ("kda_safe_gate", False, "kda_safe_gate"),
    ("use_kda_lora", True, "use_kda_lora"),
    ("gated_attention_proj_granularity_type", "element_wise", "granularity"),
    ("num_kv_heads_for_linear_attn", 2, "num_kv_heads_for_linear_attn"),
    ("group_norm_size", 4, "group_norm_size"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rotary_dim", 16, "rotary_dim"),
    ("layers_kept", [0, 6, 7, 8, 9, 10, 11, 5], "layers_kept"),
    # A clamped SwiGLU on a layer that is kept (published layer 11 here).
    ("expert_swiglu_limit_list", [0] * 10 + [4, 4], "swiglu"),
    ("share_expert_swiglu_limit_list", [7] + [0] * 11, "swiglu"),
])
def test_config_refuses_what_the_layers_do_not_compute(key, value, says):
    with pytest.raises(ValueError, match=says):
        _cfg(dict(HF, **{key: value}))
    if "swiglu" in says:
        with pytest.raises(ValueError, match="clamps nothing"):
            reference._cfg_items(dict(HF, **{key: value}))


def test_config_refuses_a_decay_that_overflows_a_sub_chunk():
    with pytest.raises(ValueError, match="overflows"):
        _cfg(dict(HF, kda_lower_bound=-6))
    with pytest.raises(ValueError, match="sub-chunks"):
        _cfg(chunk=24)


# ---------------------------------------------------------------------------
# The KDA layer.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("length,chunk", [
    (PAGE, 16), (3 * PAGE, 16), (2 * PAGE + 5, 16), (3, 16),
    (4 * PAGE, 64), (5 * PAGE + 7, 64), (4 * PAGE, 32),
])
def test_chunked_kda_equals_the_sequential_recurrence(length, chunk, carried):
    """``kda_chunk_scan`` (within a chunk the triangular system over pairwise
    decays formed a sub-chunk of 16 at a time, between chunks the carried
    state) against the recurrence a position at a time, for lengths that are
    and are not whole chunks, chunks of one and of several sub-chunks, from
    zeros and from a state handed in, with a channel whose ``log alpha`` sits
    at -5 at every position (``exp(-G)`` over a whole chunk of 64 would be
    ``exp(320)``: nothing overflows, every value is finite) and one that
    never decays: outputs and final state to float32 rounding (limit 2e-5 of
    the largest value; readings under 2e-6)."""
    rng = np.random.default_rng(length + chunk)
    b, h, dk, dv = 2, 3, 16, 8
    ops = _operands(rng, b, length, h, dk, dv)
    state = (rng.standard_normal((b, h, dk, dv)).astype(np.float32)
             if carried else np.zeros((b, h, dk, dv), np.float32))
    o, final = lh.kda_chunk_scan(*(jnp.asarray(t) for t in ops), chunk,
                                 jnp.asarray(state) if carried else None)
    want_o, want_final = _sequential(*ops, state)
    assert o.shape == (b, length, h, dv)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(
        jnp.all(jnp.isfinite(final)))
    for got, want in ((o, want_o), (final, want_final)):
        assert np.max(np.abs(np.asarray(got) - want)) < 2e-5 * np.max(
            np.abs(want))


def test_a_decay_a_head_is_the_gated_delta_rule():
    """With every channel of a head decaying alike, the chunked KDA is
    ``olmo_hybrid.gdn_chunk_scan``: the same carried scan under both."""
    from torch_cgx_tpu.models import olmo_hybrid as oh

    rng = np.random.default_rng(4)
    q, k, v, g, beta = _operands(rng, 2, 3 * PAGE + 2, 3, 16, 8, False)
    g = np.broadcast_to(g[..., :1], g.shape)
    o, final = lh.kda_chunk_scan(*(jnp.asarray(t) for t in
                                   (q, k, v, g, beta)), PAGE)
    o_h, final_h = oh.gdn_chunk_scan(*(jnp.asarray(t) for t in
                                       (q, k, v, g[..., 0], beta)), PAGE)
    assert float(jnp.max(jnp.abs(o - o_h))) < 2e-5 * float(
        jnp.max(jnp.abs(o_h)))
    assert float(jnp.max(jnp.abs(final - final_h))) < 2e-5 * float(
        jnp.max(jnp.abs(final_h)))


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_kda_step_equals_the_references_step(form):
    """The one-step update, the ``jax.numpy`` form and the kernel
    (interpreted here), in the lanes' layout ``(dk, H * dv)``, against the
    reference's recurrence a position at a time, through a dozen steps:
    outputs and final state to float32 rounding (limit 2e-5 of the largest
    value)."""
    rng = np.random.default_rng(8)
    b, s, h, dk, dv = 2, 12, 4, 16, 128
    q, k, v, g, beta = _operands(rng, b, s, h, dk, dv)
    update = (gdn.gdn_update_xla if form == "xla" else
              lambda *a: gdn.gdn_update_pallas(*a, name="cgx_kda_update",
                                               interpret=True))
    lanes = jnp.zeros((b, dk, h * dv), jnp.float32)
    outs = []
    for t in range(s):
        lanes, o_t = update(lanes, *(jnp.asarray(x[:, t]) for x in
                                     (q, k, v, np.exp(g), beta)))
        outs.append(o_t.reshape(b, h, dv))
    for lane in range(b):
        want_o, want_state = reference.kda_recurrence(
            *(jnp.asarray(t[lane]) for t in (q, k, v, np.exp(g), beta)))
        got_o = jnp.stack([o[lane] for o in outs])
        got_state = lanes[lane].reshape(dk, h, dv).transpose(1, 0, 2)
        for got, want in ((got_o, want_o), (got_state, want_state)):
            assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
                jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads,dk,dv,blocks", [
    (4, 16, 128, (4, 1)),    # whole vectors a head, one block
    (32, 128, 128, (16, 1)),  # the published widths: two grid steps a lane
    (4, 8, 192, (4, 2)),     # heads two at a time, the decay spread with k
    (4, 8, 16, (4, 4)),      # not whole groups: the row one block
])
def test_kda_update_lowerings_agree(heads, dk, dv, blocks, state_dtype):
    """``cgx_kda_update`` (``ops/gdn.py``'s one kernel under the call site's
    name, interpreted here) against its ``jax.numpy`` form with a decay a key
    channel, to float32 rounding, over a state no lane of which is zero; the
    kernel writes the state over its operand, and a decay a head handed in
    as ``(B, H)`` (the kernel's row operand) gives what that number repeated
    down a column gives (its column operand), to float32 rounding."""
    rng = np.random.default_rng(heads * dv + dk)
    b = 2
    assert gdn.head_blocks(heads, dk, dv) == blocks
    q, k, v, g, beta = (jnp.asarray(t[:, 0])
                        for t in _operands(rng, b, 1, heads, dk, dv))
    state = jnp.asarray(rng.standard_normal((b, dk, heads * dv)), state_dtype)
    args = (state, q, k, v, jnp.exp(g), beta)
    new_k, o_k = gdn.gdn_update_pallas(*args, name="cgx_kda_update",
                                       interpret=True)
    new_x, o_x = gdn.gdn_update_xla(*args)
    assert new_k.dtype == state_dtype and o_k.dtype == jnp.float32
    step = 2.0 ** -8 if state_dtype == jnp.bfloat16 else 2e-6
    new_k, new_x = (np.asarray(t, np.float32) for t in (new_k, new_x))
    assert np.max(np.abs(new_k - new_x)) <= step * np.max(np.abs(new_x))
    assert float(jnp.max(jnp.abs(o_k - o_x))) < 1e-5 * float(
        jnp.max(jnp.abs(o_x)))
    text = str(jax.make_jaxpr(
        lambda *a: gdn.gdn_update_pallas(*a, name="cgx_kda_update",
                                         interpret=True))(*args))
    assert "input_output_aliases=((0, 0),)" in text
    assert "cgx_kda_update" in text and "cgx_gdn_update" not in text
    a_head = jnp.exp(g[..., 0])
    repeated = jnp.broadcast_to(a_head[..., None], g.shape)
    for update in (gdn.gdn_update_xla,
                   lambda *a: gdn.gdn_update_pallas(*a, interpret=True)):
        one, o_one = update(state, q, k, v, a_head, beta)
        many, o_many = update(state, q, k, v, repeated, beta)
        for got, want in ((one, many), (o_one, o_many)):
            got, want = (np.asarray(t, np.float32) for t in (got, want))
            assert np.max(np.abs(got - want)) <= step * np.max(np.abs(want))


@pytest.mark.tpu  # the compiled Mosaic kernel at the published widths
def test_kda_update_tpu():
    rng = np.random.default_rng(37)
    b, heads, dk, dv = 4, 32, 128, 128
    q, k, v, g, beta = (jnp.asarray(t[:, 0])
                        for t in _operands(rng, b, 1, heads, dk, dv))
    state = jnp.asarray(rng.standard_normal((b, dk, heads * dv)), jnp.float32)
    args = (state, q, k, v, jnp.exp(g), beta)
    new_x, o_x = gdn.gdn_update_xla(*args)
    new_k, o_k = gdn.gdn_update_pallas(*args, name="cgx_kda_update")
    assert float(jnp.max(jnp.abs(new_k - new_x))) <= 2e-6 * float(
        jnp.max(jnp.abs(new_x)))
    assert float(jnp.max(jnp.abs(o_k - o_x))) < 1e-5 * float(
        jnp.max(jnp.abs(o_x)))


@pytest.mark.parametrize("impl,lowering", [("pallas", "pallas"),
                                           ("xla", "xla"), ("auto", "xla")])
def test_kda_update_dispatch_counts_its_lowering(monkeypatch, impl, lowering):
    """``ops.dispatch.kda_update`` is dispatched as the codec is
    (``CGX_CODEC_IMPL``; off the TPU ``auto`` is the ``jax.numpy`` form) and
    counts the call site by lowering, under its own name."""
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    metrics.reset()
    b, h, dk, dv = 2, 2, 8, 64
    alpha = jnp.full((b, h, dk), 0.5).at[:, :, 0].set(1.0)
    args = (jnp.ones((b, dk, h * dv)), jnp.ones((b, h, dk)),
            jnp.ones((b, h, dk)) / 8, jnp.ones((b, h, dv)), alpha,
            jnp.ones((b, h)))
    new, o = ops_dispatch.kda_update(*args)
    assert new.shape == (b, dk, h * dv) and o.shape == (b, h * dv)
    # S~ rows: 1 and seven of 0.5; S~^T k = 4.5 / 8; u = 0.4375; S' = S~ +
    # 0.125 u; o = sum of S' rows
    assert float(o[0, 0]) == pytest.approx(4.5 + 8 * 0.125 * 0.4375)
    assert metrics.snapshot("cgx.codec.lowering.") == {
        f"cgx.codec.lowering.kda_update.{lowering}": 1.0}


@pytest.mark.parametrize("last_idx", [PAGE - 2, PAGE - 1, PAGE, PAGE + 1,
                                      2 * PAGE - 1, 2 * PAGE, 0, 2])
def test_prefill_state_is_the_state_at_the_last_real_position(params,
                                                              last_idx):
    """A right-padded prompt, its last real position on either side of a
    chunk's edge and inside the convolution's first window: the state
    ``kda_prefill`` returns is the one after ``last_idx`` (the pad takes
    ``beta = 0`` and ``log alpha = 0``; the convolution's state is its inputs
    ending at ``last_idx``), equal to the unpadded prompt's and to ``last_idx
    + 1`` single steps from zeros."""
    cfg, pk = _cfg(), params["layer_1"]["kda"]
    rng = np.random.default_rng(last_idx)
    n = last_idx + 1
    y = jnp.asarray(rng.standard_normal((2, n, 64)), jnp.float32)
    junk = jnp.asarray(rng.standard_normal((2, 3 * PAGE - n, 64)),
                       jnp.float32) * 50
    out, conv, state = lh.kda_prefill(cfg, pk, y, last_idx)
    out_p, conv_p, state_p = lh.kda_prefill(
        cfg, pk, jnp.concatenate([y, junk], axis=1), last_idx)
    scale = max(float(jnp.max(jnp.abs(state))), 1e-3)
    assert conv.shape == (2, 3, D_QKV) and state.shape == (2, 16, D_INNER)
    assert float(jnp.max(jnp.abs(conv - conv_p))) < 1e-5
    assert float(jnp.max(jnp.abs(state - state_p))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(out - out_p[:, :n]))) < 1e-5
    c = jnp.zeros((2, cfg.d_conv - 1, cfg.d_qkv))
    s = jnp.zeros((2, cfg.d_head, cfg.d_inner))
    for t in range(n):
        o, c, s = lh.kda_step(cfg, pk, y[:, t], c, s)
    assert float(jnp.max(jnp.abs(c - conv))) < 1e-5
    assert float(jnp.max(jnp.abs(s - state))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(o - out[:, last_idx]))) < 1e-5


# ---------------------------------------------------------------------------
# The model against the reference.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("length", [PAGE, 2 * PAGE + 5])
def test_model_forward_equals_the_reference(params, length):
    """The program's full forward (chunked KDA, expanded latent attention in
    query blocks, sorted assignments over the held experts) against the plain
    reference's (a ``lax.scan`` over positions, a loop over the held
    experts), float32 both: logits to 5e-5 of the reference's spread."""
    tokens = jnp.asarray(_prompt(length, seed=length), jnp.int32)
    got = np.asarray(lh.forward(_cfg(), params, tokens[None])[0])
    want = np.asarray(reference.forward(params, tokens, HF, q_block=32,
                                        expert_block=8))
    assert np.max(np.abs(got - want)) < 5e-5 * np.std(want)


def test_a_low_rank_query_takes_the_latent_models_path(params):
    """A tree whose latent-attention layer holds ``q_a``, ``q_a_norm`` and
    ``q_b`` (a non-null ``q_lora_rank``) is projected as ``mla_moe`` projects
    it; with ``q_b`` the full-rank ``q`` behind an identity ``q_a`` whose norm
    is undone, the logits are the full-rank tree's."""
    from torch_cgx_tpu.models import mla_moe

    cfg, pa = _cfg(), params["layer_6"]["attn"]
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.standard_normal((1, 5, 64)), jnp.float32)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.eps)
    positions = jnp.arange(5)[None]
    low = dict({k: v for k, v in pa.items() if k != "q"},
               q_a=jnp.eye(64), q_a_norm=jnp.ones((64,)), q_b=pa["q"])
    full = mla_moe.mla_project(cfg, y, pa, positions)
    ranked = mla_moe.mla_project(cfg, y, low, positions)
    for a, b in zip(full, ranked):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def _served_logits(params, cfg, prompt, gen, **server_kw):
    """Serve one request through the scheduler and return ``(tokens, the
    decode steps' logits (gen - 1, V))``: the logits are read by the
    adapter's own ``decode_forward`` on the very state each ``decode_step``
    call is given."""
    server = HybridLatentMoEServer(cfg, params, _serve(), **server_kw)
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
        params, jnp.asarray(prompt + tokens[:-1], jnp.int32), HF, q_block=32,
        expert_block=8))
    assert tokens[0] == int(np.argmax(ref[len(prompt) - 1]))
    ref = ref[len(prompt): len(prompt) + len(got)]
    return float(np.max(np.abs(got - ref)) / np.std(ref))


# What the served path may cost, as the largest logit difference over the
# reference's spread across the vocabulary, float32 activations and state.
# Readings here over the four prompts: 8-bit latent pages 0.021-0.036, 4-bit
# pages 0.42-0.49; PAGES_LIMIT lies 2.8 times above the largest sound reading
# and 4.2 times below the smallest 4-bit one. A bfloat16 state reads 0.10-0.14
# over 8-bit pages and over raw ones alike (it rounds once a token for the
# life of the request), too near the pages' limit to be told apart by it, so
# the state is held to its own limit over raw (float16) pages: readings
# 0.0007-0.0010 with a float32 state, 0.11-0.14 with a bfloat16 one;
# STATE_LIMIT lies 4 times above the one and 27 below the other.
PAGES_LIMIT = 0.1
STATE_LIMIT = 0.004


@pytest.mark.parametrize("prompt_len", [2 * PAGE + 3, 2 * PAGE, 3, PAGE - 1],
                         ids=["mid_page", "page_edge", "under_the_conv",
                              "fills_its_tail"])
@pytest.mark.parametrize("bits,limit", [("8", PAGES_LIMIT),
                                        ("0", STATE_LIMIT)],
                         ids=["pages_8bit", "pages_raw"])
def test_prefill_then_decode_matches_reference_logits(params, monkeypatch,
                                                      prompt_len, bits,
                                                      limit):
    """Prefill of a right-padded prompt (chunked KDA, state taken at
    ``last_idx``, latent pages into the pools), then decode through the
    per-lane state and the pages (tails committing on the way, the absorbed
    attention, the held experts), against the plain reference's full forward
    over ``prompt + served tokens``: logits at every decode position, for
    prompts that end mid-page, on a page edge, before the convolution's
    window is full, and one token short of a page."""
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


def test_the_kernel_serves_what_its_jax_numpy_form_serves(params,
                                                          monkeypatch):
    """The whole served path with ``cgx_kda_update`` (interpreted here, the
    state donated and aliased through every decode step) against the same
    path with the ``jax.numpy`` form: the same tokens, logits to 1e-3 of
    their spread (float32 rounding in another order through twenty steps of
    seven layers; reading 3e-4)."""
    monkeypatch.setenv("CGX_KV_BITS", "0")
    prompt, gen = _prompt(PAGE + 3, seed=5), PAGE + 4
    served = {}
    for impl in ("xla", "pallas"):
        monkeypatch.setenv("CGX_CODEC_IMPL", impl)
        sched_mod.invalidate_decode_cache("test")
        served[impl] = _served_logits(params, _cfg(), prompt, gen)
    assert served["xla"][0] == served["pallas"][0]
    x, k = served["xla"][1], served["pallas"][1]
    assert np.max(np.abs(x - k)) < 1e-3 * np.std(x)


# ---------------------------------------------------------------------------
# The adapter behind the scheduler.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl,lowering", [("xla", "xla"),
                                           ("pallas", "pallas")])
def test_kda_update_call_sites_are_counted_by_lowering(params, monkeypatch,
                                                       impl, lowering):
    """``cgx.codec.lowering.kda_update.<lowering>`` counts the decode
    program's call sites, one a KDA layer; no ``gdn_update`` site is
    counted."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    sched_mod.invalidate_decode_cache("test")
    metrics.reset()
    server = HybridLatentMoEServer(_cfg(), params, _serve())
    sched = ContinuousBatchScheduler(server)
    jax.make_jaxpr(sched._prog.decode_step)(server.p, sched._state)
    assert metrics.snapshot("cgx.codec.lowering.kda_update.") == {
        f"cgx.codec.lowering.kda_update.{lowering}": float(len(KDA))}
    assert metrics.snapshot("cgx.codec.lowering.gdn_update.") == {}


def test_state_streams_and_latent_streams_build_in_one_model(params,
                                                             monkeypatch):
    """The adapter's layers name state streams and latent streams and the
    programs build: ``c`` and ``kr`` pools and tails on the latent-attention
    layer alone, ``conv`` and ``kda`` state rows a lane on the KDA layers
    alone, None where a layer has no such stream; the program key holds the
    state streams, so a narrower state is another program; the state's bytes
    are the scheduler's gauge and the memory ledger's ``serve.state``
    owner."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    noted = []
    monkeypatch.setattr(
        memledger, "note_alloc",
        lambda owner, n=1, nbytes=0: noted.append((owner, n, nbytes)))
    cfg = _cfg()
    server = HybridLatentMoEServer(cfg, params, _serve())
    sched = ContinuousBatchScheduler(server)
    prog, st = sched._prog, sched._state
    assert prog.names == ("c", "kr") and prog.state_names == ("conv", "kda")
    for layer in range(cfg.n_layer):
        latent = layer in LATENT
        assert sorted(st["pools"][layer]) == (["c", "kr"] if latent else [])
        assert (prog.specs[layer] is not None) == latent
        for name in ("tail_c", "tail_kr"):
            assert (st[name][layer] is not None) == latent
        for name in ("state_conv", "state_kda"):
            assert (st[name][layer] is None) == latent
    c, kr = (spec for _, spec in prog.streams[6])
    assert (c.n_head, c.d_head, c.bits) == (1, 32, 8)
    assert (kr.n_head, kr.d_head, kr.bits) == (1, 8, 8)
    assert st["tail_c"][6].shape == (3, PAGE, 32)
    assert st["state_conv"][0].shape == (3, 3, D_QKV)
    assert st["state_kda"][5].shape == (3, 16, D_INNER)
    assert st["state_kda"][5].dtype == jnp.float32
    held = 3 * len(KDA) * (3 * D_QKV + 16 * D_INNER) * 4
    assert server.state_bytes_per_lane() * 3 == held
    assert metrics.get("cgx.serve.state.bytes") == held
    assert ("serve.state", 3, held) in noted
    assert cfg.kv_bytes_per_token() == (32 + 8) * 4
    key = sched_mod._program_key(server)
    assert key[0] == "hybrid_kda_mla"
    narrow = HybridLatentMoEServer(cfg, params, _serve(),
                                   state_dtype=jnp.bfloat16)
    assert sched_mod._program_key(narrow) != key
    assert narrow.state_bytes_per_lane() * 2 == server.state_bytes_per_lane()
    monkeypatch.setenv("CGX_KV_BITS", "4")
    assert sched_mod._program_key(server) != key


def test_a_decode_step_counts_the_held_experts(params, monkeypatch):
    """``step_counters`` are the held layer's (``moe.HELD_STATS``): over a
    run, ``cgx.serve.moe.assignments`` is every assignment the router made
    (active lanes x ``top_k`` x expert layers a step),
    ``cgx.serve.moe.held_assignments`` those that fell on the 16 experts held
    of 64 (some, not all), nothing dropped; a tree that holds every expert
    counts ``moe.STATS`` alone."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    server = HybridLatentMoEServer(_cfg(), params, _serve())
    assert server.step_counters == tuple(
        f"moe.{n}" for n in moe.HELD_STATS)
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    for i in range(3):
        sched.submit(Request(id=f"r{i}", tokens=_prompt(5 + i, seed=i),
                             max_new_tokens=9))
    assert sched.run(deadline_s=300.0)
    made = metrics.get("cgx.serve.moe.assignments")
    held = metrics.get("cgx.serve.moe.held_assignments")
    # 3 lanes x 8 decode steps x 4 experts a token x 6 expert layers.
    assert made == 3 * 8 * 4 * 6
    assert 0 < held < made
    assert metrics.get("cgx.serve.moe.dropped") == 0
    assert 0 < metrics.get("cgx.serve.moe.experts_touched") <= 8 * 6 * 16
    assert metrics.get("cgx.serve.state.lane_writes") == 3
    whole = _cfg(dict(HF, num_experts=64))
    assert HybridLatentMoEServer(
        whole, params, _serve()).step_counters == tuple(
        f"moe.{n}" for n in moe.STATS)


def test_a_lane_does_not_depend_on_what_other_lanes_hold_or_held(
        params, monkeypatch):
    """A request's tokens are the same served alone in a fresh scheduler and
    served in a lane that a longer request has just left beside two other
    busy lanes: an admission overwrites the lane's recurrent state whole, a
    lane's state reaches no other lane, and the experts' sort over all lanes'
    assignments gives each row its own."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    cfg = _cfg()
    probe = Request(id="probe", tokens=_prompt(PAGE + 5, seed=9),
                    max_new_tokens=PAGE + 6)
    alone = ContinuousBatchScheduler(
        HybridLatentMoEServer(cfg, params, _serve()))
    alone.submit(probe)
    assert alone.run(deadline_s=300.0)
    want = list(probe.output)

    sched = ContinuousBatchScheduler(
        HybridLatentMoEServer(cfg, params, _serve()))
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
    assert np.any(np.asarray(sched._state["state_kda"][0])[lane] != 0)
    again = Request(id="probe2", tokens=list(probe.tokens),
                    max_new_tokens=probe.max_new_tokens)
    sched.submit(again)
    sched.step()
    assert sched._lanes[lane] is again
    assert sched.run(deadline_s=300.0)
    assert again.output == want


# sha256 of the decode step's jaxpr at ``_cfg()`` / ``_serve()`` and 8-bit
# pages, computed on the parent commit's ``git archive`` (PR 46), by lowering.
PARENT_DECODE_STEP = {"xla": "dff40274e9ccce3a", "pallas": "c5ce042cb2e16f9d"}


@pytest.mark.parametrize("impl", sorted(PARENT_DECODE_STEP))
def test_the_decode_step_reads_its_whole_table_as_the_parent_did(
        params, monkeypatch, impl):
    """The latent layers' read takes no guard (``layer_cache_rows`` without
    ``live``: the adapter says so), so the decode program is the one from
    before the K/V adapters' global read had one, jaxpr for jaxpr, on the
    XLA codec and on the kernel."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    server = HybridLatentMoEServer(_cfg(), params, _serve())
    assert not server.guards_global_read
    assert serving_guard.decode_step_sha(server) == PARENT_DECODE_STEP[impl]


def test_disaggregated_path_refuses_a_recurrent_state(params):
    """The transport's frames are K and V pages of every layer; no frame
    kind ships a lane's recurrent state or a latent page. The adapter is
    refused by name at both ends, before anything is shipped."""
    server = HybridLatentMoEServer(_cfg(), params, _serve())
    store = FakeStore()
    with pytest.raises(ValueError, match="ships K and V page frames") as e:
        ContinuousBatchScheduler(server, receiver=KvPageReceiver(store))
    assert "'hybrid_kda_mla'" in str(e.value)
    with pytest.raises(ValueError, match="local prefill only"):
        PrefillWorker(server, store)
