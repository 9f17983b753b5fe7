"""The hybrid state-space adapter (``serving/hybrid.py``) through the one
scheduler, against the plain reference
(``benchmark/reference_granite_hybrid.py``, the recurrence a position at a
time).

Tiny sizes, seeded weights (``benchmark/weights_granite_hybrid.py``),
float32 activations at full matmul precision unless a test says otherwise,
so that what a tolerance bounds is the thing it names (a page's rounding, a
narrower state) and not the CPU's arithmetic.
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

from benchmark import reference_granite_hybrid as reference  # noqa: E402
from benchmark import weights_granite_hybrid as weights  # noqa: E402
from torch_cgx_tpu.models import granite_hybrid as gh  # noqa: E402
from torch_cgx_tpu.models.gpt2 import GPT2Config  # noqa: E402
from torch_cgx_tpu.models.granite_hybrid import HybridConfig  # noqa: E402
from torch_cgx_tpu.ops import dispatch as ops_dispatch  # noqa: E402
from torch_cgx_tpu.ops import ssm  # noqa: E402
from torch_cgx_tpu.serving import scheduler as sched_mod  # noqa: E402
from torch_cgx_tpu.serving import hybrid as hybrid_mod  # noqa: E402
from torch_cgx_tpu.serving.hybrid import HybridSSMServer  # noqa: E402
from torch_cgx_tpu.serving.prefill import PrefillWorker  # noqa: E402
from torch_cgx_tpu.serving.adapter import ServeConfig  # noqa: E402
from torch_cgx_tpu.serving.gpt2 import GPT2Server  # noqa: E402
from torch_cgx_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.serving.transport import KvPageReceiver  # noqa: E402
from torch_cgx_tpu.utils.logging import metrics  # noqa: E402
from torch_cgx_tpu.wire import edges  # noqa: E402

from test_faults import FakeStore  # noqa: E402
import serving_guard  # noqa: E402

PAGE = 16  # = the scan's chunk: a padded prompt is whole chunks
HF = dict(
    vocab_size=512, hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, shared_intermediate_size=128,
    layer_types=["mamba", "attention", "mamba", "mamba", "attention"],
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=PAGE, mamba_n_groups=1, embedding_multiplier=12,
    residual_multiplier=0.22, attention_multiplier=0.125, logits_scaling=8,
    rms_norm_eps=1e-5, precision={"params": "float32"},
    # Queries and keys drawn large (attention without positions is then
    # peaked and a page's rounding shows in the logits), the recurrence's
    # operands too (the state's term is then a fair share of a mixer's
    # output beside the skip), as on the chip.
    init={"q_std": 0.3, "k_std": 0.3, "xbc_std": 0.1},
)
MAMBA, ATTENTION = (0, 2, 3), (1, 4)


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
    return HybridConfig.from_hf(HF, **{"dtype": jnp.float32, **kw})


def _serve(**kw):
    base = dict(page_tokens=PAGE, max_batch=3, max_pages=24, max_seq=96,
                ship_depth=4)
    return ServeConfig(**{**base, **kw})


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, HF["vocab_size"], n)]


def _sequential(x, dt, a, bm, cm, state):
    """The recurrence one position at a time, in float64 numpy."""
    x, dt, a, bm, cm = (np.asarray(t, np.float64) for t in (x, dt, a, bm, cm))
    h = np.asarray(state, np.float64).copy()
    ys = []
    for t in range(x.shape[1]):
        decay = np.exp(dt[:, t] * a)[:, :, None, None]
        h = decay * h + (dt[:, t, :, None] * x[:, t])[..., None] * bm[
            :, t, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", h, cm[:, t]))
    return np.stack(ys, axis=1), h


@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("length", [PAGE, 3 * PAGE, 2 * PAGE + 5, 3])
def test_chunked_scan_equals_the_sequential_recurrence(length, carried):
    """``ssd_scan`` (within a chunk by matrix products, between chunks by
    the carried state) against the recurrence a position at a time, for
    lengths that are and are not whole chunks, from zeros and from a state
    handed in: outputs and final state to float32 rounding (limit 2e-5 of
    the largest value; readings under 2e-6)."""
    rng = np.random.default_rng(length)
    b, h, p, n = 2, 4, 8, 16
    x = rng.standard_normal((b, length, h, p)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, (b, length, h)).astype(np.float32)
    a = -rng.uniform(1.0, 16.0, (h,)).astype(np.float32)
    bm, cm = (rng.standard_normal((b, length, n)).astype(np.float32)
              for _ in range(2))
    state = (rng.standard_normal((b, h, p, n)).astype(np.float32)
             if carried else np.zeros((b, h, p, n), np.float32))
    y, final = gh.ssd_scan(*(jnp.asarray(t) for t in (x, dt, a, bm, cm)),
                           PAGE, jnp.asarray(state) if carried else None)
    want_y, want_final = _sequential(x, dt, a, bm, cm, state)
    assert y.shape == (b, length, h, p)
    for got, want in ((y, want_y), (final, want_final)):
        assert np.max(np.abs(np.asarray(got) - want)) < 2e-5 * np.max(
            np.abs(want))


@pytest.mark.parametrize("length", [PAGE, 2 * PAGE + 5])
def test_model_forward_equals_the_reference(params, length):
    """The program's full forward (chunked scan, grouped attention) against
    the plain reference's (a ``lax.scan`` over positions, every K/V head
    repeated), float32 both: logits to 1e-5 of the reference's spread."""
    tokens = jnp.asarray(_prompt(length, seed=length), jnp.int32)
    got = np.asarray(gh.forward(_cfg(), params, tokens[None])[0])
    want = np.asarray(reference.forward(params, tokens, HF))
    assert np.max(np.abs(got - want)) < 1e-5 * np.std(want)


def test_prefill_state_is_the_state_at_the_last_real_position(params):
    """A right-padded prompt: the state ``mamba_prefill`` returns is the one
    after ``last_idx`` (the pad takes ``dt = 0``; the convolution's state is
    its inputs ending at ``last_idx``), equal to the unpadded prompt's and
    to ``last_idx + 1`` single steps from zeros."""
    cfg, pm = _cfg(), params["layer_0"]["mamba"]
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.standard_normal((2, 21, 64)), jnp.float32)
    junk = jnp.asarray(rng.standard_normal((2, 11, 64)), jnp.float32) * 50
    out, conv, state = gh.mamba_prefill(cfg, pm, y, 20)
    out_p, conv_p, state_p = gh.mamba_prefill(
        cfg, pm, jnp.concatenate([y, junk], axis=1), 20)
    scale = float(jnp.max(jnp.abs(state)))
    assert float(jnp.max(jnp.abs(conv - conv_p))) < 1e-5
    assert float(jnp.max(jnp.abs(state - state_p))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(out - out_p[:, :21]))) < 1e-5
    c = jnp.zeros((2, cfg.d_conv - 1, cfg.d_xbc))
    s = jnp.zeros((2, cfg.d_state, cfg.d_inner))
    for t in range(21):
        o, c, s = gh.mamba_step(cfg, pm, y[:, t], c, s)
    assert float(jnp.max(jnp.abs(c - conv))) < 1e-5
    assert float(jnp.max(jnp.abs(s - state))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(o - out[:, 20]))) < 1e-5


def _served_logits(params, cfg, prompt, gen, **server_kw):
    """Serve one request through the scheduler and return ``(tokens, the
    decode steps' logits (gen - 1, V))``: the logits are read by the
    adapter's own ``decode_forward`` on the very state each
    ``decode_step`` call is given."""
    server = HybridSSMServer(cfg, params, _serve(), **server_kw)
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


def _gap(got, ref):
    """Largest |difference| of two logit arrays over the reference's
    spread (its standard deviation over the vocabulary)."""
    return float(np.max(np.abs(got - ref)) / np.std(ref))


def _reference_steps(params, prompt, tokens, gen):
    ref = np.asarray(reference.forward(
        params, jnp.asarray(prompt + tokens[:-1], jnp.int32), HF))
    assert tokens[0] == int(np.argmax(ref[len(prompt) - 1]))
    return ref[len(prompt): len(prompt) + gen - 1]


# What the served path may cost, as the largest logit difference over the
# reference's spread across the vocabulary, float32 activations and state,
# so that pages are all that differs. Readings here over the four prompts:
# raw (float16) pages 1.0e-5 to 2.1e-5, 8-bit pages 2.4e-4 to 6.7e-4, 4-bit
# pages 6.2e-3 to 1.2e-2, a bfloat16 state over 8-bit pages 1.4e-3 to
# 3.1e-3 (the mid-page prompt: 3.1e-3, 5.3 times its 8-bit reading). The
# limit lies 2.2 times above the largest sound reading; both lower
# precisions read over twice the limit on the mid-page prompt.
SERVED_LIMIT = 0.0015


@pytest.mark.parametrize("prompt_len", [2 * PAGE + 3, 2 * PAGE, 3, PAGE - 1],
                         ids=["mid_page", "page_edge", "under_the_conv",
                              "fills_its_tail"])
def test_prefill_then_decode_matches_reference_logits(params, monkeypatch,
                                                      prompt_len):
    """Prefill of a right-padded prompt (chunked scan, state taken at
    ``last_idx``, K/V pages quantized into the pools), then decode through
    the per-lane state and the 8-bit pages (tails committing on the way),
    against the plain reference's full forward over ``prompt + served
    tokens``: logits at every decode position, for prompts that end
    mid-page, on a page edge, before the convolution's window is full, and
    one token short of a page."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    prompt, gen = _prompt(prompt_len, seed=prompt_len), 2 * PAGE + 4
    tokens, got = _served_logits(params, _cfg(), prompt, gen)
    gap = _gap(got, _reference_steps(params, prompt, tokens, gen))
    assert gap < SERVED_LIMIT, gap


@pytest.mark.parametrize("lower", ["pages_4bit", "state_bfloat16"])
def test_a_lower_precision_fails_the_served_limit(params, monkeypatch, lower):
    """4-bit pages in place of 8-bit ones, and a bfloat16 recurrent state
    in place of the float32 one (it rounds once a token for the life of the
    request), each leave the limit: the comparison can see both."""
    monkeypatch.setenv("CGX_KV_BITS", "4" if lower == "pages_4bit" else "8")
    kw = {"state_dtype": jnp.bfloat16} if lower == "state_bfloat16" else {}
    prompt, gen = _prompt(2 * PAGE + 3, seed=2 * PAGE + 3), 2 * PAGE + 4
    tokens, got = _served_logits(params, _cfg(), prompt, gen, **kw)
    ref = np.asarray(reference.forward(
        params, jnp.asarray(prompt + tokens[:-1], jnp.int32), HF))
    gap = _gap(got, ref[len(prompt): len(prompt) + gen - 1])
    assert gap > 1.5 * SERVED_LIMIT, gap


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
    alone = ContinuousBatchScheduler(HybridSSMServer(cfg, params, _serve()))
    alone.submit(probe)
    assert alone.run(deadline_s=300.0)
    want = list(probe.output)

    sched = ContinuousBatchScheduler(HybridSSMServer(cfg, params, _serve()))
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
    held = np.asarray(sched._state["state_ssm"][0])[lane]
    assert np.any(held != 0)  # not reset at release
    for _ in range(3):  # the free lane idles through decode steps
        sched.step()
    for name in ("state_ssm", "state_conv"):
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
@pytest.mark.parametrize("width", [256, 2048, 40])
def test_ssm_update_lowerings_agree(width, state_dtype):
    """``cgx_ssm_update`` (interpreted here) against its ``jax.numpy``
    form, to float32 rounding (XLA may fuse the state's multiply and add
    into one rounding, and the kernel adds ``y``'s ``d_state`` products in
    another order; a narrower state may then round one step apart); for a
    width of whole 128-lane vectors in one block (256), in two (2,048) and
    one that is not (40: the whole row a block)."""
    rng = np.random.default_rng(width)
    b, n = 3, 16
    state = jnp.asarray(rng.standard_normal((b, n, width)), state_dtype)
    decay = jnp.asarray(rng.uniform(0.2, 1.0, (b, width)), jnp.float32)
    dtx, = (jnp.asarray(rng.standard_normal((b, width)), jnp.float32),)
    bm, cm = (jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
              for _ in range(2))
    assert ssm.lane_tile(width) == {256: 256, 2048: 1024, 40: 40}[width]
    new_k, y_k = ssm.ssm_update_pallas(state, decay, dtx, bm, cm,
                                       interpret=True)
    new_x, y_x = ssm.ssm_update_xla(state, decay, dtx, bm, cm)
    assert new_k.dtype == state_dtype and y_k.dtype == jnp.float32
    step = 2.0 ** -8 if state_dtype == jnp.bfloat16 else 1e-6
    new_k, new_x = (np.asarray(t, np.float32) for t in (new_k, new_x))
    assert np.max(np.abs(new_k - new_x)) <= step * np.max(np.abs(new_x))
    assert float(jnp.max(jnp.abs(y_k - y_x))) < 1e-5 * float(
        jnp.max(jnp.abs(y_x)))


@pytest.mark.parametrize("impl,lowering", [("pallas", "pallas"),
                                           ("xla", "xla"), ("auto", "xla")])
def test_ssm_update_dispatch_counts_its_lowering(monkeypatch, impl, lowering):
    """``ops.dispatch.ssm_update`` is dispatched as the codec is
    (``CGX_CODEC_IMPL``; off the TPU ``auto`` is the ``jax.numpy`` form)
    and counts the call site by lowering."""
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    metrics.reset()
    args = (jnp.ones((2, 16, 128)), jnp.ones((2, 128)), jnp.ones((2, 128)),
            jnp.ones((2, 16)), jnp.ones((2, 16)))
    new, y = ops_dispatch.ssm_update(*args)
    assert new.shape == (2, 16, 128) and y.shape == (2, 128)
    assert float(y[0, 0]) == 2.0 * 16
    assert metrics.snapshot("cgx.codec.lowering.") == {
        f"cgx.codec.lowering.ssm_update.{lowering}": 1.0}


def test_layers_that_name_different_streams_build(params, monkeypatch):
    """The adapter's layers name different streams and the programs build:
    ``k`` and ``v`` pools and tails on the attention layers alone, ``conv``
    and ``ssm`` state rows a lane on the Mamba layers alone, None where a
    layer has no such stream; the program key holds the state streams, so
    a narrower state is another program."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    cfg = _cfg()
    server = HybridSSMServer(cfg, params, _serve())
    sched = ContinuousBatchScheduler(server)
    prog, st = sched._prog, sched._state
    assert prog.names == ("k", "v") and prog.state_names == ("conv", "ssm")
    for layer in range(cfg.n_layer):
        attention = layer in ATTENTION
        assert sorted(st["pools"][layer]) == (["k", "v"] if attention else [])
        assert (prog.specs[layer] is not None) == attention
        for name in ("tail_k", "tail_v"):
            assert (st[name][layer] is not None) == attention
        for name in ("state_conv", "state_ssm"):
            assert (st[name][layer] is None) == attention
    spec = prog.specs[1]
    assert (spec.n_head, spec.d_head, spec.bits) == (2, 8, 8)
    assert st["tail_k"][4].shape == (3, PAGE, 2 * 8)
    assert st["state_conv"][0].shape == (3, 3, cfg.d_xbc)
    assert st["state_ssm"][3].shape == (3, cfg.d_state, cfg.d_inner)
    assert st["state_ssm"][3].dtype == jnp.float32
    key = sched_mod._program_key(server)
    assert key[0] == "hybrid_ssm"
    narrow = HybridSSMServer(cfg, params, _serve(), state_dtype=jnp.bfloat16)
    assert sched_mod._program_key(narrow) != key
    assert narrow.state_bytes_per_lane() * 2 == server.state_bytes_per_lane()
    monkeypatch.setenv("CGX_KV_BITS", "4")
    assert sched_mod._program_key(server) != key


def test_adapters_without_state_keep_their_programs(monkeypatch):
    """``GPT2Server`` and ``LatentMoEServer`` state no recurrent state: the
    decode state has the entries it had, the prefill leaves no state and
    ``admit_lane`` takes no operand beyond the state, the lane's scalars
    and its tails."""
    from benchmark import weights as gpt2_weights
    from benchmark import weights_mla_moe
    from test_latent_serving import HF as LATENT_HF
    from torch_cgx_tpu.models.mla_moe import MlaMoeConfig
    from torch_cgx_tpu.serving.latent import LatentMoEServer

    monkeypatch.setenv("CGX_KV_BITS", "8")
    tiny = dict(vocab_size=512, n_layer=2, n_head=4, n_embd=64,
                n_positions=128, init={})
    servers = {
        "gpt2": (GPT2Server(
            GPT2Config(vocab_size=512, n_layer=2, n_head=4, d_model=64,
                       max_seq=128),
            gpt2_weights.make_params(tiny, 1), _serve()), ("k", "v")),
        "mla_moe": (LatentMoEServer(
            MlaMoeConfig.from_hf(LATENT_HF, dtype=jnp.float32, q_block=8),
            weights_mla_moe.make_params(LATENT_HF, 3), _serve()),
            ("c", "kr")),
    }
    for kind, (server, names) in servers.items():
        assert all(server.state_streams(layer) == ()
                   for layer in range(server.n_layer))
        assert server.state_bytes_per_lane() == 0
        sched = ContinuousBatchScheduler(server)
        prog, st = sched._prog, sched._state
        assert (prog.names, prog.state_names) == (names, ())
        assert sorted(st) == sorted(
            ["pools", "page_table", "n_pages", "tail_len", "tokens", "pos",
             "active"] + [f"tail_{n}" for n in names])
        ready = sched._local_prefill(
            Request(id=kind, tokens=_prompt(PAGE + 3), max_new_tokens=4))
        assert ready.states == {}
        lane_args = (np.int32(0), np.full((6,), -1, np.int32), np.int32(1),
                     np.int32(3), np.int32(7), np.int32(PAGE + 3))
        jaxpr = jax.make_jaxpr(prog.admit_lane)(
            sched._state, *lane_args, ready.tails, ready.states)
        assert len(jaxpr.jaxpr.invars) == (
            len(jax.tree.leaves(sched._state)) + len(lane_args)
            + len(names))


def _mixed_batch():
    """A short request (it finishes first and leaves its lane vacant), one
    that commits its second page on the way and one prefilled at four pages
    of its table's six, as ``(prompt, gen)`` pairs."""
    return [(_prompt(5, seed=10), 3), (_prompt(PAGE + 9, seed=11), 14),
            (_prompt(4 * PAGE + 5, seed=12), 14)]


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
        HybridSSMServer(_cfg(), params, sv), hybrid_mod, _mixed_batch())
    share = serving_guard.assert_held_lanes_bit_for_bit(seen, sv.pages_per_seq)
    assert 0.2 < share < 0.4  # 5-6 of the table's 18 slots


def test_a_hybrid_adapter_counts_what_its_global_read_decodes(
        params, monkeypatch):
    """An adapter with no ring writes the global class's three counters: a
    dispatched step adds the device's ``page_live`` (summed) to
    ``kv.decoded_pages.global`` and ``kv.live_pages.global`` and the whole
    table, ``max_batch x pages_per_seq``, to ``kv.table_pages.global``; the
    window class's counters are not written."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    sv = _serve()
    before = metrics.snapshot("cgx.serve.kv.")
    device, host = serving_guard.device_and_host_pages(
        HybridSSMServer(_cfg(), params, sv), _mixed_batch())
    after = metrics.snapshot("cgx.serve.kv.")
    assert len(host) == len(device) > 12
    assert [h[0] for h in host] == device and [h[1] for h in host] == device
    assert {h[2] for h in host} == {float(sv.max_batch * sv.pages_per_seq)}
    assert max(device) > min(device) > 0  # a tail committed on the way
    for name in ("live_pages", "decoded_pages"):
        key = f"cgx.serve.kv.{name}.window"
        assert after.get(key, 0.0) == before.get(key, 0.0)


def test_disaggregated_path_refuses_a_recurrent_state(params):
    """The transport's frames are K and V pages of every layer; no frame
    kind ships a lane's recurrent state. The hybrid adapter is refused by
    name and in plain words at both ends, before anything is shipped."""
    server = HybridSSMServer(_cfg(), params, _serve())
    store = FakeStore()
    with pytest.raises(ValueError, match="ships K and V page frames") as e:
        ContinuousBatchScheduler(server, receiver=KvPageReceiver(store))
    assert "'hybrid_ssm'" in str(e.value)
    assert "recurrent state ['conv', 'ssm']" in str(e.value)
    with pytest.raises(ValueError, match="local prefill only"):
        PrefillWorker(server, store)


def test_the_serve_plan_is_told_pages_and_state_apart(params, monkeypatch):
    """``ServeConfig.from_env`` tells the serve plan what a token's pages
    weigh over the attention layers alone (and that they are that many
    layers' frames) and, apart, what a lane's recurrent state weighs
    whatever its length; the plan's TTFT holds the state's crossing."""
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
    state = 3 * (3 * (128 + 2 * 16) + 16 * 128) * 4
    assert cfg.kv_bytes_per_token() == 2 * 2 * (2 * 8) * 4
    assert cfg.state_bytes_per_lane() == state
    ServeConfig.from_env(cfg)
    ServeConfig.from_env(GPT2Config.tiny())
    assert asked == [(2 * 2 * 16 * 4, 2, state), (2 * 2 * 128 * 4, 2, 0)]
    plain = real(prompt_tokens=64, kv_token_bytes=256, n_layers=2, bits=8,
                 bucket=512)
    told = real(prompt_tokens=64, kv_token_bytes=256, n_layers=2, bits=8,
                bucket=512, state_lane_bytes=10**9)
    assert (told.page_tokens, told.ship_depth) == (
        plain.page_tokens, plain.ship_depth)
    assert told.predicted_ttft_s > plain.predicted_ttft_s
