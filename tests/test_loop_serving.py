"""The looped adapter (``serving/loop.py``'s ``LoopServer``) through the one
scheduler, against the plain reference (``benchmark/reference_loop.py``), and
the serving plane's pass dimension beside the adapters that have none.

Tiny sizes: three passes over two layers, so that a cache slot ``(pass,
layer)`` and a layer cannot be confused (six slots, two weight layers), four
heads of 16, pages of 8. Seeded weights (``benchmark/weights_loop.py``),
float32 activations at full matmul precision, so that what a tolerance
bounds is the thing it names (a page's rounding, a planted fault) and not the
CPU's arithmetic. Logits are compared, not tokens.
"""

from __future__ import annotations

import hashlib
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import reference_loop as reference  # noqa: E402
from benchmark import weights_loop as weights  # noqa: E402
from torch_cgx_tpu.models import ouro  # noqa: E402
from torch_cgx_tpu.models.ouro import OuroConfig  # noqa: E402
from torch_cgx_tpu.serving import loop as loop_mod  # noqa: E402
from torch_cgx_tpu.serving import programs  # noqa: E402
from torch_cgx_tpu.serving import transport as tp  # noqa: E402
from torch_cgx_tpu.serving.adapter import ServeConfig  # noqa: E402
from torch_cgx_tpu.serving.loop import LoopServer  # noqa: E402
from torch_cgx_tpu.serving.prefill import PrefillWorker  # noqa: E402
from torch_cgx_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.utils.logging import metrics  # noqa: E402
from torch_cgx_tpu.wire import edges  # noqa: E402

PAGE, PASSES, LAYERS = 8, 3, 2
HF = dict(
    model_type="ouro", vocab_size=512, num_hidden_layers=LAYERS,
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, intermediate_size=128, hidden_act="silu",
    total_ut_steps=PASSES, early_exit_threshold=1.0,
    layer_types=["full_attention"] * LAYERS, max_window_layers=LAYERS,
    use_sliding_window=False, sliding_window=None, rope_theta=1000000,
    rope_scaling=None, rms_norm_eps=1e-6, tie_word_embeddings=False,
    precision={"params": "float32"},
    # Scores that spread over 64 inputs as the configuration's do over 2,048
    # (deviation 64 x 0.15**2 = 1.4), a token an equal part of the stream,
    # a gate that moves with the token.
    init={"qk_std": 0.15, "embed_std": 1.0, "exit_std": 0.1},
)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("CGX_KV_BITS", "8")
    edges.clear_edges()
    with jax.default_matmul_precision("highest"):
        yield
    edges.clear_edges()


@pytest.fixture(scope="module")
def params():
    return weights.make_params(HF, 52)


def _cfg(hf=HF, **kw):
    return OuroConfig.from_hf(hf, **{"dtype": jnp.float32, "q_block": 16,
                                     **kw})


def _serve(**kw):
    base = dict(page_tokens=PAGE, max_batch=2, max_pages=20, max_seq=80,
                ship_depth=2)
    return ServeConfig(**{**base, **kw})


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, HF["vocab_size"], n)]


def _serve_requests(params, requests, hf=HF):
    """Serve ``requests`` ((prompt, gen) pairs) through one scheduler, all
    submitted at once, and return ``[(tokens, the decode steps' logits (gen
    - 1, V))]`` and the scheduler: the logits are read by the adapter's own
    ``decode_forward`` on the very state each ``decode_step`` call is given,
    at the request's lane."""
    server = LoopServer(_cfg(hf), params, _serve())
    sched = ContinuousBatchScheduler(server)
    prog = sched._prog
    probe = jax.jit(lambda p, st: server.with_params(p).decode_forward(
        st, prog.streams)[0])
    reqs = [Request(id=f"r{i}", tokens=p, max_new_tokens=g)
            for i, (p, g) in enumerate(requests)]
    seen = {r.id: [] for r in reqs}

    def decode_step(p, state):
        logits = np.asarray(probe(p, state))
        for lane, req in enumerate(sched._lanes):
            if req is not None and sched._left[lane] > 0:
                seen[req.id].append(logits[lane])
        return prog.decode_step(p, state)

    sched._prog = SimpleNamespace(**{**vars(prog), "decode_step": decode_step})
    for r in reqs:
        sched.submit(r)
    assert sched.run(deadline_s=600.0)
    assert sched.cache.free_pages == sched.cache.max_pages
    return [(r.output, np.stack(seen[r.id])) for r in reqs], sched


def _reference_steps(params, prompt, tokens, cfg=HF):
    """The reference's logits at the positions the decode steps served."""
    ref = np.asarray(reference.forward(
        params, jnp.asarray(prompt + tokens[:-1], jnp.int32), cfg,
        q_block=32)[0])
    return ref[len(prompt) - 1], ref[len(prompt): len(prompt) + len(tokens) - 1]


def _gaps(got, ref):
    """The decode steps' largest |difference| of two logit arrays ``(steps,
    V)``, over the reference's spread (its standard deviation over the
    vocabulary): ``(the widest step, the mean step)``."""
    steps = np.max(np.abs(got - ref), axis=1) / np.std(ref)
    return float(np.max(steps)), float(np.mean(steps))


# What the pages may cost a run (float32 activations, so pages are all that
# differs), as the logit difference over the reference's spread; each test
# prints its readings. Read here over the runs below:
# * 8-bit pages: widest step 0.04-0.11, mean step 0.026-0.043 (a bucket of 512
#   values at 255 levels, read by six slots a token; the sandwich norms bring
#   every sub-layer's output back to unit scale, so a page's rounding does not
#   grow with depth or with the passes); a lane that never fills a page reads
#   the float32 tail alone and differs by nothing.
# * raw pages (float16 pools): widest 0.0012-0.0032, mean 0.0008-0.0016: the
#   limits are 3 times that, and 8-bit pages fail both.
# * the planted faults: 4-bit pages read mean 0.84, a pass reading the pass
#   before's pages mean 2.8, the reference without the norm between passes
#   mean 2.4: the 8-bit limit on the mean lies 3.5 times above the sound runs
#   and 5.6 times under the nearest fault.
LIMIT_WIDEST, LIMIT_MEAN = 0.3, 0.15
RAW_WIDEST, RAW_MEAN = 0.01, 0.005

# (prompt tokens, tokens served): a lane that never fills a page (5 + 2 = 7
# positions: every read is the tail's), one whose prompt ends inside a page
# and that commits four pages on its way (19 + 30 = 49 positions:
# ``last_idx`` 18 of a padded 24), one whose prompt is whole pages (16 + 12).
RUNS = {"tail_only": (5, 3), "across": (19, 30), "whole_pages": (16, 12)}


@pytest.fixture(scope="module")
def served(params):
    """The sound runs, served once for the tests that read them."""
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, (n, gen) in RUNS.items():
            prompt = _prompt(n, seed=len(name))
            before = metrics.snapshot("cgx.serve.")
            [(tokens, got)], sched = _serve_requests(params, [(prompt, gen)])
            after = metrics.snapshot("cgx.serve.")
            out[name] = (prompt, tokens, got, {
                k.split("cgx.serve.", 1)[1]: v - before.get(k, 0.0)
                for k, v in after.items() if isinstance(v, float)})
    return out


@pytest.mark.parametrize("run", sorted(RUNS))
def test_prefill_then_decode_matches_reference(params, served, run):
    """Prefill (one scan over the passes, every pass's pages written into
    its rows of the pools), then decode (every pass over its own pages and
    tails, tails committing on the way) against the plain reference's full
    forward, at every decode position and whatever ``last_idx`` is."""
    prompt, tokens, got, _ = served[run]
    first, steps = _reference_steps(params, prompt, tokens)
    assert tokens[0] == int(np.argmax(first))
    widest, mean = _gaps(got, steps)
    print(f"{run}: widest step {widest:.4f}, mean step {mean:.4f}")
    assert widest < LIMIT_WIDEST and mean < LIMIT_MEAN, (widest, mean)


@pytest.mark.parametrize("run", ["across", "whole_pages"])
def test_raw_pages_match_reference_tighter(params, run, monkeypatch):
    """With raw (float16) pools nothing is quantized and the cache costs a
    float16 rounding of ``k`` and ``v``: the same comparison holds 30 times
    tighter, and the 8-bit run of the same request does not pass it."""
    monkeypatch.setenv("CGX_KV_BITS", "0")
    n, gen = RUNS[run]
    prompt = _prompt(n, seed=len(run))
    [(tokens, got)], sched = _serve_requests(params, [(prompt, gen)])
    assert not sched._prog.specs[0].quantized
    first, steps = _reference_steps(params, prompt, tokens)
    assert tokens[0] == int(np.argmax(first))
    widest, mean = _gaps(got, steps)
    print(f"{run} raw: widest step {widest:.5f}, mean step {mean:.5f}")
    assert widest < RAW_WIDEST and mean < RAW_MEAN, (widest, mean)


def test_eight_bit_pages_fail_the_raw_limits(params, served):
    prompt, tokens, got, _ = served["across"]
    _, steps = _reference_steps(params, prompt, tokens)
    widest, mean = _gaps(got, steps)
    assert widest > RAW_WIDEST and mean > RAW_MEAN, (widest, mean)


def test_every_passes_hidden_state_and_gate_match_reference(params):
    """The prefill's scan hands back every pass's closed stream and gate:
    each agrees with the reference's, pass by pass, and the exit mass built
    from them sums to one."""
    prompt = _prompt(21, seed=3)
    server = LoopServer(_cfg(), params, _serve())
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    x, lam, ks, vs = jax.jit(server.prefill_passes)(
        tokens, jnp.arange(len(prompt), dtype=jnp.int32)[None])
    _, hidden, lams = reference.forward(params, tokens[0], HF, q_block=32)
    assert x.shape == (PASSES, 1, 21, 64) and lam.shape == (PASSES, 1, 21)
    assert len(ks) == LAYERS and ks[0].shape == (PASSES, 1, 21, 4, 16)
    for t in range(PASSES):
        np.testing.assert_allclose(x[t, 0], hidden[t], atol=2e-4)
        np.testing.assert_allclose(lam[t, 0], lams[t], atol=2e-5)
    # the passes differ: a slot of pass 1 is not pass 0's
    assert float(jnp.max(jnp.abs(ks[0][1] - ks[0][0]))) > 0.1
    assert 0.02 < float(jnp.min(lams)) and float(jnp.max(lams)) < 0.98
    mass = ouro.exit_mass(lam)
    np.testing.assert_allclose(mass.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(mass, reference.exit_mass(lams)[:, None],
                               atol=2e-5)


def test_one_pass_is_one_plain_pass(params):
    """``total_ut_steps`` 1: the programs have no pass dimension at all (the
    state is any K/V adapter's), the served logits are the reference's at one
    pass, and that pass is the three-pass model's first."""
    hf = dict(HF, total_ut_steps=1)
    prompt = _prompt(19, seed=6)
    [(tokens, got)], sched = _serve_requests(params, [(prompt, 12)], hf)
    assert sched._prog.passes == 1
    assert sched._state["tail_k"][0].shape == (2, PAGE, 64)
    assert sched._state["pools"][1]["v"][0].shape[0] == 20 + 1
    first, steps = _reference_steps(params, prompt, tokens, hf)
    assert tokens[0] == int(np.argmax(first))
    widest, mean = _gaps(got, steps)
    assert widest < LIMIT_WIDEST and mean < LIMIT_MEAN, (widest, mean)
    ids = jnp.asarray(prompt, jnp.int32)
    one = reference.forward(params, ids, hf, q_block=32)[1]
    three = reference.forward(params, ids, HF, q_block=32)[1]
    np.testing.assert_array_equal(one[0], three[0])


def _slot_before(state, tails, at_pass, serve):
    """The planted fault: a pass's committed pages read from the slot of the
    pass before (pass 0 from its own)."""
    return _SOUND_VIEW(state, tails, jnp.maximum(at_pass - 1, 0), serve)


_SOUND_VIEW = loop_mod.pass_view


@pytest.mark.parametrize("fault", ["slot_of_the_pass_before",
                                   "no_norm_between_passes",
                                   "four_bit_pages"])
def test_a_planted_fault_is_caught(params, served, fault, monkeypatch):
    """Each of three departures from the model fails the tolerance the
    sound runs pass: a pass reading the pages the pass before it wrote, the
    final norm left out between passes (the reference's reading of it,
    against the sound served run), and 4-bit pages."""
    prompt, tokens, got, _ = served["across"]
    hf = HF
    if fault == "no_norm_between_passes":
        hf = dict(HF, norm_between_passes=False)
    else:
        if fault == "four_bit_pages":
            monkeypatch.setenv("CGX_KV_BITS", "4")
        else:
            monkeypatch.setattr(loop_mod, "pass_view", _slot_before)
        [(tokens, got)], _ = _serve_requests(params, [(prompt, 30)])
    _, steps = _reference_steps(params, prompt, tokens, hf)
    widest, mean = _gaps(got, steps)
    print(f"{fault}: widest step {widest:.4f}, mean step {mean:.4f}")
    assert mean > 2 * LIMIT_MEAN, (widest, mean)


def test_a_committed_page_lands_in_every_passes_slot(params):
    """A tail that fills mid-decode is quantized into the page id's row of
    every pass's block of every layer's pools, and nowhere else: the rows of
    an unused id and every pass's scratch row stay as they were."""
    server = LoopServer(_cfg(), params, _serve())
    sched = ContinuousBatchScheduler(server)
    stride = server.serve.max_pages + 1
    assert sched._state["pools"][0]["k"][0].shape[0] == PASSES * stride
    assert sched._state["tail_v"][1].shape == (PASSES, 2, PAGE, 64)
    req = Request(id="r", tokens=_prompt(5), max_new_tokens=8)
    sched.submit(req)
    while len(req.output) < 6:  # 5 + 3 decode steps fill the first page
        sched.step()
    (pid,) = sched.cache._seqs["r"].pages
    other = next(p for p in range(20) if p != pid)
    for layer in range(LAYERS):
        for name in ("k", "v"):
            words = np.asarray(sched._state["pools"][layer][name][0])
            for t in range(PASSES):
                assert words[t * stride + pid].any(), (layer, name, t)
                assert not words[t * stride + other].any()
            # pass t's page is not pass 0's
            assert (words[pid] != words[stride + pid]).any()
    assert np.asarray(sched._state["n_pages"]).tolist().count(1) == 1
    assert sched.run(deadline_s=600.0)


def test_lanes_of_a_batch_are_served_apart(params):
    """Two lanes at once, one committing pages while the other fills its
    first: each is the request served alone."""
    requests = [(_prompt(19, seed=6), 30), (_prompt(5, seed=9), 3)]
    both, _ = _serve_requests(params, requests)
    for (tokens, got), request in zip(both, requests):
        [(alone, got_alone)], _ = _serve_requests(params, [request])
        assert tokens == alone
        np.testing.assert_allclose(got, got_alone, atol=1e-4)


@pytest.mark.parametrize("key,value,says", [
    ("early_exit_threshold", 0.5, "different result"),
    ("layer_types", ["full_attention", "sliding_attention"],
     "full_attention"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("num_key_value_heads", 2, "multi-head"),
])
def test_a_config_this_block_is_not_is_refused(key, value, says):
    """Leaving the loop early at a threshold under 1 is a different result
    and another PR's; the other keys state a block this is not."""
    with pytest.raises(ValueError, match=says):
        OuroConfig.from_hf(dict(HF, **{key: value}))
    if key != "use_sliding_window":  # the reference reads the same keys
        with pytest.raises(ValueError):
            reference._cfg_items(dict(HF, **{key: value}))


def test_the_gates_are_counted_every_step(served):
    """``loop.passes`` reads T a lane a step and the exit mass 1,000 a lane
    a step over the passes, each pass's share inside (0, 1,000)."""
    for run, (_, tokens, _, counted) in served.items():
        steps = counted["decode_steps"]
        assert steps == len(tokens) - 1
        assert counted["loop.passes"] == PASSES * steps
        mass = [counted[f"loop.exit_mass.{t + 1}"] for t in range(PASSES)]
        assert sum(mass) == 1000 * steps, (run, mass)
        assert all(0 < m < 1000 * steps for m in mass), (run, mass)
        assert f"loop.exit_mass.{PASSES + 1}" not in counted


def _eqns(jaxpr, inside=False):
    """``(primitive, params, whether inside a scan)`` of every equation of
    ``jaxpr``, inner jaxprs included."""
    for e in jaxpr.eqns:
        yield e.primitive.name, e.params, inside
        for value in e.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner, inside or e.primitive.name == "scan")


@pytest.mark.parametrize("program", ["decode_step", "prefill_pages"])
def test_a_program_holds_one_scan_and_the_layers_once(params, program):
    """The compiled program is one ``scan`` of length T whose body is the
    two layers once: four norms a layer and the one that closes the pass (an
    ``rsqrt`` each) and seven weight products a layer, all inside the scan
    and none a second time outside it."""
    server = LoopServer(_cfg(), jax.eval_shape(lambda: params), _serve())
    prog = programs.build(server)
    state = jax.eval_shape(
        lambda: programs.fresh_state(prog, server.serve))
    if program == "decode_step":
        jaxpr = jax.make_jaxpr(prog.decode_step)(server.p, state)
    else:
        toks = np.zeros((1, 3 * PAGE), np.int32)
        jaxpr = jax.make_jaxpr(prog.prefill_pages)(
            server.p, state["pools"], toks, toks, np.int32(18),
            np.zeros((3,), np.int32), np.int32(3))
    found = list(_eqns(jaxpr.jaxpr))
    # (a prefill's attention loops over its query blocks inside a pass)
    scans = [p for name, p, inside in found if name == "scan" and not inside]
    assert [p["length"] for p in scans] == [PASSES]
    norms = [inside for name, _, inside in found if name == "rsqrt"]
    assert norms == [True] * (4 * LAYERS + 1)
    heads = 1  # the head's product, after the scan
    products = [inside for name, _, inside in found if name == "dot_general"]
    attention = 4 if program == "decode_step" else 2  # scores, weighted sums
    assert products.count(False) == heads
    assert products.count(True) == (7 + attention) * LAYERS


# The first 16 hex digits of the SHA-256 of each program's jaxpr as text, for
# four adapters that state no passes, at the sizes ``test_serving_layers``
# builds them with and under this file's matmul precision, computed on the
# parent commit's ``git archive`` (PR 52's parent, 31671b7): with ``cache_passes`` 1 the pass dimension changes no
# program. A PR that changes a program on purpose reads the new values off
# this test's failure.
PARENT_PROGRAMS = {
    "gpt2": {"decode_step": "497d17db17c85b0d", "commit": "cca538ce1cc0ad50",
             "prefill_pages": "31346d9a8267f000",
             "admit_lane": "78cfaf64282de800"},
    "afmoe": {"decode_step": "ee0ef924327858aa", "commit": "55624eb4fffc427f",
              "prefill_pages": "7574b2eb716283c5",
              "admit_lane": "3684dba508070ea9"},
    "mla_moe": {"decode_step": "1a1b49f27d27c9f4",
                "commit": "d041fa02e38e8be6",
                "prefill_pages": "159c7ea05ec58b23",
                "admit_lane": "542c41669ca965ca"},
    "hybrid_gdn": {"decode_step": "9672d63a2351cf39",
                   "commit": "9afe7feb5ed049d1",
                   "prefill_pages": "b55163cae6ee9ae1",
                   "admit_lane": "bad2043c46550817"},
}


def _sha(x) -> str:
    return hashlib.sha256(str(x).encode()).hexdigest()[:16]


def _program_shas(server) -> dict:
    """The four programs that touch pools or tails, traced from shapes."""
    sv = server.serve
    prog = programs.build(server)
    state = jax.eval_shape(lambda: programs.fresh_state(prog, sv))
    ids = np.zeros((sv.commit_lanes,), np.int32)
    two = np.zeros((2,), np.int32)
    toks = np.zeros((1, 2 * sv.page_tokens), np.int32)
    prefill = (server.p, state["pools"], toks, toks,
               np.int32(2 * sv.page_tokens - 3), two, np.int32(2)) + (
                   (two,) if prog.ring else ())
    out = jax.eval_shape(prog.prefill_pages, *prefill)
    admit = (state, np.int32(0), np.full((sv.pages_per_seq,), -1, np.int32),
             np.int32(1), np.int32(2), np.int32(3), np.int32(10), out[2],
             out[4]) + ((np.full((prog.ring,), -1, np.int32),)
                        if prog.ring else ())
    return {
        "decode_step": _sha(jax.make_jaxpr(prog.decode_step)(server.p,
                                                             state)),
        "commit": _sha(jax.make_jaxpr(prog.commit)(
            state, ids, ids, *((ids,) if prog.ring else ()))),
        "prefill_pages": _sha(jax.make_jaxpr(prog.prefill_pages)(*prefill)),
        "admit_lane": _sha(jax.make_jaxpr(prog.admit_lane)(*admit)),
    }


@pytest.mark.parametrize("kind", sorted(PARENT_PROGRAMS))
def test_an_adapter_of_one_pass_builds_the_parents_programs(kind):
    import test_serving_layers as layers

    make = {
        "gpt2": lambda: layers.gpt2_weights.make_params(layers.GPT2_HF, 1),
        "afmoe": lambda: layers.afmoe.weights.make_params(
            layers.afmoe.HF, 1),
        "mla_moe": lambda: layers.latent.weights.make_params(
            layers.latent.HF, 1),
        "hybrid_gdn": lambda: layers.olmo.weights.make_params(
            layers.olmo.HF, 1),
    }[kind]
    cls, cfg, serve = layers.SERVERS[kind]
    server = cls(cfg(), jax.eval_shape(make), serve())
    assert server.cache_passes == 1
    assert _program_shas(server) == PARENT_PROGRAMS[kind]


def test_the_page_transport_refuses_a_looped_adapter_by_name(params):
    """A frame names a layer and a page and no pass: the disaggregated path
    (the receiver's scheduler, the prefill worker) refuses the adapter in
    plain words, before anything is shipped."""
    server = LoopServer(_cfg(), params, _serve())
    says = (r"adapter 'loop' runs its layers 3 times a token and keeps a "
            r"cache a pass.*local prefill only")
    with pytest.raises(ValueError, match=says):
        tp.require_kv_streams(server)
    with pytest.raises(ValueError, match=says):
        ContinuousBatchScheduler(server, receiver=object())
    with pytest.raises(ValueError, match=says):
        PrefillWorker(server, store=None)


def test_passes_beside_rings_or_state_are_refused(params):
    """The pass dimension is the global page pools' and the tails': an
    adapter that states passes and a window is refused when its programs are
    built."""
    class Ringed(LoopServer):
        def page_window(self, layer):
            return 16

    with pytest.raises(ValueError, match="3 cache passes beside window"):
        programs.build(Ringed(_cfg(), params, _serve()))


def test_sizing_counts_every_passes_layers(params, monkeypatch):
    """A token's cache is T times its layers': the config's bytes a token,
    the planner's layer count (``ServeConfig.from_env``), the pools' and the
    tails' gauges, the committed pages' count."""
    cfg = _cfg()
    assert cfg.n_cache_layers == PASSES * LAYERS
    assert cfg.kv_bytes_per_token() == 2 * PASSES * LAYERS * 64 * 4
    for name in ("CGX_KV_PAGE_TOKENS", "CGX_KV_SHIP_DEPTH"):
        monkeypatch.delenv(name, raising=False)
    planned = ServeConfig.from_env(cfg)
    assert planned.page_tokens >= 1 and planned.ship_depth >= 1
    server = LoopServer(cfg, params, _serve())
    assert server.kv_bytes_per_token() == cfg.kv_bytes_per_token()
    before = metrics.get("cgx.serve.pages_committed")
    sched = ContinuousBatchScheduler(server)
    rows = PASSES * 21
    page = 128 * 4 + 2 * 4  # 512 values in bytes, one bucket's pair
    assert metrics.get("cgx.serve.kv.pool_bytes.global") == (
        2 * LAYERS * rows * page)
    assert metrics.get("cgx.serve.kv.tail_bytes") == (
        2 * LAYERS * PASSES * 2 * PAGE * 64 * 4)
    assert sched._prog.class_streams == (2 * LAYERS * PASSES, 0)
    sched.submit(Request(id="r", tokens=_prompt(7), max_new_tokens=4))
    assert sched.run(deadline_s=600.0)
    assert metrics.get("cgx.serve.pages_committed") - before == (
        2 * LAYERS * PASSES)


def test_page_qerr_is_observed_for_every_passes_page(params, monkeypatch):
    """``CGX_QERR_STATS``: a layer's ``kv_page`` stream gets one observation
    a pass for a prefilled page and one a pass for a page committed
    mid-decode (the leading stream's rows, read from the pass's tail)."""
    monkeypatch.setenv("CGX_QERR_STATS", "1")
    key = "cgx.qerr.wire:kv_page:layer_1.count"
    before = metrics.snapshot("cgx.qerr.").get(key, 0.0)
    sched = ContinuousBatchScheduler(LoopServer(_cfg(), params, _serve()))
    sched.submit(Request(id="q", tokens=_prompt(2 * PAGE + 3),
                         max_new_tokens=7))  # 19 + 6 steps: one commit
    assert sched.run(deadline_s=600.0)
    got = metrics.snapshot("cgx.qerr.").get(key, 0.0) - before
    assert got == PASSES * (2 + 1)
