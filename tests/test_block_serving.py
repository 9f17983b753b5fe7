"""The block-diffusion adapter (``serving/block.py``'s
``BlockDiffusionServer``) through the one scheduler, against the plain
reference's replay (``benchmark/reference_block_diffusion.py``), and the
serving plane's block step beside the adapters that have none.

Tiny sizes: two layers, four query heads over two K/V heads of 16, eight
experts of which two a token, blocks of ``L = 4`` positions denoised in ``T =
4`` steps, pages of 8. Seeded weights (``benchmark/weights_block_diffusion
.py``), float32 activations at full matmul precision, so that what a
tolerance bounds is the thing it names (a page's rounding, a planted fault)
and not the CPU's arithmetic. Logits and confidences are compared, and
tokens by the gap of their reference logit, never by identity: with seeded
weights near-ties flip on rounding.
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

from benchmark import reference_block_diffusion as reference  # noqa: E402
from benchmark import weights_block_diffusion as weights  # noqa: E402
from torch_cgx_tpu.models.attention import dense_attention  # noqa: E402
from torch_cgx_tpu.models.sdar_moe import SdarMoeConfig  # noqa: E402
from torch_cgx_tpu.ops import prefill_attention as pfa  # noqa: E402
from torch_cgx_tpu.serving import adapter  # noqa: E402
from torch_cgx_tpu.serving import block as block_mod  # noqa: E402
from torch_cgx_tpu.serving import programs  # noqa: E402
from torch_cgx_tpu.serving import scheduler as scheduler_mod  # noqa: E402
from torch_cgx_tpu.serving import transport as tp  # noqa: E402
from torch_cgx_tpu.serving.adapter import ServeConfig  # noqa: E402
from torch_cgx_tpu.serving.block import BlockDiffusionServer  # noqa: E402
from torch_cgx_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.utils.logging import metrics  # noqa: E402
from torch_cgx_tpu.wire import edges  # noqa: E402

PAGE, L, T, MASK = 8, 4, 4, 511
HF = dict(
    model_type="sdar_moe", vocab_size=512, num_hidden_layers=2,
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], hidden_act="silu",
    attention_bias=False, use_sliding_window=False, sliding_window=None,
    max_window_layers=2, rope_theta=1000000, rope_scaling=None,
    rms_norm_eps=1e-6, tie_word_embeddings=False,
    block_length=L, denoising_steps=T, mask_token_id=MASK,
    confidence_threshold=0.9, precision={"params": "float32"},
    # What the attention and the experts add to the stream is about the
    # stream's own size over 64 inputs as the configuration's is over 2,048,
    # and a token half of it: a masked position's logits are its context's.
    init={"std": 0.1, "embed_std": 0.5, "o_std": 0.2,
          "expert_down_std": 0.05, "router_std": 0.3},
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
    return weights.make_params(HF, 54)


def _cfg(hf=HF, **kw):
    return SdarMoeConfig.from_hf(hf, **{"dtype": jnp.float32, "q_block": 16,
                                        **kw})


def _serve(**kw):
    base = dict(page_tokens=PAGE, max_batch=4, max_pages=40, max_seq=80,
                ship_depth=2)
    return ServeConfig(**{**base, **kw})


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, MASK, n)]


def _server(params, hf=HF, **serve):
    return BlockDiffusionServer(_cfg(hf), params, _serve(**serve))


def _serve_requests(params, requests, hf=HF, ahead=True, **serve):
    """Serve ``requests`` ((prompt, gen) pairs) through one scheduler, all
    submitted at once. Returns the requests, the scheduler and ``{request
    id: {(block, step): (logits (L, V), confidences (L,))}}`` of every
    DENOISE forward, read by the adapter's own ``decode_forward`` on the
    very state each ``decode_step`` call is given, at the request's lane.
    On the way the host's counts are held to the device's: at every
    dispatch, queued ahead or not, the host's tail lengths and pages of the
    held lanes are the state's."""
    server = _server(params, hf, **serve)
    sched = ContinuousBatchScheduler(server)
    if not ahead:
        sched._runs_ahead = lambda: False
    prog = sched._prog
    probe = jax.jit(lambda p, st: server.with_params(p).decode_forward(
        st, prog.streams)[0])
    reqs = [Request(id=f"r{i}", tokens=p, max_new_tokens=g)
            for i, (p, g) in enumerate(requests)]
    seen = {r.id: {} for r in reqs}

    def decode_step(p, state):
        logits = np.asarray(probe(p, state))
        st = {k: np.asarray(state[k]) for k in (
            "known", "block_step", "pos", "tail_len", "n_pages", "active")}
        held = [i for i, r in enumerate(sched._lanes) if r is not None]
        assert st["active"][held].all()
        np.testing.assert_array_equal(sched._tail_len[held],
                                      st["tail_len"][held])
        np.testing.assert_array_equal(sched._n_pages[held],
                                      st["n_pages"][held])
        assert (st["tail_len"] <= PAGE - L).all()  # room for a store
        for lane in held:
            req = sched._lanes[lane]
            if st["known"][lane].all():
                continue  # the store forward
            first = len(req.tokens) // L * L
            at = ((int(st["pos"][lane]) - first) // L,
                  int(st["block_step"][lane]))
            z = logits[lane] - logits[lane].max(-1, keepdims=True)
            conf = 1.0 / np.exp(z).sum(-1)
            seen[req.id][at] = (logits[lane], conf)
        return prog.decode_step(p, state)

    sched._prog = SimpleNamespace(**{**vars(prog), "decode_step": decode_step})
    for r in reqs:
        sched.submit(r)
    assert sched.run(deadline_s=600.0)
    assert sched.cache.free_pages == sched.cache.max_pages
    assert not sched._left.any() and not sched._store_next.any()
    return reqs, sched, seen


def _compare(params, req, seen, hf=HF):
    """A served request against the reference's replay of every denoising
    step of its whole blocks. Returns ``(widest, mean)`` of the steps'
    largest |logit difference| over the reference's spread, the widest
    relative difference of a confidence, and ``(widest, mean)`` of the
    served tokens' reference-logit gap over the same spread."""
    steps, confs, gaps = [], [], []
    when = np.asarray([-1] * len(req.tokens) + req.unmask_step)
    first = len(req.tokens) // L * L
    replayed = reference.replay(params, req.tokens, req.output,
                                req.unmask_step, hf, q_block=32)
    assert len(replayed) >= reference.whole_blocks(req.tokens, req.output, hf)
    for b, s, ref, ref_conf in replayed:
        got, got_conf = seen[(b, s)]
        spread = np.std(ref)
        steps.append(np.max(np.abs(got - ref)) / spread)
        confs.append(np.max(np.abs(got_conf - ref_conf) / ref_conf))
        at = first + b * L + np.arange(L)
        for i in np.flatnonzero(when[at] == s):
            token = (req.tokens + req.output)[at[i]]
            gaps.append((ref[i].max() - ref[i][token]) / spread)
    return ((float(np.max(steps)), float(np.mean(steps))),
            float(np.max(confs)),
            (float(np.max(gaps)), float(np.mean(gaps))))


# What the pages may cost a run (float32 activations, so the pages are all
# that differs from the reference), a step's largest logit difference over
# the reference's spread (its standard deviation over the vocabulary); each
# test prints its readings. Read here over the runs below:
# * 8-bit pages: mean step 0.030-0.055, widest step 0.043-0.24 (a bucket of a
#   page's 256 values at 255 levels; the widest is a step at which the
#   router's second expert falls the other way on some position: a spike, not
#   a level, which is why the experts of this fixture weigh a sixth of what
#   the attention does). Confidences (the softmax's largest of 512) differ by
#   1.3-3.2 %; every served token is the reference's own choice (gap 0).
# * raw pages (float16 pools): widest 0.0016-0.0019, mean 0.0011-0.0013: the
#   limits are 3 times that, and the 8-bit runs fail them.
# * the planted faults: 4-bit pages read mean 0.60, a causal mask inside the
#   block mean 1.16, K and V stored from the last forward that still held a
#   mask mean 1.17: the 8-bit limit on the mean lies 2.7 times above the
#   sound runs and 4 times under the nearest fault.
LIMIT_WIDEST, LIMIT_MEAN, LIMIT_CONF, LIMIT_GAP = 0.6, 0.15, 0.1, 0.1
RAW_WIDEST, RAW_MEAN = 0.006, 0.004

# (prompt tokens, tokens asked for): all four remainders of the prompt's
# length over the block, so the lanes' first blocks open with 1, 2, 3 and 0
# known tokens and the lanes run out of phase; 19 + 29 = 48 positions commit
# five pages on the way; 10 + 18 ends on a block's edge, the others inside
# one (the last block's other tokens are discarded).
BATCH = [(9, 16), (10, 18), (19, 29), (16, 11)]


@pytest.fixture(scope="module")
def served(params):
    """The sound batch, served once for the tests that read it."""
    with jax.default_matmul_precision("highest"):
        before = metrics.snapshot("cgx.serve.")
        reqs, sched, seen = _serve_requests(
            params, [(_prompt(n, seed=n), g) for n, g in BATCH])
        after = metrics.snapshot("cgx.serve.")
    counted = {k.split("cgx.serve.", 1)[1]: v - before.get(k, 0.0)
               for k, v in after.items() if isinstance(v, float)}
    return reqs, seen, counted


@pytest.mark.parametrize("lane", range(len(BATCH)))
def test_prefill_then_blocks_match_the_replay(params, served, lane):
    """(a) Prefill of the prompt's whole blocks, then blocks through pages
    and tails, tails committing on the way, four lanes out of phase: every
    denoise step's logits and confidences and every emitted token against
    the reference's replay of that step."""
    reqs, seen, _ = served
    req = reqs[lane]
    assert len(req.output) == BATCH[lane][1] == len(req.unmask_step)
    assert MASK not in req.output
    steps, conf, gaps = _compare(params, req, seen[req.id])
    print(f"lane {lane}: logits widest {steps[0]:.4f} mean {steps[1]:.4f}, "
          f"confidence {conf:.4f}, token gap widest {gaps[0]:.4f} mean "
          f"{gaps[1]:.5f}")
    assert steps[0] < LIMIT_WIDEST and steps[1] < LIMIT_MEAN, steps
    assert conf < LIMIT_CONF and gaps[0] < LIMIT_GAP, (conf, gaps)


def test_the_static_schedule_takes_a_forward_a_position(served):
    """On seeded weights no confidence reaches the threshold, so every block
    is denoised a position a step: a first block's masked positions one by
    one, every other block's four, each unmask step once; 1.25 forwards a
    token but for the first blocks' prompt tokens and the discarded ones."""
    reqs, _, counted = served
    for req, (n, gen) in zip(reqs, BATCH):
        opens = L - n % L
        steps = req.unmask_step
        assert sorted(steps[:opens]) == list(range(opens))
        for at in range(opens, len(steps) - L + 1, L):
            assert sorted(steps[at: at + L]) == list(range(T))
    assert counted["block.early"] == 0
    assert counted["tokens_generated"] == sum(g for _, g in BATCH)
    blocks = sum(-(-(n + g) // L) - n // L for n, g in BATCH)
    assert counted["block.stores"] == blocks
    discarded = sum(-(n + g) % L for n, g in BATCH)
    assert counted["decode.discarded_tokens"] == discarded
    assert counted["block.unmasked"] == (
        counted["tokens_generated"] + discarded)
    # a denoise forward a generated position and a store a block, on the
    # lanes a request holds; a lane whose request finished under a step
    # queued ahead runs that step too
    assert counted["block.lane_steps"] >= counted["block.unmasked"] + blocks


@pytest.mark.parametrize("lane", [1, 2])
def test_raw_pages_match_the_replay_tighter(params, lane, monkeypatch):
    """(b) With raw (float16) pools nothing is quantized: the same
    comparison holds 20 times tighter, and the 8-bit run does not pass
    it."""
    monkeypatch.setenv("CGX_KV_BITS", "0")
    n, gen = BATCH[lane]
    reqs, sched, seen = _serve_requests(params, [(_prompt(n, seed=n), gen)])
    assert not sched._prog.specs[0].quantized
    steps, _, _ = _compare(params, reqs[0], seen["r0"])
    print(f"lane {lane} raw: widest {steps[0]:.5f}, mean {steps[1]:.5f}")
    assert steps[0] < RAW_WIDEST and steps[1] < RAW_MEAN, steps


def test_eight_bit_pages_fail_the_raw_limits(params, served):
    reqs, seen, _ = served
    steps, _, _ = _compare(params, reqs[2], seen["r2"])
    assert steps[0] > RAW_WIDEST and steps[1] > RAW_MEAN, steps


def _stored_with_a_mask(state):
    """The planted fault: the tail takes the K and V of the last forward
    that still held a mask, and the store forward's are dropped."""
    return state["active"] & (jnp.sum(state["known"], axis=-1) == L - 1)


@pytest.mark.parametrize("fault", ["four_bit_pages", "causal_inside_a_block",
                                   "stored_with_a_mask"])
def test_a_planted_fault_is_caught(params, served, fault, monkeypatch):
    """(c) Each of three departures fails the tolerance the sound runs
    pass: 4-bit pages; a causal mask inside the block (the reference's
    reading of it, against the sound served run); K and V stored from a
    forward that still held a mask."""
    reqs, seen, _ = served
    req, seen, hf = reqs[2], seen["r2"], HF
    if fault == "causal_inside_a_block":
        hf = dict(HF, in_block="causal")
    else:
        if fault == "four_bit_pages":
            monkeypatch.setenv("CGX_KV_BITS", "4")
        else:
            monkeypatch.setattr(block_mod, "block_stores",
                                _stored_with_a_mask)
            scheduler_mod.invalidate_decode_cache("test")
        n, gen = BATCH[2]
        (req,), _, seen = _serve_requests(params,
                                          [(_prompt(n, seed=n), gen)])
        seen = seen["r0"]
        scheduler_mod.invalidate_decode_cache("test")
    steps, _, _ = _compare(params, req, seen, hf)
    print(f"{fault}: widest step {steps[0]:.4f}, mean step {steps[1]:.4f}")
    assert steps[1] > 2 * LIMIT_MEAN, steps


def _confident(params, scale=40.0):
    """The same weights under a head ``scale`` times as large: the logits'
    spread grows with it and the greedy token's probability passes 0.9 at
    many positions."""
    return {**params, "head": params["head"] * scale}


@pytest.mark.parametrize("ahead", [True, False])
def test_confident_positions_finish_a_block_early(params, ahead):
    """(d) ``low_confidence_dynamic``: with planted confident logits a
    denoise step unmasks every position past the threshold, blocks finish
    in fewer than ``T + 1`` forwards, and the host's counts, commits and
    tokens left follow what each read says, with the next step queued ahead
    and without (``_serve_requests`` holds the host's tail lengths and pages
    to the device's at every dispatch)."""
    before = metrics.snapshot("cgx.serve.")
    sharp = _confident(params)
    reqs, sched, seen = _serve_requests(
        sharp, [(_prompt(n, seed=n), g) for n, g in BATCH], ahead=ahead)
    after = metrics.snapshot("cgx.serve.")
    counted = {k.split("cgx.serve.", 1)[1]: v - before.get(k, 0.0)
               for k, v in after.items() if isinstance(v, float)}
    assert [len(r.output) for r in reqs] == [g for _, g in BATCH]
    assert counted["block.early"] > 5
    assert (counted.get("decode.ahead", 0) > 0) == ahead
    forwards = counted["block.lane_steps"] / counted["tokens_generated"]
    print(f"ahead {ahead}: {forwards:.3f} forwards a token, "
          f"{counted['block.early']:.0f} early steps")
    assert forwards < 1.1
    # a block's unmask steps: some step unmasked several positions
    steps = np.asarray(reqs[2].unmask_step)
    assert steps.max() < T and len(set(steps.tolist())) > 1
    full = steps[L - 19 % L:][: len(steps) // L * L - L]
    assert any(len(set(b)) < L for b in full.reshape(-1, L).tolist())
    # and the served run is still the reference's, step by step
    widest, conf, gaps = _compare(sharp, reqs[2], seen["r2"])
    assert widest[1] < LIMIT_MEAN and gaps[0] < LIMIT_GAP, (widest, gaps)
    # the rule, applied to the reference's own confidences, is the server's
    # up to near-ties: where it differs the confidences are within a percent
    for b, s, ref, ref_conf in reference.replay(
            sharp, reqs[2].tokens, reqs[2].output, reqs[2].unmask_step, HF,
            blocks=[1, 2, 3], q_block=32):
        when = steps[L - 19 % L + (b - 1) * L:][:L]
        unmask, _ = adapter.unmask_block(
            ref_conf[None], (when < s)[None], np.asarray([s]), T, 0.9)
        differ = np.asarray(unmask[0]) != (when == s)
        assert (np.abs(ref_conf[differ] - 0.9) < 0.02).all() or (
            np.ptp(ref_conf[when >= s]) < 0.02), (b, s, ref_conf, when)


def test_the_unmask_rule():
    """Both published schedules are one function: at a threshold no
    confidence passes the schedule's share a step, most confident first and
    the earlier position first among equals; under it every confident
    position at once where they are at least the share."""
    conf = np.asarray([[0.5, 0.95, 0.2, 0.93], [0.3, 0.3, 0.1, 0.3],
                       [0.99, 0.1, 0.2, 0.3]], np.float32)
    known = np.asarray([[False] * 4, [False, False, False, True],
                        [True, False, False, False]])
    step = np.asarray([0, 1, 0])
    static, early = adapter.unmask_block(conf, known, step, 4, 1.0)
    assert np.asarray(static).tolist() == [[False, True, False, False],
                                           [True, False, False, False],
                                           [False, False, False, True]]
    assert not np.asarray(early).any()
    dynamic, early = adapter.unmask_block(conf, known, step, 4, 0.9)
    assert np.asarray(dynamic).tolist() == [[False, True, False, True],
                                            [True, False, False, False],
                                            [False, False, False, True]]
    assert np.asarray(early).tolist() == [True, False, False]
    # two steps for four positions: two a step, no more than are masked
    two, _ = adapter.unmask_block(conf, known, np.asarray([0, 0, 1]), 2, 1.0)
    assert np.asarray(two).sum(-1).tolist() == [2, 2, 2]
    last, _ = adapter.unmask_block(
        conf[:1], np.asarray([[True, True, False, True]]), np.asarray([1]),
        2, 1.0)
    assert np.asarray(last).tolist() == [[False, False, True, False]]


def test_an_answer_ends_inside_a_block(params, served):
    """(e) A request whose ``max_new_tokens`` is no multiple of the block
    keeps the block's first tokens and the rest is discarded; one that ends
    by ``eos_token`` inside a block keeps the tokens up to it."""
    reqs, _, _ = served
    sound = reqs[2]
    n, gen = BATCH[2]
    # the token at a block's second generated position, not seen before it
    opens = L - n % L
    at = next(i for i in range(opens + 1, gen, L)
              if sound.output[i] not in sound.output[:i])
    before = metrics.get("cgx.serve.decode.discarded_tokens")
    (req,), sched, _ = _serve_requests(
        params, [(_prompt(n, seed=n), gen)], eos_token=sound.output[at])
    assert req.output == sound.output[: at + 1]
    assert req.unmask_step == sound.unmask_step[: at + 1]
    assert metrics.get("cgx.serve.decode.discarded_tokens") - before == 2
    # by count: 19 + 29 = 48 is a block's edge; 19 + 27 leaves two behind
    before = metrics.get("cgx.serve.decode.discarded_tokens")
    (req,), _, _ = _serve_requests(params, [(_prompt(n, seed=n), gen - 2)])
    assert req.output == sound.output[: gen - 2]
    assert metrics.get("cgx.serve.decode.discarded_tokens") - before == 2


def test_a_lane_evicted_for_a_page_is_prefilled_again(params):
    """(f) Under pool pressure a lane whose full tail finds no page goes
    back to the queue, its blocks so far dropped, and is prefilled again
    when pages free up: every request ends with what it asked for and the
    pool whole."""
    before = metrics.snapshot("cgx.serve.")
    requests = [(_prompt(n, seed=n), g) for n, g in
                [(17, 30), (18, 30), (19, 29), (16, 30)]]
    reqs, sched, _ = _serve_requests(params, requests, max_pages=13)
    after = metrics.snapshot("cgx.serve.")
    assert (after["cgx.serve.decode_evictions"]
            - before.get("cgx.serve.decode_evictions", 0.0)) >= 1
    for req, (prompt, gen) in zip(reqs, requests):
        assert len(req.output) == gen == len(req.unmask_step)
        assert req.first_token_at is not None


def test_a_recovery_cascade_mid_block_is_prefilled_again(params, served):
    """``supervisor.invalidate_trace_caches`` under lanes that are mid-block
    drops the programs and the page tables: every lane goes back to the
    queue, its blocks so far and its open block dropped with the host's
    counts of them, and the requests end with the tokens they would have
    had."""
    from torch_cgx_tpu.robustness.supervisor import invalidate_trace_caches

    sound = served[0]
    sched = ContinuousBatchScheduler(_server(params))
    reqs = [Request(id=f"r{i}", tokens=_prompt(n, seed=n), max_new_tokens=g)
            for i, (n, g) in enumerate(BATCH)]
    for r in reqs:
        sched.submit(r)
    while not all(r.output for r in reqs):  # every lane past a first store
        sched.step()
    assert sched._tail_len.any() and sched._left.any()
    generation = sched.cache.generation
    invalidate_trace_caches()
    assert sched.cache.generation == generation + 1
    assert sched.run(deadline_s=600.0)
    assert not sched._store_next.any() and not sched._left.any()
    assert sched.cache.free_pages == sched.cache.max_pages
    for got, want in zip(reqs, sound):
        assert len(got.output) == len(want.output) == len(got.unmask_step)
        # the same request served again: the same tokens but for near-ties
        same = np.mean(np.asarray(got.output) == np.asarray(want.output))
        assert same > 0.8, same


# name: (S, H, Hk, d, queries a tile, keys a block, q_block of the loop)
MASK_CASES = {
    "one tile, one block": (32, 4, 2, 16, 32, 32, 16),
    "tiles of two blocks": (96, 4, 2, 16, 32, 16, 32),
    "blocks longer than the tiles": (96, 4, 1, 16, 16, 48, 16),
    "head 128, bfloat16": (256, 8, 1, 128, 128, 128, 64),
}


@pytest.mark.parametrize("case", sorted(MASK_CASES))
@pytest.mark.parametrize("how", ["kernel", "loop"])
def test_prefill_attention_under_the_block_mask(case, how):
    """(g) ``prefill_attention``'s ``block`` mask, the kernel (interpret
    mode) and the loop, against ``dense_attention`` with the mask built
    plainly: key ``j`` visible iff ``j // L <= i // L``."""
    s, h, hk, d, tq, tk, q_block = MASK_CASES[case]
    dtype = jnp.bfloat16 if d == 128 else jnp.float32
    rng = np.random.default_rng(s + h)
    q, k, v = (jnp.asarray(rng.standard_normal((1, s, n, d)), dtype)
               for n in (h, hk, hk))
    scale = 1.0 / np.sqrt(d)
    at = np.arange(s)
    seen = jnp.asarray(at[None, :] // L <= at[:, None] // L)
    heads = lambda x: jnp.repeat(x, h // x.shape[2], axis=2).transpose(  # noqa: E731
        0, 2, 1, 3).astype(jnp.float32)
    want = dense_attention(heads(q), heads(k), heads(v), causal=False,
                           mask=seen[None, None]).transpose(0, 2, 1, 3)
    if how == "kernel":
        got = pfa.prefill_attention_pallas(
            q, k, v, window=0, scale=float(scale), tq=tq, tk=tk,
            interpret=True, block=L)
    else:
        got = pfa.prefill_attention_xla(
            q, k, v, window=0, scale=scale, q_block=q_block, dtype=dtype,
            block=L)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.reshape(1, s, h * d))))
    assert err < (3e-2 if d == 128 else 2e-5), err
    # and the causal mask is another answer
    causal = pfa.prefill_attention_xla(
        q, k, v, window=0, scale=scale, q_block=q_block, dtype=dtype)
    assert float(jnp.max(jnp.abs(causal.astype(jnp.float32)
                                 - want.reshape(1, s, h * d)))) > 0.05


def test_a_block_mask_refuses_what_it_cannot_tile():
    q = jnp.zeros((1, 30, 4, 16))
    k = jnp.zeros((1, 30, 2, 16))
    with pytest.raises(ValueError, match="whole blocks"):
        pfa.prefill_attention_xla(q, k, k, window=0, scale=1.0, q_block=16,
                                  dtype=jnp.float32, block=L)
    with pytest.raises(ValueError, match="no window"):
        pfa.prefill_attention_xla(q[:, :28], k[:, :28], k[:, :28], window=8,
                                  scale=1.0, q_block=16, dtype=jnp.float32,
                                  block=L)


def test_an_adapter_that_states_no_block_is_what_it_was():
    """(h) With ``block_tokens`` 1 the program key, the state's tree and
    the programs are the parent's: the key has no new entry, the state none
    of the block's, and ``tests/test_loop_serving.py``'s and
    ``tests/test_serving_layers.py``'s pins of the programs' jaxprs and the
    state's tree (``PARENT_PROGRAMS``, ``PARENT_STATE``), computed on
    commits before this one, still hold."""
    import test_serving_layers as layers

    make = {
        "gpt2": lambda: layers.gpt2_weights.make_params(layers.GPT2_HF, 1),
        "afmoe": lambda: layers.afmoe.weights.make_params(
            layers.afmoe.HF, 1),
        "mla_moe": lambda: layers.latent.weights.make_params(
            layers.latent.HF, 1),
        "hybrid_gdn": lambda: layers.olmo.weights.make_params(
            layers.olmo.HF, 1),
    }
    for kind in sorted(make):
        cls, cfg, serve = layers.SERVERS[kind]
        server = cls(cfg(), jax.eval_shape(make[kind]), serve())
        assert server.block_tokens == 1
        key = scheduler_mod._program_key(server)
        assert len(key) == 8 and key[0] == kind and key[1] == server.geometry
        prog = programs.build(server)
        state = jax.eval_shape(
            lambda: programs.fresh_state(prog, server.serve))
        assert prog.block == 1
        assert state["tokens"].shape == (server.serve.max_batch,)
        assert not {"known", "unmask_step", "block_step"} & set(state)


def test_the_block_adapters_state_and_key(params):
    """What the property adds: ``tokens (B, L)`` beside ``known``,
    ``unmask_step`` and ``block_step``, the block's parameters in the
    program key's geometry, the block counters last."""
    server = _server(params)
    prog = programs.build(server)
    state = programs.fresh_state(prog, server.serve)
    assert prog.block == L
    assert state["tokens"].shape == state["known"].shape == (4, L)
    assert state["unmask_step"].shape == (4, L)
    assert state["block_step"].shape == (4,)
    assert state["tail_k"][0].shape == (4, PAGE, 32)
    geometry = dict(scheduler_mod._program_key(server)[1])
    assert geometry["block_tokens"] == str(L)
    assert geometry["unmask_threshold"] == "0.9"
    assert server.step_counters[-4:] == adapter.BLOCK_COUNTERS
    other = BlockDiffusionServer(
        _cfg(dict(HF, confidence_threshold=1.0)), params, _serve())
    assert (scheduler_mod._program_key(other)
            != scheduler_mod._program_key(server))


def test_what_the_programs_refuse(params):
    """A page that is no whole blocks, a block beside passes, and the page
    transport (a stream brings a first token; a block adapter has none)."""
    with pytest.raises(ValueError, match="not whole blocks of 4"):
        programs.build(_server(params, page_tokens=6, max_seq=60))

    class Looped(BlockDiffusionServer):
        cache_passes = 2

    with pytest.raises(ValueError, match="a block of 4 positions"):
        programs.build(Looped(_cfg(), params, _serve()))
    says = r"adapter 'block_diffusion' runs a block of 4 positions a step"
    with pytest.raises(ValueError, match=says):
        tp.require_kv_streams(_server(params))
    with pytest.raises(ValueError, match=says):
        ContinuousBatchScheduler(_server(params), receiver=object())


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2),
    ("tie_word_embeddings", True),
    ("norm_topk_prob", False),
])
def test_a_config_this_block_is_not_is_refused(key, value):
    """``from_hf`` refuses, by name, what the block cannot honour; the
    reference reads the same keys."""
    with pytest.raises(ValueError, match=f"sdar_moe: {key}"):
        SdarMoeConfig.from_hf(dict(HF, **{key: value}))
    with pytest.raises(ValueError, match=key):
        reference._cfg_items(dict(HF, **{key: value}))


def test_a_schedule_the_block_cannot_run_is_refused():
    with pytest.raises(ValueError, match="5 denoising steps for a block"):
        SdarMoeConfig.from_hf(dict(HF, denoising_steps=5))
    with pytest.raises(ValueError, match="mask token 512"):
        SdarMoeConfig.from_hf(dict(HF, mask_token_id=512))


def test_a_request_longer_than_a_lane_is_refused(params):
    """A lane holds whole blocks: 70 + 9 positions end in a block that
    would pass ``max_seq`` 80."""
    sched = ContinuousBatchScheduler(_server(params))
    ok = Request(id="ok", tokens=_prompt(70), max_new_tokens=10)
    long = Request(id="long", tokens=_prompt(71), max_new_tokens=10)
    for req in (ok, long):
        sched.submit(req)
    assert sched.run(deadline_s=600.0)
    assert len(ok.output) == 10 and long.output == [] and long.done
