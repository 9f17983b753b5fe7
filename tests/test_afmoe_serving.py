"""The afmoe adapter (``serving/window.py``'s ``AfmoeServer``) through the
one scheduler, against the plain reference (``benchmark/reference_afmoe.py``).

Tiny sizes (window 32, pages of 8: a ring of 5; 64 published experts of which
the chip holds 8 and a token takes 4; layer 0 of the six leading dense layers
and one whole period after them, layers 8-11: sliding, sliding, sliding,
full), seeded weights (``benchmark/weights_afmoe.py``), float32 activations
at full matmul precision unless a test says otherwise, so that what a
tolerance bounds is the thing it names (a page's rounding, an altered reading
of the block) and not the CPU's arithmetic. Logits are compared, not tokens.
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

from benchmark import reference_afmoe as reference  # noqa: E402
from benchmark import weights_afmoe as weights  # noqa: E402
from torch_cgx_tpu.models.afmoe import AfmoeConfig  # noqa: E402
from torch_cgx_tpu.models.mla_moe import swiglu  # noqa: E402
from torch_cgx_tpu.parallel import moe  # noqa: E402
from torch_cgx_tpu.serving.adapter import ServeConfig  # noqa: E402
from torch_cgx_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.serving import window as window_mod  # noqa: E402
from torch_cgx_tpu.serving.window import AfmoeServer  # noqa: E402
from torch_cgx_tpu.utils.logging import metrics  # noqa: E402
from torch_cgx_tpu.wire import edges  # noqa: E402

import serving_guard  # noqa: E402

PAGE, WINDOW = 8, 32
RING = WINDOW // PAGE + 1
KEPT = [0, 8, 9, 10, 11]
HF = dict(
    model_type="afmoe", vocab_size=512, num_hidden_layers=len(KEPT),
    layers_kept=KEPT, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, num_experts=8, num_experts_published=64,
    first_expert=16, num_experts_per_tok=4, num_shared_experts=1,
    num_dense_layers=6, sliding_window=WINDOW,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    rope_theta=10000, rope_scaling=None, rms_norm_eps=1e-5,
    score_func="sigmoid", route_norm=True, route_scale=2.448, n_group=1,
    topk_group=1, mup_enabled=True, precision={"params": "float32"},
    # A router whose logits spread over 64 inputs as the configuration's do
    # over 3,072 (deviation 0.8 and 1.1) and a bias that moves the choice.
    init={"router_std": 0.1, "bias_std": 0.02},
)
EXPERT_LAYERS = sum(1 for i in KEPT if i >= HF["num_dense_layers"])


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("CGX_KV_BITS", "8")
    edges.clear_edges()
    with jax.default_matmul_precision("highest"):
        yield
    edges.clear_edges()


@pytest.fixture(scope="module")
def params():
    return weights.make_params(HF, 45)


def _cfg(**kw):
    return AfmoeConfig.from_hf(HF, **{"dtype": jnp.float32, "q_block": 16,
                                      **kw})


def _serve(**kw):
    base = dict(page_tokens=PAGE, max_batch=2, max_pages=60, max_seq=208,
                ship_depth=4)
    return ServeConfig(**{**base, **kw})


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, HF["vocab_size"], n)]


def _serve_requests(params, requests, serve=None):
    """Serve ``requests`` ((prompt, gen) pairs) through one scheduler, all
    submitted at once, and return ``[(tokens, the decode steps' logits (gen
    - 1, V))]``: the logits are read by the adapter's own ``decode_forward``
    on the very state each ``decode_step`` call is given, at the request's
    lane."""
    server = AfmoeServer(_cfg(), params, serve or _serve())
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
    assert sched.cache.free_rings == server.serve.max_batch
    return [(r.output, np.stack(seen[r.id])) for r in reqs], sched


def _reference_steps(params, prompt, tokens, cfg=HF):
    """The reference's logits at the positions the decode steps served."""
    ref = np.asarray(reference.forward(
        params, jnp.asarray(prompt + tokens[:-1], jnp.int32), cfg,
        q_block=32, expert_block=4))
    return ref[len(prompt) - 1], ref[len(prompt): len(prompt) + len(tokens) - 1]


def _gaps(got, ref):
    """The decode steps' largest |difference| of two logit arrays ``(steps,
    V)``, over the reference's spread (its standard deviation over the
    vocabulary): ``(the widest step, the mean step)``."""
    steps = np.max(np.abs(got - ref), axis=1) / np.std(ref)
    return float(np.max(steps)), float(np.mean(steps))


# What 8-bit pages may cost a run (float32 activations, so pages are all
# that differs), as the logit difference over the reference's spread; each
# test prints its readings. Read here over the runs below:
# * mean step: sound 0.019-0.027 (0.042 for a lane of the three-lane batch),
#   every altered reading of the block 0.30-4.5, 4-bit pages 0.37. The limit
#   lies 2.4 times above the sound runs and 3 times below the nearest altered
#   one: it is the limit that tells the readings and the pages' width apart.
# * widest step: a sound run's level is 0.03-0.04, but a run of 149 steps has
#   a step or two at 0.32-0.46: a page's rounding turns a near tie between
#   the router's fourth expert and its fifth, and where one of the two is
#   held, the layer's output (normed to unit scale whatever it holds) moves
#   by one expert's share. A spike, not a level; the altered readings' widest
#   step starts at 0.56, too near to tell. The limit is held against what
#   moves every logit at once (a lost page, a wrong position): 1.5 times the
#   largest spike read.
LIMIT_WIDEST, LIMIT_MEAN = 0.7, 0.1

# (prompt tokens, tokens served): a lane that stays under the window (9 + 12
# = 21 positions), one that starts inside it and ends four turns of the ring
# past it (11 + 150 = 161 positions, 20 pages through a ring of 5), and one
# prefilled at more than twice the window (the ring keeps its last 5 of 9
# pages, the rest go to scratch).
RUNS = {"under": (9, 12), "across": (11, 150), "beyond": (75, 24)}


@pytest.fixture(scope="module")
def served(params):
    """The sound runs, served once for the tests that read them."""
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, (n, gen) in RUNS.items():
            prompt = _prompt(n, seed=len(name))
            before = metrics.snapshot("cgx.serve.")
            [(tokens, got)], _ = _serve_requests(params, [(prompt, gen)])
            after = metrics.snapshot("cgx.serve.")
            out[name] = (prompt, tokens, got, {
                k.split("cgx.serve.", 1)[1]: v - before.get(k, 0.0)
                for k, v in after.items() if isinstance(v, float)})
    return out


@pytest.mark.parametrize("run", sorted(RUNS))
def test_prefill_then_decode_matches_reference(params, served, run):
    """Prefill (QK-normed, banded attention in query blocks, the ring's pages
    written by slot), then decode (a sliding layer over its ring, the full
    one over the page table, tails committing on the way) against the plain
    reference's full forward under its banded mask, at every decode
    position."""
    prompt, tokens, got, counted = served[run]
    first, steps = _reference_steps(params, prompt, tokens)
    assert tokens[0] == int(np.argmax(first))
    widest, mean = _gaps(got, steps)
    print(f"{run}: widest step {widest:.4f}, mean step {mean:.4f}, window "
          f"pages recycled {counted['window.pages_recycled']:.0f}")
    assert widest < LIMIT_WIDEST and mean < LIMIT_MEAN, (widest, mean)
    # Four sliding layers of two streams; "across" commits 18 pages, the 14
    # from page 5 on over a page that slid out, "beyond" 3, all over one.
    assert counted["window.pages_recycled"] == {
        "under": 0, "across": 8 * 14, "beyond": 8 * 3}[run]


@pytest.mark.parametrize("run", sorted(RUNS))
def test_a_step_counts_its_expert_layers_held_share(served, run):
    """The dense layer is no expert layer: a step's assignments are the four
    expert layers' (4 a token each, over all 64 published experts), of which
    the held eight get their share; nothing is dropped."""
    (_, gen), counted = RUNS[run], served[run][3]
    steps = gen - 1
    assert counted["decode_steps"] == steps
    assert counted["moe.assignments"] == steps * EXPERT_LAYERS * 4
    assert 0 < counted["moe.held_assignments"] < counted["moe.assignments"]
    assert counted["moe.experts_touched"] <= counted["moe.held_assignments"]
    assert counted["moe.dropped"] == 0


ALTERED = {
    "rotary_on_full": dict(rotate_full_layers=True),
    "no_qk_norm": dict(qk_norm=False),
    "head_wise_gate": dict(attention_gate="head_wise"),
    "no_post_norm": dict(sandwich=False),
}


@pytest.mark.parametrize("run", ["across", "beyond"])
@pytest.mark.parametrize("altered", sorted(ALTERED))
def test_an_altered_reading_of_the_block_fails_the_limit(params, served,
                                                         altered, run):
    """The served logits against the reference with one reading of the block
    altered (rotation on the full layer too, no QK norm, one gate number a
    head, a sub-layer's output added without its norm): each leaves the
    mean step's limit, which the sound run is inside, twice over."""
    prompt, tokens, got, _ = served[run]
    _, steps = _reference_steps(params, prompt, tokens,
                                {**HF, **ALTERED[altered]})
    widest, mean = _gaps(got, steps)
    print(f"{altered} / {run}: widest step {widest:.4f}, mean {mean:.4f}")
    assert mean > 2 * LIMIT_MEAN, (widest, mean)


def test_four_bit_pages_fail_the_limit(params, monkeypatch):
    """The same run over 4-bit pages leaves the 8-bit limit: all five layers
    read pages, so the served logits feel the width."""
    monkeypatch.setenv("CGX_KV_BITS", "4")
    n, gen = RUNS["beyond"]
    prompt = _prompt(n, seed=len("beyond"))
    [(tokens, got)], _ = _serve_requests(params, [(prompt, gen)])
    _, steps = _reference_steps(params, prompt, tokens)
    widest, mean = _gaps(got, steps)
    print(f"4-bit pages: widest step {widest:.4f}, mean step {mean:.4f}")
    assert mean > 2 * LIMIT_MEAN, (widest, mean)


def test_lanes_under_across_and_past_the_window_in_one_batch(params):
    """A lane inside its window, a lane that crosses it while decoding and a
    lane prefilled beyond it decode in one batch, each under its own masks
    and its own ring."""
    requests = [(_prompt(13, seed=1), 12), (_prompt(27, seed=2), 30),
                (_prompt(77, seed=3), 30)]
    results, sched = _serve_requests(params, requests,
                                     serve=_serve(max_batch=3))
    for (prompt, _), (tokens, got) in zip(requests, results):
        first, steps = _reference_steps(params, prompt, tokens)
        assert tokens[0] == int(np.argmax(first))
        widest, mean = _gaps(got, steps)
        assert widest < LIMIT_WIDEST and mean < LIMIT_MEAN, (widest, mean)
    assert sched._prog.ring == RING
    assert sched._prog.windows == (WINDOW, WINDOW, WINDOW, WINDOW, 0)


@pytest.mark.parametrize("guard", ["page_live", "ring_live"])
def test_a_guard_leaves_every_held_lanes_logits_bit_for_bit(params, guard):
    """A step's logits with both reads guarded (the global layer's by the
    lane's committed pages, ``adapter.page_live``; the window layers' by
    the ring's live slots) are the logits with ``guard`` taken off on every
    held lane, finite on a vacated one, through a batch of a short request
    (it finishes first and leaves its lane vacant), one of 2 pages of its
    table's 26 and one prefilled past the window."""
    sv = _serve(max_batch=3)
    seen = serving_guard.steps_with_and_without_the_guard(
        AfmoeServer(_cfg(), params, sv), window_mod,
        [(_prompt(9, seed=10), 3), (_prompt(17, seed=11), 14),
         (_prompt(85, seed=12), 14)], guard=guard)
    share = serving_guard.assert_held_lanes_bit_for_bit(seen, sv.pages_per_seq)
    assert 0.1 < share < 0.2  # 12-13 of the table's 78 slots


def test_the_eight_shares_add_up_to_the_uncut_references_expert_layer(
        params):
    """The share tied to the model: the routed parts the eight shares of a
    layer compute (``moe.dropless_moe(held=)`` over the 64-wide router, as
    the block calls it), with the shared expert counted once, add up to what
    the plain reference gives for the layer whole (float32, 1e-5 of the
    largest value: the sums are taken in another order)."""
    whole = {**HF, "num_experts": 64, "first_expert": 0}
    pm = weights.make_params(whole, 7)["layer_1"]["moe"]
    cfg = _cfg()
    m = jax.random.normal(jax.random.key(3), (24, 64), jnp.float32)
    items = reference._cfg_items(whole)
    w, want = reference._moe_head(
        m, {k: pm[k] for k in ("router", "bias", "shared")}, items)
    want = want + reference._experts_block(m, w, pm["gate"], pm["up"],
                                           pm["down"])
    total, held = swiglu(m, pm["shared"], jnp.float32), 0
    for share in range(8):
        at = slice(8 * share, 8 * share + 8)
        part, stats = moe.dropless_moe(
            m, pm["router"], pm["bias"], pm["gate"][at], pm["up"][at],
            pm["down"][at], top_k=cfg.top_k, scale=cfg.route_scale,
            dtype=jnp.float32, held=8 * share)
        total = total + part
        held += int(stats[moe.HELD_STATS.index("held_assignments")])
    assert held == 24 * 4
    assert float(jnp.max(jnp.abs(total - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2), ("score_func", "softmax"),
    ("route_norm", False), ("rope_scaling", {"type": "yarn"}),
])
def test_a_config_the_block_is_not_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        AfmoeConfig.from_hf({**HF, key: value})


def test_the_reference_refuses_a_router_in_groups(params):
    with pytest.raises(ValueError, match="n_group"):
        reference.forward(params, jnp.zeros((4,), jnp.int32),
                          {**HF, "n_group": 2})
