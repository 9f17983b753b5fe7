"""The window/global adapter (``serving/window.py``) through the one
scheduler, against the plain reference
(``benchmark/reference_window_moe.py``).

Tiny sizes (window 32, pages of 8: a ring of 5; 8 experts of which a token
takes 2; two periods of one global and three window layers), seeded weights
(``benchmark/weights_window_moe.py``), float32 activations at full matmul
precision unless a test says otherwise, so that what a tolerance bounds is
the thing it names (a page's rounding, an altered reading of the model) and
not the CPU's arithmetic. Logits are compared, not tokens.
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

from benchmark import reference_window_moe as reference  # noqa: E402
from benchmark import weights_window_moe as weights  # noqa: E402
from torch_cgx_tpu.models import window_moe as wm  # noqa: E402
from torch_cgx_tpu.models.gpt2 import GPT2, GPT2Config  # noqa: E402
from torch_cgx_tpu.models.window_moe import WindowMoeConfig  # noqa: E402
from torch_cgx_tpu.ops import prefill_attention as pfa  # noqa: E402
from torch_cgx_tpu.serving import adapter as adapter_mod  # noqa: E402
from torch_cgx_tpu.serving.adapter import ServeConfig  # noqa: E402
from torch_cgx_tpu.serving.gpt2 import GPT2Server  # noqa: E402
from torch_cgx_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.serving.transport import KvPageReceiver  # noqa: E402
from torch_cgx_tpu.serving.window import WindowMoEServer  # noqa: E402
from torch_cgx_tpu.utils.logging import metrics  # noqa: E402
from torch_cgx_tpu.wire import edges  # noqa: E402

from test_faults import FakeStore  # noqa: E402
import serving_guard  # noqa: E402

PAGE, WINDOW = 8, 32
RING = WINDOW // PAGE + 1
HF = dict(
    vocab_size=512, num_hidden_layers=8, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_num_primary_experts=8, moe_num_active_primary_experts=2,
    moe_ffn_hidden_size=32, sliding_window_size=WINDOW,
    sliding_window_layout=[0, 1, 1, 1] * 2, rope_layout=[0, 1, 1, 1] * 2,
    rope_theta=1500000, rms_norm_eps=1e-6,
    precision={"params": "float32"},
    # Attention scores with a spread of about 1.5 and experts that weigh in
    # the stream, so that what a test alters shows in the logits.
    init={"qk_std": 0.15, "expert_down_std": 0.05, "router_std": 0.3},
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
    return weights.make_params(HF, 41)


def _cfg(**kw):
    return WindowMoeConfig.from_hf(
        HF, **{"dtype": jnp.float32, "q_block": 16, **kw}
    )


def _serve(**kw):
    base = dict(page_tokens=PAGE, max_batch=2, max_pages=60, max_seq=208,
                ship_depth=4)
    return ServeConfig(**{**base, **kw})


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, HF["vocab_size"], n)]


def _serve_requests(params, requests, serve=None, together=True):
    """Serve ``requests`` ((prompt, gen) pairs) through one scheduler, all
    submitted at once or one after the other has finished, and return
    ``[(tokens, the decode steps' logits (gen - 1, V))]``: the logits are
    read by the adapter's own ``decode_forward`` on the very state each
    ``decode_step`` call is given, at the request's lane."""
    server = WindowMoEServer(_cfg(), params, serve or _serve())
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
    for batch in ([reqs] if together else [[r] for r in reqs]):
        for r in batch:
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
# * widest step: sound 0.036 and 0.247 (a run of 149 steps has a step at
#   which the router's second expert falls the other way: a spike, not a
#   level), every altered reading of the model 1.15-5.25. The limit lies
#   2.2 times above the sound runs and 2.1 times below the altered ones.
# * mean step: sound 0.023 and 0.026, 4-bit pages 0.383 and 0.391 (their
#   widest step, 0.60-0.68, is too near a sound spike to tell). The limit
#   lies 3.8 times above the sound runs and 3.8 times below 4-bit pages.
LIMIT_WIDEST, LIMIT_MEAN = 0.55, 0.1

# (prompt tokens, tokens served): a context that starts inside the window
# and ends four turns of the ring past it (11 + 150 = 161 positions, 20
# pages through a ring of 5), and one prefilled at more than twice the
# window (the ring keeps its last 5 of 9 pages, the rest go to scratch).
RUNS = {"across": (11, 150), "beyond": (75, 24)}


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
def test_prefill_then_decode_through_the_ring_matches_reference(
        params, served, run):
    """Prefill (banded attention in query blocks, the ring's pages written
    by slot), then decode (a window layer over its ring, a global one over
    the page table, tails committing on the way: a window layer's over the
    page that slid out) against the plain reference's full forward under
    its banded mask, at every decode position."""
    prompt, tokens, got, counted = served[run]
    recycled = counted["window.pages_recycled"]
    first, steps = _reference_steps(params, prompt, tokens)
    assert tokens[0] == int(np.argmax(first))
    widest, mean = _gaps(got, steps)
    print(f"{run}: widest step {widest:.4f}, mean step {mean:.4f}, window "
          f"pages recycled {recycled:.0f}")
    assert widest < LIMIT_WIDEST and mean < LIMIT_MEAN, (widest, mean)
    # Six window layers of two streams. "across": page 0 from the prefill,
    # 18 commits, the 14 from page 5 on over a page that slid out (the ring
    # turns nearly three times); "beyond": 3 commits, all over one.
    assert recycled == {"across": 12 * 14, "beyond": 12 * 3}[run]


@pytest.mark.parametrize("run", sorted(RUNS))
def test_live_and_committed_pages_are_counted_by_class(served, run):
    """The host's counts against the lengths: a step at position ``pos``
    has ``pos // PAGE`` committed pages, all live on a global layer, and on
    a window layer those from the page of the oldest position it sees."""
    (n, gen), counted = RUNS[run], served[run][3]
    positions = np.arange(n, n + gen - 1)  # one decode step each
    pages = positions // PAGE
    oldest = np.maximum(positions - WINDOW + 1, 0) // PAGE
    assert counted["decode_steps"] == gen - 1
    assert counted["kv.live_pages.global"] == pages.sum()
    assert counted["kv.decoded_pages.global"] == pages.sum()
    assert counted["kv.table_pages.global"] == (
        (gen - 1) * 2 * _serve().pages_per_seq)  # two lanes' rows a step
    assert counted["kv.live_pages.window"] == (pages - oldest).sum()
    assert counted["kv.decoded_pages.window"] == (pages - oldest).sum()
    assert (pages - oldest).max() == RING - 1  # the spare slot is never live
    commits = (n + gen - 2) // PAGE - n // PAGE
    assert counted["window.pages_committed"] == 12 * commits
    assert counted["pages_committed"] == 4 * commits  # the global layers'


ALTERED = {
    "window_ignored": dict(sliding_window_layout=[0] * 8),
    "rotary_on_global": dict(rope_layout=[1] * 8),
    "router_reads_z": dict(router_reads="expert_input"),
    "silu_gate": dict(expert_gate="silu"),
}


@pytest.mark.parametrize("run", sorted(RUNS))
@pytest.mark.parametrize("altered", sorted(ALTERED))
def test_an_altered_reading_of_the_model_fails_the_limit(params, served,
                                                         altered, run):
    """The served logits against the reference with one reading of the
    model altered (full attention on the window layers, rotary on the
    global layers, the router fed the experts' input, a SiLU gate): each
    leaves the limit the sound run is inside, so the comparison tells the
    readings apart (by the widest step, and by the mean step too)."""
    prompt, tokens, got, _ = served[run]
    _, steps = _reference_steps(params, prompt, tokens,
                                {**HF, **ALTERED[altered]})
    widest, mean = _gaps(got, steps)
    print(f"{altered} / {run}: widest step {widest:.4f}, mean {mean:.4f}")
    assert widest > 2 * LIMIT_WIDEST and mean > LIMIT_MEAN, (widest, mean)


def test_four_bit_pages_fail_the_limit(params, monkeypatch):
    """The same run over 4-bit pages leaves the 8-bit limit: all eight
    layers read pages, so the served logits feel the width."""
    monkeypatch.setenv("CGX_KV_BITS", "4")
    n, gen = RUNS["beyond"]
    prompt = _prompt(n, seed=len("beyond"))
    [(tokens, got)], _ = _serve_requests(params, [(prompt, gen)])
    _, steps = _reference_steps(params, prompt, tokens)
    widest, mean = _gaps(got, steps)
    print(f"4-bit pages: widest step {widest:.4f}, mean step {mean:.4f}")
    assert mean > 2 * LIMIT_MEAN, (widest, mean)


def test_two_lanes_of_different_lengths_in_one_batch(params):
    """A lane inside its window and a lane prefilled beyond it decode in
    one batch, each under its own masks and its own ring."""
    requests = [(_prompt(13, seed=1), 30), (_prompt(77, seed=2), 30)]
    results, sched = _serve_requests(params, requests)
    for (prompt, _), (tokens, got) in zip(requests, results):
        first, steps = _reference_steps(params, prompt, tokens)
        assert tokens[0] == int(np.argmax(first))
        widest, mean = _gaps(got, steps)
        assert widest < LIMIT_WIDEST and mean < LIMIT_MEAN, (widest, mean)
    assert sched._prog.ring == RING


def test_a_lane_taken_again_reads_nothing_of_the_last_ring(params):
    """One lane: a long request fills and turns its ring, then a short one
    takes the lane (and the ring) and is served as if alone."""
    requests = [(_prompt(70, seed=3), 20), (_prompt(9, seed=4), 12)]
    results, sched = _serve_requests(
        params, requests, serve=_serve(max_batch=1), together=False)
    table = np.asarray(sched._state["ring_table"])
    assert (table == -1).all()  # released with the lane
    for (prompt, _), (tokens, got) in zip(requests, results):
        _, steps = _reference_steps(params, prompt, tokens)
        widest, mean = _gaps(got, steps)
        assert widest < LIMIT_WIDEST and mean < LIMIT_MEAN, (widest, mean)


def test_pools_and_tables_by_page_class(params):
    """A window layer's pools hold a ring a lane and the scratch row, a
    global layer's ``max_pages + 1``; the state holds the second table; the
    gauges say what a uniform table would have held."""
    server = WindowMoEServer(_cfg(), params, _serve())
    sched = ContinuousBatchScheduler(server)
    st = sched._state
    for layer, window in enumerate(sched._prog.windows):
        words, meta = st["pools"][layer]["k"]
        rows = 2 * RING + 1 if window else 60 + 1
        assert words.shape[0] == rows and meta.shape[0] == rows
    assert sched._prog.windows == (0, WINDOW, WINDOW, WINDOW) * 2
    assert st["ring_table"].shape == (2, RING)
    assert st["page_table"].shape == (2, 26)
    held = metrics.get("cgx.serve.kv.pool_bytes.window")
    uniform = metrics.get("cgx.serve.kv.pool_bytes.uniform")
    assert held * 61 == uniform * (2 * RING + 1)


def test_ring_masks_hide_what_slid_out():
    """Slot ``s`` holds the newest page ``n`` with ``n % ring == s``; a row
    is live where the lane's position can still see it."""
    sv = _serve()
    state = {
        "tokens": jnp.zeros((3,), jnp.int32),
        "n_pages": jnp.asarray([0, 3, 12], jnp.int32),
        "pos": jnp.asarray([5, 3 * PAGE + 2, 12 * PAGE + 7], jnp.int32),
    }
    got = np.asarray(adapter_mod.ring_masks(sv, state, WINDOW))
    want = np.zeros((3, RING * PAGE), bool)
    for lane, (n_pages, pos) in enumerate([(0, 5), (3, 26), (12, 103)]):
        for page in range(max(n_pages - RING, 0), n_pages):
            for row in range(PAGE):
                if pos - (page * PAGE + row) < WINDOW:
                    want[lane, (page % RING) * PAGE + row] = True
    assert (got == want).all()
    # Lane 2 sees positions 72..103: pages 9, 10, 11 whole, none of 7, 8.
    assert got[2].sum() == 3 * PAGE


def test_ring_live_is_ring_masks_by_slot():
    """A slot is live iff one of its rows is: never-written slots, the slot
    whose page slid out and a vacated lane's whole ring are dead."""
    sv = _serve(max_batch=4)
    state = {
        "tokens": jnp.zeros((4,), jnp.int32),
        "n_pages": jnp.asarray([0, 3, 12, 5], jnp.int32),
        "pos": jnp.asarray([5, 3 * PAGE + 2, 12 * PAGE + 7, 5 * PAGE],
                           jnp.int32),
    }
    rows = np.asarray(adapter_mod.ring_masks(sv, state, WINDOW))
    live = np.asarray(adapter_mod.ring_live(sv, state, WINDOW))
    assert live.shape == (4, RING)
    assert (live == rows.reshape(4, RING, PAGE).any(-1)).all()
    # no page; pages 0-2; pages 9-11 of 7-11 held; pages 1-4 of 0-4 held.
    assert live.sum(-1).tolist() == [0, 3, 3, 4]


def test_page_live_is_lane_masks_by_page():
    """A slot of the page table is live iff one of its rows is committed: a
    lane's first ``n_pages``; a vacated lane's row is dead whole, a full
    lane's live whole."""
    sv = _serve(max_batch=4, max_seq=6 * PAGE)
    state = {
        "tokens": jnp.zeros((4,), jnp.int32),
        "n_pages": jnp.asarray([0, 1, 4, 6], jnp.int32),
        "tail_len": jnp.asarray([0, 3, PAGE - 1, 0], jnp.int32),
    }
    _, rows, _ = adapter_mod.lane_masks(sv, state)
    live = np.asarray(adapter_mod.page_live(sv, state))
    assert live.shape == (4, 6) and live.dtype == bool
    assert (live == np.asarray(rows).reshape(4, 6, PAGE).any(-1)).all()
    assert (live == np.asarray(rows).reshape(4, 6, PAGE).all(-1)).all()
    assert live.sum(-1).tolist() == [0, 1, 4, 6]
    assert live[2].tolist() == [True] * 4 + [False] * 2


def _mixed_batch():
    """A short request (it finishes first and leaves its lane vacant), one
    of 2 pages and one prefilled at 85 tokens, whose ring of 5 has turned
    twice: a batch of three, as ``(prompt, gen)`` pairs."""
    return [(_prompt(n, seed=10 + i), gen)
            for i, (n, gen) in enumerate([(9, 3), (17, 14), (85, 14)])]


def _mixed_batch_steps(params, watch):
    """Serve :func:`_mixed_batch`; ``watch(sched, p, state)`` is called
    before every decode step on the state the step is given."""
    server = WindowMoEServer(_cfg(), params, _serve(max_batch=3))
    sched = ContinuousBatchScheduler(server)
    prog = sched._prog

    def decode_step(p, state):
        watch(sched, p, state)
        return prog.decode_step(p, state)

    sched._prog = SimpleNamespace(**{**vars(prog), "decode_step": decode_step})
    for i, (prompt, gen) in enumerate(_mixed_batch()):
        sched.submit(Request(id=f"r{i}", tokens=prompt, max_new_tokens=gen))
    assert sched.run(deadline_s=600.0)


@pytest.mark.parametrize("guard", ["ring_live", "page_live"])
def test_the_guard_leaves_every_held_lanes_logits_bit_for_bit(params, guard):
    """A step's logits with both guards are the logits without ``guard``
    (the rings', the page table's) on every held lane, through steps at
    which the batch holds a lane with 2 pages (of 26 slots of its table), a
    lane whose ring has turned twice and a vacated lane (whose row is never
    served and is finite either way: its slots are all dead, its scores'
    mask a finite -1e30)."""
    from torch_cgx_tpu.serving import window as window_mod

    sv = _serve(max_batch=3)
    seen = serving_guard.steps_with_and_without_the_guard(
        WindowMoEServer(_cfg(), params, sv), window_mod, _mixed_batch(),
        guard=guard)
    serving_guard.assert_held_lanes_bit_for_bit(seen, sv.pages_per_seq)
    # the batch the docstring names was really seen
    assert sum(lanes == [False, True, True]
               and n_pages.tolist()[1:] in ([2, 10], [2, 11])
               for lanes, n_pages, _, _ in seen) >= 5


def test_the_hosts_live_pages_are_the_devices_mask_at_every_step(params):
    """The device's page mask (``ring_masks`` reduced by page, which is
    ``ring_live``) sums at every dispatched step to what the host adds to
    ``cgx.serve.kv.live_pages.window`` and to ``.decoded_pages.window`` from
    its own counts, through a run that crosses commits and slide-outs."""
    device, host = [], []

    def watch(sched, p, state):
        sv, b = sched.server.serve, sched.server.serve.max_batch
        rows = np.asarray(adapter_mod.ring_masks(sv, state, WINDOW))
        live = np.asarray(adapter_mod.ring_live(sv, state, WINDOW))
        assert (live == rows.reshape(b, RING, PAGE).any(-1)).all()
        device.append(float(live.sum()))
        if not host:
            note = sched._note_live_pages

            def counted(held):
                names = [f"cgx.serve.kv.{n}.window"
                         for n in ("live_pages", "decoded_pages")]
                before = [metrics.get(n) for n in names]
                note(held)
                host.append([metrics.get(n) - b
                             for n, b in zip(names, before)])

            sched._note_live_pages = counted

    _mixed_batch_steps(params, watch)
    assert len(host) == len(device) > 12
    assert [h[0] for h in host] == device and [h[1] for h in host] == device
    # The long lane commits pages 10 and 11 on the way (85 + 13 positions):
    # each commit slides a page out, so its live slots stay at RING - 1 or
    # fall to RING - 2 while the short lanes' grow.
    assert max(device) > min(device)


def test_the_hosts_decoded_pages_are_the_devices_page_mask_at_every_step(
        params):
    """The device's page mask (``page_live``) sums at every dispatched step
    to what the host adds to ``cgx.serve.kv.decoded_pages.global`` (and to
    ``.live_pages.global``) from its own counts, and
    ``.table_pages.global`` grows by the whole table, ``max_batch x
    pages_per_seq``, a step: what the global read decoded before it had a
    guard."""
    sv = _serve(max_batch=3)
    device, host = serving_guard.device_and_host_pages(
        WindowMoEServer(_cfg(), params, sv), _mixed_batch())
    assert len(host) == len(device) > 12
    assert [h[0] for h in host] == device and [h[1] for h in host] == device
    assert {h[2] for h in host} == {float(3 * sv.pages_per_seq)}
    # The engaged share: the table is 3 x 26 slots, the lanes hold 12-13.
    assert 0.1 < sum(device) / sum(h[2] for h in host) < 0.2


def test_banded_prefill_equals_full_attention_under_the_band():
    """``attend_blocks`` over the keys a block can see against one masked
    softmax over all of them, with and without a window, at a length that
    is no multiple of the block."""
    cfg = _cfg(q_block=8)
    rng = np.random.default_rng(0)
    s = 53
    q = jnp.asarray(rng.standard_normal((2, s, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, s, 2, 16)), jnp.float32)
    back = np.arange(s)[:, None] - np.arange(s)[None, :]
    for window in (0, 16):
        seen = (back >= 0) & ((back < window) if window else True)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)
                            ) / 4.0
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        want = jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.repeat(v, 2, axis=2))
        got = wm.attend_blocks(cfg, q, k, v, window)
        assert np.allclose(np.asarray(got), np.asarray(want).reshape(2, s, -1),
                           atol=1e-5)


def test_the_page_transport_refuses_a_ring(params):
    server = WindowMoEServer(_cfg(), params, _serve())
    with pytest.raises(ValueError, match="ring of its window"):
        ContinuousBatchScheduler(
            server, receiver=KvPageReceiver(FakeStore()))


def test_windows_must_agree_and_hold_a_page(params):
    cfg = _cfg()
    two = WindowMoEServer(
        WindowMoeConfig(**{**vars(cfg), "windows": (0, 32, 16, 32) * 2}),
        params, _serve())
    with pytest.raises(ValueError, match="one ring table"):
        ContinuousBatchScheduler(two)
    short = WindowMoEServer(
        WindowMoeConfig(**{**vars(cfg), "windows": (0, 4, 4, 4) * 2}),
        params, _serve())
    with pytest.raises(ValueError, match="shorter than a page"):
        ContinuousBatchScheduler(short)


# What ``GPT2Server``'s programs and state were at the parent of the PR that
# brought page classes (PR 41), at the sizes below and under this file's
# matmul precision: the first 16 hex digits
# of the SHA-256 of each program's jaxpr as text and of the state's tree. An
# adapter that states no window has to build exactly those. A PR that changes
# the programs on purpose reads the new values off this test's failure.
# (PR 46 did: a pool's meta is ``(pages, 2, buckets)``, so the leaves and
# every program that takes the pools differ from PR 41's parent in that shape
# and in one ``transpose`` of the few rows a writer writes or the XLA codec's
# read gathers; the tree's structure is the parent's.)
PARENT = {
    "state": "5b9dad382d4ed71c", "leaves": "8df471e6b5bf5241",
    "decode_step": "c212ea5f445bab5b", "commit": "6e161bd35f5b344f",
    "prefill_pages": "e8dcbcdea5e5f606", "admit_lane": "13d3de0864f2362a",
}


def _sha(x) -> str:
    return hashlib.sha256(str(x).encode()).hexdigest()[:16]


def test_an_adapter_without_windows_builds_the_parents_programs():
    cfg = GPT2Config(vocab_size=64, n_layer=2, n_head=2, d_model=32,
                     max_seq=64)
    tree = GPT2(cfg).init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    sv = ServeConfig(page_tokens=8, max_batch=2, max_pages=8, max_seq=32,
                     ship_depth=2)
    server = GPT2Server(cfg, tree, sv)
    sched = ContinuousBatchScheduler(server)
    prog, state = sched._prog, sched._state
    assert prog.ring == 0 and "ring_table" not in state
    shapes = [(a.shape, str(a.dtype))
              for a in jax.tree_util.tree_leaves(state)]
    got = {"state": _sha(jax.tree_util.tree_structure(state)),
           "leaves": _sha(shapes)}
    k = sv.commit_lanes
    zeros = np.zeros((k,), np.int32)
    toks = np.zeros((1, 16), np.int32)
    prefill = (server.p, state["pools"], toks, toks, np.int32(9),
               np.zeros((2,), np.int32), np.int32(2))
    out = jax.eval_shape(prog.prefill_pages, *prefill)
    row = np.full((sv.pages_per_seq,), -1, np.int32)
    got.update(
        decode_step=_sha(jax.make_jaxpr(prog.decode_step)(server.p,
                                                          state)),
        commit=_sha(jax.make_jaxpr(prog.commit)(state, zeros, zeros)),
        prefill_pages=_sha(jax.make_jaxpr(prog.prefill_pages)(*prefill)),
        admit_lane=_sha(jax.make_jaxpr(prog.admit_lane)(
            state, np.int32(0), row, np.int32(1), np.int32(2),
            np.int32(3), np.int32(10), out[2], out[4])),
    )
    assert got == PARENT


def test_gpt2s_decode_step_on_the_kernel_is_the_parents(monkeypatch):
    """``GPT2Server`` reads its whole table (``layer_cache_rows`` without
    ``live``; ``guards_global_read`` False): on the paged kernel too its
    decode step is the jaxpr from before the K/V adapters' global read had
    a guard (sha256 computed on the parent commit's ``git archive``)."""
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    cfg = GPT2Config(vocab_size=64, n_layer=2, n_head=2, d_model=32,
                     max_seq=64)
    tree = GPT2(cfg).init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    server = GPT2Server(cfg, tree, ServeConfig(
        page_tokens=8, max_batch=2, max_pages=8, max_seq=32, ship_depth=2))
    assert not server.guards_global_read
    assert serving_guard.decode_step_sha(server) == "9bf94dc333d9f7d6"


def test_a_long_prompt_through_the_kernel_serves_the_loops_tokens(
        params, served, monkeypatch):
    """The run prefilled at more than twice the window (75 positions: five
    query blocks), with ``ops.dispatch.prefill_attention`` on the kernel
    (interpreted here, at tiles of 16 queries by 16 keys so that a layer is
    several tiles and a window layer's tiles skip blocks at both ends): all
    eight layers' call sites count ``.pallas``, the tokens served through
    the turned ring are the loop's, and the decode steps' logits are as
    near the reference's as the loop's were."""
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    monkeypatch.setattr(pfa, "KEY_BLOCK", 16)
    monkeypatch.setattr(pfa, "TILE_ROWS", 32)  # two query heads a K/V head
    prompt, tokens, got, _ = served["beyond"]
    site = "cgx.codec.lowering.prefill_attention."
    before = metrics.snapshot(site)
    [(tokens_k, got_k)], _ = _serve_requests(
        params, [(prompt, RUNS["beyond"][1])])
    after = metrics.snapshot(site)
    assert after[site + "pallas"] - before.get(site + "pallas", 0) == 8
    assert after.get(site + "xla", 0) == before.get(site + "xla", 0)
    assert tokens_k == tokens
    _, steps = _reference_steps(params, prompt, tokens)
    widest, mean = _gaps(got_k, steps)
    print(f"kernel: widest step {widest:.4f}, mean step {mean:.4f}; from "
          f"the loop's logits {np.max(np.abs(got_k - got)):.2e}")
    assert widest < LIMIT_WIDEST and mean < LIMIT_MEAN, (widest, mean)
    assert np.max(np.abs(got_k - got)) < 1e-3 * np.std(steps)
