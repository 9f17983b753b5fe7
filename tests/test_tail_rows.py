"""The raw tails are kept as the rows the attention reads (ISSUE 36): float32
``(lanes, page_tokens, n_head * d_head)``, a token's row written where it
goes, nothing relaid between what is kept and what is contracted or
committed. One set of cases over the four adapters, each at the geometry of
its own test file.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from torch_cgx_tpu.models.gpt2 import GPT2, GPT2Config  # noqa: E402
from torch_cgx_tpu.ops import paged_kv  # noqa: E402
from torch_cgx_tpu.serving import programs as programs_mod  # noqa: E402
from torch_cgx_tpu.serving import scheduler as sched_mod  # noqa: E402
from torch_cgx_tpu.serving.hybrid import (  # noqa: E402
    HybridGDNServer,
    HybridSSMServer,
)
from torch_cgx_tpu.serving.latent import LatentMoEServer  # noqa: E402
from torch_cgx_tpu.serving.gpt2 import GPT2Server  # noqa: E402
from torch_cgx_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.wire import edges  # noqa: E402

import test_hybrid_serving as hybrid_tests  # noqa: E402
import test_latent_serving as latent_tests  # noqa: E402
import test_olmo_hybrid_serving as olmo_tests  # noqa: E402
import test_serving as gpt2_tests  # noqa: E402

STEPS = 3  # decode steps that fill the admitted lane's tail


def _gpt2():
    cfg = GPT2Config.tiny()
    params = GPT2(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    return GPT2Server(cfg, params, gpt2_tests._serve_cfg()), cfg.vocab_size


def _from(tests, adapter):
    def build():
        params = tests.weights.make_params(tests.HF, 3)
        server = adapter(tests._cfg(), params, tests._serve())
        return server, tests.HF["vocab_size"]

    return build


ADAPTERS = {
    "gpt2": _gpt2,
    "mla_moe": _from(latent_tests, LatentMoEServer),
    "hybrid_ssm": _from(hybrid_tests, HybridSSMServer),
    "hybrid_gdn": _from(olmo_tests, HybridGDNServer),
}


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("CGX_KV_BITS", "8")
    edges.clear_edges()
    with jax.default_matmul_precision("highest"):
        yield
    edges.clear_edges()


@pytest.fixture(params=sorted(ADAPTERS))
def served(request):
    """``(server, scheduler, vocabulary)`` of one adapter, nothing
    admitted."""
    server, vocab = ADAPTERS[request.param]()
    return server, ContinuousBatchScheduler(server), vocab


def _tail_entries(prog):
    """``(stream, layer, spec)`` of every tail the state holds."""
    return [
        (name, layer, spec)
        for layer, layer_streams in enumerate(prog.streams)
        for name, spec in layer_streams
    ]


def test_fresh_state_keeps_tails_as_rows(served):
    """A tail is float32 ``(B, page_tokens, n_head * d_head)``: a position a
    row, its heads side by side as a page holds them."""
    server, sched, _ = served
    sv = server.serve
    entries = _tail_entries(sched._prog)
    assert entries
    for name, layer, spec in entries:
        tail = sched._state[f"tail_{name}"][layer]
        assert tail.dtype == jnp.float32
        assert tail.shape == (
            sv.max_batch, sv.page_tokens, spec.n_head * spec.d_head)


def test_decode_step_relays_no_tail(served):
    """No ``reshape``, ``squeeze`` or ``transpose`` of ``decode_step``'s
    jaxpr, nested programs included, has an operand of a tail's shape: the
    tail is written, contracted and handed back as it is kept."""
    server, sched, _ = served
    prog, state = sched._prog, sched._state
    shapes = {
        tuple(state[f"tail_{name}"][layer].shape)
        for name, layer, _ in _tail_entries(prog)
    }
    found, seen = [], set()

    def walk(jp):
        for eqn in jp.eqns:
            seen.add(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call":
                continue  # a kernel's body holds blocks, not the state
            if eqn.primitive.name in ("reshape", "squeeze", "transpose"):
                for v in eqn.invars:
                    if tuple(v.aval.shape) in shapes:
                        found.append((eqn.primitive.name, v.aval))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(prog.decode_step)(server.p, state).jaxpr)
    assert "dot_general" in seen  # it did walk inside the program
    assert found == []


def _where_append(tail, tail_idx, fresh, dtype):
    """The reference's write: every row of every lane's tail chosen between
    what it held and this token's values by a one-hot over positions, as the
    forwards wrote it before ISSUE 36."""
    b, pt, width = tail.shape
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (b, pt), 1) == tail_idx[:, None]
    )[:, :, None]
    new = jnp.where(
        onehot, fresh.reshape(b, 1, width).astype(jnp.float32), tail)
    return new, new.astype(dtype)


def test_tails_through_prefill_decode_and_commit(served, monkeypatch):
    """A prefill whose last page is a tail, ``STEPS`` decode steps that fill
    it and one commit. The admitted tail holds the prefill forward's rows
    and zeros behind them; after every step every lane's tails equal,
    exactly in float32, the tails of the reference's write (a ``where``
    over the whole tail) from the same state, and the lane's rows before
    this token's are the ones it held; the committed page's words and meta
    are ``quantize_page_rows`` of the full tail's rows."""
    server, sched, vocab = served
    prog, sv = sched._prog, server.serve
    pt = sv.page_tokens
    entries = _tail_entries(prog)
    assert all(spec.quantized for _, _, spec in entries)
    n_full, tail_len = 1, pt - STEPS
    s = n_full * pt + tail_len
    prompt = [int(t) for t in np.random.default_rng(5).integers(0, vocab, s)]
    lane = gpt2_tests._admit_only(
        sched, Request(id="a", tokens=prompt, max_new_tokens=STEPS + 4))
    state = sched._state

    padded = sched_mod._pad_prompt(np.asarray(prompt, np.int32), pt)
    _, payloads = prog.prefill(
        server.p, padded[None],
        np.arange(padded.shape[0], dtype=np.int32)[None], np.int32(s - 1),
    )
    held = {}
    for name, layer, spec in entries:
        want = np.zeros((pt, spec.n_head * spec.d_head), np.float32)
        want[:tail_len] = np.asarray(
            payloads[name][layer][0, n_full * pt: s], np.float32
        ).reshape(tail_len, -1)
        assert want[:tail_len].any()
        held[name, layer] = np.asarray(state[f"tail_{name}"][layer])
        np.testing.assert_array_equal(held[name, layer][lane], want)

    with monkeypatch.context() as m:
        m.setattr(paged_kv, "append_tail_rows", _where_append)
        reference = jax.jit(
            lambda p, st: server.with_params(p).decode_forward(
                st, prog.streams)[1]
        ).lower(server.p, state).compile()

    for step in range(STEPS):
        at = tail_len + step
        assert int(state["tail_len"][lane]) == at
        want = reference(server.p, state)
        state, _ = prog.decode_step(server.p, state)  # donates the old one
        for name, layer, _ in entries:
            got = np.asarray(state[f"tail_{name}"][layer])
            np.testing.assert_array_equal(
                got, np.asarray(want[name][layer]))
            np.testing.assert_array_equal(
                got[lane, :at], held[name, layer][lane, :at])
            assert got[lane, at].any() and not got[lane, at + 1:].any()
            held[name, layer] = got
    assert int(state["tail_len"][lane]) == pt

    k = sv.commit_lanes
    pid = next(i for i in range(sv.max_pages)
               if i not in np.asarray(state["page_table"])[lane])
    state = prog.commit(
        state, np.full((k,), lane, np.int32),
        np.asarray([pid] + [sv.max_pages] * (k - 1), np.int32),
    )
    lane_now = {
        key: np.asarray(state[key])[lane]
        for key in ("n_pages", "tail_len", "page_table")
    }
    assert (lane_now["n_pages"], lane_now["tail_len"]) == (n_full + 1, 0)
    assert lane_now["page_table"][n_full] == pid
    for name, layer, spec in entries:
        rows = held[name, layer][lane].reshape(1, -1)
        np.testing.assert_array_equal(
            np.asarray(state[f"tail_{name}"][layer]), held[name, layer])
        want_pool = programs_mod._ingest_pool(
            paged_kv.empty_pool(sv.max_pages + 1, spec), jnp.asarray([pid]),
            paged_kv.quantize_page_rows(jnp.asarray(rows), spec), spec,
        )
        for got, want in zip(jax.tree.leaves(state["pools"][layer][name]),
                             jax.tree.leaves(want_pool)):
            np.testing.assert_array_equal(
                np.asarray(got)[pid], np.asarray(want)[pid])


# ---------------------------------------------------------------------------
# The pools' meta lies as the read kernel takes it (ISSUE 46): ``(pool rows,
# 2, buckets)``, and no program relays it. Over all seven adapters.
# ---------------------------------------------------------------------------


def _all_adapters():
    import test_afmoe_serving as afmoe_tests
    import test_ling_hybrid_serving as ling_tests
    import test_window_moe_serving as window_tests
    from torch_cgx_tpu.serving.hybrid import HybridLatentMoEServer
    from torch_cgx_tpu.serving.window import AfmoeServer, WindowMoEServer

    return {
        **ADAPTERS,
        "hybrid_kda_mla": _from(ling_tests, HybridLatentMoEServer),
        "window_moe": _from(window_tests, WindowMoEServer),
        "afmoe": _from(afmoe_tests, AfmoeServer),
    }


# What may take a pool-sized meta operand: the programs' own nesting, the
# read (the kernel, or the XLA codec's gather of the table's rows) and a
# writer's scatter of the rows it wrote.
_POOL_META_TAKERS = {"jit", "pjit", "pallas_call", "gather", "scatter"}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("adapter", [
    "gpt2", "mla_moe", "hybrid_ssm", "hybrid_gdn", "hybrid_kda_mla",
    "window_moe", "afmoe",
])
def test_no_program_relays_a_pools_meta(adapter, impl, monkeypatch):
    """Every quantized pool holds its meta as ``(pool rows, 2, buckets)``
    float32, and in ``decode_step`` and ``commit`` (the programs of every
    tick) nothing but the read and the writers' scatters has an operand of
    that shape: no ``transpose``, ``reshape``, ``copy`` or ``convert`` of a
    pool's meta stands between the state and the ``pallas_call`` / the
    ``gather`` that reads it or the ``scatter`` that writes it. What a writer
    turns is the few rows it wrote: a ``transpose`` of ``(K, buckets, 2)``."""
    if impl == "pallas":
        monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    server, _ = _all_adapters()[adapter]()
    sched = ContinuousBatchScheduler(server)
    prog, state, sv = sched._prog, sched._state, server.serve
    metas = {}
    for name, layer, spec in _tail_entries(prog):
        words, meta = state["pools"][layer][name]
        assert meta.dtype == jnp.float32
        assert meta.shape == (words.shape[0], 2, spec.num_buckets)
        metas[tuple(meta.shape)] = spec.num_buckets
    assert metas

    k = sv.commit_lanes
    zeros = np.zeros((k,), np.int32)
    programs = {
        "decode_step": jax.make_jaxpr(prog.decode_step)(server.p, state),
        "commit": jax.make_jaxpr(prog.commit)(
            state, zeros, zeros, *((zeros,) if prog.ring else ())),
    }
    for which, jaxpr in programs.items():
        takers = {name for shape in metas
                  for name in gpt2_tests._eqns_touching(jaxpr, shape)}
        assert takers and takers <= _POOL_META_TAKERS, (which, takers)
        assert ("scatter" in takers) == (which == "commit")
        if which == "commit":  # the K rows the commit wrote, never the pool
            for nb in metas.values():
                assert "transpose" in gpt2_tests._eqns_touching(
                    jaxpr, (k, nb, 2))
