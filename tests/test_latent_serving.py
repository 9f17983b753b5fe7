"""The latent-attention adapter (``serving/latent.py``) through the one
scheduler, against the plain reference (``benchmark/reference_mla_moe.py``).

Tiny sizes, seeded weights (``benchmark/weights_mla_moe.py``), float32
activations at full matmul precision unless a test says otherwise, so that
what a tolerance bounds is the thing it names (a page's rounding, a lower
precision) and not the CPU's arithmetic.
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

from benchmark import reference_mla_moe as reference  # noqa: E402
from benchmark import weights_mla_moe as weights  # noqa: E402
from torch_cgx_tpu.models import mla_moe  # noqa: E402
from torch_cgx_tpu.models.gpt2 import GPT2Config  # noqa: E402
from torch_cgx_tpu.models.mla_moe import MlaMoeConfig  # noqa: E402
from torch_cgx_tpu.ops import prefill_attention as pfa  # noqa: E402
from torch_cgx_tpu.parallel import moe  # noqa: E402
from torch_cgx_tpu.serving import scheduler as sched_mod  # noqa: E402
from torch_cgx_tpu.serving.latent import LatentMoEServer  # noqa: E402
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

PAGE = 8
HF = dict(
    vocab_size=512, num_hidden_layers=3, hidden_size=64,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, n_routed_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=32, n_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, rope_theta=32000000, rms_norm_eps=1e-6,
    norm_topk_prob=True, precision={"params": "float32"},
    # Peaked attention (queries and keys drawn large): a cached latent's
    # rounding then shows in the logits, as on the chip.
    init={"q_b_std": 0.3, "kv_b_std": 0.3},
)


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


def _serve(**kw):
    base = dict(page_tokens=PAGE, max_batch=3, max_pages=24, max_seq=64,
                ship_depth=4)
    return ServeConfig(**{**base, **kw})


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, HF["vocab_size"], n)]


def _served_logits(params, cfg, prompt, gen):
    """Serve one request through the scheduler and return ``(tokens, the
    decode steps' logits (gen - 1, V))``: the logits are read by the
    adapter's own ``decode_forward`` on the very state each
    ``decode_step`` call is given."""
    server = LatentMoEServer(cfg, params, _serve())
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


# What each page width may cost, as the largest logit difference over the
# reference's spread across the vocabulary. Read here at these sizes over
# three prompts (float32 activations, so that pages are all that differs):
# raw (float16 pages) 0.0019-0.0144, 8-bit 0.055-0.068, 4-bit 0.91-1.49;
# bfloat16 activations over raw pages 0.075-0.173. Each limit is two to
# three times its largest reading and under half the smallest reading of
# the width below it, so a width served in place of another fails, and so
# do bfloat16 activations where float32 is stated.
PAGE_LIMITS = {"0": 0.03, "8": 0.2, "4": 4.0}


@pytest.mark.parametrize("bits", ["0", "8", "4"])
def test_prefill_then_decode_matches_reference_logits(params, monkeypatch,
                                                      bits):
    """Prefill (expanded attention, pages quantized into the pools), then
    decode (absorbed attention against gathered pages + tails, tails
    committing into pages on the way) against the plain reference's full
    forward over ``prompt + served tokens``: logits compared at every
    decode position, each page width inside its own limit and OUTSIDE the
    limit of the width above it."""
    monkeypatch.setenv("CGX_KV_BITS", bits)
    prompt, gen = _prompt(2 * PAGE + 3), 14  # two commits while decoding
    tokens, got = _served_logits(params, _cfg(), prompt, gen)
    seq = prompt + tokens[:-1]
    ref = np.asarray(reference.forward(
        params, jnp.asarray(seq, jnp.int32), HF, q_block=16, expert_block=8))
    # The first served token is the prefill's argmax at the last prompt
    # position; served token j > 0 is decode step j - 1's.
    assert tokens[0] == int(np.argmax(ref[len(prompt) - 1]))
    ref_steps = ref[len(prompt): len(prompt) + gen - 1]
    gap = _gap(got, ref_steps)
    assert gap < PAGE_LIMITS[bits], gap
    tighter = {"8": "0", "4": "8"}.get(bits)
    if tighter:
        assert gap > PAGE_LIMITS[tighter], (
            f"{bits}-bit pages read {gap}: no worse than the "
            f"{tighter}-bit limit, so the limits tell nothing apart")


def test_lower_precision_activations_fail_the_raw_limit(params, monkeypatch):
    """The configuration here states float32 activations. Computed in
    bfloat16 instead (raw pages, so nothing else differs) the logits leave
    the raw limit (reading 0.075, 2.5 times the limit): the comparison can
    see a lower precision than stated."""
    monkeypatch.setenv("CGX_KV_BITS", "0")
    prompt, gen = _prompt(2 * PAGE + 3), 14
    tokens, got = _served_logits(
        params, _cfg(dtype=jnp.bfloat16), prompt, gen)
    ref = np.asarray(reference.forward(
        params, jnp.asarray(prompt + tokens[:-1], jnp.int32), HF,
        q_block=16, expert_block=8))
    gap = _gap(got, ref[len(prompt): len(prompt) + gen - 1])
    assert gap > 2 * PAGE_LIMITS["0"], gap


def test_absorbed_attention_equals_expanded(params):
    """Decode's absorbed form (``W_kvb`` folded into the query and the
    output, cached latents never expanded) against prefill's expanded form
    at the last position of a sequence: the same mathematics, so float32
    rounding alone separates them (limit 1e-5 of the output's largest
    value; reading 5e-7)."""
    cfg, pa = _cfg(), params["layer_1"]["attn"]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 21, HF["hidden_size"])),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(21), (2, 21))
    q_nope, q_rope, c, k_r = mla_moe.mla_project(cfg, x, pa, pos)
    full = mla_moe.attend_expanded(cfg, pa, q_nope, q_rope, c, k_r)
    # Cached positions beyond the live ones hold garbage and are masked.
    junk = jnp.full((2, 3, c.shape[-1]), 1e3, jnp.float32)
    last = mla_moe.attend_absorbed(
        cfg, pa, q_nope[:, -1], q_rope[:, -1],
        jnp.concatenate([c, junk], axis=1),
        jnp.concatenate([k_r, junk[..., : k_r.shape[-1]]], axis=1),
        jnp.arange(24)[None, :] < jnp.asarray([[21], [21]]),
    )
    want = np.asarray(full[:, -1])
    assert np.max(np.abs(np.asarray(last) - want)) < 1e-5 * np.max(
        np.abs(want))


# What a lane holds, as (committed pages, tail_len) of the two lanes.
_LANE_KINDS = {
    "no_page": ((0, 0), (0, 3)),
    "some_pages": ((2, 5), (3, 1)),
    "full_tail": ((1, PAGE - 1), (0, PAGE - 1)),
}


@pytest.mark.parametrize("lanes", sorted(_LANE_KINDS))
@pytest.mark.parametrize("bits", [8, 4, 0])
def test_latent_read_and_attend_matches_the_old_composition(params, bits,
                                                            lanes):
    """The latent read and absorbed attention as ISSUE 28 left them
    (``cfg.dtype`` rows from ``gather_dequant_pages``, the tail attended
    apart under one softmax) against the composition they replaced (each
    stream decoded to float32, the tail concatenated, cast, one attention
    over the joined table), to ``bfloat16`` rounding of the output."""
    from torch_cgx_tpu.ops import paged_kv

    cfg, pa = _cfg(dtype=jnp.bfloat16), params["layer_1"]["attn"]
    b, p, max_pages = 2, 3, 8
    widths = {"c": cfg.kv_lora_rank, "kr": cfg.d_rope}
    rng = np.random.default_rng(bits * 10 + len(lanes))

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    specs = {
        name: paged_kv.PageSpec(
            PAGE, 1, w, bits, paged_kv.default_bucket(PAGE * w) if bits else 1)
        for name, w in widths.items()
    }
    pools = {
        name: paged_kv.commit_page_rows(
            paged_kv.empty_pool(max_pages, spec), jnp.arange(max_pages),
            normal(max_pages, spec.flat), spec)
        for name, spec in specs.items()
    }
    (n0, t0), (n1, t1) = _LANE_KINDS[lanes]
    table = jnp.asarray(rng.permutation(max_pages)[: b * p].reshape(b, p),
                        jnp.int32)
    table = jnp.where(
        jnp.arange(p)[None, :] < jnp.asarray([[n0], [n1]]), table, -1)
    mask_c = jnp.arange(p * PAGE)[None, :] < jnp.asarray(
        [[n0 * PAGE], [n1 * PAGE]])
    mask_t = jnp.arange(PAGE)[None, :] <= jnp.asarray([[t0], [t1]])
    q_nope = normal(b, cfg.n_head, cfg.d_nope).astype(cfg.dtype)
    q_rope = normal(b, cfg.n_head, cfg.d_rope).astype(cfg.dtype)
    tails = {name: normal(b, PAGE, 1, w) for name, w in widths.items()}

    pages = {
        name: paged_kv.gather_dequant_pages(
            pools[name], table, specs[name], cfg.dtype)
        for name in widths
    }
    assert pages["c"].shape == (b, p * PAGE, cfg.kv_lora_rank)
    assert pages["c"].dtype == cfg.dtype
    got = mla_moe.attend_absorbed(
        cfg, pa, q_nope, q_rope, pages["c"], pages["kr"], mask_c,
        tail=tuple(tails[n][:, :, 0].astype(cfg.dtype) for n in widths)
        + (mask_t,),
    )
    joined = {
        name: jnp.concatenate(
            [paged_kv.gather_dequant_pages(
                pools[name], table, specs[name], jnp.float32
            )[:, :, None], tails[name]], axis=1,
        )[:, :, 0].astype(cfg.dtype)
        for name in widths
    }
    want = mla_moe.attend_absorbed(
        cfg, pa, q_nope, q_rope, joined["c"], joined["kr"],
        jnp.concatenate([mask_c, mask_t], axis=1),
    )
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    # One bfloat16 step of the latents' weighted sum, which the output
    # projection carries into every value it mixes.
    assert np.max(np.abs(got - want)) <= 2.0 ** -7 * np.abs(want).max()


def test_latent_decode_step_holds_no_table_sized_glue(params, monkeypatch):
    """Structure of the traced latent ``decode_step``: no ``concatenate``
    and no ``convert_element_type`` over either stream's table (the
    walker is tests/test_serving.py's). Pages of 512 tokens make both
    streams' pages whole chunks of 128-wide buckets, so both reads are the
    flat kernel's own ``bfloat16`` store, as on the chip; at these toy
    widths (32 and 8) a row is not whole lanes, so the rows keep the
    reshape after the kernel (tests/test_serving.py holds the kernel's
    row tiling)."""
    from test_serving import table_sized_glue

    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "128")
    monkeypatch.setenv("CGX_KV_BITS", "8")
    cfg = _cfg(dtype=jnp.bfloat16)
    sv = _serve(page_tokens=512, max_batch=3, max_pages=6, max_seq=1024)
    server = LatentMoEServer(cfg, params, sv)
    sched = ContinuousBatchScheduler(server)
    rows = (sv.max_batch * sv.pages_per_seq * sv.page_tokens,
            sv.max_batch * (sv.pages_per_seq + 1) * sv.page_tokens)
    metrics.reset()
    jaxpr = jax.make_jaxpr(sched._prog.decode_step)(server.p, sched._state)
    assert table_sized_glue(jaxpr, rows, cfg.d_rope) == []
    assert metrics.get(
        "cgx.codec.lowering.dequantize.pallas_flat.bfloat16"
    ) == 2 * cfg.n_layer
    assert metrics.get(
        "cgx.codec.lowering.dequantize_rows.pallas_flat") == 0
    assert metrics.get(
        "cgx.codec.lowering.dequantize_rows.xla_reshape") == 2 * cfg.n_layer


def _loop_moe(y, pm, top_k, scale):
    """The expert layer one token and one expert at a time, in numpy."""
    y = np.asarray(y, np.float64)
    router, bias = np.asarray(pm["router"], np.float64), np.asarray(
        pm["bias"], np.float64)
    gate, up, down = (np.asarray(pm[k], np.float64)
                      for k in ("gate", "up", "down"))
    out = np.zeros_like(y)
    load = np.zeros(router.shape[1], int)
    for t, row in enumerate(y):
        s = 1.0 / (1.0 + np.exp(-(row @ router)))
        chosen = np.argsort(-(s + bias), kind="stable")[:top_k]
        for e in chosen:
            g = row @ gate[e]
            h = g / (1.0 + np.exp(-g)) * (row @ up[e])
            out[t] += scale * s[e] / s[chosen].sum() * (h @ down[e])
            load[e] += 1
    return out, load


@pytest.mark.parametrize("impl", ["auto", "pallas"])
@pytest.mark.parametrize("skew", [0.0, 100.0])
def test_dropless_experts_match_a_per_token_loop(monkeypatch, params, skew,
                                                 impl):
    """``parallel.moe.dropless_moe`` against a loop over tokens and their
    experts, first as drawn, then with a selection bias so skewed that one
    expert takes EVERY token: nothing is dropped (no capacity exists to
    overflow) and the counts say what happened. float32 against float64:
    limit 1e-5 of the output's largest value (reading 4e-7). Under
    ``CGX_CODEC_IMPL=pallas`` the products are the ``cgx_grouped_matmul``
    kernel's (interpreted): the skewed case is one group over two row
    tiles and fifteen groups of none."""
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    pm = dict(params["layer_2"]["moe"])
    pm["bias"] = pm["bias"].at[5].add(skew)
    rng = np.random.default_rng(2)
    t, k = 37, HF["num_experts_per_tok"]
    y = jnp.asarray(rng.standard_normal((t, HF["hidden_size"])), jnp.float32)
    got, stats = moe.dropless_moe(
        y, pm["router"], pm["bias"], pm["gate"], pm["up"], pm["down"],
        top_k=k, scale=2.5, dtype=jnp.float32,
    )
    want, load = _loop_moe(y, pm, k, 2.5)
    assert np.max(np.abs(np.asarray(got) - want)) < 1e-5 * np.max(
        np.abs(want))
    counted = dict(zip(moe.STATS, np.asarray(stats)))
    assert counted == {
        "assignments": t * k, "experts_touched": int((load > 0).sum()),
        "load_max": int(load.max()), "dropped": 0,
    }
    if skew:
        assert load[5] == t  # one expert took every token


def test_idle_lanes_are_left_out_of_the_counts(params):
    pm = params["layer_1"]["moe"]
    y = jnp.ones((6, HF["hidden_size"]), jnp.float32)
    mask = jnp.asarray([True, False, True, False, False, False])
    _, stats = moe.dropless_moe(
        y, pm["router"], pm["bias"], pm["gate"], pm["up"], pm["down"],
        top_k=4, scale=2.5, dtype=jnp.float32, count_mask=mask,
    )
    # Identical rows choose identical experts: 2 counted rows x 4 experts.
    assert list(np.asarray(stats)) == [8, 4, 2, 0]


def test_step_counters_arrive_with_the_tokens(params, monkeypatch):
    """The decode step returns the lanes' tokens and the expert counts in
    ONE array, and the scheduler publishes them as ``cgx.serve.moe.*``:
    4 experts x 2 expert layers a token a step, none dropped."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    names = ("assignments", "experts_touched", "load_max", "dropped")
    before = {n: metrics.get(f"cgx.serve.moe.{n}") for n in names}
    steps0 = metrics.get("cgx.serve.decode_steps")
    server = LatentMoEServer(_cfg(), params, _serve())
    assert server.step_counters == tuple(f"moe.{n}" for n in names)
    sched = ContinuousBatchScheduler(server)
    reqs = [Request(id=f"r{i}", tokens=_prompt(11 + i, seed=i),
                    max_new_tokens=5) for i in range(2)]
    for r in reqs:
        sched.submit(r)
    assert sched.run(deadline_s=300.0)
    steps = metrics.get("cgx.serve.decode_steps") - steps0
    after = {n: metrics.get(f"cgx.serve.moe.{n}") - before[n] for n in names}
    # Both lanes decode together for 4 steps: 2 tokens x 4 experts x 2
    # expert layers each.
    assert steps == 4 and after["assignments"] == steps * 2 * 4 * 2
    assert 4 * steps <= after["experts_touched"] <= after["assignments"]
    assert steps <= after["load_max"] <= 2 * steps
    assert after["dropped"] == 0
    out, emitted = sched._prog.decode_step(server.p, sched._state)
    assert emitted.shape == (server.serve.max_batch + len(names),)


def test_latent_streams_in_state_pools_and_key(params, monkeypatch):
    """The adapter's two streams are what the scheduler's state holds: a
    ``c`` and a ``kr`` pool a layer, each with its own page geometry, and
    ``tail_c`` / ``tail_kr``; the program key holds the adapter's kind, so
    a GPT-2 server of any geometry can never hit these programs."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    server = LatentMoEServer(_cfg(), params, _serve())
    sched = ContinuousBatchScheduler(server)
    st = sched._state
    assert sched._prog.names == ("c", "kr")
    assert sorted(st["pools"][0]) == ["c", "kr"]
    c_spec, kr_spec = (spec for _, spec in sched._prog.streams[1])
    assert (c_spec.n_head, c_spec.d_head) == (1, HF["kv_lora_rank"])
    assert (kr_spec.n_head, kr_spec.d_head) == (1, HF["qk_rope_head_dim"])
    assert c_spec.bits == kr_spec.bits == 8
    assert st["tail_c"][2].shape == (3, PAGE, HF["kv_lora_rank"])
    assert st["tail_kr"][2].shape == (3, PAGE, HF["qk_rope_head_dim"])
    key = sched_mod._program_key(server)
    assert key[0] == "mla_moe"
    gpt2 = GPT2Server(GPT2Config.tiny(), {"params": {}}, _serve())
    assert sched_mod._program_key(gpt2)[0] == "gpt2"
    monkeypatch.setenv("CGX_KV_BITS", "4")
    assert sched_mod._program_key(server) != key


# ``HF`` with a latent wide enough that the kernel fetches ``c``'s pages by
# id (``serving_guard.LATENT_PAGE``): the geometry the guard is made for.
GUARD_HF = dict(HF, kv_lora_rank=serving_guard.LATENT_RANK)


@pytest.fixture(scope="module")
def guard_params():
    return weights.make_params(GUARD_HF, 3)


def _guard_server(params, hf=GUARD_HF):
    cfg = MlaMoeConfig.from_hf(hf, dtype=jnp.float32, q_block=8)
    return LatentMoEServer(cfg, params, _serve(
        page_tokens=serving_guard.LATENT_PAGE, max_seq=96))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_the_guard_leaves_every_held_lanes_logits_bit_for_bit(
        guard_params, monkeypatch, impl):
    """A step's logits with the latent layers' read guarded by the lane's
    committed pages (``adapter.page_live``, taken by ``c``) are the logits
    of the read of the whole table on every held lane, finite on a vacated
    one, on the XLA codec (a ``where``) and on the kernel (interpreted: a
    dead slot's rows stored as zeros), through ``mixed_batch``."""
    from torch_cgx_tpu.serving import latent as latent_mod

    serving_guard.latent_guard_env(monkeypatch, impl)
    serving_guard.assert_the_latent_guard_is_bit_for_bit(
        _guard_server(guard_params), latent_mod, _prompt)


def test_the_hosts_decoded_pages_are_the_devices_page_mask(guard_params,
                                                           monkeypatch):
    """A dispatched step adds the device's ``page_live`` (summed) to
    ``kv.decoded_pages.global`` and the whole table, ``max_batch x
    pages_per_seq``, to ``kv.table_pages.global``: the share of its table
    the latent read decodes."""
    serving_guard.latent_guard_env(monkeypatch)
    serving_guard.assert_the_host_counts_the_devices_mask(
        _guard_server(guard_params), _prompt)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_the_guard_goes_to_c_and_kr_is_read_whole(params, guard_params,
                                                  monkeypatch, impl):
    """The guard is handed to the stream whose pages the kernel fetches by
    id and to no other (``layer_cache_rows``' ``paged_only``): on the kernel
    every layer's ``c`` read takes the page ids and the guard, and no
    ``where`` goes over either decoded table; on the XLA codec the ``where``
    goes over ``c``'s rows alone. ``kr``'s table gains no pass. At ``HF``'s
    32-wide latent, which gathers too, nothing is guarded."""
    serving_guard.latent_guard_env(monkeypatch, impl)
    server = _guard_server(guard_params)
    n = server.cfg.n_layer
    metrics.reset()
    selects, kernels = serving_guard.guard_sites(server)
    site = "cgx.codec.lowering.dequantize_pages."
    if impl == "pallas":
        assert (selects, kernels) == ([], [2] * n)
        # ... on the plane loop: the adapter's own read asks for no unpack
        # (ISSUE 53; ``tests/test_serving_layers.py`` pins its program).
        assert metrics.snapshot(site) == {
            site + "pallas_paged.meta_planes": n, site + "unpack.planes": n,
            site + "xla_gather": n}
    else:
        assert (selects, kernels) == ([server.cfg.kv_lora_rank] * n, [])
    narrow = LatentMoEServer(_cfg(), params, _serve())
    assert serving_guard.guard_sites(narrow) == ([], [])


def test_disaggregated_path_refuses_latent_streams(params):
    """The transport's frames are K and V pages: a latent adapter is
    refused in plain words at both ends, before anything is shipped."""
    server = LatentMoEServer(_cfg(), params, _serve())
    store = FakeStore()
    with pytest.raises(ValueError, match="ships K and V page frames"):
        ContinuousBatchScheduler(server, receiver=KvPageReceiver(store))
    with pytest.raises(ValueError, match="local prefill only"):
        PrefillWorker(server, store)


def test_from_env_asks_the_adapter_for_its_cache_bytes(params, monkeypatch):
    """``ServeConfig.from_env`` sizes pages from what a token's cache
    weighs by the adapter's own count: a latent cache is ``n_layer * (Rkv
    + d_rope) * 4`` bytes, not GPT-2's ``2 * n_layer * d_model * 4``."""
    from torch_cgx_tpu.parallel import planner

    asked = []
    real = planner.solve_serve_plan

    def spy(**kw):
        asked.append(kw["kv_token_bytes"])
        return real(**kw)

    monkeypatch.setattr(planner, "solve_serve_plan", spy)
    for name in ("CGX_KV_PAGE_TOKENS", "CGX_KV_SHIP_DEPTH"):
        monkeypatch.delenv(name, raising=False)
    cfg = _cfg()
    ServeConfig.from_env(cfg)
    LatentMoEServer(cfg, params)  # no ServeConfig given: asks from_env
    gpt2 = GPT2Config.tiny()
    ServeConfig.from_env(gpt2)
    assert asked == [3 * (32 + 8) * 4] * 2 + [2 * 2 * 128 * 4]
    assert cfg.kv_bytes_per_token() * 7 < 2 * 3 * 4 * 64 * 4 * 4


def test_gpt2_server_says_what_it_is():
    with pytest.raises(ValueError, match="dense-MLP GPT-2 adapter"):
        GPT2Server(GPT2Config.tiny(n_experts=4), {"params": {}}, _serve())


def test_a_long_prompt_through_the_kernel_serves_the_loops_tokens(
        params, monkeypatch):
    """A prompt of five query blocks (35 positions, four pages and a tail)
    served with ``ops.dispatch.prefill_attention`` on the kernel
    (interpreted here, at tiles of 8 queries by 16 keys so that a layer is
    several tiles and blocks, all four heads a grid step against the shared
    rotated key): the three layers' call sites count ``.pallas``, the tokens
    served from the latent pages are the loop's, and the decode steps'
    logits inside the 8-bit limit and a tenth of it from the loop's."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    prompt, gen = _prompt(4 * PAGE + 3, seed=7), 10
    tokens, got = _served_logits(params, _cfg(), prompt, gen)
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    monkeypatch.setattr(pfa, "KEY_BLOCK", 16)
    monkeypatch.setattr(pfa, "TILE_ROWS", 32)
    site = "cgx.codec.lowering.prefill_attention."
    before = metrics.snapshot(site)
    tokens_k, got_k = _served_logits(params, _cfg(), prompt, gen)
    after = metrics.snapshot(site)
    assert after[site + "pallas"] - before.get(site + "pallas", 0) == 3
    assert after.get(site + "xla", 0) == before.get(site + "xla", 0)
    assert tokens_k == tokens
    ref = np.asarray(reference.forward(
        params, jnp.asarray(prompt + tokens[:-1], jnp.int32), HF,
        q_block=16, expert_block=8))
    ref_steps = ref[len(prompt): len(prompt) + gen - 1]
    print(f"8-bit pages against the reference: loop {_gap(got, ref_steps):.4f}"
          f", kernel {_gap(got_k, ref_steps):.4f}; kernel against loop "
          f"{_gap(got_k, got):.4f}")
    assert _gap(got_k, ref_steps) < PAGE_LIMITS["8"]
    # A latent within a last place of a bucket's edge rounds the other way:
    # a tenth of what the pages themselves cost, a tenth of the limit.
    assert _gap(got_k, got) < 0.1 * PAGE_LIMITS["8"]
