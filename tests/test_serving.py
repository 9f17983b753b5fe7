"""Serving data plane (ISSUE 15): paged quantized KV-cache wire for
disaggregated prefill/decode with continuous batching.

Covers the acceptance set:

* 8-bit KV decode bit envelope — greedy decode TOKEN-IDENTICAL to the
  raw-f16 baseline on the test model (and to the full-model recompute);
* paged-allocator stress — alloc/free/refcount under churn, prefix
  forks, double-free detection, pool exhaustion backpressure;
* chaos — a prefill worker killed mid-stream degrades through the
  bounded failover rung (local prefill) instead of wedging decode;
* transport hardening — frame checksum, publish-after-write ordering,
  wire-spec mismatch rejection;
* knob→cache-key completeness + the recovery cascade into the serving
  memos (supervisor.invalidate_trace_caches);
* the planner's serve terms and the SLO controller's budget law.
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_cgx_tpu import config as cfg_mod
from torch_cgx_tpu.models.gpt2 import GPT2, GPT2Config
from torch_cgx_tpu.serving import kv_cache as kv_mod
from torch_cgx_tpu.serving import programs as programs_mod
from torch_cgx_tpu.serving import scheduler as sched_mod
from torch_cgx_tpu.serving import transport as tp
from torch_cgx_tpu.serving.prefill import PrefillWorker
from torch_cgx_tpu.serving.adapter import ServeConfig
from torch_cgx_tpu.serving.gpt2 import GPT2Server
from torch_cgx_tpu.serving.scheduler import (
    ContinuousBatchScheduler,
    Request,
)
from torch_cgx_tpu.serving.slo import ServeSloController
from torch_cgx_tpu.serving.transport import (
    KvPageReceiver,
    KvPageSender,
    frame_page,
    unframe_page,
)
from torch_cgx_tpu.utils.logging import metrics
from torch_cgx_tpu.wire import edges

from test_faults import FakeStore

PAGE = 8
DEADLINE_S = 300.0


@pytest.fixture(autouse=True)
def _clear_edge_registry():
    """The SLO controller registers kv_page edge configs; a registered
    edge outlives the conftest layer-registry clear and would override
    the CGX_KV_BITS env default in later tests (registered configs win
    by design — the pollution must be cleaned, not the precedence)."""
    edges.clear_edges()
    yield
    edges.clear_edges()


@pytest.fixture(scope="module")
def model_setup():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    return cfg, model, params


def _serve_cfg(**kw):
    base = dict(page_tokens=PAGE, max_batch=4, max_pages=48, max_seq=64,
                ship_depth=2)
    base.update(kw)
    return ServeConfig(**base)


def _prompts(cfg, n, lens=None, seed=1):
    rng = np.random.default_rng(seed)
    lens = lens or [13 + 3 * i for i in range(n)]
    return [
        [int(t) for t in rng.integers(0, cfg.vocab_size, ln)]
        for ln in lens[:n]
    ]


def _run_local(cfg, params, prompts, gen=10, sv=None):
    server = GPT2Server(cfg, params, sv or _serve_cfg())
    sched = ContinuousBatchScheduler(server)
    reqs = [
        Request(id=f"r{i}", tokens=list(p), max_new_tokens=gen)
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        sched.submit(r)
    assert sched.run(deadline_s=DEADLINE_S), "serving run wedged"
    return [r.output for r in reqs]


# ---------------------------------------------------------------------------
# Bit envelope: greedy decode token identity.
# ---------------------------------------------------------------------------


# Slow tier: the exhaustive full-model oracle (~30 s);
# test_8bit_kv_token_identical_to_f16 keeps the decode-path token
# identity in tier-1.
@pytest.mark.slow
def test_decode_matches_full_model_greedy(model_setup, monkeypatch):
    """Raw-KV serving decode == full-model greedy recompute, token for
    token (the paged-cache forward is the module's math)."""
    cfg, model, params = model_setup
    monkeypatch.setenv("CGX_KV_BITS", "0")
    prompt = _prompts(cfg, 1, lens=[21])[0]
    (out,) = _run_local(cfg, params, [prompt], gen=8)
    seq = list(prompt)
    ref = []
    for _ in range(8):
        logits = model.apply(
            params, jnp.asarray([seq], jnp.int32), train=False
        )
        nxt = int(jnp.argmax(logits[0, -1]))
        ref.append(nxt)
        seq.append(nxt)
    assert out == ref


def test_8bit_kv_token_identical_to_f16(model_setup, monkeypatch):
    """The acceptance bit envelope: 8-bit quantized KV pages decode to
    the SAME greedy tokens as raw f16 shipping on the test model —
    multi-request, multi-page, with tail commits crossing page
    boundaries mid-generation."""
    cfg, _model, params = model_setup
    prompts = _prompts(cfg, 3, lens=[21, 16, 11])
    monkeypatch.setenv("CGX_KV_BITS", "0")
    raw = _run_local(cfg, params, prompts, gen=12)
    monkeypatch.setenv("CGX_KV_BITS", "8")
    q8 = _run_local(cfg, params, prompts, gen=12)
    assert q8 == raw
    # The quantized arm really quantized: kv_page wire bytes were
    # accounted below the raw f32 footprint.
    snap = metrics.snapshot("cgx.wire.bytes_")
    assert snap.get("cgx.wire.bytes_wire.kv_page", 0) > 0
    assert (
        snap["cgx.wire.bytes_wire.kv_page"]
        < snap["cgx.wire.bytes_raw.kv_page"] / 2
    )


def test_4bit_kv_stays_in_envelope(model_setup, monkeypatch):
    """4-bit KV is NOT required to be token-identical — but the decode
    must complete and produce the right shape of output (the envelope
    degrades gracefully, never crashes)."""
    cfg, _model, params = model_setup
    monkeypatch.setenv("CGX_KV_BITS", "4")
    prompts = _prompts(cfg, 2, lens=[13, 16])
    outs = _run_local(cfg, params, prompts, gen=6)
    assert all(len(o) == 6 for o in outs)


# ---------------------------------------------------------------------------
# Paged allocator stress.
# ---------------------------------------------------------------------------


def test_allocator_churn_no_leaks():
    cache = kv_mod.PagedKvCache(max_pages=32, page_tokens=8)
    rng = np.random.default_rng(0)
    live = {}
    for round_idx in range(200):
        sid = f"s{rng.integers(0, 12)}"
        if sid in live and rng.random() < 0.4:
            freed = cache.free_seq(sid)
            assert freed == len(live.pop(sid))
        else:
            pid = cache.alloc(sid)
            if pid is None:
                continue  # pool pressure is backpressure, not an error
            live.setdefault(sid, []).append(pid)
            assert cache.refcount(pid) == 1
    for sid in list(live):
        cache.free_seq(sid)
    assert cache.free_pages == 32
    assert cache.live_pages == 0


def test_allocator_fork_refcounts():
    cache = kv_mod.PagedKvCache(max_pages=8, page_tokens=4)
    for _ in range(3):
        cache.alloc("base")
    shared = cache.fork("base", "child")
    assert shared == cache.pages_of("base")
    for pid in shared:
        assert cache.refcount(pid) == 2
    # base frees: shared pages survive under the child's refcount
    assert cache.free_seq("base") == 0
    for pid in shared:
        assert cache.refcount(pid) == 1
    assert cache.free_seq("child") == len(shared)
    assert cache.free_pages == 8


def test_allocator_exhaustion_and_counters():
    cache = kv_mod.PagedKvCache(max_pages=2, page_tokens=4)
    assert cache.alloc("a") is not None
    assert cache.alloc("a") is not None
    before = metrics.get("cgx.serve.pool_exhausted")
    assert cache.alloc("a") is None
    assert metrics.get("cgx.serve.pool_exhausted") == before + 1


def test_allocator_invalidate_bumps_generation():
    cache = kv_mod.PagedKvCache(max_pages=4, page_tokens=4)
    cache.alloc("s")
    gen = cache.generation
    before = metrics.get("cgx.serve.cache_invalidations")
    kv_mod.invalidate_page_tables("test")
    assert cache.generation == gen + 1
    assert metrics.get("cgx.serve.cache_invalidations") > before
    assert not cache.has_seq("s")
    assert cache.free_pages == 4


# ---------------------------------------------------------------------------
# Transport hardening.
# ---------------------------------------------------------------------------


def test_frame_roundtrip_and_checksum():
    payload = np.random.default_rng(0).bytes(333)
    buf = frame_page(3, tp.K_PAGE, 7, 8, 512, 1024, payload)
    f = unframe_page(buf)
    assert (f.layer, f.kind, f.page_idx, f.bits, f.bucket, f.numel) == (
        3, tp.K_PAGE, 7, 8, 512, 1024
    )
    assert f.payload == payload
    corrupted = bytearray(buf)
    corrupted[-1] ^= 0xFF
    from torch_cgx_tpu.robustness.errors import WireCorruptionError

    with pytest.raises(WireCorruptionError):
        unframe_page(bytes(corrupted))
    # checksum off: the sentinel crc skips the verify
    un = frame_page(0, tp.META, 0, 0, 0, 0, b"{}", checksum=False)
    assert unframe_page(un).payload == b"{}"


def test_publish_after_write_poll_never_blocks():
    store = FakeStore()
    sender = KvPageSender(store, "s0", depth=2)
    recv = KvPageReceiver(store)
    recv.add_stream("s0")
    assert recv.poll() == []  # nothing published: returns, not blocks
    sender.post_meta({"frames": 3, "pages": 1, "prompt_tokens": 4,
                      "page_tokens": 4, "tail_tokens": 0,
                      "first_token": 1})
    sender.post_page(0, tp.K_PAGE, 0, 8, 512, 16, b"x" * 16)
    sender.post_page(0, tp.V_PAGE, 0, 8, 512, 16, b"y" * 16)
    deadline = time.monotonic() + 30.0
    got = []
    while len(got) < 3 and time.monotonic() < deadline:
        got.extend(recv.poll())
        time.sleep(0.005)
    sender.stop()
    assert [f.kind for _s, f in got] == [tp.META, tp.K_PAGE, tp.V_PAGE]
    assert recv.complete("s0")


def test_stream_spec_mismatch_fails_over_to_local(model_setup):
    """A stream whose frames carry the wrong wire spec (prefill resolved
    different kv_page bits than decode) is rejected at ingest and the
    request completes through the local-prefill rung — never a wedge,
    never a silently mis-decoded page."""
    cfg, _model, params = model_setup
    store = FakeStore()
    recv = KvPageReceiver(store)
    server = GPT2Server(cfg, params, _serve_cfg())
    sched = ContinuousBatchScheduler(server, receiver=recv)
    req = Request(id="bad", tokens=_prompts(cfg, 1, lens=[PAGE])[0],
                  max_new_tokens=4)
    sched.submit(req, remote=True)
    sender = KvPageSender(store, "bad", depth=4)
    spec = sched._prog.specs[0]
    n_frames = 1 + 2 * cfg.n_layer + 2 * cfg.n_layer
    sender.post_meta({
        "frames": n_frames, "pages": 1, "prompt_tokens": PAGE,
        "page_tokens": PAGE, "tail_tokens": 0, "first_token": 1,
    })
    wrong_bits = 3
    assert wrong_bits != spec.bits
    for layer in range(cfg.n_layer):
        for kind in (tp.K_PAGE, tp.V_PAGE):
            sender.post_page(layer, kind, 0, wrong_bits, 64, spec.flat,
                             b"\x00" * 64)
        for kind in (tp.K_TAIL, tp.V_TAIL):
            sender.post_page(layer, kind, 0, 0, 0, 0, b"")
    before = metrics.get("cgx.serve.ingest_errors")
    assert sched.run(deadline_s=DEADLINE_S)
    sender.stop()
    assert len(req.output) == 4
    assert metrics.get("cgx.serve.ingest_errors") == before + 1


# ---------------------------------------------------------------------------
# Disaggregated end-to-end + chaos.
# ---------------------------------------------------------------------------


def test_remote_prefill_matches_local(model_setup, monkeypatch):
    cfg, _model, params = model_setup
    monkeypatch.setenv("CGX_SERVE_PREFILL_TIMEOUT_MS", "60000")
    prompts = _prompts(cfg, 3, lens=[16, 16, 24])
    store = FakeStore()
    recv = KvPageReceiver(store)
    server = GPT2Server(cfg, params, _serve_cfg())
    sched = ContinuousBatchScheduler(server, receiver=recv)
    worker = PrefillWorker(server, store)
    reqs = [
        Request(id=f"r{i}", tokens=list(p), max_new_tokens=8)
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        sched.submit(r, remote=True)
    before = metrics.snapshot("cgx.serve.")
    t = threading.Thread(
        target=lambda: [worker.serve(r.id, r.tokens) for r in reqs]
    )
    t.start()
    ok = sched.run(deadline_s=DEADLINE_S)
    t.join(timeout=30)
    worker.stop()
    assert ok
    assert metrics.get("cgx.serve.prefill_failovers") == 0
    after = metrics.snapshot("cgx.serve.")
    grown = lambda name: after.get(f"cgx.serve.{name}", 0.0) - before.get(
        f"cgx.serve.{name}", 0.0)
    # the wire lost nothing: every frame the worker shipped, decode polled
    assert grown("frames_shipped") > 0 and grown("frames_lost") == 0
    assert grown("frames_received") == grown("frames_shipped")
    assert grown("ingest_s.count") == len(reqs)  # a span a stream
    local = _run_local(cfg, params, prompts, gen=8)
    assert [r.output for r in reqs] == local


def test_prefill_death_mid_stream_degrades_not_wedges(
    model_setup, monkeypatch
):
    """Chaos: the prefill worker dies after shipping only a PARTIAL
    stream (some frames published, completion never arrives). Decode
    must detect the stall within the bounded failover window, re-prefill
    locally, and finish every request — the PR 5 degrade-don't-die
    contract on the serving plane."""
    cfg, _model, params = model_setup
    monkeypatch.setenv("CGX_SERVE_PREFILL_TIMEOUT_MS", "500")
    store = FakeStore()
    recv = KvPageReceiver(store)
    server = GPT2Server(cfg, params, _serve_cfg())
    sched = ContinuousBatchScheduler(server, receiver=recv)
    prompts = _prompts(cfg, 2, lens=[24, 16])
    reqs = [
        Request(id=f"r{i}", tokens=list(p), max_new_tokens=6)
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        sched.submit(r, remote=True)
    # Worker "dies" mid-stream: r0's meta + a few frames publish, then
    # nothing — and r1's stream never even opens.
    sender = KvPageSender(store, "r0", depth=2)
    sender.post_meta({
        "frames": 99, "pages": 2, "prompt_tokens": 24,
        "page_tokens": PAGE, "tail_tokens": 0, "first_token": 1,
    })
    sender.post_page(0, tp.K_PAGE, 0, 8, 512, 16, b"z" * 16)
    t0 = time.monotonic()
    ok = sched.run(deadline_s=DEADLINE_S)
    wall = time.monotonic() - t0
    sender.stop()
    assert ok, "decode wedged behind a dead prefill worker"
    assert metrics.get("cgx.serve.prefill_failovers") == 2.0
    assert [len(r.output) for r in reqs] == [6, 6]
    # Degraded output is still CORRECT output (local prefill is the
    # same math).
    assert [r.output for r in reqs] == _run_local(
        cfg, params, prompts, gen=6
    )
    # Bounded: stall detection + recovery, not a 300 s timeout crawl.
    assert wall < DEADLINE_S / 2


def test_request_id_flow_survives_prefill_death(
    model_setup, monkeypatch, tmp_path
):
    """ISSUE 17: the request_id thread survives the failover rung. A
    prefill worker that dies after meta + one frame forces the local
    re-prefill; the span stream must still carry ONE coherent flow for
    the request (submit -> failover -> local prefill -> admit), and the
    critical-path engine must decompose its TTFT with the failover
    counted — traceability must not die with the worker."""
    from torch_cgx_tpu.observability import critpath, timeline

    cfg, _model, params = model_setup
    monkeypatch.setenv("CGX_SERVE_PREFILL_TIMEOUT_MS", "500")
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    timeline.reset()
    try:
        store = FakeStore()
        recv = KvPageReceiver(store)
        server = GPT2Server(cfg, params, _serve_cfg())
        sched = ContinuousBatchScheduler(server, receiver=recv)
        (prompt,) = _prompts(cfg, 1, lens=[24])
        req = Request(id="r0", tokens=list(prompt), max_new_tokens=6)
        sched.submit(req, remote=True)
        sender = KvPageSender(store, "r0", depth=2)
        sender.post_meta({
            "frames": 99, "pages": 2, "prompt_tokens": 24,
            "page_tokens": PAGE, "tail_tokens": 0, "first_token": 1,
        })
        # the dead worker's META frame already stamped the request id:
        # the wire stream joins back to the request without the
        # scheduler's stream registry
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            try:
                meta_frame_bytes = store.get("cgxkv/r0/1")
                break
            except KeyError:
                time.sleep(0.01)
        assert b'"request_id": "r0"' in meta_frame_bytes
        sender.post_page(0, tp.K_PAGE, 0, 8, 512, 16, b"z" * 16)
        assert sched.run(deadline_s=DEADLINE_S)
        sender.stop()
        assert len(req.output) == 6
        timeline.flush()
        flow = critpath.analyze(str(tmp_path), use_cache=False)["requests"]
        assert "r0" in flow, flow
        r0 = flow["r0"]
        assert r0["failovers"] >= 1
        assert r0["events"] >= 3  # submit + failover + prefill + admit
        assert r0["ttft_s"] is not None and r0["ttft_s"] > 0.0
        c = r0["components"]
        # the local re-prefill is attributed as prefill, and the stall
        # window that preceded the failover shows up (other/admission),
        # the decomposition summing to the TTFT
        assert c["prefill"] > 0.0
        assert sum(c.values()) == pytest.approx(r0["ttft_s"], abs=0.01)
    finally:
        timeline.reset()


def test_continuous_batching_admits_midstream(model_setup):
    """More requests than lanes: later requests admit as earlier lanes
    complete (the batch never drains), and every output matches the
    request's own single-request run."""
    cfg, _model, params = model_setup
    sv = _serve_cfg(max_batch=2, max_pages=64)
    prompts = _prompts(cfg, 5, lens=[16, 13, 11, 16, 24])
    outs = _run_local(cfg, params, prompts, gen=7, sv=sv)
    assert metrics.get("cgx.serve.requests_completed") >= 5
    for i, p in enumerate(prompts):
        (solo,) = _run_local(cfg, params, [p], gen=7, sv=sv)
        assert outs[i] == solo, f"request {i} diverged under batching"


# ---------------------------------------------------------------------------
# Knob→cache-key completeness + the recovery cascade.
# ---------------------------------------------------------------------------


def test_serve_knobs_rekey_decode_program(model_setup, monkeypatch):
    cfg, _model, params = model_setup
    server = GPT2Server(cfg, params, _serve_cfg())
    k0 = sched_mod._program_key(server)
    monkeypatch.setenv("CGX_KV_BITS", "4")
    k1 = sched_mod._program_key(server)
    assert k0 != k1, "CGX_KV_BITS flip must re-key the decode program"
    monkeypatch.delenv("CGX_KV_BITS")
    assert sched_mod._program_key(server) == k0
    # the serving knobs ride the shared trace fingerprint too
    fp0 = cfg_mod.trace_knob_fingerprint()
    monkeypatch.setenv("CGX_SERVE_MAX_BATCH", "3")
    assert cfg_mod.trace_knob_fingerprint() != fp0


def test_registry_write_rekeys_program(model_setup):
    cfg, _model, params = model_setup
    server = GPT2Server(cfg, params, _serve_cfg())
    k0 = sched_mod._program_key(server)
    edges.set_edge_config(
        edges.EDGE_KV_PAGE, "^layer_0$",
        edges.EdgeConfig(cc=cfg_mod.CompressionConfig(bits=5,
                                                      bucket_size=0)),
    )
    assert sched_mod._program_key(server) != k0
    specs = programs_mod._resolved_specs(server)
    assert specs[0].bits == 5
    assert specs[1].bits == cfg_mod.kv_bits()


def test_supervisor_cascade_reaches_serving(model_setup):
    """supervisor.invalidate_trace_caches must drop the decode-program
    LRU and bump every live cache's generation; a mid-flight scheduler
    then re-derives (re-prefills) and still completes correctly."""
    from torch_cgx_tpu.robustness.supervisor import invalidate_trace_caches

    cfg, _model, params = model_setup
    server = GPT2Server(cfg, params, _serve_cfg())
    sched = ContinuousBatchScheduler(server)
    prompts = _prompts(cfg, 2, lens=[16, 13])
    reqs = [
        Request(id=f"r{i}", tokens=list(p), max_new_tokens=6)
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        sched.submit(r)
    # run a few steps, then yank the rug mid-generation
    for _ in range(3):
        sched.step()
    gen_before = sched.cache.generation
    invalidate_trace_caches()
    assert sched.cache.generation == gen_before + 1
    assert len(sched_mod._PROGRAM_CACHE) == 0
    assert sched.run(deadline_s=DEADLINE_S)
    assert [r.output for r in reqs] == _run_local(
        cfg, params, prompts, gen=6
    )


# ---------------------------------------------------------------------------
# Planner serve terms.
# ---------------------------------------------------------------------------


def test_predict_serve_prices_quantization():
    from torch_cgx_tpu.parallel.planner import CostModel

    m = CostModel.default()
    kv_b = 2 * 2 * 128 * 4
    ttft_q, _ = m.predict_serve(96, kv_b, 2, 8, 512, 16, 4)
    ttft_raw, _ = m.predict_serve(96, kv_b, 2, 0, 512, 16, 4)
    assert ttft_q < ttft_raw, "8-bit pages must predict faster than f16"
    # deeper shipping pipelines never predict slower
    ttft_d1, _ = m.predict_serve(96, kv_b, 2, 8, 512, 16, 1)
    assert ttft_q <= ttft_d1 + 1e-12


def test_solve_serve_plan_picks_candidates():
    from torch_cgx_tpu.parallel import planner

    plan = planner.solve_serve_plan(96, 2 * 2 * 128 * 4, 2, 8, 512)
    assert plan.page_tokens in planner.SERVE_PAGE_CANDIDATES
    assert plan.ship_depth in planner.SERVE_DEPTH_CANDIDATES
    assert plan.predicted_ttft_s > 0
    assert metrics.get("cgx.plan.serve_page_tokens") == plan.page_tokens


def test_serve_config_from_env_uses_planner(model_setup, monkeypatch):
    cfg, _model, _params = model_setup
    sv = ServeConfig.from_env(cfg)
    from torch_cgx_tpu.parallel import planner

    assert sv.page_tokens in planner.SERVE_PAGE_CANDIDATES
    monkeypatch.setenv("CGX_KV_PAGE_TOKENS", "8")
    monkeypatch.setenv("CGX_KV_SHIP_DEPTH", "2")
    sv2 = ServeConfig.from_env(cfg)
    assert (sv2.page_tokens, sv2.ship_depth) == (8, 2)


# ---------------------------------------------------------------------------
# SLO controller.
# ---------------------------------------------------------------------------


def test_slo_controller_drops_and_recovers_bits(monkeypatch):
    monkeypatch.setenv("CGX_KV_BITS", "8")
    ctl = ServeSloController(
        ttft_slo_ms=100.0, every=0, min_bits=2, max_bits=8
    )
    assert ctl.engaged
    # violate: TTFT p90 far over target
    for _ in range(20):
        metrics.observe("cgx.serve.ttft_ms", 400.0)
    counted = [metrics.get("cgx.serve.slo_violations"),
               metrics.get("cgx.serve.slo_updates")]
    ctl.update()
    assert ctl.budget == 7
    assert metrics.get("cgx.serve.slo_violations") == counted[0] + 1
    assert metrics.get("cgx.serve.slo_updates") == counted[1] + 1
    cc = kv_mod.resolve_kv_config("layer_0")
    assert cc is not None and cc.bits == 7
    v0 = cfg_mod.registry_version()
    # hold: p90 between 0.8x and 1.0x of slo -> no movement, no churn
    metrics.reset()
    for _ in range(20):
        metrics.observe("cgx.serve.ttft_ms", 90.0)
    ctl.update()
    assert ctl.budget == 7
    assert cfg_mod.registry_version() == v0
    # comfortable: p90 well under target -> budget recovers
    metrics.reset()
    for _ in range(20):
        metrics.observe("cgx.serve.ttft_ms", 10.0)
    ctl.update()
    assert ctl.budget == 8
    cc = kv_mod.resolve_kv_config("layer_0")
    assert cc is not None and cc.bits == 8


def test_slo_controller_per_layer_solve_with_qerr(monkeypatch):
    """With kv_page qerr telemetry streaming, the budget re-allocates
    ACROSS layers (the scoped WireController solve): the error-heavy
    layer keeps more bits under the same average budget."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    from torch_cgx_tpu.wire import dispatch as wire_dispatch

    wire_dispatch.note_external_edge(
        "kv_page", "layer_0", numel=4096, bits=8,
        raw_bytes=16384, wire_bytes=4096,
    )
    wire_dispatch.note_external_edge(
        "kv_page", "layer_1", numel=4096, bits=8,
        raw_bytes=16384, wire_bytes=4096,
    )
    for _ in range(10):
        metrics.observe("cgx.qerr.wire:kv_page:layer_0", 0.10)
        metrics.observe("cgx.qerr.wire:kv_page:layer_1", 0.001)
    ctl = ServeSloController(
        ttft_slo_ms=100.0, every=0, min_bits=2, max_bits=8,
        min_observations=1,
    )
    for _ in range(20):
        metrics.observe("cgx.serve.ttft_ms", 400.0)
    alloc = ctl.update()
    b0 = alloc.get("wire:kv_page:layer_0")
    b1 = alloc.get("wire:kv_page:layer_1")
    assert b0 is not None and b1 is not None
    assert b0 > b1, "noisier layer must keep more bits"
    assert kv_mod.resolve_kv_config("layer_0").bits == b0
    assert kv_mod.resolve_kv_config("layer_1").bits == b1


def test_slo_scoped_controller_leaves_training_edges_alone(monkeypatch):
    """The serving objective must never re-bit a training edge: a
    ring_kv qerr stream outside the kv_page scope stays untouched by the
    SLO solve."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    from torch_cgx_tpu.wire import dispatch as wire_dispatch

    wire_dispatch.note_external_edge(
        "kv_page", "layer_0", numel=4096, bits=8,
        raw_bytes=16384, wire_bytes=4096,
    )
    edges.set_edge_config(
        edges.EDGE_RING_KV, "^train$",
        edges.EdgeConfig(cc=cfg_mod.CompressionConfig(bits=6,
                                                      bucket_size=0)),
    )
    wire_dispatch.note_external_edge(
        "ring_kv", "train", numel=4096, bits=6,
        raw_bytes=16384, wire_bytes=4096,
    )
    for _ in range(10):
        metrics.observe("cgx.qerr.wire:kv_page:layer_0", 0.05)
        metrics.observe("cgx.qerr.wire:ring_kv:train", 0.05)
    ctl = ServeSloController(
        ttft_slo_ms=100.0, every=0, min_observations=1
    )
    for _ in range(20):
        metrics.observe("cgx.serve.ttft_ms", 400.0)
    alloc = ctl.update()
    assert all(k.startswith("wire:kv_page:") for k in alloc)
    ring = edges.resolve_edge(edges.EDGE_RING_KV, "train")
    assert ring is not None and ring.cc.bits == 6


# ---------------------------------------------------------------------------
# Page codec layout cross-checks (pool rows == host wire bytes).
# ---------------------------------------------------------------------------


def _pool_row_wire_bytes(pool, i):
    """Pool row ``i`` of a quantized pool as its frame payload: the
    host codec's ``meta | words`` (``HostQTensor.to_bytes``), the row's two
    meta planes back as the wire's (unit, minimum) pairs."""
    from torch_cgx_tpu.ops import paged_kv

    words, meta = (np.asarray(a) for a in pool)
    pairs = np.ascontiguousarray(paged_kv.wire_meta(meta[i]))
    return np.concatenate([
        pairs.reshape(-1).view(np.uint8), words[i].reshape(-1).view(np.uint8)
    ])


def test_host_wire_bytes_drop_into_pool_rows():
    """The transport's host-codec page bytes and the decode pool's own
    jit commit produce IDENTICAL pool rows — the zero-re-encoding
    contract the receiver relies on. A pool row holds the wire words as
    rows of 128 int32 (``PageSpec.word_shape``, the flat kernels' operand
    layout): the frame's words by a reshape, byte for byte; and the frame's
    (unit, minimum) pairs as two planes, ``(2, num_buckets)``."""
    from torch_cgx_tpu.ops import codec_host, paged_kv

    spec = paged_kv.PageSpec(
        page_tokens=PAGE, n_head=4, d_head=32, bits=8, bucket_size=512
    )
    assert spec.word_shape == (2, 128) and spec.packed_words == 256
    rng = np.random.default_rng(3)
    row = rng.standard_normal(spec.flat).astype(np.float32)
    words_j, meta_j = paged_kv.quantize_page_rows(row[None], spec)
    assert words_j.shape == (1, 2, 128) and words_j.dtype == jnp.int32
    q_host = codec_host.quantize(row, spec.bits, spec.bucket_size)
    buf = np.asarray(q_host.to_bytes())
    rehydrated = codec_host.from_bytes(
        buf, spec.flat, spec.bits, spec.bucket_size, np.float32
    )
    np.testing.assert_array_equal(
        np.asarray(words_j[0]),
        paged_kv.pool_words(rehydrated.packed[None], spec)[0],
    )
    np.testing.assert_array_equal(
        np.asarray(paged_kv.wire_words(words_j, spec)[0]), rehydrated.packed
    )
    assert meta_j.shape == (1, 2, spec.num_buckets)
    np.testing.assert_array_equal(
        np.asarray(meta_j[0]), paged_kv.pool_meta(rehydrated.meta)
    )
    np.testing.assert_array_equal(
        np.asarray(paged_kv.wire_meta(meta_j)[0]), rehydrated.meta
    )
    assert buf.nbytes == spec.wire_bytes()
    np.testing.assert_array_equal(
        _pool_row_wire_bytes((words_j, meta_j), 0), buf
    )


@pytest.mark.parametrize("writer", ["commit_page_rows", "ingest_pool",
                                    "prefill_pages"])
@pytest.mark.parametrize("geo", ["xla-tail", "flat-kernel"])
def test_a_written_page_has_the_frames_wire_bytes(model_setup, monkeypatch,
                                                  writer, geo):
    """ISSUE 30 changed the pool's stored shape, not a byte of a page:
    whichever program writes a page — the commit of a full tail, the
    ingest of a received frame, the local prefill — pool row ``id`` is the
    frame the prefill worker would ship for that payload
    (``prefill._encode_page``: the host codec's ``meta | words``), in
    ``(max_pages + 1, *spec.word_shape) int32`` rows and (ISSUE 46) its
    pairs as the two planes of a ``(max_pages + 1, 2, num_buckets)`` meta;
    with pages of a chunk tail through the XLA codec and with whole-chunk
    pages through the flat Pallas kernel (interpret mode)."""
    from types import SimpleNamespace

    from torch_cgx_tpu.ops import paged_kv
    from torch_cgx_tpu.serving import prefill as prefill_mod

    cfg, _model, params = model_setup
    monkeypatch.setenv("CGX_KV_BITS", "8")
    pt = PAGE
    if geo == "flat-kernel":
        monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
        monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "128")
        pt = 32
    sv = _serve_cfg(page_tokens=pt, max_batch=2, max_pages=12, max_seq=128)
    server = GPT2Server(cfg, params, sv)
    sched = ContinuousBatchScheduler(server)
    spec = sched._prog.streams[0][0][1]
    assert (spec.num_buckets % 32 == 0) == (geo == "flat-kernel")
    n_full = 2
    s = n_full * pt + 3
    (prompt,) = _prompts(cfg, 1, lens=[s], seed=11)
    padded = sched_mod._pad_prompt(np.asarray(prompt, np.int32), pt)
    _, ks, _ = jax.jit(server.prefill_forward)(
        padded[None], np.arange(padded.shape[0], dtype=np.int32)[None],
        np.int32(s - 1),
    )
    rows = np.asarray(ks[0][0, : n_full * pt]).reshape(n_full, -1)
    frames = [prefill_mod._encode_page(r, spec) for r in rows]
    assert {len(f) for f in frames} == {spec.wire_bytes()}

    metrics.reset()
    if writer == "prefill_pages":
        lane = _admit_only(sched, Request(id="a", tokens=prompt,
                                          max_new_tokens=2))
        ids = np.asarray(sched._state["page_table"])[lane, :n_full]
        pool = sched._state["pools"][0]["k"]
    else:
        ids = np.asarray([5, 2])
        empty = paged_kv.empty_pool(sv.max_pages + 1, spec)
        if writer == "commit_page_rows":
            pool = paged_kv.commit_page_rows(
                empty, jnp.asarray(ids), jnp.asarray(rows), spec)
        else:
            stacked = sched_mod._stack_rows([
                sched_mod._decode_page_payload(
                    SimpleNamespace(payload=f), spec)
                for f in frames
            ], spec)
            pool = programs_mod._ingest_pool(
                empty, jnp.asarray(ids), stacked, spec)
    words, meta = pool
    assert words.shape == (sv.max_pages + 1,) + spec.word_shape
    assert words.dtype == jnp.int32 and spec.word_shape[1] == 128
    assert meta.shape == (sv.max_pages + 1, 2, spec.num_buckets)
    for i, frame in zip(ids, frames):
        np.testing.assert_array_equal(
            _pool_row_wire_bytes(pool, int(i)),
            np.frombuffer(frame, np.uint8),
        )
    if writer != "ingest_pool":  # the device quantized: by which codec
        assert metrics.get("cgx.codec.lowering.quantize.pallas_flat") == (
            (2 * cfg.n_layer if writer == "prefill_pages" else 1)
            if geo == "flat-kernel" else 0
        )


# A shipped page over the boundary that keeps the wire (ISSUE 46): the
# GPT-2 large page (160 buckets: the paged kernel) and a page that ends in a
# chunk tail (40 buckets: the XLA codec's gather), with their transport bytes
# as they were before the pool's meta became planes.
_SHIPPED = {
    "pallas_paged.meta_planes": ((64, 20, 64), 2 * 160 * 4 + 81920),
    "xla_gather": ((16, 20, 64), 2 * 40 * 4 + 20480),
}


@pytest.mark.parametrize("lowering", sorted(_SHIPPED))
def test_a_shipped_page_reads_back_as_the_senders_pool_row(lowering,
                                                           monkeypatch):
    """A page a prefill worker quantizes (``prefill._encode_page``: the host
    codec's ``meta | words``, the meta as ``(buckets, 2)`` pairs) and ships
    as frames (``transport.frame_page`` / ``unframe_page``) is ingested
    into a pool whose meta lies as planes (``_decode_page_payload`` ->
    ``_stack_rows`` -> ``_ingest_pool``) and read back, in either lowering,
    to the rows the sender's own pool gives, bit for bit. The frame is the
    parent's: ``PageSpec.wire_bytes`` and the pairs at the head of the
    payload are unchanged, and a pool row goes back to them
    (``wire_meta``)."""
    from torch_cgx_tpu.ops import paged_kv
    from torch_cgx_tpu.serving import prefill as prefill_mod

    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    page, wire_bytes = _SHIPPED[lowering]
    spec = paged_kv.PageSpec(*page, 8, 512)
    assert spec.wire_bytes() == wire_bytes
    nb, n, max_pages = spec.num_buckets, 3, 8
    rng = np.random.default_rng(46)
    rows = rng.standard_normal((n, spec.flat)).astype(np.float32)
    sent_ids, got_ids = np.asarray([1, 4, 6]), np.asarray([7, 0, 3])
    sender = paged_kv.commit_page_rows(
        paged_kv.empty_pool(max_pages + 1, spec), jnp.asarray(sent_ids),
        jnp.asarray(rows), spec)

    payloads = []
    for i, row in enumerate(rows):
        buf = tp.frame_page(0, tp.K_PAGE, i, spec.bits, spec.bucket_size,
                            spec.flat, prefill_mod._encode_page(row, spec))
        frame = tp.unframe_page(buf)
        assert len(frame.payload) == wire_bytes
        pairs = np.frombuffer(frame.payload[:8 * nb], np.float32)
        np.testing.assert_array_equal(
            pairs.reshape(nb, 2),
            np.asarray(paged_kv.wire_meta(sender[1][sent_ids[i]])))
        payloads.append(sched_mod._decode_page_payload(frame, spec))
    assert payloads[0][1].shape == (2, nb)
    receiver = programs_mod._ingest_pool(
        paged_kv.empty_pool(max_pages + 1, spec), jnp.asarray(got_ids),
        sched_mod._stack_rows(payloads, spec), spec)
    assert receiver[1].shape == (max_pages + 1, 2, nb)

    def read(pool, ids):
        table = jnp.asarray([[ids[2], ids[0], -1, ids[1]]], jnp.int32)
        return paged_kv.gather_dequant_pages(pool, table, spec, jnp.bfloat16)

    metrics.reset()
    got, want = read(receiver, got_ids), read(sender, sent_ids)
    site = "cgx.codec.lowering.dequantize_pages."
    assert metrics.snapshot(site) == {
        site + lowering: 2,
        **({} if lowering == "xla_gather" else {site + "unpack.planes": 2})}
    live = np.repeat([True, True, False, True], spec.page_tokens)
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint16)[0, live],
        np.asarray(want).view(np.uint16)[0, live])
    # ... and both are the page the worker had, within the codec's step.
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[0, : spec.page_tokens].reshape(-1),
        rows[2], atol=0.05)


_READ_GEOS = {
    # name: (page tokens, heads, head size, rows' type, paged tile or None)
    "gpt2l-kv": (64, 20, 64, jnp.bfloat16, 10),
    "gpt2l-kv-f32": (64, 20, 64, jnp.float32, 10),
    "joyai-c": (256, 1, 512, jnp.bfloat16, 16),
    "joyai-kr": (256, 1, 64, jnp.bfloat16, None),
}


@pytest.mark.parametrize("geo", sorted(_READ_GEOS))
def test_paged_read_is_the_gathered_read_bit_for_bit(geo, monkeypatch):
    """ISSUE 30: ``gather_dequant_pages`` on Pallas dispatch (interpret
    mode here) walks the page table inside the decode kernel where the
    geometry allows (``pallas_paged.*``: the GPT-2 K/V pages, the latent
    ``c``), and its rows are those of the composition it replaced — gather
    the table's pool rows, ``dequantize_batch`` over them — bit for bit,
    for a permuted table with sentinels, in ``bfloat16`` and ``float32``
    rows. The 64-wide ``kr`` keeps that composition (``xla_gather``)."""
    from torch_cgx_tpu.ops import dispatch as ops_dispatch
    from torch_cgx_tpu.ops import paged_kv

    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    pt, h, d, dt, tile = _READ_GEOS[geo]
    spec = paged_kv.PageSpec(pt, h, d, 8, 512)
    b, p, max_pages = 2, 4, 8
    assert spec.paged_read_tile(b * p, dt) == tile
    rng = np.random.default_rng(len(geo))
    pool = paged_kv.commit_page_rows(
        paged_kv.empty_pool(max_pages + 1, spec), jnp.arange(max_pages),
        jnp.asarray(rng.standard_normal((max_pages, spec.flat)),
                    jnp.float32), spec)
    table = rng.permutation(max_pages).reshape(b, p).astype(np.int32)
    table[0, 2:] = -1
    table[1, 3:] = -1
    table = jnp.asarray(table)

    metrics.reset()
    got = paged_kv.gather_dequant_pages(pool, table, spec, dt)
    assert got.shape == (b, p * pt, h * d) and got.dtype == dt
    site = "cgx.codec.lowering.dequantize_pages."
    # The kernel notes the unpack it took (the default: the plane loop); a
    # gather has no kernel to ask.
    assert metrics.snapshot(site) == (
        {site + "pallas_paged.meta_planes": 1, site + "unpack.planes": 1}
        if tile else {site + "xla_gather": 1})
    assert metrics.get("cgx.codec.lowering.dequantize_rows."
                       + ("pallas_flat" if tile else "xla_reshape")) == 1

    ids = jnp.maximum(table.reshape(-1), 0)
    want = ops_dispatch.dequantize_batch(
        paged_kv.pool_qtensor(*pool, ids, spec), out_dtype=dt,
        row_width=h * d,
    ).reshape(got.shape)
    kind = {2: np.uint16, 4: np.uint32}[np.dtype(dt).itemsize]
    np.testing.assert_array_equal(
        np.asarray(got).view(kind), np.asarray(want).view(kind))


@pytest.mark.parametrize("window", [False, True], ids=["table", "ring"])
@pytest.mark.parametrize("geo", sorted(_READ_GEOS) + ["raw"])
def test_guarded_read_zeroes_dead_entries_in_every_lowering(geo, window,
                                                            monkeypatch):
    """ISSUE 42: with ``live``, ``gather_dequant_pages`` returns one array
    whichever lowering writes it (the kernel's guard on Pallas dispatch, a
    ``where`` over the gathered decode or over a raw pool's rows): a live
    entry's rows are the unguarded read's bit for bit, a dead entry's are
    zeros whether its slot names a sentinel or a stale page. ISSUE 53: so
    for a ring and for a page table, and with either unpack asked for: the
    byte unpack's rows are the plane loop's bit for bit, guarded and bare,
    and the call site notes which the kernel took (a gather has no kernel
    to ask, and notes none)."""
    from torch_cgx_tpu.ops import paged_kv

    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    pt, h, d, dt, tile = _READ_GEOS.get(geo, (64, 20, 64, jnp.float16, None))
    spec = paged_kv.PageSpec(pt, h, d, *((0, 1) if geo == "raw" else (8, 512)))
    b, p, max_pages = 2, 4, 8
    rng = np.random.default_rng(len(geo))
    pool = paged_kv.commit_page_rows(
        paged_kv.empty_pool(max_pages + 1, spec), jnp.arange(max_pages),
        jnp.asarray(rng.standard_normal((max_pages, spec.flat)),
                    jnp.float32), spec)
    table = rng.permutation(max_pages).reshape(b, p).astype(np.int32)
    table[0, 2:] = -1  # a short lane: sentinels, dead
    live = np.asarray([[1, 1, 0, 0], [1, 0, 1, 1]], bool)  # and a stale page
    table = jnp.asarray(table)
    kind = {2: np.uint16, 4: np.uint32}[np.dtype(dt).itemsize]

    def pages(a):
        return np.asarray(a).view(kind).reshape(b, p, pt, h * d)

    bare = paged_kv.gather_dequant_pages(pool, table, spec, dt)
    assert pages(bare)[~live].any()
    for unpack in ("planes", "bytes"):
        read = functools.partial(
            paged_kv.gather_dequant_pages, pool, table, spec, dt,
            window=window, unpack=unpack)
        np.testing.assert_array_equal(pages(read()), pages(bare))
        metrics.reset()
        got = read(live=jnp.asarray(live))
        if geo != "raw":
            site = ("cgx.codec.lowering.dequantize_pages."
                    + ("window." if window else ""))
            lowering = "pallas_paged.meta_planes" if tile else "xla_gather"
            assert metrics.snapshot(
                "cgx.codec.lowering.dequantize_pages.") == {
                site + lowering: 1,
                **({site + f"unpack.{unpack}": 1} if tile else {})}
        assert got.shape == bare.shape and got.dtype == bare.dtype == dt
        np.testing.assert_array_equal(pages(got)[live], pages(bare)[live])
        assert not pages(got)[~live].any()


@pytest.mark.parametrize("page,bits,bucket,why", [
    ((64, 20, 64), 0, 1, "a raw pool: nothing to decode"),
    ((16, 20, 64), 8, 512, "40 buckets: a chunk tail, the XLA codec's"),
    ((256, 1, 64), 8, 512, "64-wide rows are not whole lanes"),
    ((64, 20, 64), 8, 64, "a bucket under 128 lanes: the chunk kernels'"),
    ((64, 20, 24), 8, 512, "480-wide rows are not whole lanes"),
])
def test_paged_read_rule_refuses_what_the_kernel_cannot_store(page, bits,
                                                              bucket, why):
    """``PageSpec.paged_read_tile`` is a static function of the geometry:
    None (the read gathers) for raw pools, pages that are not whole
    chunks of 128-lane buckets and rows the kernel cannot store itself; a
    tile of whole pages for the serving cells' K/V and latent pages."""
    from torch_cgx_tpu.ops import paged_kv

    spec = paged_kv.PageSpec(*page, bits, bucket)
    for dt in (jnp.bfloat16, jnp.float32, jnp.float16):
        assert spec.paged_read_tile(512, dt) is None, why
    assert paged_kv.PageSpec(64, 20, 64, 8, 512).paged_read_tile(
        512, jnp.bfloat16) == 10
    assert paged_kv.PageSpec(256, 1, 512, 8, 512).paged_read_tile(
        512, jnp.bfloat16) == 16
    # Off Pallas dispatch (this suite's default) every read gathers.
    metrics.reset()
    spec = paged_kv.PageSpec(64, 20, 64, 8, 512)
    jax.eval_shape(
        lambda pool, table: paged_kv.gather_dequant_pages(
            pool, table, spec, jnp.bfloat16),
        jax.eval_shape(lambda: paged_kv.empty_pool(9, spec)),
        jax.ShapeDtypeStruct((2, 4), jnp.int32),
    )
    assert metrics.get(
        "cgx.codec.lowering.dequantize_pages.xla_gather") == 1


def test_rekey_drains_active_lanes_without_token_loss(
    model_setup, monkeypatch
):
    """An SLO/knob re-key mid-generation must NOT evict active lanes:
    admission pauses, the running lane finishes under the old program
    (keeping every generated token), and the new width adopts at the
    drain point — while a waiting request admitted after adoption runs
    under the new bits."""
    cfg, _model, params = model_setup
    monkeypatch.setenv("CGX_KV_BITS", "8")
    drains0 = metrics.get("cgx.serve.rekey_drains")
    adopts0 = metrics.get("cgx.serve.bits_adoptions")
    server = GPT2Server(cfg, params, _serve_cfg(max_batch=2))
    sched = ContinuousBatchScheduler(server)
    first = Request(id="a", tokens=_prompts(cfg, 1, lens=[16])[0],
                    max_new_tokens=8)
    sched.submit(first)
    for _ in range(3):
        sched.step()
    tokens_so_far = list(first.output)
    assert tokens_so_far, "lane should be generating"
    # the SLO controller's write: re-keys the program mid-flight
    monkeypatch.setenv("CGX_KV_BITS", "5")
    second = Request(id="b", tokens=_prompts(cfg, 1, lens=[16])[0],
                     max_new_tokens=4)
    sched.submit(second)
    sched.step()
    # drain pending: the running lane kept its tokens, b not admitted
    assert first.output[: len(tokens_so_far)] == tokens_so_far
    assert metrics.get("cgx.serve.rekey_drains") == drains0 + 1
    assert sched.run(deadline_s=DEADLINE_S)
    assert len(first.output) == 8 and len(second.output) == 4
    assert metrics.get("cgx.serve.bits_adoptions") == adopts0 + 1
    assert programs_mod._resolved_specs(server)[0].bits == 5
    # nothing leaked: every page returned to the pool
    assert sched.cache.free_pages == sched.cache.max_pages


def test_prefill_ahead_bounded_by_free_lanes(model_setup):
    """One scheduler step must not prefill the whole waiting queue:
    prefill-ahead is bounded by free lanes, so queued requests hold no
    pool pages until a lane can actually take them."""
    cfg, _model, params = model_setup
    before = metrics.get("cgx.serve.local_prefills")
    server = GPT2Server(cfg, params, _serve_cfg(max_batch=2))
    sched = ContinuousBatchScheduler(server)
    for i, p in enumerate(_prompts(cfg, 6, lens=[16] * 6)):
        sched.submit(Request(id=f"r{i}", tokens=list(p),
                             max_new_tokens=4))
    sched.step()
    prefilled = metrics.get("cgx.serve.local_prefills") - before
    assert prefilled <= 2, (
        f"step prefilled {prefilled} requests for 2 lanes"
    )
    assert sched.run(deadline_s=DEADLINE_S)


# ---------------------------------------------------------------------------
# Admission as compiled programs (ISSUE 26): the prefill writes its own
# pages, the tails stay on the device, one call writes a lane.
# ---------------------------------------------------------------------------


def _admit_only(sched, req, *, remote=False):
    """Admit ``req`` without decoding: transport drain + ``_admit`` (the
    dispatch) until the request holds a lane, then the read of its first
    token. Returns the lane."""
    sched.submit(req, remote=remote)
    deadline = time.monotonic() + DEADLINE_S
    while req not in sched._lanes:
        assert time.monotonic() < deadline, "admission wedged"
        sched._drain_transport()
        sched._admit()
    lane = sched._lanes.index(req)
    assert req.output == [] and req.first_token_at is None  # dispatched only
    sched._read_first_tokens()
    return lane


def _lane_snapshot(sched, lane):
    """Everything an admission wrote, as host arrays: the lane's
    bookkeeping, its tails, and the pool rows at its page ids."""
    st = sched._state
    n_pages = int(st["n_pages"][lane])
    ids = np.asarray(st["page_table"])[lane, :n_pages]
    return {
        "lane": {
            k: np.asarray(st[k])[lane]
            for k in ("page_table", "n_pages", "tail_len", "tokens", "pos",
                      "active")
        },
        "tail_k": [np.asarray(t)[lane] for t in st["tail_k"]],
        "tail_v": [np.asarray(t)[lane] for t in st["tail_v"]],
        "rows": [
            [np.asarray(a)[ids] for a in jax.tree.leaves(pool)]
            for pool in st["pools"]
        ],
    }


def _assert_same(got, want):
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for a, b in zip(flat_got, flat_want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("tail", [0, 1, PAGE - 1])
@pytest.mark.parametrize("bits", ["8", "0"])
def test_admission_matches_hand_composition(model_setup, monkeypatch, bits,
                                            tail):
    """After one admission the pool rows at the request's page ids and
    the lane's tails are BYTE-identical to the pieces composed by hand:
    ``prefill_forward`` -> ``quantize_page_rows`` -> ``_ingest_pool`` for
    the full pages, the remaining rows zero-padded for the tails."""
    from torch_cgx_tpu.ops import paged_kv

    cfg, _model, params = model_setup
    monkeypatch.setenv("CGX_KV_BITS", bits)
    server = GPT2Server(cfg, params, _serve_cfg())
    sched = ContinuousBatchScheduler(server)
    n_full = 2
    s = n_full * PAGE + tail
    (prompt,) = _prompts(cfg, 1, lens=[s], seed=7 + tail)
    req = Request(id="a", tokens=prompt, max_new_tokens=4)
    lane = _admit_only(sched, req)
    got = _lane_snapshot(sched, lane)

    specs = sched._prog.specs
    assert {sp.bits for sp in specs} == {int(bits)}
    padded = sched_mod._pad_prompt(np.asarray(prompt, np.int32), PAGE)
    logits, ks, vs = jax.jit(server.prefill_forward)(
        padded[None], np.arange(padded.shape[0], dtype=np.int32)[None],
        np.int32(s - 1),
    )
    ids = jnp.asarray(got["lane"]["page_table"][:n_full])
    assert sorted(np.asarray(ids)) == sorted(sched.cache.pages_of("a"))
    want_rows, want_tails = [], {"k": [], "v": []}
    for layer, spec in enumerate(specs):
        pools = {}
        for kind, cache in (("k", ks), ("v", vs)):
            rows = cache[layer][0, : n_full * PAGE].reshape(n_full, -1)
            if spec.quantized:
                rows = paged_kv.quantize_page_rows(rows, spec)
            pools[kind] = programs_mod._ingest_pool(
                paged_kv.empty_pool(server.serve.max_pages + 1, spec), ids,
                rows, spec,
            )
            t = np.zeros((PAGE, server.n_head * server.d_head), np.float32)
            t[:tail] = np.asarray(
                cache[layer][0, n_full * PAGE: s]).reshape(tail, t.shape[1])
            want_tails[kind].append(t)
        want_rows.append([
            np.asarray(a)[np.asarray(ids)] for a in jax.tree.leaves(pools)
        ])
    table_row = np.full((server.serve.pages_per_seq,), -1, np.int32)
    table_row[:n_full] = np.asarray(ids)
    _assert_same(got, {
        "lane": {
            "page_table": table_row, "n_pages": np.int32(n_full),
            "tail_len": np.int32(tail),
            "tokens": np.asarray(jnp.argmax(logits[0]), np.int32),
            "pos": np.int32(s), "active": np.bool_(True),
        },
        "tail_k": want_tails["k"], "tail_v": want_tails["v"],
        "rows": want_rows,
    })
    assert req.output == [int(jnp.argmax(logits[0]))]


def test_prompt_lengths_under_one_padded_length_compile_nothing(
    model_setup
):
    """One compiled prefill per PADDED length and none per prompt length
    (a compile listener, not a timing): a tail, another tail and a whole
    number of pages under one padded length share the first's programs."""
    from jax import monitoring

    cfg, _model, params = model_setup
    server = GPT2Server(cfg, params, _serve_cfg())
    sched = ContinuousBatchScheduler(server)
    lens = [2 * PAGE + 1, 2 * PAGE + 3, 3 * PAGE - 1, 3 * PAGE, 3 * PAGE + 1]
    reqs = [
        Request(id=f"r{i}", tokens=p, max_new_tokens=4)
        for i, p in enumerate(_prompts(cfg, len(lens), lens=lens))
    ]
    compiles = []

    def on_duration(event, duration, **_):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    _admit_only(sched, reqs[0])  # compiles the padded length's programs
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for req in reqs[1:4]:
            _admit_only(sched, req)
        assert compiles == []
        sched_mod.invalidate_decode_cache("test: a padded length not seen")
        server2 = GPT2Server(cfg, params, _serve_cfg())
        _admit_only(ContinuousBatchScheduler(server2), reqs[4])
        assert compiles, "the listener hears no compile at all"
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    assert [len(r.output) for r in reqs] == [1] * 5


def test_stream_admission_leaves_the_local_lane_state(model_setup,
                                                      monkeypatch):
    """A stream admitted through ``_ingest_stream`` goes through the same
    lane write as a local prefill and leaves the same lane: bookkeeping
    and pool rows byte-identical, tails identical up to the f16 the wire
    carries them in."""
    cfg, _model, params = model_setup
    monkeypatch.setenv("CGX_SERVE_PREFILL_TIMEOUT_MS", "60000")
    (prompt,) = _prompts(cfg, 1, lens=[2 * PAGE + 3])
    server = GPT2Server(cfg, params, _serve_cfg())

    local = ContinuousBatchScheduler(server)
    want = _lane_snapshot(local, _admit_only(
        local, Request(id="a", tokens=prompt, max_new_tokens=4)))

    store = FakeStore()
    remote = ContinuousBatchScheduler(server, receiver=KvPageReceiver(store))
    worker = PrefillWorker(server, store)
    before = metrics.get("cgx.serve.local_prefills")
    req = Request(id="a", tokens=prompt, max_new_tokens=4)
    worker.serve(req.id, req.tokens)
    got = _lane_snapshot(remote, _admit_only(remote, req, remote=True))
    worker.stop()
    assert metrics.get("cgx.serve.local_prefills") == before  # no failover

    for kind in ("tail_k", "tail_v"):
        assert any(t.any() for t in want[kind])
        want[kind] = [
            t.astype(np.float16).astype(np.float32) for t in want[kind]
        ]
    _assert_same(got, want)


def test_failed_prefill_frees_pages_and_keeps_pools_usable(model_setup,
                                                           monkeypatch):
    """A prefill program that raises marks its request errored, frees the
    pages it reserved, and leaves the donated pools usable: the lane
    admitted before it and the request after it both finish with the
    tokens an undisturbed run gives."""
    cfg, _model, params = model_setup
    prompts = _prompts(cfg, 3, lens=[2 * PAGE + 1, 3 * PAGE + 2,
                                     3 * PAGE + 5])
    want = _run_local(cfg, params, prompts, gen=6)
    sched_mod.invalidate_decode_cache("test: trace the programs afresh")
    server = GPT2Server(cfg, params, _serve_cfg())
    sched = ContinuousBatchScheduler(server)
    reqs = [Request(id=f"r{i}", tokens=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    _admit_only(sched, reqs[0])
    held = sched.cache.free_pages
    errors = metrics.get("cgx.serve.request_errors")

    def refuse(*_a, **_k):
        raise RuntimeError("refused while tracing")

    with monkeypatch.context() as m:
        m.setattr(sched_mod.paged_kv, "commit_page_rows", refuse)
        sched.submit(reqs[1])
        sched._admit()  # a padded length not traced yet: the trace raises
    assert reqs[1].done and reqs[1].output == []
    assert metrics.get("cgx.serve.request_errors") == errors + 1
    assert sched.cache.free_pages == held
    sched.submit(reqs[2])
    assert sched.run(deadline_s=DEADLINE_S)
    assert [reqs[0].output, reqs[2].output] == [want[0], want[2]]
    assert sched.cache.free_pages == sched.cache.max_pages


def test_prefill_observes_page_qerr_only_when_asked(model_setup,
                                                     monkeypatch):
    """``CGX_QERR_STATS`` keeps its view of a local prefill: one
    ``kv_page`` observation per layer per FULL page (the scratch-row page
    of a tail is not a page), and none, nor any row fetched, when off."""
    cfg, _model, params = model_setup
    (prompt,) = _prompts(cfg, 1, lens=[2 * PAGE + 3])

    def observed(knob):
        monkeypatch.setenv("CGX_QERR_STATS", knob)
        sched = ContinuousBatchScheduler(
            GPT2Server(cfg, params, _serve_cfg()))
        key = "cgx.qerr.wire:kv_page:layer_1.count"
        before = metrics.snapshot("cgx.qerr.").get(key, 0.0)
        _admit_only(sched, Request(id="q", tokens=prompt, max_new_tokens=2))
        return metrics.snapshot("cgx.qerr.").get(key, 0.0) - before

    assert observed("1") == 2
    assert observed("0") == 0


def test_sender_retry_keeps_seq_dense():
    """A transient store failure mid-ship must not burn a sequence
    number: the retried frame publishes under the SAME seq, so the
    receiver's dense walk still completes the stream."""

    class FlakyStore(FakeStore):
        def __init__(self):
            super().__init__()
            self.fail_next = 1

        def set(self, k, v):
            if "cgxkv/" in k and self.fail_next:
                self.fail_next -= 1
                raise RuntimeError("transient store failure")
            super().set(k, v)

    store = FlakyStore()
    errors = metrics.get("cgx.serve.ship_errors")
    lost = metrics.get("cgx.serve.frames_lost")
    sender = KvPageSender(store, "s0", depth=4)
    recv = KvPageReceiver(store)
    recv.add_stream("s0")
    sender.post_meta({"frames": 2, "pages": 0, "prompt_tokens": 1,
                      "page_tokens": 4, "tail_tokens": 0,
                      "first_token": 0})
    sender.post_page(0, tp.K_TAIL, 0, 0, 0, 4, b"\x00" * 8)
    deadline = time.monotonic() + 30.0
    got = []
    while len(got) < 2 and time.monotonic() < deadline:
        got.extend(recv.poll())
        time.sleep(0.005)
    sender.stop()
    assert len(got) == 2, "retried frame never became fetchable"
    assert recv.complete("s0")
    assert metrics.get("cgx.serve.ship_errors") == errors + 1
    assert metrics.get("cgx.serve.frames_lost") == lost
    assert metrics.get("cgx.serve.send_backlog") == 0  # the queue drained


def test_tps_only_slo_recovers(monkeypatch):
    """A tokens/s-only SLO must recover bits when throughput is back
    over target — not just drop them (the one-way ratchet bug)."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    ctl = ServeSloController(tps_slo=100.0, every=0)
    metrics.set("cgx.serve.tokens_per_s", 50.0)
    ctl.update()
    assert ctl.budget == 7
    metrics.set("cgx.serve.tokens_per_s", 200.0)
    ctl.update()
    assert ctl.budget == 8


def test_training_controller_excludes_kv_page_labels(monkeypatch):
    """Colocated train-and-serve: the DEFAULT (unscoped) training
    controller must not ingest serving kv_page telemetry — re-widthing
    serving pages from the training objective is the cross-plane write
    the scoping exists to prevent."""
    from torch_cgx_tpu.wire import dispatch as wire_dispatch
    from torch_cgx_tpu.wire.controller import WireController

    monkeypatch.setenv("CGX_KV_BITS", "8")
    wire_dispatch.note_external_edge(
        "kv_page", "layer_0", numel=4096, bits=8,
        raw_bytes=16384, wire_bytes=4096,
    )
    for _ in range(10):
        metrics.observe("cgx.qerr.wire:kv_page:layer_0", 0.05)
    ctl = WireController(avg_bits=4.0, every=0, min_observations=1)
    alloc = ctl.update()
    assert not any(k.startswith("wire:kv_page:") for k in alloc)
    assert kv_mod.resolve_kv_config("layer_0").bits == 8


# ---------------------------------------------------------------------------
# ISSUE 28: the cache read hands attention what it consumes.
# ---------------------------------------------------------------------------

from torch_cgx_tpu.models.attention import decode_attention  # noqa: E402
from torch_cgx_tpu.ops import paged_kv  # noqa: E402

# What a lane holds, as (committed pages, tail_len) of the two lanes.
_LANE_KINDS = {
    "no_page": ((0, 0), (0, 3)),
    "some_pages": ((2, 5), (3, 1)),
    "full_tail": ((1, 15), (0, 15)),
}


def table_sized_glue(jaxpr, rows, width):
    """Every ``concatenate`` / ``convert_element_type`` equation of a
    (closed) jaxpr, nested programs included, with an operand that holds a
    cache table: leading dimensions that multiply to one of ``rows`` (a
    row a cached position: the static page table's ``B * P * page_tokens``,
    or that with the tails joined) and at least ``width`` values a row. A
    Pallas kernel's body is left out: what it converts is a block in
    VMEM, never a table in HBM."""
    found = []

    def is_table(shape):
        lead = np.cumprod(shape).tolist()
        return any(r in lead and lead[-1] >= r * width for r in rows)

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            if eqn.primitive.name in ("concatenate", "convert_element_type"):
                shapes = [tuple(v.aval.shape) for v in eqn.invars]
                if any(is_table(shape) for shape in shapes):
                    found.append((eqn.primitive.name, shapes))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


def _old_decode_attention(q, k, v, *, kv_mask):
    """``models.attention.decode_attention`` as it stood before ISSUE 28:
    ``q (B, H, 1, D)`` against ``k``/``v (B, H, T, D)``."""
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.float32(np.sqrt(d))
    scores = jnp.where(kv_mask[:, None, None, :], scores, np.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("lanes", sorted(_LANE_KINDS))
@pytest.mark.parametrize("bits", [8, 4, 0])
def test_read_and_attend_matches_the_old_composition(bits, lanes,
                                                     monkeypatch):
    """The new cache read and attention (``cfg.dtype`` rows from the
    kernel, contracted where they lie, the tail attended apart) against
    the composition it replaced (decode to float32, concatenate the tail,
    transpose heads-major, cast, one attention), to ``bfloat16`` rounding
    of the output. Pages are one whole chunk of 128-wide buckets and the
    Pallas codec is forced, so the flat kernel's own store and row tiling
    are what is read (interpret mode)."""
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    b, p, pt, h, d, max_pages = 2, 3, 16, 4, 64, 8
    dt = jnp.bfloat16
    spec = paged_kv.PageSpec(pt, h, d, bits, 128 if bits else 1)
    rng = np.random.default_rng(bits * 10 + len(lanes))

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    pools = [
        paged_kv.commit_page_rows(
            paged_kv.empty_pool(max_pages, spec), jnp.arange(max_pages),
            normal(max_pages, spec.flat), spec)
        for _ in range(2)
    ]
    table = jnp.asarray(rng.permutation(max_pages)[: b * p].reshape(b, p),
                        jnp.int32)
    (n0, t0), (n1, t1) = _LANE_KINDS[lanes]
    table = jnp.where(
        jnp.arange(p)[None, :] < jnp.asarray([[n0], [n1]]), table, -1)
    mask_c = jnp.arange(p * pt)[None, :] < jnp.asarray([[n0 * pt], [n1 * pt]])
    mask_t = jnp.arange(pt)[None, :] <= jnp.asarray([[t0], [t1]])
    q = normal(b, h, d).astype(dt)
    tk, tv = normal(b, pt, h, d), normal(b, pt, h, d)

    metrics.reset()
    kc, vc = (paged_kv.gather_dequant_pages(pool, table, spec, dt)
              for pool in pools)
    assert kc.shape == (b, p * pt, h * d) and kc.dtype == dt
    if bits:
        for name in ("dequantize.pallas_flat.bfloat16",
                     "dequantize_rows.pallas_flat"):
            assert metrics.get(f"cgx.codec.lowering.{name}") == 2, name
    got = decode_attention(
        q, kc, vc, tk.reshape(b, pt, h * d).astype(dt),
        tv.reshape(b, pt, h * d).astype(dt), mask=mask_c, tail_mask=mask_t,
    )

    def old_read(pool, tail):
        pages = paged_kv.gather_dequant_pages(
            pool, table, spec, jnp.float32).reshape(b, p * pt, h, d)
        return jnp.concatenate([pages, tail], axis=1).transpose(
            0, 2, 1, 3).astype(dt)

    want = _old_decode_attention(
        q[:, :, None], old_read(pools[0], tk), old_read(pools[1], tv),
        kv_mask=jnp.concatenate([mask_c, mask_t], axis=1),
    )[:, :, 0].reshape(b, h * d)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    # One bfloat16 step of the value (2**-7 of it), and a floor for sums
    # that cancel.
    assert np.all(np.abs(got - want)
                  <= 2.0 ** -7 * np.abs(want) + 1e-3 * np.abs(want).max())


def test_decode_step_holds_no_table_sized_glue(model_setup, monkeypatch):
    """Structure of the traced ``decode_step``: between the cache read and
    the attention nothing of the table's size is concatenated or cast, so
    the glue PR 27's trace showed (104.8 ms of a 169.6 ms step) cannot
    come back unseen. Traced with the Pallas codec on whole-chunk pages,
    the program the chip runs; the old composition, traced the same way,
    is caught."""
    cfg, _, params = model_setup
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "128")
    monkeypatch.setenv("CGX_KV_BITS", "8")
    sv = _serve_cfg(page_tokens=32, max_batch=3, max_pages=12, max_seq=128)
    server = GPT2Server(cfg, params, sv)
    sched = ContinuousBatchScheduler(server)
    streams = sched._prog.streams
    spec = streams[0][0][1]
    assert spec.num_buckets == 32 and cfg.dtype == jnp.bfloat16
    rows = (sv.max_batch * sv.pages_per_seq * sv.page_tokens,
            sv.max_batch * (sv.pages_per_seq + 1) * sv.page_tokens)

    metrics.reset()
    jaxpr = jax.make_jaxpr(sched._prog.decode_step)(server.p, sched._state)
    assert table_sized_glue(jaxpr, rows, cfg.d_model) == []
    reads = 2 * cfg.n_layer
    assert metrics.get(
        "cgx.codec.lowering.dequantize.pallas_flat.bfloat16") == reads
    assert metrics.get(
        "cgx.codec.lowering.dequantize_rows.pallas_flat") == reads

    def old_read(state):
        pages = paged_kv.gather_dequant_pages(
            state["pools"][0]["k"], state["page_table"], spec, jnp.float32)
        assert state["tail_k"][0].shape == (
            sv.max_batch, sv.page_tokens, cfg.d_model)
        return jnp.concatenate(
            [pages, state["tail_k"][0]], axis=1
        ).astype(cfg.dtype)

    old = table_sized_glue(
        jax.make_jaxpr(old_read)(sched._state), rows, cfg.d_model)
    assert sorted(name for name, _ in old) == [
        "concatenate", "convert_element_type"]


def _eqns_touching(jaxpr, shape):
    """Names of the equations of a (closed) jaxpr, nested programs
    included, with an operand of ``shape``."""
    names = []

    def walk(jp):
        for eqn in jp.eqns:
            if any(tuple(getattr(v.aval, "shape", ())) == shape
                   for v in eqn.invars):
                names.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return names


def test_decode_step_hands_the_pool_to_the_kernel_alone(model_setup,
                                                        monkeypatch):
    """Structure of the traced ``decode_step`` since ISSUE 30: a quantized
    pool's words and meta go into the decode kernel as they are stored and
    nowhere else — no gather, reshape or bitcast of the pool in front of
    it (16-19 ms of the 76.7 ms GPT-2 large step, PERF.md section 6, PR
    30). The gathered read, traced the same way, is caught."""
    from torch_cgx_tpu.ops import dispatch as ops_dispatch
    from torch_cgx_tpu.ops import paged_kv

    cfg, _, params = model_setup
    monkeypatch.setenv("CGX_CODEC_IMPL", "pallas")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "128")
    monkeypatch.setenv("CGX_KV_BITS", "8")
    sv = _serve_cfg(page_tokens=32, max_batch=3, max_pages=12, max_seq=128)
    server = GPT2Server(cfg, params, sv)
    sched = ContinuousBatchScheduler(server)
    spec = sched._prog.streams[0][0][1]
    words, meta = sched._state["pools"][0]["k"]
    assert words.shape == (sv.max_pages + 1,) + spec.word_shape

    metrics.reset()
    jaxpr = jax.make_jaxpr(sched._prog.decode_step)(server.p, sched._state)
    reads = 2 * cfg.n_layer
    # ``GPT2Server`` builds its own read and asks for no unpack (ISSUE 53).
    site = "cgx.codec.lowering.dequantize_pages."
    assert metrics.snapshot(site) == {
        site + "pallas_paged.meta_planes": reads,
        site + "unpack.planes": reads}
    # (The jitted impl around the kernel is the one other equation.)
    for shape in (words.shape, meta.shape):
        assert sorted(set(_eqns_touching(jaxpr, shape))) == [
            "jit", "pallas_call"], shape
        assert _eqns_touching(jaxpr, shape).count("pallas_call") == reads

    def gathered_read(state):
        ids = jnp.maximum(state["page_table"].reshape(-1), 0)
        return ops_dispatch.dequantize_batch(
            paged_kv.pool_qtensor(*state["pools"][0]["k"], ids, spec),
            out_dtype=cfg.dtype, row_width=cfg.d_model)

    old = jax.make_jaxpr(gathered_read)(sched._state)
    assert "gather" in _eqns_touching(old, words.shape)
    assert "gather" in _eqns_touching(old, meta.shape)


def test_a_prefilled_request_takes_its_lane_before_the_next_prefill(
        model_setup):
    """Order of one tick's admissions: prefill, lane write, prefill, lane
    write. With every prefill before the first lane write, a request's
    first token waited for the prefills of the requests behind it; in a
    closed loop whose step got shorter, the 51 s window then reached ticks
    of more admissions and the prefill cell's TTFT p90 read 13 % worse
    with no admission any slower (PERF.md section 6, PR 28)."""
    cfg, _model, params = model_setup
    sched = ContinuousBatchScheduler(GPT2Server(cfg, params, _serve_cfg()))
    order = []
    for name in ("_local_prefill", "_admit_lane"):
        inner = getattr(sched, name)

        def noted(*args, _inner=inner, _name=name):
            order.append(_name)
            return _inner(*args)

        setattr(sched, name, noted)
    reqs = [Request(id=f"r{i}", tokens=list(p), max_new_tokens=3)
            for i, p in enumerate(_prompts(cfg, 3))]
    for r in reqs:
        sched.submit(r)
    sched._admit()
    assert order == ["_local_prefill", "_admit_lane"] * 3
    assert all(r in sched._lanes for r in reqs)
    # Dispatched back to back (ISSUE 32), two in flight at most: the
    # newest two have no token and no stamp yet, the first was read when
    # the third was about to be queued; the read goes in the same order.
    assert [len(r.output) for r in reqs] == [1, 0, 0]
    assert [r.first_token_at is None for r in reqs] == [False, True, True]
    sched._read_first_tokens()
    stamps = [r.first_token_at for r in reqs]
    assert stamps == sorted(stamps) and all(len(r.output) == 1 for r in reqs)
    assert sched.run(deadline_s=DEADLINE_S)


def test_a_burst_of_admissions_holds_two_prefills_outputs_at_most(
        model_setup):
    """With every lane free at once a tick admits a request a lane. Each
    prefill queued holds its outputs on the device from its dispatch (a
    lane's tails and recurrent state: 77 MB for granite-4.0-h-micro, and
    64 of them overran the chip; PERF.md section 6, PR 32), so the burst
    reads the oldest first token before it queues a third."""
    cfg, _model, params = model_setup
    sched = ContinuousBatchScheduler(
        GPT2Server(cfg, params, _serve_cfg(max_batch=6)))
    inner, queued = sched._local_prefill, []

    def noted(req):
        queued.append(len(sched._unread))
        return inner(req)

    sched._local_prefill = noted
    reqs = [Request(id=f"r{i}", tokens=list(p), max_new_tokens=3)
            for i, p in enumerate(_prompts(cfg, 6, lens=[9 + i for i in
                                                           range(6)]))]
    for r in reqs:
        sched.submit(r)
    sched._admit()
    assert queued == [0, 1, 1, 1, 1, 1]  # the one before it still runs
    assert [len(r.output) for r in reqs] == [1, 1, 1, 1, 0, 0]
    assert sched.run(deadline_s=DEADLINE_S)
    assert all(len(r.output) == 3 for r in reqs)


# ---------------------------------------------------------------------------
# A tick queues everything before it reads anything (ISSUE 32): whatever
# the tick dispatched ahead of its reads, every request gets the tokens of a
# plain decode.
# ---------------------------------------------------------------------------

# (prompt length, tokens asked), each in pages and odd tokens: two pairs
# that are admitted together and ask for the same count, so they finish in
# the same tick; long answers that fill tail after tail until the pool runs
# dry; one that asks for its first token alone.
_MIX = [((2, 1), (3, 6)), ((1, 3), (3, 6)), ((2, 1), (3, 6)),
        ((1, 5), (1, 1)), ((1, 5), (1, 1)), ((0, 5), (0, 1)),
        ((1, 2), (1, 4))]
_MIX_LANES = 3
_MIX_PAGES = 11  # three lanes of the long answers would hold fifteen


def _mix_adapter(kind, model_setup, lanes=_MIX_LANES, pages=_MIX_PAGES):
    """(build(eos) -> adapter at its small CPU configuration, its page
    size, its vocabulary)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

    def serve(page, eos):
        return ServeConfig(page_tokens=page, max_batch=lanes,
                           max_pages=pages, max_seq=8 * page,
                           ship_depth=2, eos_token=eos)

    if kind == "gpt2":
        cfg, _model, params = model_setup
        return (lambda eos: GPT2Server(cfg, params, serve(PAGE, eos)),
                PAGE, cfg.vocab_size)
    if kind == "latent":
        from benchmark import weights_mla_moe
        from test_latent_serving import HF
        from torch_cgx_tpu.models.mla_moe import MlaMoeConfig
        from torch_cgx_tpu.serving.latent import LatentMoEServer

        cfg = MlaMoeConfig.from_hf(HF, dtype=jnp.float32, q_block=8)
        params = weights_mla_moe.make_params(HF, 3)
        return (lambda eos: LatentMoEServer(cfg, params, serve(PAGE, eos)),
                PAGE, HF["vocab_size"])
    from benchmark import weights_granite_hybrid
    from test_hybrid_serving import HF, PAGE as chunk
    from torch_cgx_tpu.models.granite_hybrid import HybridConfig
    from torch_cgx_tpu.serving.hybrid import HybridSSMServer

    cfg = HybridConfig.from_hf(HF, dtype=jnp.float32)
    params = weights_granite_hybrid.make_params(HF, 3)
    return (lambda eos: HybridSSMServer(cfg, params, serve(chunk, eos)),
            chunk, HF["vocab_size"])


def _mix_requests(page, vocab, tag):
    rng = np.random.default_rng(32)
    size = lambda pages, odd: pages * page + odd
    return [
        Request(id=f"{tag}{i}", max_new_tokens=size(*gen),
                tokens=[int(t) for t in rng.integers(0, vocab, size(*prompt))])
        for i, (prompt, gen) in enumerate(_MIX)
    ]


@pytest.mark.parametrize("with_eos", [False, True], ids=["no-eos", "eos"])
@pytest.mark.parametrize("kind", ["gpt2", "latent", "hybrid"])
def test_mixed_ticks_serve_the_tokens_of_a_plain_decode(
        model_setup, monkeypatch, kind, with_eos):
    """A seeded mix in which lanes finish in the same tick, tails fill,
    the pool runs dry mid-decode (eviction and re-prefill) and one prefill
    raises, with and without an end-of-sequence token: every request's
    tokens are those of a greedy decode of that request alone (a lane free
    beside it, so no step of it is ever queued ahead), the failed request
    errors alone with its pages freed, and ``run()`` leaves no step in
    flight."""
    from types import SimpleNamespace

    monkeypatch.setenv("CGX_KV_BITS", "8")
    build, page, vocab = _mix_adapter(kind, model_setup)

    plain = []
    for req in _mix_requests(page, vocab, "p"):
        alone = ContinuousBatchScheduler(build(None))
        alone.submit(req)
        assert alone.run(deadline_s=DEADLINE_S)
        plain.append(req.output)
    asked = [r.max_new_tokens for r in _mix_requests(page, vocab, "p")]
    assert [len(o) for o in plain] == asked
    eos = None
    if with_eos:
        # a token that ends some answers early and leaves others whole
        inner = [t for o in plain for t in o[1:-1]]
        eos = max(sorted(set(inner)), key=inner.count)
        plain = [o[: o.index(eos) + 1] if eos in o else o for o in plain]
        assert any(len(o) < n for o, n in zip(plain, asked))

    metrics.reset()
    sched = ContinuousBatchScheduler(build(eos))
    reqs = _mix_requests(page, vocab, "m")
    bad = Request(id="bad", tokens=reqs[3].tokens[: page + 1],
                  max_new_tokens=4)
    prog = sched._prog

    def prefill_pages(params, pools, tokens, positions, last_idx, *rest):
        if int(last_idx) == len(bad.tokens) - 1:  # no other prompt's length
            raise RuntimeError("refused before anything was donated")
        return prog.prefill_pages(params, pools, tokens, positions,
                                  last_idx, *rest)

    sched._prog = SimpleNamespace(
        **{**vars(prog), "prefill_pages": prefill_pages})
    for r in reqs[:4] + [bad] + reqs[4:]:
        sched.submit(r)
    together, done, ticks = 0, set(), 0
    while sched.outstanding():
        sched.step()
        ticks += 1
        assert ticks < 2000, "serving run wedged"
        now = {r.id for r in reqs if r.done}
        together = max(together, len(now - done))
        done = now
    assert not sched._steps and not sched._unread
    assert [r.output for r in reqs] == plain
    assert bad.done and bad.output == [] and bad.first_token_at is None
    assert metrics.get("cgx.serve.request_errors") == 1.0
    assert metrics.get("cgx.serve.requests_completed") == len(reqs)
    assert sched.cache.free_pages == _MIX_PAGES
    assert together >= 2  # lanes finished in the same tick
    assert metrics.get("cgx.serve.pages_committed") > 0  # tails filled
    assert metrics.get("cgx.serve.decode.ahead") > 0  # steps queued ahead
    assert (metrics.get("cgx.serve.host_reads")
            == metrics.get("cgx.serve.decode_steps")
            + metrics.get("cgx.serve.requests_admitted"))
    if eos is None:
        assert metrics.get("cgx.serve.decode_evictions") > 0  # the pool ran dry
        assert metrics.get("cgx.serve.decode.discarded_tokens") == 0.0
    # run() on an empty scheduler has nothing to drain
    assert sched.run(deadline_s=1.0) and not sched._steps


# ---------------------------------------------------------------------------
# The commit quantizes the lanes that filled a page (ISSUE 34): the program
# takes K lane indices, and the all-lanes masked commit it replaced is the
# oracle for every byte it writes.
# ---------------------------------------------------------------------------

_COMMIT_LANES = 6  # K = 4 at these page sizes: six full tails take two calls


@pytest.mark.parametrize("lanes,page,k", [
    (32, 64, 4), (32, 256, 4), (64, 256, 4), (96, 64, 8),  # the five cells
    (_COMMIT_LANES, PAGE, 4), (3, PAGE, 3), (256, 16, 64),
])
def test_commit_lanes_follow_the_serve_geometry(lanes, page, k):
    """``K`` is four times the tails that fill a step with every lane
    decoding, as a power of two, at least 4 and at most the lanes."""
    sv = ServeConfig(page_tokens=page, max_batch=lanes, max_pages=8,
                     max_seq=page, ship_depth=1)
    assert sv.commit_lanes == k


def _masked_commit(sched, state, mask, page_ids):
    """The commit as it was until PR 34: every lane's tail of every layer
    and stream quantized, the lanes outside ``mask`` sent to the scratch
    row; ``page_table``, ``n_pages`` and ``tail_len`` by ``where``."""
    from torch_cgx_tpu.ops import paged_kv

    sv, prog = sched.server.serve, sched._prog
    b = mask.shape[0]
    ids = jnp.where(mask, page_ids, sv.max_pages)
    out = dict(state)
    out["pools"] = tuple(
        {
            name: paged_kv.commit_page_rows(
                state["pools"][layer][name], ids,
                state[f"tail_{name}"][layer].reshape(b, -1), spec,
            )
            for name, spec in layer_streams
        }
        for layer, layer_streams in enumerate(prog.streams)
    )
    p_iota = jax.lax.broadcasted_iota(
        jnp.int32, state["page_table"].shape, 1)
    slot = (p_iota == state["n_pages"][:, None]) & mask[:, None]
    out["page_table"] = jnp.where(slot, page_ids[:, None],
                                  state["page_table"])
    out["n_pages"] = state["n_pages"] + mask.astype(jnp.int32)
    out["tail_len"] = jnp.where(mask, 0, state["tail_len"])
    return out


def _held_lanes_with_full_tails(sched, full, page):
    """Every lane held by a request mid-answer over a state drawn at
    random (pools, tails, recurrent state, page tables), the tails of the
    lanes ``full`` at a whole page. Returns a copy of that state."""
    sv = sched.server.serve
    rng = np.random.default_rng(34)

    def drawn(a):
        if a.dtype == jnp.int32:  # a pool's words
            return jnp.asarray(rng.integers(-2**31, 2**31, a.shape,
                                            dtype=np.int64).astype(np.int32))
        return jnp.asarray(rng.standard_normal(a.shape).astype(a.dtype))

    st = sched._state
    for name in list(st):
        if name == "pools" or name.startswith(("tail_", "state_")):
            st[name] = jax.tree.map(drawn, st[name])
    b = sv.max_batch
    tail_len = rng.integers(0, page, b)
    tail_len[full] = page
    n_pages = rng.integers(0, sv.pages_per_seq, b)
    table = rng.integers(0, sv.max_pages, (b, sv.pages_per_seq))
    table[np.arange(sv.pages_per_seq)[None] >= n_pages[:, None]] = -1
    st.update(
        tail_len=jnp.asarray(tail_len, jnp.int32),
        n_pages=jnp.asarray(n_pages, jnp.int32),
        page_table=jnp.asarray(table, jnp.int32),
        tokens=jnp.asarray(rng.integers(0, 50, b), jnp.int32),
        pos=jnp.asarray(rng.integers(0, 50, b), jnp.int32),
        active=jnp.ones((b,), bool),
    )
    for lane in range(b):
        sched._lanes[lane] = Request(id=f"c{lane}", tokens=[1],
                                     max_new_tokens=64)
    sched._left[:] = 32
    sched._tail_len[:] = tail_len
    return jax.tree.map(lambda a: jnp.array(a, copy=True), st)


def _note_allocs(sched):
    """{request id: the page ``cache.alloc`` gave it}, filled as it goes."""
    given, alloc = {}, sched.cache.alloc

    def noted(seq_id):
        pid = alloc(seq_id)
        if pid is not None:
            given[seq_id] = pid
        return pid

    sched.cache.alloc = noted
    return given


def _masked_operands(lanes, given):
    """``(mask, page_ids)`` of the all-lanes commit that promotes
    ``lanes`` to the pages :func:`_note_allocs` saw them given."""
    mask = np.zeros((_COMMIT_LANES,), bool)
    mask[lanes] = True
    pids = np.zeros((_COMMIT_LANES,), np.int32)
    pids[lanes] = [given[f"c{lane}"] for lane in lanes]
    return jnp.asarray(mask), jnp.asarray(pids)


def _assert_state_is(sched, want):
    """The scheduler's state against ``want``, bit for bit, but for the
    pools' scratch row (written by whatever a call did not want)."""
    got, scratch = sched._state, sched.server.serve.max_pages
    assert sorted(got) == sorted(want)
    for name in want:
        cut = (lambda a: a[:scratch]) if name == "pools" else (lambda a: a)
        _assert_same(jax.tree.map(cut, got[name]),
                     jax.tree.map(cut, want[name]))


@pytest.mark.parametrize("full", ["one", "k", "all"])
@pytest.mark.parametrize("kind", ["gpt2", "latent", "hybrid"])
def test_indexed_commit_writes_what_the_all_lanes_commit_wrote(
        model_setup, monkeypatch, kind, full):
    """One full tail, ``K`` of them, and every lane's at once (two calls,
    the second padded): pools, page table and counts are the masked
    all-lanes commit's bit for bit, for a K/V adapter, the latent one (``c``
    and ``kr``) and a hybrid one, whose recurrent state, like every tail,
    token and position, is left as it was."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    build, page, _vocab = _mix_adapter(kind, model_setup,
                                       lanes=_COMMIT_LANES, pages=24)
    sched = ContinuousBatchScheduler(build(None))
    k = sched.server.serve.commit_lanes
    assert k == 4
    lanes = {"one": [4], "k": [0, 2, 3, 5],
             "all": list(range(_COMMIT_LANES))}[full]
    before = _held_lanes_with_full_tails(sched, lanes, page)
    given = _note_allocs(sched)
    metrics.reset()
    sched._commit_full_tails()
    assert sorted(given) == [f"c{lane}" for lane in lanes]
    want = _masked_commit(sched, before, *_masked_operands(lanes, given))
    _assert_state_is(sched, want)
    for name in want:  # and nothing but pools and bookkeeping moved
        if name not in ("pools", "page_table", "n_pages", "tail_len"):
            _assert_same(want[name], before[name])
    assert not sched._tail_len[lanes].any()
    calls = -(-len(lanes) // k)
    assert metrics.get("cgx.serve.commit.calls") == calls
    assert metrics.get("cgx.serve.commit.rows") == calls * k
    assert metrics.get("cgx.serve.commit.lanes") == len(lanes)
    assert metrics.get("cgx.serve.pages_committed") == len(lanes) * sum(
        len(layer) for layer in sched._prog.streams)


@pytest.mark.parametrize("kind", ["gpt2", "latent", "hybrid"])
def test_commit_and_eviction_in_one_tick(model_setup, monkeypatch, kind):
    """Five tails full with three pages left in the pool: the first three
    are committed in one call, the other two evicted back to the queue
    with their lanes released, and the state is the all-lanes commit's
    over the released lanes."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    build, page, _vocab = _mix_adapter(kind, model_setup,
                                       lanes=_COMMIT_LANES, pages=24)
    sched = ContinuousBatchScheduler(build(None))
    lanes = [0, 1, 3, 4, 5]
    before = _held_lanes_with_full_tails(sched, lanes, page)
    for i in range(21):  # another 21 pages are somebody's
        assert sched.cache.alloc(f"other{i % 3}") is not None
    given = _note_allocs(sched)
    reqs = list(sched._lanes)
    metrics.reset()
    sched._commit_full_tails()
    kept, evicted = lanes[:3], lanes[3:]
    assert sorted(given) == [f"c{lane}" for lane in kept]
    assert metrics.get("cgx.serve.decode_evictions") == 2
    assert sched._waiting == [reqs[lane] for lane in evicted]
    assert [sched._lanes[lane] for lane in evicted] == [None, None]
    assert not sched._left[evicted].any() and not sched._released
    released = np.zeros((_COMMIT_LANES,), bool)
    released[evicted] = True
    want = dict(before)
    want.update(sched._prog.release_lanes(
        {name: before[name] for name in programs_mod._LANE_RESET}, released))
    _assert_state_is(sched, _masked_commit(
        sched, want, *_masked_operands(kept, given)))
    assert not sched._tail_len[lanes].any()
    assert metrics.get("cgx.serve.commit.calls") == 1
    assert metrics.get("cgx.serve.commit.lanes") == 3


@pytest.mark.parametrize("kind", ["gpt2", "latent", "hybrid"])
def test_a_step_queued_ahead_finds_the_pages_committed_before_it(
        model_setup, monkeypatch, kind):
    """Six requests of one prompt length on six lanes: every tail fills
    in the same tick, so the commit in front of a step queued ahead runs
    the program twice, and every request still gets the tokens of a
    greedy decode of that request alone."""
    monkeypatch.setenv("CGX_KV_BITS", "8")
    build, page, vocab = _mix_adapter(kind, model_setup,
                                      lanes=_COMMIT_LANES, pages=30)
    rng = np.random.default_rng(34)

    def requests(tag):
        return [Request(id=f"{tag}{i}", max_new_tokens=2 * page + 3,
                        tokens=[int(t) for t in row])
                for i, row in enumerate(
                    rng.integers(0, vocab, (_COMMIT_LANES, page + 3)))]

    rng_state = rng.bit_generator.state
    plain = []
    for req in requests("p"):
        alone = ContinuousBatchScheduler(build(None))
        alone.submit(req)
        assert alone.run(deadline_s=DEADLINE_S)
        plain.append(req.output)
    rng.bit_generator.state = rng_state
    metrics.reset()
    sched = ContinuousBatchScheduler(build(None))
    inner, ahead = sched._commit_full_tails, []

    def noted():
        lanes = metrics.get("cgx.serve.commit.lanes")
        inner()
        if sched._steps:  # under a step dispatched and not read yet
            ahead.append(metrics.get("cgx.serve.commit.lanes") - lanes)

    sched._commit_full_tails = noted
    reqs = requests("a")
    for r in reqs:
        sched.submit(r)
    assert sched.run(deadline_s=DEADLINE_S)
    assert [r.output for r in reqs] == plain
    assert _COMMIT_LANES in ahead  # all six at once, ahead of a step
    commits = [n for n in ahead if n]
    assert metrics.get("cgx.serve.commit.calls") > len(commits)
    assert metrics.get("cgx.serve.commit.lanes") == 2 * _COMMIT_LANES
    assert metrics.get("cgx.serve.decode.ahead") > 0
    assert sched.cache.free_pages == 30
