"""The serving scheduler's spans (ISSUE 25, re-cut by ISSUE 26): every phase
of admission and of a decode tick goes through ``utils.tracing.trace_span`` —
one annotation on the profiler's host plane, one histogram under
``cgx.serve.``, one timeline record — plus the two waits of a request and the
full-collection pauses. An admission is two compiled programs (ISSUE 26), so
a prefill has two phases: the program's call, and the read of the first token
that waits for it.

CPU, the tiny model: counts, containment and nesting are what a CPU run can
say; the times themselves are read on the chip (PERF.md).
"""

from __future__ import annotations

import gc
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_cgx_tpu.models.gpt2 import GPT2, GPT2Config
from torch_cgx_tpu.observability import timeline
from torch_cgx_tpu.serving.scheduler import (
    ContinuousBatchScheduler,
    GPT2Server,
    Request,
    ServeConfig,
)
from torch_cgx_tpu.utils.logging import metrics
from torch_cgx_tpu.utils.tracing import GcPauses, install_gc_hook, trace_span

PAGE = 8
# A tail alone, pages with a tail, whole pages: every admission runs both
# prefill phases whatever its shape.
PROMPT_LENS = (5, 19, 24)
GEN = 5

# Histogram of each span of the table (ISSUE 26), under ``cgx.serve.``.
PREFILL_PHASES = ("prefill_forward_s", "prefill_first_token_s")
PER_ADMISSION = (
    "prefill_s", *PREFILL_PHASES, "admit_lane_s", "queue_wait_s",
    "ready_wait_s",
)
PER_DECODE_STEP = ("decode_prepare_s", "decode_step_s", "decode_emit_s")
# Span -> the span that holds it (names as the timeline has them; the
# profiler's trace has them under ``cgx.``).
PARENT = {
    "serve.prefill.forward": "serve.prefill.local",
    "serve.prefill.first_token": "serve.prefill.local",
    "serve.prefill.local": "serve.step",
    "serve.admit_lane": "serve.step",
    "serve.decode.prepare": "serve.step",
    "serve.decode_step": "serve.step",
    "serve.decode.emit": "serve.step",
}
REQUEST_SPANS = [n for n in PARENT if "prefill" in n or "admit" in n]


@pytest.fixture(scope="module")
def server():
    cfg = GPT2Config.tiny()
    params = GPT2(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    return GPT2Server(cfg, params, ServeConfig(
        page_tokens=PAGE, max_batch=4, max_pages=48, max_seq=64,
        ship_depth=2,
    ))


def _serve(server, lens=PROMPT_LENS, gen=GEN):
    """Submit one request per prompt length and tick until all are done.
    Returns (requests, ticks, the ``cgx.serve.`` counters before and
    after) — the same start/end pair the benchmark's readers get."""
    rng = np.random.default_rng(1)
    sched = ContinuousBatchScheduler(server)
    reqs = [
        Request(
            id=f"r{i}", max_new_tokens=gen,
            tokens=[int(t) for t in
                    rng.integers(0, server.cfg.vocab_size, n)],
        )
        for i, n in enumerate(lens)
    ]
    start = metrics.snapshot("cgx.serve.")
    for r in reqs:
        sched.submit(r)
    ticks = 0
    while sched.outstanding():
        sched.step()
        ticks += 1
        assert ticks < 1000, "serving run wedged"
    return reqs, ticks, start, metrics.snapshot("cgx.serve.")


def _delta(start, end, key):
    return end.get(f"cgx.serve.{key}", 0.0) - start.get(
        f"cgx.serve.{key}", 0.0)


def test_every_histogram_counts_admissions_or_steps(server):
    reqs, ticks, start, end = _serve(server)
    assert all(len(r.output) == GEN for r in reqs)
    steps = _delta(start, end, "decode_steps")
    assert steps > 0
    assert _delta(start, end, "step_s.count") == ticks
    for name in PER_ADMISSION:
        assert _delta(start, end, f"{name}.count") == len(reqs), name
    for name in PER_DECODE_STEP:
        assert _delta(start, end, f"{name}.count") == steps, name


def test_prefill_phases_sum_within_the_prefill_span(server):
    _, _, start, end = _serve(server)
    whole = _delta(start, end, "prefill_s.sum")
    parts = [_delta(start, end, f"{n}.sum") for n in PREFILL_PHASES]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= whole


def test_waits_and_spans_decompose_ttft(server):
    """One request: submit -> first token is queue wait, prefill, ready
    wait, then the lane write up to the first-token stamp."""
    (req,), _, start, end = _serve(server, lens=(19,))
    ttft = req.first_token_at - req.submitted_at
    before_lane = sum(
        _delta(start, end, f"{n}.sum")
        for n in ("queue_wait_s", "prefill_s", "ready_wait_s")
    )
    lane = _delta(start, end, "admit_lane_s.sum")
    assert before_lane <= ttft <= before_lane + lane + 1e-3


def test_request_spans_carry_req_in_the_timeline(server, tmp_path,
                                                 monkeypatch):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    timeline.reset()
    timeline.set_rank(0)
    reqs, _, _, _ = _serve(server)
    timeline.flush()
    spans = [
        e for e in map(json.loads, open(tmp_path / "spans-rank0.jsonl"))
        if e.get("kind") == "span"
    ]
    ids = {r.id for r in reqs}
    for name in REQUEST_SPANS:
        mine = [s for s in spans if s["name"] == name]
        assert {s["req"] for s in mine} == ids, name
        assert len(mine) == len(reqs), name
    for s in spans:
        if s["name"].startswith("serve.decode") or s["name"] == "serve.step":
            assert "req" not in s, s["name"]
    local = next(s for s in spans if s["name"] == "serve.prefill.local")
    assert local["prompt_tokens"] in PROMPT_LENS
    assert local["queue_wait_ms"] >= 0
    lane = next(s for s in spans if s["name"] == "serve.admit_lane")
    assert lane["ready_wait_ms"] >= 0 and 0 <= lane["lane"] < 4


def _host_events(trace_dir, prefix):
    """[(name, start_ns, end_ns, stats, line)] of the host plane's events
    whose name starts with ``prefix``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((
                        ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats), line.name,
                    ))
    return out


def test_profiler_trace_holds_the_spans_nested_on_one_clock(server,
                                                            tmp_path):
    _serve(server)  # compile outside the traced stretch
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("cgx.test.window"):
            reqs, ticks, _, _ = _serve(server)
    events = _host_events(tmp_path, "cgx.")
    (window,) = [e for e in events if e[0] == "cgx.test.window"]
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    assert len(by_name["cgx.serve.step"]) == ticks
    for name, _, _, _, line in events:
        if name.startswith("cgx.serve."):  # cgx.host.gc: any thread's
            assert line == window[4], f"{name} is on another line"
    for child, parent in PARENT.items():
        mine = by_name["cgx." + child]
        for _, a, b, _, _ in mine:
            assert window[1] <= a and b <= window[2], child
            assert any(
                pa <= a and b <= pb
                for _, pa, pb, _, _ in by_name["cgx." + parent]
            ), f"{child} lies outside every {parent}"
    ids = {r.id for r in reqs}
    for name in REQUEST_SPANS:
        assert {e[3]["req"] for e in by_name["cgx." + name]} == ids, name


# ---------------------------------------------------------------------------
# trace_span itself.
# ---------------------------------------------------------------------------


def test_trace_span_names_its_histogram():
    before = set(metrics.snapshot())
    with trace_span("unit.default"):
        pass
    with trace_span("unit.named", hist="cgx.unit.elsewhere_s"):
        pass
    new = {k.rsplit(".", 1)[0] for k in set(metrics.snapshot()) - before}
    assert new == {"cgx.unit.default_s", "cgx.unit.elsewhere_s"}


def test_trace_span_fields_reach_the_annotation(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace_span("unit.annotated", req="q7", lane=3):
            pass
    ((name, a, b, stats, _),) = _host_events(tmp_path, "cgx.unit.")
    assert name == "cgx.unit.annotated" and b >= a
    assert stats == {"req": "q7", "lane": 3}


# ---------------------------------------------------------------------------
# Full-collection pauses.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("generation,expected", [(0, 0), (1, 0), (2, 1)])
def test_gc_hook_counts_full_collections_only(generation, expected):
    hook = install_gc_hook()
    hook.publish()
    before = metrics.get("cgx.serve.host_gc_s")
    gc.collect(generation)
    hook.publish()
    assert metrics.get("cgx.serve.host_gc_s") - before == expected


def test_gc_hook_second_install_is_a_no_op(server):
    first = install_gc_hook()
    ContinuousBatchScheduler(server)  # the constructor installs it too
    assert install_gc_hook() is first
    assert sum(isinstance(cb, GcPauses) for cb in gc.callbacks) == 1


def test_gc_pause_is_published_by_the_next_tick(server):
    sched = ContinuousBatchScheduler(server)
    sched.step()
    before = metrics.get("cgx.serve.host_gc_s")
    gc.collect()
    assert metrics.get("cgx.serve.host_gc_s") == before  # stamped only
    sched.step()
    assert metrics.get("cgx.serve.host_gc_s") == before + 1


# -- the recurrent state beside the pages (ISSUE 31) -------------------------

HYBRID = dict(
    vocab_size=512, hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, shared_intermediate_size=128,
    layer_types=["mamba", "attention", "mamba"], mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=PAGE,
    mamba_n_groups=1, embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.125, logits_scaling=8, rms_norm_eps=1e-5,
    precision={"params": "float32"},
)


@pytest.fixture
def hybrid_server(monkeypatch):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmark import weights_granite_hybrid
    from torch_cgx_tpu.models.granite_hybrid import HybridConfig
    from torch_cgx_tpu.serving.hybrid import HybridSSMServer

    monkeypatch.setenv("CGX_KV_BITS", "8")
    cfg = HybridConfig.from_hf(HYBRID, dtype=jnp.float32)
    serve = ServeConfig(page_tokens=PAGE, max_batch=2, max_pages=16,
                        max_seq=64, ship_depth=4)
    return HybridSSMServer(
        cfg, weights_granite_hybrid.make_params(HYBRID, 1), serve)


def _run_hybrid(server, n_requests=3):
    sched = ContinuousBatchScheduler(server)
    reqs = [Request(id=f"h{i}", tokens=list(range(3, 3 + n)),
                    max_new_tokens=GEN)
            for i, n in enumerate(PROMPT_LENS[:n_requests])]
    for r in reqs:
        sched.submit(r)
    assert sched.run(deadline_s=300.0)
    return sched


def test_state_bytes_gauge_is_what_the_lanes_hold(hybrid_server):
    """``cgx.serve.state.bytes``: the recurrent state the scheduler holds,
    every lane of every Mamba layer, from the adapter's own count; 0 for a
    model without state streams (GPT-2)."""
    metrics.reset()
    sched = ContinuousBatchScheduler(hybrid_server)
    held = sum(t.nbytes for name in ("state_conv", "state_ssm")
               for t in sched._state[name] if t is not None)
    assert held == 2 * hybrid_server.state_bytes_per_lane() > 0
    assert metrics.get("cgx.serve.state.bytes") == float(held)


def test_state_bytes_gauge_reads_zero_without_state(server):
    metrics.reset()
    ContinuousBatchScheduler(server)
    assert metrics.get("cgx.serve.state.bytes") == 0.0


def test_state_is_a_memledger_owner(hybrid_server, monkeypatch):
    """The state is registered with the memory ledger under
    ``serve.state``, lanes and bytes; a rebuild releases what it drops."""
    from torch_cgx_tpu.observability import memledger

    noted = []
    monkeypatch.setattr(
        memledger, "note_alloc",
        lambda owner, n=1, nbytes=0: noted.append(("alloc", owner, n, nbytes)))
    monkeypatch.setattr(
        memledger, "note_release",
        lambda owner, n=1, nbytes=0: noted.append(
            ("release", owner, n, nbytes)))
    sched = ContinuousBatchScheduler(hybrid_server)
    held = int(metrics.get("cgx.serve.state.bytes"))
    assert ("alloc", "serve.state", 2, held) in noted
    sched._state = sched._fresh_state()
    ours = [e for e in noted if e[1] == "serve.state"]
    assert ours == [("alloc", "serve.state", 2, held),
                    ("release", "serve.state", 2, held),
                    ("alloc", "serve.state", 2, held)]


def test_lane_writes_count_admissions_that_wrote_a_state(hybrid_server,
                                                         server):
    """``cgx.serve.state.lane_writes``: one an admission of a model with
    state streams, none for GPT-2's."""
    metrics.reset()
    _run_hybrid(hybrid_server)
    assert metrics.get("cgx.serve.state.lane_writes") == 3.0
    assert metrics.get("cgx.serve.requests_admitted") == 3.0
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    sched.submit(Request(id="g", tokens=list(range(3, 9)),
                         max_new_tokens=GEN))
    assert sched.run(deadline_s=300.0)
    assert metrics.get("cgx.serve.requests_admitted") == 1.0
    assert metrics.get("cgx.serve.state.lane_writes") == 0.0


@pytest.mark.parametrize("impl,lowering", [("xla", "xla"),
                                           ("pallas", "pallas")])
def test_ssm_update_call_sites_are_counted_by_lowering(
        hybrid_server, monkeypatch, impl, lowering):
    """``cgx.codec.lowering.ssm_update.<lowering>`` counts the decode
    program's call sites, one a Mamba layer, as the codec counts its own."""
    from torch_cgx_tpu.serving import scheduler as sched_mod

    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    sched_mod.invalidate_decode_cache("test")
    metrics.reset()
    sched = ContinuousBatchScheduler(hybrid_server)
    jax.make_jaxpr(sched._prog.decode_step)(hybrid_server.p, sched._state)
    counted = {k: v for k, v in metrics.snapshot(
        "cgx.codec.lowering.ssm_update.").items()}
    assert counted == {f"cgx.codec.lowering.ssm_update.{lowering}": 2.0}
