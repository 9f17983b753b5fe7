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
