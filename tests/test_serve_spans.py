"""The serving scheduler's spans (ISSUE 25, re-cut by ISSUE 26): every phase
of admission and of a decode tick goes through ``utils.tracing.trace_span`` —
one annotation on the profiler's host plane, one histogram under
``cgx.serve.``, one timeline record — plus the two waits of a request and the
full-collection pauses. An admission is two compiled programs (ISSUE 26), so
a prefill has two phases: the program's call, and the read of the first token
that waits for it. Since ISSUE 32 the two lie in different phases of the tick
(the dispatch in ``_admit``, the read after the decode step is queued), so
``serve.prefill.local``, which spans both, is a histogram and a timeline
record closed at the read, with no annotation of its own; and the counters of
what the tick read, queued ahead and dropped are checked here too. ISSUE 35
cut the tick where the host stops (a span at every dispatch and every
blocking read) and reads three accounts from the cuts: the tick's (with the
stall record), the first token's, and the unfed device's. ISSUE 50 adds the
device's own: what each program cost the chip, from the stamps of the reads
that waited for it, held here to a fake device whose programs take a set time
on a clock the tests move.

CPU, the tiny model: counts, containment and nesting are what a CPU run can
say; the times themselves are read on the chip (PERF.md).
"""

from __future__ import annotations

import gc
import glob
import json
import logging
import re
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_cgx_tpu.models.gpt2 import GPT2, GPT2Config
from torch_cgx_tpu.observability import timeline
from torch_cgx_tpu.serving.adapter import ServeConfig
from torch_cgx_tpu.serving.gpt2 import GPT2Server
from torch_cgx_tpu.serving.scheduler import (
    ContinuousBatchScheduler,
    Request,
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
    "ready_wait_s", "ttft_behind_s",
)
# ``dispatch_step_s`` counts steps dispatched and ``wait_step_s`` steps read:
# the same number once a run has read every step it queued.
PER_DECODE_STEP = ("decode_prepare_s", "decode_step_s", "decode_emit_s",
                   "dispatch_step_s", "wait_step_s")
# Span -> the span that holds it (names as the timeline has them; the
# profiler's trace has them under ``cgx.``).
PARENT = {
    "serve.prefill.forward": "serve.step",
    # in serve.decode_step, but for a burst's: read before a third is queued
    "serve.prefill.first_token": "serve.step",
    "serve.admit_lane": "serve.step",
    "serve.decode.prepare": "serve.step",
    "serve.decode_step": "serve.step",
    "serve.decode.emit": "serve.step",
    # the cuts of ISSUE 35: the fresh commit lies in serve.decode.prepare,
    # the run-ahead's, both step dispatches and the wait in serve.decode_step
    "serve.dispatch.step": "serve.step",
    "serve.dispatch.commit": "serve.step",
    "serve.wait.step": "serve.step",
}
ANNOTATED_REQUEST_SPANS = [n for n in PARENT
                           if "prefill" in n or "admit" in n]
REQUEST_SPANS = ["serve.prefill.local", *ANNOTATED_REQUEST_SPANS]


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
    """One request: submit -> first token is the queue wait, then the
    prefill span, which runs from the prefill's dispatch to the first token
    on the host and so holds the ready wait, the lane write and both of its
    own phases."""
    (req,), _, start, end = _serve(server, lens=(19,))
    ttft = req.first_token_at - req.submitted_at
    total = lambda *names: sum(
        _delta(start, end, f"{n}.sum") for n in names)
    whole = total("queue_wait_s", "prefill_s")
    assert whole <= ttft <= whole + 1e-3
    assert total("prefill_forward_s", "ready_wait_s", "admit_lane_s",
                 "ttft_behind_s", "prefill_first_token_s"
                 ) <= total("prefill_s")


def _slow_step_dispatch(sched, seconds):
    """Every ``decode_step`` dispatch of ``sched`` takes ``seconds`` more
    (its programs are the module's cached ones: a copy, not a patch)."""
    prog = sched._prog

    def decode_step(params, state):
        time.sleep(seconds)
        return prog.decode_step(params, state)

    sched._prog = SimpleNamespace(**{**vars(prog),
                                     "decode_step": decode_step})


def _served_with_timeline(server, run_dir, sizes, slow_s):
    """Serve ``sizes`` with every step dispatch slowed; returns the
    requests, each one's TTFT parts in seconds from its own spans' fields
    and durations in the timeline, and the steps queued ahead."""
    timeline.reset()
    timeline.set_rank(0)
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    _slow_step_dispatch(sched, slow_s)
    reqs = _requests(server, sizes, tag=run_dir.name)
    _tick_until_done(sched, reqs)
    timeline.flush()
    spans = [e for e in map(json.loads, open(run_dir / "spans-rank0.jsonl"))
             if e.get("kind") == "span" and "req" in e]
    parts = {}
    for r in reqs:
        mine = {e["name"]: e for e in spans if e["req"] == r.id}
        local, lane = mine["serve.prefill.local"], mine["serve.admit_lane"]
        read = mine["serve.prefill.first_token"]
        parts[r.id] = {
            "queue_wait": local["queue_wait_ms"] / 1e3,
            "prefill_forward": mine["serve.prefill.forward"]["dur_s"],
            "ready_wait": lane["ready_wait_ms"] / 1e3,
            "admit_lane": lane["dur_s"],
            "behind": read["behind_ms"] / 1e3,
            "first_token": read["dur_s"],
        }
    return reqs, parts, metrics.get("cgx.serve.decode.ahead")


def test_every_requests_ttft_is_its_six_parts(server, tmp_path, monkeypatch):
    """TTFT = queue wait + prefill dispatch + ready wait + lane write +
    what stood behind the lane write + the read, for every request, from
    the spans' own fields (``behind_ms`` on ``serve.prefill.first_token``).
    ``behind`` holds the step dispatches between a lane write and the read
    of its first token: one in a tick that queued no step ahead (a lone
    request, three lanes free), two in a tick that did (the fourth of four
    long answers on four lanes)."""
    _serve(server)  # compile outside the timed stretch
    slow = 0.05
    runs = {}
    for name, sizes in (("alone", [(5, 4)]),
                        ("full", [(5, 12), (9, 12), (12, 12), (7, 12)])):
        run_dir = tmp_path / name
        run_dir.mkdir()
        monkeypatch.setenv("CGX_METRICS_DIR", str(run_dir))
        runs[name] = _served_with_timeline(server, run_dir, sizes, slow)
    for reqs, parts, _ in runs.values():
        for r in reqs:
            ttft = r.first_token_at - r.submitted_at
            whole = sum(parts[r.id].values())
            # the parts leave out the few statements between them
            assert whole <= ttft + 1e-5, (r.id, parts[r.id])
            assert ttft - whole <= max(2e-3, 0.05 * ttft), (r.id, parts[r.id])
    (lone,), lone_parts, lone_ahead = runs["alone"]
    _, full_parts, full_ahead = runs["full"]
    assert lone_ahead == 0.0 and full_ahead > 0.0
    behind_alone = lone_parts[lone.id]["behind"]
    behind_ahead = max(p["behind"] for p in full_parts.values())
    assert slow <= behind_alone < 2 * slow <= behind_ahead


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
    for name in ANNOTATED_REQUEST_SPANS:
        assert {e[3]["req"] for e in by_name["cgx." + name]} == ids, name
    assert "cgx.serve.prefill.local" not in by_name  # it would not nest


# ---------------------------------------------------------------------------
# What a tick reads, queues ahead and drops (ISSUE 32).
# ---------------------------------------------------------------------------


def _requests(server, sizes, tag="c", seed=7):
    """One request a ``(prompt length, tokens asked)`` of ``sizes``."""
    rng = np.random.default_rng(seed)
    return [
        Request(id=f"{tag}{i}", max_new_tokens=gen,
                tokens=[int(t) for t in
                        rng.integers(0, server.cfg.vocab_size, n)])
        for i, (n, gen) in enumerate(sizes)
    ]


def _tick_until_done(sched, reqs, each_tick=lambda: None):
    for r in reqs:
        sched.submit(r)
    ticks = 0
    while sched.outstanding():
        sched.step()
        each_tick()
        ticks += 1
        assert ticks < 1000, "serving run wedged"
    return ticks


def test_a_tick_reads_once_for_its_step_and_once_an_admission(
        server, monkeypatch):
    """``cgx.serve.host_reads``: every tick that reads a step makes one
    read for it and one for each first token, and nothing else on the
    device is read: not in ``serve.decode.prepare``, whose commit goes by
    the host's own count of the tails, nor anywhere outside the two reads
    (counted here: every ``int()`` of a device array, and every
    ``np.asarray`` of one that the scheduler's module makes)."""
    from jax._src import array as jax_array
    from torch_cgx_tpu.serving import scheduler as sched_mod

    value = jax_array.ArrayImpl._value
    copies = []

    def counted_value(self):
        if self._npy_value is None:
            copies.append(1)
        return value.fget(self)

    class CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *args, **kwargs):
            if isinstance(x, jax.Array):
                copies.append(1)
            return np.asarray(x, *args, **kwargs)

    monkeypatch.setattr(jax_array.ArrayImpl, "_value",
                        property(counted_value))
    monkeypatch.setattr(sched_mod, "np", CountingNumpy())
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    in_prepare = []
    commit = sched._commit_full_tails

    def prepare():
        before = len(copies)
        commit()
        in_prepare.append(len(copies) - before)

    sched._commit_full_tails = prepare
    seen = {"reads": 0.0, "steps": 0.0, "admitted": 0.0}

    def each_tick():
        now = {"reads": metrics.get("cgx.serve.host_reads"),
               "steps": metrics.get("cgx.serve.decode_steps"),
               "admitted": metrics.get("cgx.serve.requests_admitted")}
        grew = {k: now[k] - seen[k] for k in now}
        assert grew["steps"] in (0.0, 1.0)
        assert grew["reads"] == grew["steps"] + grew["admitted"]
        seen.update(now)

    # six requests on four lanes, answers long enough that tails fill
    reqs = _requests(server, [(5, 12), (19, 20), (24, 9), (7, 14), (11, 3),
                              (13, 10)])
    _tick_until_done(sched, reqs, each_tick)
    assert metrics.get("cgx.serve.pages_committed") > 0
    assert in_prepare and not any(in_prepare)
    assert len(copies) == seen["reads"] == seen["steps"] + len(reqs)


def test_no_step_is_queued_ahead_while_a_lane_finishes_every_tick(server):
    """Every answer is two tokens: the first at admission, the second at
    the one step its lane decodes. Each step is some lane's last, so each
    is read before anything else is queued and the next request's prefill
    goes in front of the next step."""
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    reqs = _requests(server, [(5 + i, 2) for i in range(9)])
    _tick_until_done(sched, reqs)
    assert all(len(r.output) == 2 for r in reqs)
    assert metrics.get("cgx.serve.decode_steps") > 0
    assert metrics.get("cgx.serve.decode.ahead") == 0.0


def test_most_steps_are_queued_ahead_while_every_lane_is_busy(server):
    """Four long answers on four lanes: until the first of them is one
    step from its last token, nothing could be admitted whatever arrived,
    and every step but the first is dispatched before the one in front of
    it is read."""
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    reqs = _requests(server, [(5, 40), (9, 44), (12, 48), (7, 50)])
    _tick_until_done(sched, reqs)
    steps = metrics.get("cgx.serve.decode_steps")
    ahead = metrics.get("cgx.serve.decode.ahead")
    assert ahead == 38.0  # steps 2..39: the 39th is the first answer's last
    assert steps == 49.0 and ahead > steps / 2
    assert metrics.get("cgx.serve.decode.discarded_tokens") == 0.0


def test_discarded_tokens_are_the_eos_finishes_under_a_queued_step(server):
    """Two long answers on two lanes, and an end-of-sequence token that
    the first of them alone produces, in mid-answer: it is found at the
    read of a step behind which the next was already queued, so that step
    decodes one token for the lane, which is dropped and counted; from
    then on a lane is free and nothing runs ahead. Without ``eos_token``
    nothing is ever dropped."""
    import dataclasses

    two = dataclasses.replace(server.serve, max_batch=2)
    sizes = [(5, 30), (9, 34)]

    def served(eos):
        metrics.reset()
        adapter = GPT2Server(server.cfg, {"params": server.p},
                             dataclasses.replace(two, eos_token=eos))
        reqs = _requests(server, sizes, seed=9)
        _tick_until_done(ContinuousBatchScheduler(adapter), reqs)
        return ([r.output for r in reqs],
                metrics.get("cgx.serve.decode.discarded_tokens"),
                metrics.get("cgx.serve.tokens_generated"))

    (first, second), dropped, _ = served(None)
    assert dropped == 0.0
    at, eos = next((i, t) for i, t in enumerate(first)
                   if 2 <= i < len(first) - 2 and t not in second
                   and t not in first[:i])
    outputs, dropped, generated = served(eos)
    assert outputs == [first[: at + 1], second]
    assert dropped == 1.0
    assert generated == at + 1 + len(second)  # the dropped one is no token


def test_first_token_and_its_stamp_are_written_at_the_read(server):
    """``req.output[0]`` and ``first_token_at`` appear together, at the
    read of the first token and not at the dispatch of the lane write; a
    first token is stamped when the host holds it. ``cgx.serve.prefill_s``
    runs from the prefill's dispatch to that read, whatever lies between."""
    import time

    _serve(server)  # compile outside the timed stretch
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    reqs = _requests(server, [(5, GEN), (19, GEN)])
    for r in reqs:
        sched.submit(r)
    sched._admit()
    assert all(r in sched._lanes for r in reqs)
    assert all(r.output == [] and r.first_token_at is None for r in reqs)
    found = metrics.snapshot("cgx.serve.")
    assert "cgx.serve.ttft_ms.count" not in found
    assert "cgx.serve.prefill_s.count" not in found
    time.sleep(0.05)
    before = time.monotonic()
    sched._read_first_tokens()
    assert all(len(r.output) == 1 and r.first_token_at >= before
               for r in reqs)
    found = metrics.snapshot("cgx.serve.")
    assert found["cgx.serve.ttft_ms.count"] == 2.0
    assert found["cgx.serve.prefill_s.count"] == 2.0
    assert found["cgx.serve.prefill_s.sum"] >= 2 * 0.05
    dispatch_and_read = (found["cgx.serve.prefill_forward_s.sum"]
                         + found["cgx.serve.prefill_first_token_s.sum"])
    assert dispatch_and_read < 0.05
    while sched.outstanding():
        sched.step()
        assert all((r.first_token_at is None) == (r.output == [])
                   for r in reqs)


def test_a_first_token_whose_read_raises_fails_its_request_alone(server):
    """The failed-prefill contract at the later read: the request errors
    alone, its pages are freed and its lane released, and the lane beside
    it, decoding under the same steps, gets the tokens it would have."""
    (want,) = [r.output for r in _serve(server, lens=(19,), gen=8)[0]]
    metrics.reset()
    sched = ContinuousBatchScheduler(server)

    class Unreadable:
        def __int__(self):
            raise RuntimeError("the device lost it")

    rng = np.random.default_rng(1)  # _serve's first prompt again
    good = Request(id="good", max_new_tokens=8, tokens=[
        int(t) for t in rng.integers(0, server.cfg.vocab_size, 19)])
    (bad,) = _requests(server, [(11, 6)], tag="bad")
    sched.submit(good)
    sched.submit(bad)
    sched._admit()
    _, ready = sched._unread[1]
    assert ready.req is bad
    ready.first_token = Unreadable()  # the lane write has its operand
    assert sched.run(deadline_s=300.0)
    assert bad.done and bad.output == [] and bad.first_token_at is None
    assert good.done and good.output == want
    assert metrics.get("cgx.serve.request_errors") == 1.0
    assert metrics.get("span.serve.prefill.local.errors") == 1.0
    assert sched.cache.free_pages == server.serve.max_pages
    assert sched._lanes == [None] * server.serve.max_batch


# ---------------------------------------------------------------------------
# The tick cut where the host stops, and its three accounts (ISSUE 35).
# ---------------------------------------------------------------------------


def test_commit_dispatch_is_timed_once_a_tick_that_committed(server):
    """``dispatch_commit_s`` has one sample for every call of
    ``_commit_full_tails`` that promoted a tail, the fresh step's (inside
    ``serve.decode.prepare``) and the run-ahead's alike, and none for a
    call that found no tail full."""
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    commit = sched._commit_full_tails
    calls = {"all": 0, "committed": 0}

    def counted():
        before = metrics.get("cgx.serve.commit.lanes")
        commit()
        calls["all"] += 1
        calls["committed"] += metrics.get("cgx.serve.commit.lanes") > before

    sched._commit_full_tails = counted
    reqs = _requests(server, [(5, 40), (9, 44), (12, 48), (7, 50), (11, 3)])
    _tick_until_done(sched, reqs)
    assert metrics.get("cgx.serve.decode.ahead") > 0  # both kinds ran
    assert 0 < calls["committed"] < calls["all"]
    assert metrics.get("cgx.serve.dispatch_commit_s") == calls["committed"]
    steps = metrics.get("cgx.serve.decode_steps")
    assert metrics.get("cgx.serve.dispatch_step_s") == steps
    assert metrics.get("cgx.serve.wait_step_s") == steps
    # every blocking copy is one of the two waits
    assert metrics.get("cgx.serve.host_reads") == steps + metrics.get(
        "cgx.serve.prefill_first_token_s")


def test_ticks_and_the_time_between_them_add_up_to_the_loop(server):
    """``step_s.sum + between_steps_s.sum`` is the wall time of the loop
    that calls ``step()``: the divisor of every share of the tick."""
    _serve(server)  # compile outside the timed stretch
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    for r in _requests(server, [(5, 40), (9, 44), (12, 48), (7, 50)]):
        sched.submit(r)
    start = time.perf_counter()
    assert sched.run(deadline_s=300.0)
    wall = time.perf_counter() - start
    found = metrics.snapshot("cgx.serve.")
    ticks = found["cgx.serve.step_s.count"]
    assert found["cgx.serve.between_steps_s.count"] == ticks - 1
    summed = (found["cgx.serve.step_s.sum"]
              + found["cgx.serve.between_steps_s.sum"])
    assert summed <= wall and wall - summed <= 0.02 * wall


def test_device_unfed_is_observed_once_a_tick_that_leaves_nothing_queued(
        server):
    """While every step is queued ahead a read never leaves the device
    with nothing to run, and ``device_unfed_s`` observes nothing; once a
    lane finishes every tick, each tick's last read does, and the next
    tick's first dispatch closes the gap: one sample a tick."""
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    seen = {"ahead": 0.0}
    unfed_while_ahead = []

    def each_tick():
        ahead = metrics.get("cgx.serve.decode.ahead")
        if ahead > seen["ahead"]:
            unfed_while_ahead.append(
                metrics.get("cgx.serve.device_unfed_s"))
        seen["ahead"] = ahead

    _tick_until_done(
        sched, _requests(server, [(5, 40), (9, 44), (12, 48), (7, 50)]),
        each_tick)
    assert len(unfed_while_ahead) == 38 and not any(unfed_while_ahead)
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    _tick_until_done(sched, _requests(server, [(5 + i, 2) for i in range(9)]))
    steps = metrics.get("cgx.serve.decode_steps")
    assert metrics.get("cgx.serve.decode.ahead") == 0.0 and steps > 1
    # the last tick's gap has no dispatch to end it
    assert metrics.get("cgx.serve.device_unfed_s") == steps - 1


class _Lines(logging.Handler):
    """The package logger's warning lines (it does not propagate)."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture
def stall_lines():
    handler = _Lines()
    logger = logging.getLogger("torch_cgx_tpu")
    logger.addHandler(handler)
    yield handler.lines
    logger.removeHandler(handler)


def _largest_item(line):
    """The phase a stall line gives most of the tick to."""
    items = re.findall(r"(\S+)=([\d.]+)", line.split("|")[0])
    return max(items, key=lambda item: float(item[1]))[0]


def test_a_stall_is_counted_once_and_its_line_names_the_phase(
        server, stall_lines, monkeypatch):
    """Steady ticks record no stall. A sleep inside the read of a step's
    tokens gives one, whose line puts the time under ``wait.step``; a
    sleep between two ``step()`` calls gives one under ``between_steps``:
    the caller was away, so neither ``stall_s`` (the loop's own slow
    ticks) nor ``between_steps_s`` (its turn-arounds) observes that gap.
    A scheduler's first 32 ticks record nothing, whatever they take."""
    from torch_cgx_tpu.serving import scheduler as sched_mod

    _serve(server)  # compile outside the timed stretch
    armed = []

    class SleepyNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *args, **kwargs):
            if armed and isinstance(x, jax.Array):
                time.sleep(armed.pop())
            return np.asarray(x, *args, **kwargs)

    monkeypatch.setattr(sched_mod, "np", SleepyNumpy())
    metrics.reset()
    sched = ContinuousBatchScheduler(server)
    (req,) = _requests(server, [(5, 56)])
    sched.submit(req)
    stalls = lambda: metrics.get("cgx.serve.stalls")
    while sched.outstanding():
        if sched._ticks == 3:
            armed.append(0.6)  # inside the warm-up: recorded nowhere
        if sched._ticks == 40:
            assert stalls() == 0.0 and stall_lines == []
            armed.append(0.6)
        if sched._ticks == 48:
            assert stalls() == 1.0
            time.sleep(0.6)
        sched.step()
        assert sched._ticks < 1000, "serving run wedged"
    assert len(req.output) == 56
    assert stalls() == 2.0 and metrics.get("cgx.serve.stall_s") == 1.0
    assert metrics.histogram_stats("cgx.serve.stall_s")["min"] >= 0.6
    gaps = metrics.histogram_stats("cgx.serve.between_steps_s")
    assert gaps["count"] == sched._ticks - 2 and gaps["max"] < 0.5
    in_read, between = stall_lines
    assert "stalled tick 41" in in_read and "stalled tick 49" in between
    assert _largest_item(in_read) == "wait.step"
    assert _largest_item(between) == "between_steps"


def test_compiles_count_a_program_rebuild_and_no_steady_tick(server):
    """``cgx.serve.compiles``: JAX's own count of the programs it built.
    Serving the same shapes again builds none; after the program cache is
    dropped the next scheduler's programs are built anew and counted."""
    from torch_cgx_tpu.serving import scheduler as sched_mod

    _serve(server)
    warm = metrics.get("cgx.serve.compiles")
    _serve(server)
    assert metrics.get("cgx.serve.compiles") == warm
    sched_mod.invalidate_decode_cache("test")
    _serve(server)
    rebuilt = metrics.get("cgx.serve.compiles") - warm
    assert rebuilt >= 3  # prefill_pages, admit_lane, decode_step at least
    assert metrics.get("cgx.serve.compile_s") == metrics.get(
        "cgx.serve.compiles")

# ---------------------------------------------------------------------------
# The device's account, from the scheduler's own stamps (ISSUE 50).
# ---------------------------------------------------------------------------

STEP_S = 0.03  # the fake device's decode step
FOUR_LONG = [(5, 40), (9, 44), (12, 48), (7, 50)]


class _Clock:
    """The scheduler's and the spans' ``time``, made of nothing: a reading
    costs the host a microsecond, a sleep or a blocking read moves it on.
    What the account says of a fake device is then exact, on any machine."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        self.now += 1e-6
        return self.now

    monotonic = perf_counter

    def sleep(self, seconds):
        self.now += seconds


class _Pending:
    """A program's output still on the fake device: the copy to the host
    returns when its program has ended, at once if it has."""

    def __init__(self, value, clock, done_at):
        self.value, self.clock, self.done_at = value, clock, done_at

    def _wait(self):
        self.clock.now = max(self.clock.now, self.done_at)

    def __array__(self, dtype=None, copy=None):
        self._wait()
        return np.asarray(self.value)

    def __int__(self):
        self._wait()
        return int(self.value)


def _on_fake_device(sched, clock, step_s=STEP_S, prefill_s=0.01,
                    commit_s=0.0):
    """``sched``'s programs (a copy: they are the module's cached ones)
    with their results from the real ones and their time from a fake
    device, which runs one program at a time in dispatch order: a program
    dispatched now ends its seconds after the later of now and the end of
    the program before it, and what the scheduler reads of it, a first
    token or a step's tokens, blocks until then. ``step_s`` and
    ``prefill_s`` may be functions, of nothing and of the padded length."""
    prog, free_at = sched._prog, [0.0]
    of = lambda seconds, *a: seconds(*a) if callable(seconds) else seconds

    def run(seconds):
        free_at[0] = max(free_at[0], clock.now) + seconds
        return free_at[0]

    def prefill_pages(params, pools, tokens, *rest):
        first, *out = prog.prefill_pages(params, pools, tokens, *rest)
        done = run(of(prefill_s, tokens.shape[1]))
        return (_Pending(first, clock, done), *out)

    def admit_lane(state, lane, row, n_full, tail_len, first, *rest):
        if isinstance(first, _Pending):  # an operand still on the device
            first = first.value
        return prog.admit_lane(state, lane, row, n_full, tail_len, first,
                               *rest)

    def commit(*args):
        run(commit_s)
        return prog.commit(*args)

    def decode_step(params, state):
        state, tokens = prog.decode_step(params, state)
        return state, _Pending(tokens, clock, run(of(step_s)))

    sched._prog = SimpleNamespace(**{
        **vars(prog), "prefill_pages": prefill_pages,
        "admit_lane": admit_lane, "commit": commit,
        "decode_step": decode_step})


@pytest.fixture
def clock(server, monkeypatch):
    from torch_cgx_tpu.serving import scheduler as sched_mod
    from torch_cgx_tpu.utils import tracing

    _serve(server)  # a tick that compiles is no sample of what a program costs
    clock = _Clock()
    monkeypatch.setattr(sched_mod, "time", clock)
    monkeypatch.setattr(tracing, "time", clock)
    metrics.reset()
    return clock


def _device(name):
    return metrics.get(f"cgx.serve.device.{name}")


def _late_read(sched, clock, ticks, seconds):
    """The host comes ``seconds`` late to the step read of each tick of
    ``ticks`` (numbered from the scheduler's first)."""
    inner = sched._read_first_tokens

    def read_first_tokens(*args, **kwargs):  # the call before the step read
        if not kwargs and not args and sched._ticks in ticks:
            clock.sleep(seconds)
        return inner(*args, **kwargs)

    sched._read_first_tokens = read_first_tokens


def test_a_pure_step_interval_is_the_steps_device_time(server, clock):
    """Four long answers on four lanes, every step queued ahead: an
    interval between two blocked reads that holds one ``decode_step`` is a
    sample of ``device.step_s`` and reads the fake step's time; one that
    holds a tick's commit too is a sample of ``commit_step_s`` and reads
    the two; both whatever the caller does between two ticks."""
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock, commit_s=0.006)
    _tick_until_done(sched, _requests(server, FOUR_LONG),
                     each_tick=lambda: clock.sleep(0.004))
    assert metrics.get("cgx.serve.decode.ahead") == 38.0
    step = metrics.histogram_stats("cgx.serve.device.step_s")
    both = metrics.histogram_stats("cgx.serve.device.commit_step_s")
    assert step["count"] >= 8 and both["count"] >= 8
    assert step["count"] + both["count"] <= 38
    assert step["min"] == pytest.approx(STEP_S, abs=1e-4)
    assert step["max"] == pytest.approx(STEP_S, abs=1e-4)
    calls = _device("commit_calls") / both["count"]
    assert calls == 1.0  # four lanes fill one at a time: one call a tick
    assert (both["mean"] - step["mean"]) / calls == pytest.approx(
        0.006, abs=1e-4)
    assert _device("unsound") == 0.0
    # the running means the stall record holds a tick against
    assert sched._usual["decode_step"][1] == pytest.approx(STEP_S, abs=1e-4)
    assert sched._usual["commit"][1] == pytest.approx(0.006, abs=1e-4)


def test_steps_that_start_at_a_dispatch_are_accounted_in_no_clean_class(
        server, clock):
    """No step queued ahead: every read leaves the device nothing, so every
    interval starts at the dispatch that next feeds it. Its seconds are the
    step's device time all the same (``accounted_s``, and the running mean
    of the class), and ``device.step_s``, which is for intervals the device
    was fed through, has no sample."""
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock)
    sched._runs_ahead = lambda: False
    (req,) = _requests(server, [(5, 50)])
    sched.submit(req)
    sched.step()  # the admission's tick
    accounted, steps = _device("accounted_s"), metrics.get(
        "cgx.serve.decode_steps")
    while sched.outstanding():
        sched.step()
    steps = metrics.get("cgx.serve.decode_steps") - steps
    assert steps == 48.0 and _device("step_s") == 0.0
    assert (_device("accounted_s") - accounted) / steps == pytest.approx(
        STEP_S, abs=1e-4)
    assert sched._usual["decode_step"][1] == pytest.approx(STEP_S, abs=1e-4)
    assert _device("unsound") == 0.0


def test_a_late_host_closes_nothing_and_the_next_read_closes_both(
        server, clock):
    """The host comes to a step's read a step and a half late: the read
    does not block and closes nothing; the next read, which blocks, closes
    one interval that holds both steps, a sample of no clean class, and no
    wall time is lost."""
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock)
    _late_read(sched, clock, {20}, 1.5 * STEP_S)
    seen = {}

    def each_tick():
        seen[sched._ticks] = (
            _device("accounted_s"),
            _device("step_s") + _device("commit_step_s"),
            [r[1] for r in sched._held])

    _tick_until_done(sched, _requests(server, FOUR_LONG), each_tick)
    (before, clean, _), (_, _, held), (after, clean_after, _) = (
        seen[20], seen[21], seen[22])
    assert [k for k in held if k != "commit"] == ["decode_step"]
    assert clean_after == clean
    assert after - before == pytest.approx(2 * STEP_S, abs=1e-4)
    assert _device("unsound") == 0.0


def test_a_late_read_that_leaves_nothing_queued_is_unsound(server, clock):
    """Nothing queued ahead and the host late to a read: the device stood
    idle from an instant nobody saw, so the interval is dropped and
    counted, and the next one starts at the next dispatch."""
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock)
    sched._runs_ahead = lambda: False
    _late_read(sched, clock, {10}, 1.5 * STEP_S)
    seen = {}

    def each_tick():
        seen[sched._ticks] = (_device("accounted_s"), _device("unsound"))

    _tick_until_done(sched, _requests(server, [(5, 30)]), each_tick)
    assert seen[10][1] == 0.0 and seen[11][1] == 1.0
    assert seen[11][0] == seen[10][0]  # the late tick accounted nothing
    assert seen[12][0] - seen[11][0] == pytest.approx(STEP_S, abs=1e-4)
    assert _device("unsound") == 1.0


def test_a_first_token_read_closes_its_prefill_and_leaves_the_lane_write(
        server, clock, tmp_path, monkeypatch):
    """The read of a first token waits for its own ``prefill_pages`` and
    nothing behind it: the interval it closes is a sample of
    ``device.prefill_s`` (the timeline's record has the padded length and
    the request), and the ``admit_lane`` stays owed, to ride in the next
    interval."""
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    timeline.reset()
    timeline.set_rank(0)
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock, prefill_s=0.04)
    (req,) = _requests(server, [(19, GEN)], tag="p")
    sched.submit(req)
    sched._admit()
    assert [r[1] for r in sched._owed] == ["prefill_pages", "admit_lane"]
    sched._read_first_tokens()
    assert [(r[1], r[3]) for r in sched._owed] == [("admit_lane", req.id)]
    assert _device("prefill_s") == 0.0  # a sample once its tick is judged
    sched._settle(stalled=False, unknown=True)
    stats = metrics.histogram_stats("cgx.serve.device.prefill_s")
    assert stats["count"] == 1
    assert stats["mean"] == pytest.approx(0.04, abs=1e-4)
    assert _device("prefill_tokens") == 24.0  # 19 tokens, pages of 8
    assert _device("accounted_s") == stats["sum"]
    while sched.outstanding():
        sched.step()
    # the last release is owed until a read that never comes
    assert [r[1] for r in sched._owed] == ["release_lanes"]
    assert _device("prefill_s") == 1.0
    timeline.flush()
    (record,) = [e for e in map(json.loads,
                                open(tmp_path / "spans-rank0.jsonl"))
                 if e.get("name") == "serve.device.prefill"]
    assert record["tokens"] == 24 and record["req"] == req.id
    assert record["dur_s"] == pytest.approx(0.04, abs=1e-4)


def test_accounted_dropped_and_unfed_time_add_up_to_the_loops_wall(
        server, clock):
    """Nothing queued ahead, so the device's intervals and the unfed gaps
    between them tile the loop; with the one interval a late read dropped
    (its tick, from the step's dispatch on) they add up to its wall time
    within 2 %. ``step_s`` and ``between_steps_s`` give the same wall."""
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock)
    sched._runs_ahead = lambda: False
    _late_read(sched, clock, {10}, 1.5 * STEP_S)
    for r in _requests(server, [(5, 40), (9, 40)]):
        sched.submit(r)
    start = clock.now
    while sched.outstanding():
        sched.step()
        clock.sleep(0.002)  # the caller's turn-around: the device is unfed
    wall = clock.now - start
    assert _device("unsound") == 1.0
    unfed = metrics.histogram_stats("cgx.serve.device_unfed_s")["sum"]
    summed = _device("accounted_s") + unfed + 1.5 * STEP_S
    assert unfed > 35 * 0.002
    assert abs(wall - summed) <= 0.02 * wall, (wall, summed)
    ticks = sum(metrics.histogram_stats(f"cgx.serve.{name}")["sum"]
                for name in ("step_s", "between_steps_s"))
    assert abs(wall - ticks) <= 0.02 * wall


def test_a_callers_absence_opens_no_interval(server, clock):
    """The caller stays away for 0.6 s with a step queued ahead: the
    device finished it at an instant nobody saw, so the open interval is
    dropped with the unfed mark, uncounted, and the account starts again
    at the next read that blocks: the absence is in no interval."""
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock)
    for r in _requests(server, [(5, 50), (9, 50), (12, 50), (7, 50)]):
        sched.submit(r)
    start = clock.now
    while sched.outstanding():
        if sched._ticks == 40:
            assert sched._since is not None
            clock.sleep(0.6)
        sched.step()
        if sched._ticks == 41:
            assert sched._since is None and not sched._since_unfed
    wall = clock.now - start
    assert metrics.get("cgx.serve.stalls") == 1.0  # the absence itself
    assert _device("unsound") == 0.0
    accounted = _device("accounted_s")
    assert wall - 0.6 - 3 * STEP_S < accounted < wall - 0.6


def test_a_slow_step_is_a_stall_and_its_line_names_what_the_device_owed(
        server, clock, stall_lines):
    """A step that takes 0.7 s where its class takes 0.03: one stall,
    whose line says what the tick's reads waited for, what that usually
    costs the device, and what the process did meanwhile."""
    slow = []
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock,
                    step_s=lambda: slow.pop() if slow else STEP_S)
    for r in _requests(server, [(5, 50), (9, 50), (12, 50), (7, 50)]):
        sched.submit(r)
    while sched.outstanding():
        if sched._ticks == 44:  # its first 32 ticks teach a scheduler nothing
            assert stall_lines == []
            slow.append(0.7)
        sched.step()
    assert metrics.get("cgx.serve.stalls") == 1.0
    (line,) = stall_lines
    assert _largest_item(line) == "wait.step"
    owed = re.search(r"\| device owed: (.*) \(usual ([\d.]+)\)", line)
    kinds = owed.group(1).split()
    assert kinds[-1] == "decode_step"
    assert set(kinds[:-1]) <= {"commit", "x1"}
    assert float(owed.group(2)) == pytest.approx(STEP_S, abs=1e-3)
    process = re.search(r"\| process: cpu=([\d.]+) nivcsw=(\d+)$", line)
    assert float(process.group(1)) < 0.5  # it waited: it did not compute
    # the stalled tick's interval is the stall record's: no sample, no lesson
    assert metrics.histogram_stats("cgx.serve.device.step_s")[
        "max"] == pytest.approx(STEP_S, abs=1e-4)
    assert sched._usual["decode_step"][1] == pytest.approx(STEP_S, abs=1e-4)
    assert _device("accounted_s") > 0.7


def test_a_prefill_at_its_usual_time_is_traffic_after_three_samples(
        server, clock, stall_lines):
    """A prompt whose prefill takes the device 0.6 s is a stall by the
    rule of the running mean until its class (the power of two its padded
    length reaches up to) has closed three clean intervals, and traffic
    from then on; a prompt three times as long is another class, held
    against the known one's token until it has three of its own."""
    sched = ContinuousBatchScheduler(server)
    _on_fake_device(sched, clock, step_s=0.005,
                    prefill_s=lambda tokens: 0.075 * tokens)
    stalls = lambda: metrics.get("cgx.serve.stalls")
    (warm,) = _requests(server, [(19, 40)], tag="warm")
    _tick_until_done(sched, [warm])  # past the first 32 ticks
    assert sched._ticks >= 32 and stalls() == 0.0
    counted = []
    for i in range(5):
        _tick_until_done(sched, _requests(server, [(5, 16)], tag=f"long{i}"))
        counted.append(stalls())
    assert counted == [1.0, 2.0, 3.0, 3.0, 3.0]
    samples, a_token = sched._usual[("prefill_pages", 3)]  # 5-8 tokens
    assert samples == 5 and a_token == pytest.approx(0.6 / 8, abs=1e-5)
    # 17-32 tokens: the warm-up's, in a tick that teaches nothing
    assert ("prefill_pages", 5) not in sched._usual
    _tick_until_done(sched, _requests(server, [(19, 16)], tag="longer"))
    assert stalls() == 3.0  # 1.8 s, and 24 of the known class's tokens
    assert sched._usual[("prefill_pages", 5)][0] == 1
    # the warm-up request's, the two that were traffic and the longer one
    assert _device("prefill_s") == 4.0
    assert len(stall_lines) == 3
    assert all("release_lanes prefill_pages[8] admit_lane decode_step "
               "(usual unknown)" in line for line in stall_lines)

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
