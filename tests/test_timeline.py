"""Cross-rank trace timeline tests (ISSUE 3 tentpole + satellites).

Covers the span layer (inert without ``CGX_METRICS_DIR``, span/instant
records with monotonic clocks and thread track metadata, flush-on-raise),
its hot-path emitters (``trace_span``, the shm channel's put/take with
message keys), the ``tools/cgx_trace.py`` merger (torn-file tolerance,
clock-offset estimation on synthetic skewed ranks, Chrome trace-event
schema validity, cross-rank flow links) and the acceptance 2-rank bridge
run: per-rank span JSONL -> one ``trace.json`` with >= 1 cross-rank flow
per collective plus a step-time attribution table.
"""

from __future__ import annotations

import importlib.util
import json
import multiprocessing as mp
import os
import subprocess
import sys
import tempfile
import time
import traceback

import numpy as np
import pytest

from torch_cgx_tpu.observability import flightrec, timeline
from torch_cgx_tpu.robustness import faults
from torch_cgx_tpu.utils.logging import metrics

from test_faults import FakeStore, _channel_pair

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CGX_TRACE = os.path.join(_REPO, "tools", "cgx_trace.py")

pytestmark = pytest.mark.faults


def _load_cgx_trace():
    spec = importlib.util.spec_from_file_location("cgx_trace", _CGX_TRACE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh():
    faults.reset_injectors()
    metrics.reset()
    flightrec.reset()
    timeline.reset()
    yield
    faults.reset_injectors()
    metrics.reset()
    flightrec.reset()
    timeline.reset()


# ---------------------------------------------------------------------------
# Span layer core.
# ---------------------------------------------------------------------------


def test_timeline_inert_without_dir(tmp_path):
    assert not timeline.enabled()
    with timeline.span("op", timeline.CAT_COLLECTIVE, seq=1):
        pass
    timeline.instant("ev")
    timeline.record("x", timeline.CAT_WIRE, 0.0, 1.0)
    tl = timeline.get_timeline()
    assert tl._buf == []  # nothing buffered: the clean path records nothing
    timeline.flush()
    assert list(tmp_path.iterdir()) == []


def test_timeline_span_flush_and_meta(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    timeline.set_rank(3)
    with timeline.span("allreduce", timeline.CAT_COLLECTIVE, seq=7):
        time.sleep(0.005)
    timeline.instant("allreduce_group", bits=4)
    timeline.flush()
    path = tmp_path / "spans-rank3.jsonl"
    assert path.exists()
    lines = [json.loads(l) for l in open(path)]
    meta, events = lines[0], lines[1:]
    assert meta["kind"] == "meta" and meta["rank"] == 3
    assert "mono_wall_delta" in meta and "pid" in meta
    spans = [e for e in events if e["kind"] == "span"]
    assert spans and spans[0]["name"] == "allreduce"
    assert spans[0]["cat"] == "collective" and spans[0]["seq"] == 7
    assert spans[0]["dur_s"] >= 0.005
    assert isinstance(spans[0]["t_mono"], float)
    assert spans[0]["tid"] and spans[0]["tname"]
    instants = [e for e in events if e["kind"] == "instant"]
    assert instants and instants[0]["name"] == "allreduce_group"
    assert instants[0]["bits"] == 4
    # a second flush appends without duplicating the meta header
    with timeline.span("broadcast", timeline.CAT_COLLECTIVE, seq=8):
        pass
    timeline.flush()
    lines2 = [json.loads(l) for l in open(path)]
    assert sum(1 for l in lines2 if l["kind"] == "meta") == 1


def test_timeline_span_records_on_raise(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    timeline.set_rank(0)
    with pytest.raises(RuntimeError):
        with timeline.span("failing", timeline.CAT_COLLECTIVE, seq=1):
            raise RuntimeError("boom")
    timeline.flush()
    lines = [json.loads(l) for l in open(tmp_path / "spans-rank0.jsonl")]
    spans = [e for e in lines if e.get("kind") == "span"]
    assert spans and spans[0]["name"] == "failing"
    assert spans[0]["ok"] is False


def test_trace_span_emits_timeline(tmp_path, monkeypatch):
    from torch_cgx_tpu.utils.tracing import trace_span

    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    timeline.set_rank(0)
    with trace_span("grad_sync", req="r1", step=3):
        pass
    timeline.flush()
    lines = [json.loads(l) for l in open(tmp_path / "spans-rank0.jsonl")]
    spans = [e for e in lines if e.get("kind") == "span"]
    assert any(
        s["name"] == "grad_sync" and s["cat"] == "span" and s["ok"]
        and s["req"] == "r1" and s["step"] == 3
        for s in spans
    )


def test_shm_channel_emits_keyed_spans(tmp_path, monkeypatch):
    mdir = tmp_path / "m"
    monkeypatch.setenv("CGX_METRICS_DIR", str(mdir))
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("cgx1q/s0>1", np.ones(4096, np.uint8).tobytes())
        reader.take("cgx1q/s0>1")
    finally:
        writer.close()
        reader.close()
    timeline.flush()
    # both channels share the process singleton: rank 0 (first bind) wins
    lines = [json.loads(l) for l in open(mdir / "spans-rank0.jsonl")]
    by_name = {}
    for e in lines:
        if e.get("kind") == "span":
            by_name.setdefault(e["name"], e)
    assert by_name["shm.put"]["key"] == "cgx1q/s0>1"
    assert by_name["shm.put"]["cat"] == "wire"
    assert by_name["shm.put"]["bytes"] >= 4096
    assert by_name["shm.take.wait"]["cat"] == "wait"
    assert by_name["shm.take.copy"]["key"] == "cgx1q/s0>1"


def test_failed_take_wait_still_leaves_span(tmp_path, monkeypatch):
    # The interval that ends in BridgeTimeoutError is exactly what the
    # trace exists to show: the victim's wait must appear, ok=False.
    from torch_cgx_tpu.robustness import BridgeTimeoutError

    mdir = tmp_path / "m"
    monkeypatch.setenv("CGX_METRICS_DIR", str(mdir))
    monkeypatch.setenv("CGX_BRIDGE_TIMEOUT_MS", "200")
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        with pytest.raises(BridgeTimeoutError):
            reader.take("never-posted")
    finally:
        writer.close()
        reader.close()
    timeline.flush()
    lines = [json.loads(l) for l in open(mdir / "spans-rank0.jsonl")]
    waits = [
        e for e in lines
        if e.get("kind") == "span" and e["name"] == "shm.take.wait"
    ]
    assert waits and waits[-1]["ok"] is False
    assert waits[-1]["key"] == "never-posted"
    assert waits[-1]["dur_s"] >= 0.2  # the full timed-out wait interval


# ---------------------------------------------------------------------------
# Merger: offsets, schema, flows, torn files.
# ---------------------------------------------------------------------------


def _synthetic_rank_file(path, rank, events, delta=1000.0):
    with open(path, "w") as f:
        f.write(json.dumps({
            "kind": "meta", "rank": rank, "pid": 100 + rank,
            "t_mono": 0.0, "t_wall": delta, "mono_wall_delta": delta,
        }) + "\n")
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def _span(name, cat, t, dur, **kw):
    return {"kind": "span", "name": name, "cat": cat, "t_mono": t,
            "dur_s": dur, "tid": 1, "tname": "cgx-worker", **kw}


def test_clock_offset_estimator_synthetic_skew():
    cgx_trace = _load_cgx_trace()
    skew = 5.0  # rank 1's perf_counter runs 5 s ahead of rank 0's
    lat = 0.001  # symmetric one-way latency
    per_rank = {0: {"meta": None, "events": []},
                1: {"meta": None, "events": []}}
    for i in range(4):
        t = 10.0 + i
        # rank 0 -> rank 1: published at t (rank0 clock), header arrives
        # lat later (true time), i.e. t + lat + skew on rank 1's clock.
        per_rank[0]["events"].append(
            _span("shm.put", "wire", t, 0.0, key=f"a{i}"))
        per_rank[1]["events"].append(
            _span("shm.take.wait", "wait", t + lat + skew, 0.0, key=f"a{i}"))
        # rank 1 -> rank 0
        per_rank[1]["events"].append(
            _span("shm.put", "wire", t + 0.5 + skew, 0.0, key=f"b{i}"))
        per_rank[0]["events"].append(
            _span("shm.take.wait", "wait", t + 0.5 + lat, 0.0, key=f"b{i}"))
    offsets = cgx_trace.estimate_offsets(per_rank)
    assert offsets[0] == 0.0
    # recovered correction maps rank 1's clock back onto rank 0's:
    # off_1 ~= -skew, within the one-way latency
    assert abs(offsets[1] + skew) <= lat + 1e-9


def test_clock_offset_fallback_uses_meta_delta(tmp_path):
    cgx_trace = _load_cgx_trace()
    # no message pairs at all: fall back to wall-clock deltas
    _synthetic_rank_file(
        tmp_path / "spans-rank0.jsonl", 0,
        [_span("allreduce", "collective", 1.0, 0.1, seq=1)], delta=1000.0)
    _synthetic_rank_file(
        tmp_path / "spans-rank1.jsonl", 1,
        [_span("allreduce", "collective", 2.0, 0.1, seq=1)], delta=997.5)
    per_rank = cgx_trace.load_spans(str(tmp_path))
    offsets = cgx_trace.estimate_offsets(per_rank)
    assert offsets[0] == 0.0
    assert offsets[1] == pytest.approx(-2.5)


def _validate_chrome_trace(trace):
    """Minimal Chrome trace-event schema check (the contract
    ui.perfetto.dev / chrome://tracing load by)."""
    assert isinstance(trace, dict) and isinstance(
        trace["traceEvents"], list
    )
    flow_open = {}
    for ev in trace["traceEvents"]:
        assert isinstance(ev.get("name"), str) and ev["name"]
        ph = ev.get("ph")
        assert ph in ("X", "i", "M", "s", "f"), ph
        assert isinstance(ev.get("pid"), int)
        if ph == "M":
            assert ev["name"] in (
                "process_name", "process_sort_index", "thread_name"
            )
            assert "args" in ev
            continue
        assert isinstance(ev.get("ts"), (int, float)) and ev["ts"] >= 0
        assert isinstance(ev.get("tid"), int)
        if ph == "X":
            assert isinstance(ev.get("dur"), (int, float)) and ev["dur"] > 0
        if ph == "i":
            assert ev.get("s") in ("g", "p", "t")
        if ph == "s":
            flow_open.setdefault(ev["id"], []).append(ev)
        if ph == "f":
            assert ev.get("bp") == "e"
            assert ev["id"] in flow_open, "flow finish without start"
            src = flow_open[ev["id"]][0]
            assert ev["ts"] >= src["ts"], "flow arrow goes back in time"
    return flow_open


def test_cgx_trace_merges_flows_and_attribution(tmp_path):
    # Two synthetic ranks exchanging one SRA round (seq 1) and its
    # shm messages, plus codec/wait spans for the attribution buckets.
    ev0 = [
        _span("allreduce", "collective", 1.0, 0.5, seq=1, ok=True),
        _span("codec.compress", "quantize", 1.05, 0.08, elems=1024),
        _span("shm.put", "wire", 1.15, 0.02, key="cgx1q/s0>1", bytes=512),
        _span("shm.take.wait", "wait", 1.2, 0.1, key="cgx1q/s1>0"),
        _span("shm.take.copy", "wire", 1.3, 0.01, key="cgx1q/s1>0",
              bytes=512),
        {"kind": "instant", "name": "allreduce_group", "cat": "trace",
         "t_mono": 0.9, "tid": 1, "tname": "MainThread", "bits": 4},
    ]
    ev1 = [
        _span("allreduce", "collective", 1.02, 0.5, seq=1, ok=True),
        _span("shm.put", "wire", 1.1, 0.02, key="cgx1q/s1>0", bytes=512),
        _span("shm.take.wait", "wait", 1.18, 0.1, key="cgx1q/s0>1"),
        _span("shm.take.copy", "wire", 1.28, 0.01, key="cgx1q/s0>1",
              bytes=512),
    ]
    _synthetic_rank_file(tmp_path / "spans-rank0.jsonl", 0, ev0)
    _synthetic_rank_file(tmp_path / "spans-rank1.jsonl", 1, ev1)
    proc = subprocess.run(
        [sys.executable, _CGX_TRACE, str(tmp_path), "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["ranks"] == [0, 1]
    assert report["cross_rank_flows"] >= 3  # 1 collective + 2 msg flows
    assert report["per_op"]["allreduce"]["count"] == 2
    att0 = report["per_rank"]["0"]
    assert att0["quantize"] == pytest.approx(0.08)
    assert att0["wire"] == pytest.approx(0.03)
    assert att0["wait"] == pytest.approx(0.1)
    assert att0["other"] == pytest.approx(0.5 - 0.08 - 0.03 - 0.1)
    trace = json.load(open(tmp_path / "trace.json"))
    flow_open = _validate_chrome_trace(trace)
    assert flow_open  # at least one flow pair survived validation
    # the human report renders the attribution table
    proc = subprocess.run(
        [sys.executable, _CGX_TRACE, str(tmp_path)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0
    assert "step-time attribution" in proc.stdout
    assert "queue-wait" in proc.stdout


def test_cgx_trace_tolerates_torn_span_file(tmp_path):
    _synthetic_rank_file(
        tmp_path / "spans-rank0.jsonl", 0,
        [_span("allreduce", "collective", 1.0, 0.1, seq=1)])
    with open(tmp_path / "spans-rank1.jsonl", "w") as f:
        f.write(json.dumps({"kind": "meta", "rank": 1, "pid": 2,
                            "t_mono": 0.0, "t_wall": 0.0,
                            "mono_wall_delta": 0.0}) + "\n")
        f.write(json.dumps(_span("allreduce", "collective", 1.0, 0.1,
                                 seq=1)) + "\n")
        f.write('{"kind": "span", "name": "allr')  # killed mid-write
    proc = subprocess.run(
        [sys.executable, _CGX_TRACE, str(tmp_path), "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["per_op"]["allreduce"]["count"] == 2  # torn line dropped
    _validate_chrome_trace(json.load(open(tmp_path / "trace.json")))


def test_cgx_trace_empty_dir(tmp_path):
    proc = subprocess.run(
        [sys.executable, _CGX_TRACE, str(tmp_path)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 1
    assert "no spans" in proc.stderr


# ---------------------------------------------------------------------------
# Acceptance: 2-rank bridge run -> merged trace with cross-rank flow links
# per collective + attribution table (reuses the faults-harness pattern).
# ---------------------------------------------------------------------------


def _trace_rank_main(rank: int, ws: int, initfile: str, mdir: str, q) -> None:
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, _REPO)
        os.environ["CGX_METRICS_DIR"] = mdir
        os.environ["CGX_COMPRESSION_QUANTIZATION_BITS"] = "4"
        os.environ["CGX_BRIDGE_TIMEOUT_MS"] = "60000"
        # Chaos seasoning (the faults-marker harness): injected take
        # latency must show up as longer wait spans, not break the
        # timeline or the merge.
        os.environ["CGX_FAULTS"] = "delay_take:10ms"
        import torch
        import torch.distributed as dist
        import torch_cgx_tpu.torch_backend  # noqa: F401 — registers "cgx"

        dist.init_process_group(
            "cgx", init_method=f"file://{initfile}", rank=rank,
            world_size=ws,
        )
        t = torch.full((8192,), float(rank + 1))
        for _ in range(2):
            dist.all_reduce(t)
        dist.broadcast(t, src=0)
        dist.barrier()
        dist.destroy_process_group()
        q.put((rank, None))
    except Exception:
        q.put((rank, traceback.format_exc()))


@pytest.mark.torch_bridge
def test_two_rank_chaos_run_merges_into_chrome_trace(tmp_path):
    mdir = str(tmp_path / "metrics")
    initfile = tempfile.mktemp(prefix="cgx_trace_store_")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_trace_rank_main, args=(r, 2, initfile, mdir, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    errs = [q.get(timeout=180) for _ in procs]
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
    if os.path.exists(initfile):
        os.unlink(initfile)
    for rank, err in errs:
        assert err is None, f"rank {rank}: {err}"
    # per-rank span JSONL exists for both ranks
    for r in range(2):
        assert os.path.exists(os.path.join(mdir, f"spans-rank{r}.jsonl")), (
            os.listdir(mdir)
        )
    proc = subprocess.run(
        [sys.executable, _CGX_TRACE, mdir, "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["ranks"] == [0, 1]
    # every collective both ranks ran is cross-rank linked: 2 allreduces
    # + broadcast + barrier => at least 4 collective flow links
    trace = json.load(open(os.path.join(mdir, "trace.json")))
    _validate_chrome_trace(trace)
    coll_flow_starts = [
        ev for ev in trace["traceEvents"]
        if ev.get("ph") == "s" and ev.get("cat") == "flow.collective"
    ]
    linked_ops = {ev["name"].split("#")[0] for ev in coll_flow_starts}
    assert {"allreduce", "broadcast", "barrier"} <= linked_ops, linked_ops
    assert len(coll_flow_starts) >= 4
    assert report["cross_rank_flows"] >= 4
    # the attribution decomposition saw quantized work and waits
    for r in ("0", "1"):
        att = report["per_rank"][r]
        assert att["collective"] > 0
        assert att["quantize"] > 0
        assert att["wire"] > 0
    assert report["per_op"]["allreduce"]["count"] == 4  # 2 ops x 2 ranks
    # human-readable attribution table renders
    proc = subprocess.run(
        [sys.executable, _CGX_TRACE, mdir],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0
    assert "step-time attribution" in proc.stdout


def test_attribution_overlap_fraction_from_synthetic_spans(tmp_path):
    # ISSUE 6 satellite: overlap fraction = share of collective wall time
    # during which recorded trace_span compute was simultaneously live —
    # computed on interval unions so nested spans don't double-count.
    cgx_trace = _load_cgx_trace()
    ev0 = [
        _span("allreduce", "collective", 1.0, 0.5, seq=1),
        _span("allreduce", "collective", 2.0, 0.5, seq=2),
        # compute overlapping [1.25, 1.5) -> 0.25 s
        _span("fwd", "span", 1.25, 0.5),
        # compute overlapping [2.0, 2.1) -> 0.1 s ...
        _span("bwd", "span", 1.9, 0.2),
        # ... with a nested span inside the same window (union: no change)
        _span("bwd.inner", "span", 2.0, 0.05),
    ]
    ev1 = [_span("allreduce", "collective", 1.0, 1.0, seq=1)]
    _synthetic_rank_file(tmp_path / "spans-rank0.jsonl", 0, ev0)
    _synthetic_rank_file(tmp_path / "spans-rank1.jsonl", 1, ev1)
    proc = subprocess.run(
        [sys.executable, _CGX_TRACE, str(tmp_path), "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    # (0.25 + 0.1) s hidden under compute of 1.0 s collective time
    assert report["per_rank"]["0"]["overlap_frac"] == pytest.approx(0.35)
    # no recorded compute at all -> fully serialized communication
    assert report["per_rank"]["1"]["overlap_frac"] == 0.0
    # the human table carries the new column
    proc = subprocess.run(
        [sys.executable, _CGX_TRACE, str(tmp_path)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0
    assert "overlap" in proc.stdout
