"""Observability subsystem tests (ISSUE 2 tentpole + satellites).

Covers the typed instrument registry (backward-compat with the seed's
flat-counter API), the flight recorder (ring bounds, dump-on-failure
through the real shm channel under CGX_FAULTS injection), the periodic
exporter and store-riding cross-rank aggregation, the SRA/Ring counter
instrumentation on the JAX allreduce paths, the env-gated quantization
error stats, and the ``tools/cgx_report.py`` renderer — including the
acceptance chaos run: ``kill_rank`` + ``CGX_METRICS_DIR`` must leave a
dump naming the failed collective and the suspected dead rank, and the
report CLI must render it.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from torch_cgx_tpu.observability import exporter as obs_exporter
from torch_cgx_tpu.observability import flightrec, instruments, timeline
from torch_cgx_tpu.robustness import (
    BridgeTimeoutError,
    WireCorruptionError,
    faults,
)
from torch_cgx_tpu.utils.logging import metrics

from test_faults import FakeStore, _channel_pair

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _fresh():
    faults.reset_injectors()
    metrics.reset()
    flightrec.reset()
    timeline.reset()
    obs_exporter.stop_exporter()
    yield
    faults.reset_injectors()
    metrics.reset()
    flightrec.reset()
    timeline.reset()
    obs_exporter.stop_exporter()


# ---------------------------------------------------------------------------
# Instruments: typed registry behind the seed's flat API.
# ---------------------------------------------------------------------------


def test_registry_backward_compat():
    metrics.add("cgx.c")
    metrics.add("cgx.c", 2.0)
    metrics.set("cgx.g", 7.5)
    assert metrics.get("cgx.c") == 3.0
    assert metrics.get("cgx.g") == 7.5
    assert metrics.get("cgx.never") == 0.0
    snap = metrics.snapshot("cgx.")
    assert snap["cgx.c"] == 3.0 and snap["cgx.g"] == 7.5
    metrics.reset()
    assert metrics.get("cgx.c") == 0.0 and metrics.snapshot() == {}


def test_histogram_quantiles_and_flatten():
    for v in range(1, 101):
        metrics.observe("cgx.h", float(v))
    st = metrics.histogram_stats("cgx.h")
    assert st["count"] == 100 and st["sum"] == 5050.0
    assert st["min"] == 1.0 and st["max"] == 100.0
    assert 45.0 <= st["p50"] <= 56.0
    assert 85.0 <= st["p90"] <= 96.0
    snap = metrics.snapshot("cgx.h")
    assert snap["cgx.h.count"] == 100 and "cgx.h.p99" in snap
    # get() on a histogram reports its observation count
    assert metrics.get("cgx.h") == 100.0


def test_histogram_reservoir_bounded():
    h = instruments.Histogram()
    for v in range(10 * instruments.RESERVOIR):
        h.observe(float(v))
    assert h.count == 10 * instruments.RESERVOIR  # exact over all time
    assert len(h._recent) == instruments.RESERVOIR  # bounded memory
    # quantiles describe the recent window, not ancient history
    assert h.quantile(0.5) > 8 * instruments.RESERVOIR


def test_typed_snapshot_separates_instruments():
    metrics.add("cgx.c", 4.0)
    metrics.set("cgx.g", 1.0)
    metrics.observe("cgx.h", 0.25)
    t = metrics.snapshot_typed()
    assert t["counters"] == {"cgx.c": 4.0}
    assert t["gauges"] == {"cgx.g": 1.0}
    assert t["histograms"]["cgx.h"]["count"] == 1


# ---------------------------------------------------------------------------
# Satellite: trace_span must record the sample when the body raises.
# ---------------------------------------------------------------------------


def test_trace_span_records_duration_on_raise():
    from torch_cgx_tpu.utils.tracing import trace_span

    with pytest.raises(RuntimeError, match="boom"):
        with trace_span("failing_op"):
            time.sleep(0.01)
            raise RuntimeError("boom")
    # ONE histogram holds count and seconds; the errors counter is kept.
    stats = metrics.histogram_stats("cgx.failing_op_s")
    assert stats["count"] == 1 and stats["sum"] >= 0.01
    assert metrics.get("span.failing_op.errors") == 1.0
    # clean spans don't count errors
    with trace_span("clean_op"):
        pass
    assert metrics.get("span.clean_op.errors") == 0.0
    assert metrics.histogram_stats("cgx.clean_op_s")["count"] == 1
    # ...and nothing repeats the histogram's .sum/.count as counters.
    assert not [k for k in metrics.snapshot("span.") if "errors" not in k]


# ---------------------------------------------------------------------------
# Flight recorder core.
# ---------------------------------------------------------------------------


def test_flightrec_ring_bounded_and_ordered():
    rec = flightrec.FlightRecorder(rank=0, capacity=8)
    for i in range(20):
        rec.record("tick", i=i)
    evs = rec.events()
    assert len(evs) == 8
    assert [e["i"] for e in evs] == list(range(12, 20))
    assert evs[-1]["seq"] == 20  # seq counts all-time, ring holds the tail


def test_flightrec_events_carry_both_clocks():
    # ISSUE 3 satellite: t_mono (perf_counter) rides alongside wall ts so
    # the cross-rank merger can align ranks without trusting wall clocks.
    rec = flightrec.FlightRecorder(rank=0)
    t0 = time.perf_counter()
    rec.record("collective", op="allreduce", seq=1)
    t1 = time.perf_counter()
    ev = rec.events()[-1]
    assert t0 <= ev["t_mono"] <= t1 + 1e-6
    assert abs(ev["ts"] - time.time()) < 60.0  # wall clock, roughly now


def test_flightrec_dump_header_has_t_mono_and_report_prints_it(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    flightrec.set_rank(0)
    flightrec.record(
        "failure", error="BridgeTimeoutError", message="timed out",
        op="allreduce", key="k",
    )
    path = flightrec.dump("unit")
    header = json.loads(open(path).readline())
    assert "t_mono" in header and "ts" in header
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    # the failure line shows both clocks
    assert "ts=" in proc.stdout and "t_mono=" in proc.stdout


def test_flightrec_dump_without_dir_is_noop(tmp_path):
    rec = flightrec.FlightRecorder(rank=0)
    rec.record("tick")
    assert rec.dump("test") is None  # CGX_METRICS_DIR unset
    # explicit path works regardless
    p = rec.dump("test", path=str(tmp_path / "explicit.jsonl"))
    assert p and os.path.exists(p)


def test_flightrec_dump_format(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    metrics.add("cgx.something", 3.0)
    flightrec.set_rank(5)
    flightrec.record("collective", op="allreduce", seq=1)
    path = flightrec.dump("unit")
    assert path.endswith("flightrec-rank5.jsonl")
    lines = [json.loads(l) for l in open(path)]
    header, events = lines[0], lines[1:]
    assert header["kind"] == "dump" and header["reason"] == "unit"
    assert header["rank"] == 5 and header["events"] == 1
    assert header["metrics"]["cgx.something"] == 3.0
    assert events[0]["kind"] == "collective" and events[0]["op"] == "allreduce"
    assert metrics.get("cgx.flightrec.dumps") == 1.0


# ---------------------------------------------------------------------------
# Dump-on-failure through the real shm channel (CGX_FAULTS injection).
# ---------------------------------------------------------------------------


def _dump_files(d):
    return sorted(glob.glob(os.path.join(str(d), "flightrec-rank*.jsonl")))


def test_corrupt_wire_leaves_flight_dump(tmp_path, monkeypatch):
    mdir = tmp_path / "m"
    monkeypatch.setenv("CGX_FAULTS", "corrupt_wire:step=0")
    monkeypatch.setenv("CGX_METRICS_DIR", str(mdir))
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("payload-key", np.ones(4096, np.uint8).tobytes())
        with pytest.raises(WireCorruptionError):
            reader.take("payload-key")
    finally:
        writer.close()
        reader.close()
    files = _dump_files(mdir)
    assert files, "corruption produced no flight-recorder dump"
    lines = [json.loads(l) for l in open(files[-1])]
    header = lines[0]
    assert header["kind"] == "dump" and header["reason"] == "WireCorruptionError"
    assert header["metrics"]["cgx.wire_corrupt"] == 1.0
    failures = [e for e in lines[1:] if e["kind"] == "failure"]
    assert failures, "no failure event in the dump"
    f = failures[-1]
    assert f["error"] == "WireCorruptionError"
    assert f["op"] == "shm.take" and f["key"] == "payload-key"
    # the injected fault that caused it is in the ring too
    assert any(
        e["kind"] == "fault" and e["mode"] == "corrupt_wire"
        for e in lines[1:]
    )


def test_take_timeout_leaves_flight_dump(tmp_path, monkeypatch):
    mdir = tmp_path / "m"
    monkeypatch.setenv("CGX_BRIDGE_TIMEOUT_MS", "200")
    monkeypatch.setenv("CGX_METRICS_DIR", str(mdir))
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        with pytest.raises(BridgeTimeoutError):
            reader.take("never-posted")
    finally:
        writer.close()
        reader.close()
    files = _dump_files(mdir)
    assert files
    lines = [json.loads(l) for l in open(files[-1])]
    failures = [e for e in lines[1:] if e["kind"] == "failure"]
    assert failures and failures[-1]["error"] == "BridgeTimeoutError"
    assert "never-posted" in failures[-1]["key"]


def test_shm_put_take_timing_instrumented(tmp_path):
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("k", np.ones(100_000, np.uint8).tobytes())
        reader.take("k")
    finally:
        writer.close()
        reader.close()
    assert metrics.histogram_stats("cgx.shm.put_s")["count"] == 1
    assert metrics.histogram_stats("cgx.shm.take_wait_s")["count"] == 1
    assert metrics.histogram_stats("cgx.shm.take_copy_s")["count"] == 1
    assert metrics.get("cgx.shm.put_bytes") >= 100_000
    kinds = [e["kind"] for e in flightrec.get_recorder().events()]
    assert "shm_put" in kinds and "shm_take" in kinds


# ---------------------------------------------------------------------------
# Exporter + cross-rank aggregation.
# ---------------------------------------------------------------------------


def test_exporter_periodic_flush(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    monkeypatch.setenv("CGX_METRICS_FLUSH_S", "0.05")
    metrics.add("cgx.steps", 3.0)
    metrics.observe("cgx.lat", 0.01)
    ex = obs_exporter.start_exporter(rank=2)
    assert ex is not None
    assert obs_exporter.start_exporter(rank=2) is ex  # idempotent
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if os.path.exists(ex.path) and len(open(ex.path).readlines()) >= 2:
            break
        time.sleep(0.02)
    obs_exporter.stop_exporter()
    lines = [json.loads(l) for l in open(ex.path)]
    assert len(lines) >= 2
    rec = lines[-1]
    assert rec["rank"] == 2
    assert rec["counters"]["cgx.steps"] == 3.0
    assert rec["histograms"]["cgx.lat"]["count"] == 1


def test_exporter_inert_without_dir():
    assert obs_exporter.start_exporter(rank=0) is None


_SIGTERM_CHILD = r"""
import os, signal, sys, time
sys.path.insert(0, {repo!r})
os.environ["CGX_METRICS_DIR"] = {mdir!r}
os.environ["CGX_METRICS_FLUSH_S"] = "3600"  # no periodic flush
from torch_cgx_tpu.observability import exporter, timeline
from torch_cgx_tpu.utils.logging import metrics

metrics.add("cgx.steps", 7.0)
timeline.set_rank(0)
with timeline.span("allreduce", timeline.CAT_COLLECTIVE, seq=1):
    pass
exporter.start_exporter(rank=0)
print("READY", flush=True)
time.sleep(60)
"""


def test_exporter_sigterm_flush_leaves_snapshot(tmp_path):
    # ISSUE 3 satellite: a rank torn down between periodic flushes
    # (SIGTERM from a launcher) still leaves its last metrics snapshot
    # AND its buffered timeline spans on disk.
    import signal

    mdir = str(tmp_path / "m")
    child = subprocess.Popen(
        [sys.executable, "-c",
         _SIGTERM_CHILD.format(repo=_REPO, mdir=mdir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=_REPO,
    )
    try:
        line = child.stdout.readline()
        assert "READY" in line, child.stderr.read()
        child.send_signal(signal.SIGTERM)
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
    assert child.returncode != 0  # SIGTERM still terminates the process
    mpath = os.path.join(mdir, "metrics-rank0.jsonl")
    assert os.path.exists(mpath), os.listdir(mdir)
    lines = [json.loads(l) for l in open(mpath)]
    assert lines and lines[-1]["counters"]["cgx.steps"] == 7.0
    spath = os.path.join(mdir, "spans-rank0.jsonl")
    assert os.path.exists(spath), os.listdir(mdir)
    spans = [json.loads(l) for l in open(spath)]
    assert any(
        e.get("kind") == "span" and e["name"] == "allreduce" for e in spans
    )


def test_aggregate_over_store_merges_and_names_missing(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    store = FakeStore()
    # rank 1 publishes its snapshot (no report on non-leaders)
    metrics.add("cgx.wire_bytes", 100.0)
    metrics.observe("cgx.lat", 0.5)
    assert (
        obs_exporter.aggregate_over_store(store, 1, 3, timeout_s=0.2) is None
    )
    # rank 0 (here: same process, fresh registry) merges; rank 2 never
    # publishes -> named missing, not a hang
    metrics.reset()
    metrics.add("cgx.wire_bytes", 50.0)
    metrics.observe("cgx.lat", 0.1)
    t0 = time.monotonic()
    report = obs_exporter.aggregate_over_store(store, 0, 3, timeout_s=0.3)
    assert time.monotonic() - t0 < 5.0
    assert report["missing_ranks"] == [2]
    assert report["ranks_reporting"] == [0, 1]
    assert report["counters"]["cgx.wire_bytes"] == 150.0
    h = report["histograms"]["cgx.lat"]
    assert h["count"] == 2 and h["min"] == 0.1 and h["max"] == 0.5
    # leader also wrote the cluster report file
    lines = [json.loads(l) for l in open(tmp_path / "cluster-report.jsonl")]
    assert lines[-1]["counters"]["cgx.wire_bytes"] == 150.0


# ---------------------------------------------------------------------------
# JAX-path counters: SRA and Ring allreduce instrumentation (satellite).
# ---------------------------------------------------------------------------


def _run_allreduce_tree():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from torch_cgx_tpu.parallel.allreduce import allreduce_tree
    from torch_cgx_tpu.utils.compat import shard_map

    mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("dp",))
    g = jnp.asarray(
        np.random.default_rng(0).normal(size=(16, 32)), jnp.float32
    )
    fn = jax.jit(
        shard_map(
            lambda x: allreduce_tree({"w": x}, mesh=mesh)["w"],
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )
    )
    jax.block_until_ready(fn(g))
    return g


def test_sra_allreduce_counters_and_events(monkeypatch):
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_INNER_REDUCTION_TYPE", "SRA")
    g = _run_allreduce_tree()
    assert metrics.get("cgx.trace.allreduce.compressed_elems") == g.size
    groups = [
        e for e in flightrec.get_recorder().events()
        if e["kind"] == "allreduce_group"
    ]
    assert groups and groups[-1]["algo"] == "SRA"
    assert groups[-1]["bits"] == 4 and groups[-1]["elems"] == g.size
    assert groups[-1]["wire_ratio"] > 1.0  # 4-bit wire beats fp32


def test_ring_allreduce_counters_and_events(monkeypatch):
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_INNER_REDUCTION_TYPE", "RING")
    g = _run_allreduce_tree()
    assert metrics.get("cgx.trace.allreduce.compressed_elems") == g.size
    groups = [
        e for e in flightrec.get_recorder().events()
        if e["kind"] == "allreduce_group"
    ]
    assert groups and groups[-1]["algo"] == "RING"


def test_qerr_stats_env_gated(monkeypatch):
    import jax

    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_QERR_STATS", "1")
    _run_allreduce_tree()
    jax.effects_barrier()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if metrics.snapshot("cgx.qerr."):
            break
        time.sleep(0.05)
    qerr = metrics.snapshot("cgx.qerr.")
    assert qerr, "CGX_QERR_STATS=1 produced no qerr observations"
    # 4-bit max-min error on gaussian data: small but nonzero
    means = [v for k, v in qerr.items() if k.endswith(".mean")]
    assert means and all(0.0 < m < 0.5 for m in means)
    qerr_events = [
        e for e in flightrec.get_recorder().events() if e["kind"] == "qerr"
    ]
    assert qerr_events and qerr_events[-1]["rel_l2"] > 0.0


# ---------------------------------------------------------------------------
# The acceptance chaos run (kill_rank + CGX_METRICS_DIR -> dump naming the
# failed collective and suspected dead rank, rendered by cgx_report) lives
# in tests/test_faults.py::test_kill_rank_produces_named_timeout — it
# rides the existing 2-rank kill run instead of spawning a second one
# (tier-1 wall-clock is budget-bound).
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Report tool edge cases.
# ---------------------------------------------------------------------------


def test_cgx_report_empty_dir(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0
    assert "no events recorded" in proc.stdout


def test_cgx_report_rejects_missing_dir(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"),
         str(tmp_path / "nope")],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 2


def test_cgx_report_tolerates_torn_tail(tmp_path):
    # A killed writer can leave a torn last line; the reader must not care.
    p = tmp_path / "flightrec-rank0.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"kind": "dump", "reason": "x", "rank": 0,
                            "events": 1, "metrics": {}}) + "\n")
        f.write(json.dumps({"kind": "collective", "op": "allreduce",
                            "seq": 1, "seconds": 0.01, "ts": 0,
                            "ok": True}) + "\n")
        f.write('{"kind": "fail')  # torn mid-write
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0
    assert "allreduce" in proc.stdout
