"""Memory observability plane tests (ISSUE 18).

Unit suite: the forecaster's least-squares math against a numpy
polyfit oracle, KV-pool gauge truth under churn/fork/exhaustion (the
published ``cgx.serve.pool_free``/``pool_dedup_pages`` gauges vs an
independent shadow model of every alloc/fork/free), arena
fragmentation vs a brute-force byte-map free-extent scan, the
sliding-window leak detector (strict monotonicity fires, a sawtooth
does not), the ``mem_pressure`` lead window, snapshot flush → the
``cgx_mem`` CLI round-trip, the leader-side cluster merge, the
planner's memory envelope + staging budget, health-event plumbing,
reset-reachability from the supervisor cascade, and inertness with
``CGX_MEMLEDGER`` unset.

Chaos acceptance: a ``leak_page`` fault run — every last-reference
drop silently loses its page — where the detector names
``serve.kv_pool`` strictly before the pool exhausts and the forecaster
raises ``mem_pressure`` at least one lead window before the wall. The
bit-identity half of the acceptance (env unset ⇒ staged programs /
store keys / wire bytes unchanged) is carried by the test_grad_sync
suite, which runs with all CGX_* env cleared.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import tempfile

import numpy as np
import pytest

from torch_cgx_tpu.observability import health, memledger, watch
from torch_cgx_tpu.robustness import faults
from torch_cgx_tpu.serving import kv_cache as kv_mod
from torch_cgx_tpu.utils.logging import metrics

from test_faults import FakeStore

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


@pytest.fixture(autouse=True)
def _fresh():
    metrics.reset()
    faults.reset_injectors()
    yield
    memledger.stop()
    health.stop()
    faults.reset_injectors()
    metrics.reset()


def _install_ledger(monkeypatch, flush_s=1.0, window=3, rank=0):
    """A deterministic ledger: installed as the process singleton (so the
    note_alloc/note_release shims route to it) but never started — tests
    drive sample(now=...) by hand."""
    led = memledger.MemLedger(rank=rank, flush_s=flush_s, leak_window=window)
    monkeypatch.setattr(memledger, "_ledger", led)
    return led


# ---------------------------------------------------------------------------
# Forecaster math vs numpy oracle.
# ---------------------------------------------------------------------------


def test_trend_tte_matches_polyfit_oracle():
    from collections import deque

    rng = np.random.default_rng(3)
    ts = np.cumsum(rng.uniform(0.5, 1.5, size=12))
    free = 1000.0 - 37.0 * ts + rng.normal(0, 0.5, size=12)
    hist = deque(zip(ts.tolist(), free.tolist()))
    tte = memledger._trend_tte_s(hist)
    slope, _ = np.polyfit(ts - ts[0], free, 1)
    assert slope < 0
    assert tte == pytest.approx(free[-1] / -slope, rel=1e-6)


def test_trend_tte_none_on_flat_rising_or_short():
    from collections import deque

    assert memledger._trend_tte_s(deque([(0, 5.0), (1, 4.0)])) is None
    flat = deque([(float(i), 10.0) for i in range(6)])
    assert memledger._trend_tte_s(flat) is None
    rising = deque([(float(i), 10.0 + i) for i in range(6)])
    assert memledger._trend_tte_s(rising) is None
    # already exhausted with a downward trend: 0, not a division blow-up
    drained = deque([(0.0, 2.0), (1.0, 1.0), (2.0, 0.0)])
    assert memledger._trend_tte_s(drained) == 0.0


# ---------------------------------------------------------------------------
# KV-pool gauge truth: churn / fork / exhaustion vs a shadow model.
# ---------------------------------------------------------------------------


def _shadow_truth(held):
    """(free, dedup) from an independent seq -> pages shadow model."""
    counts: dict = {}
    for pages in held.values():
        for pid in pages:
            counts[pid] = counts.get(pid, 0) + 1
    dedup = sum(c - 1 for c in counts.values() if c > 1)
    return counts, dedup


def test_kv_pool_gauges_truthful_under_churn_and_fork():
    cache = kv_mod.PagedKvCache(max_pages=16, page_tokens=4)
    cache.publish_pool_gauges()  # gauges valid from birth, not first alloc
    rng = np.random.default_rng(0)
    held: dict = {}
    for i in range(300):
        r = rng.random()
        sid = f"s{rng.integers(0, 8)}"
        if r < 0.45:
            pid = cache.alloc(sid)
            if pid is not None:
                held.setdefault(sid, []).append(pid)
        elif r < 0.75 and sid in held:
            cache.free_seq(sid)
            held.pop(sid)
        elif sid in held:
            dst = f"f{i}"
            cache.fork(sid, dst)
            held[dst] = list(held[sid])
        counts, dedup = _shadow_truth(held)
        free_truth = cache.max_pages - len(counts)
        # The gauges ARE the pool's truth after every mutator — alloc,
        # free AND fork (the dedup-changing mutator the old
        # pool_free-only refresh missed).
        assert metrics.get("cgx.serve.pool_free") == free_truth
        assert metrics.get("cgx.serve.pool_dedup_pages") == dedup
        st = cache.pool_stats()
        assert st["free_pages"] == free_truth
        assert st["dedup_pages"] == dedup
        assert st["leaked_pages"] == 0


def test_kv_pool_exhaustion_gauge_and_ledger_tick_refresh():
    # Schedulers an earlier file of this worker left in reference cycles
    # keep their caches live until a collection; this one must be alone.
    gc.collect()
    cache = kv_mod.PagedKvCache(max_pages=2, page_tokens=4)
    assert cache.alloc("a") is not None
    assert cache.alloc("a") is not None
    assert cache.alloc("a") is None  # backpressure, not an error
    assert metrics.get("cgx.serve.pool_free") == 0
    # Between decode steps nothing mutates — the ledger's sampler still
    # refreshes the gauges from live truth (satellite 2).
    metrics.set("cgx.serve.pool_free", 99.0)  # a stale scrape value
    rows = memledger._kv_rows()
    (row,) = [r for r in rows if r["pool"].startswith("serve.kv_pool")]
    assert metrics.get("cgx.serve.pool_free") == 0
    assert row["free_units"] == 0.0
    assert row["capacity_units"] == 2.0
    cache.free_seq("a")
    assert metrics.get("cgx.serve.pool_free") == 2


# ---------------------------------------------------------------------------
# Arena fragmentation vs a brute-force byte-map scan.
# ---------------------------------------------------------------------------


def _brute_force_extents(arena):
    """Free extents per generation from a byte occupancy map over the
    pending regions — independent of the head/tail arithmetic
    mem_stats() uses."""
    with arena._lock:
        caps = {g: gf.capacity for g, gf in arena._gens.items()}
        spans = [(r.gen, r.off, r.size) for r in arena._pending]
    extents = []
    for g, cap in caps.items():
        occ = np.zeros(cap, dtype=bool)
        for gen, off, size in spans:
            if gen == g:
                occ[off:off + size] = True
        run = 0
        for byte_used in occ:
            if byte_used:
                if run:
                    extents.append(run)
                run = 0
            else:
                run += 1
        if run:
            extents.append(run)
    return extents


def test_arena_frag_matches_brute_force_scan():
    from torch_cgx_tpu.torch_backend.shm import ShmArena

    acks: dict = {}
    arena = ShmArena(
        tempfile.gettempdir(),
        f"cgxmemtest-{os.getpid()}",
        poll_ack=lambda k: acks.get(k, 0),
        drop_keys=lambda ks: None,
        min_capacity=1 << 12,  # 4 KB ring
    )
    rng = np.random.default_rng(7)
    try:
        seen_frag = set()
        for i in range(60):
            if rng.random() < 0.6:
                size = int(rng.integers(256, 1280))
                arena.write(bytes(size), f"m{i}/ack", 1)
            else:
                pend = [k for k in (f"m{j}/ack" for j in range(i))
                        if k not in acks]
                if pend:
                    acks[rng.choice(pend)] = 1
            st = arena.mem_stats()
            brute = _brute_force_extents(arena)
            total, largest = sum(brute), max(brute) if brute else 0
            assert st["free_bytes"] == total
            assert st["largest_free_bytes"] == largest
            want = (1.0 - largest / total) if total > 0 else 0.0
            assert st["frag"] == pytest.approx(want, abs=1e-4)
            seen_frag.add(round(st["frag"], 2))
        # The schedule actually exercised fragmentation, not just one
        # trivial all-free/all-full state.
        assert len(seen_frag) >= 2 and max(seen_frag) > 0.0
    finally:
        arena.close()


def test_arena_region_table_names_hoarder_oldest_first():
    from torch_cgx_tpu.torch_backend.shm import ShmArena

    arena = ShmArena(
        tempfile.gettempdir(),
        f"cgxregtest-{os.getpid()}",
        poll_ack=lambda k: 0,
        drop_keys=lambda ks: None,
        min_capacity=1 << 12,
    )
    try:
        for i in range(3):
            arena.write(bytes(512), f"hoard{i}/ack", 2)
        table = arena.region_table(limit=8)
        assert [r["owner"] for r in table[:3]] == [
            "hoard0/ack", "hoard1/ack", "hoard2/ack",
        ]
        assert all(r["size"] == 512 and r["readers"] == 2 for r in table[:3])
        assert all(r["age_s"] >= 0.0 for r in table)
    finally:
        arena.close()


# ---------------------------------------------------------------------------
# Leak detector: strict monotonicity over the full window.
# ---------------------------------------------------------------------------


def test_leak_detector_fires_on_strict_growth_only(monkeypatch):
    led = _install_ledger(monkeypatch, flush_s=1.0, window=3)
    # Sawtooth: alloc bursts that settle never fire.
    for t in range(6):
        memledger.note_alloc("app.buf")
        if t % 2:
            memledger.note_release("app.buf")
            memledger.note_release("app.buf")
            memledger.note_alloc("app.buf")
        snap = led.sample(now=float(t))
        assert not [f for f in snap["findings"] if f["kind"] == "mem_leak"]
    led.reset("test")
    # Strict growth: one extra outstanding per sample names the owner
    # exactly when the window fills, not earlier.
    hits = []
    for t in range(4):
        memledger.note_alloc("serve.kv_pool")
        snap = led.sample(now=100.0 + t)
        hits.append([
            f["owner"] for f in snap["findings"] if f["kind"] == "mem_leak"
        ])
    assert hits[0] == [] and hits[1] == []
    assert hits[2] == ["serve.kv_pool"]
    assert led.leak_suspects() == ["serve.kv_pool"]
    assert metrics.get("cgx.mem.leak_suspects") == 1
    assert metrics.get("cgx.mem.events.mem_leak") >= 1


def test_forecaster_pressure_precedes_exhaustion_by_lead(monkeypatch):
    led = _install_ledger(monkeypatch, flush_s=1.0, window=3)
    lead_s = 3 * 1.0
    free = [100.0]

    def draining_pool():
        return [{
            "pool": "test.pool", "kind": "test",
            "used_bytes": int((100.0 - free[0]) * 1024),
            "capacity_bytes": 100 * 1024,
            "free_units": free[0], "capacity_units": 100.0,
            "frag": None, "detail": {},
        }]

    led.register_sampler(draining_pool)
    first_pressure = None
    first_empty = None
    for t in range(101):
        snap = led.sample(now=float(t))
        hit = [
            f for f in snap["findings"]
            if f["kind"] == "mem_pressure" and f["owner"] == "test.pool"
        ]
        if hit and first_pressure is None:
            first_pressure = t
            assert hit[0]["value"] <= lead_s
            # The published forecast gauge carries the same tte.
            assert metrics.get(
                "cgx.mem.pool_tte_s.test.pool"
            ) == pytest.approx(hit[0]["value"])
        if free[0] <= 0 and first_empty is None:
            first_empty = t
        free[0] -= 1.0
    assert first_pressure is not None and first_empty is not None
    # The whole point: the warning lands >= one lead window before the wall.
    assert first_empty - first_pressure >= lead_s
    assert metrics.get("cgx.mem.events.mem_pressure") >= 1


def test_peak_tracks_high_water_and_bench_hook(monkeypatch):
    led = _install_ledger(monkeypatch)
    # Exact-total oracle: silence the builtin samplers so ambient jax
    # arrays left live by earlier test files can't pad the byte count.
    monkeypatch.setattr(memledger, "_BUILTIN_SAMPLERS", ())
    big = [1 << 24]

    def pool():
        return [{
            "pool": "test.big", "kind": "test", "used_bytes": big[0],
            "capacity_bytes": 0, "free_units": 0.0, "capacity_units": 0.0,
            "frag": None, "detail": {},
        }]

    led.register_sampler(pool)
    led.sample(now=0.0)
    big[0] = 1 << 20  # shrink: peak must hold the high-water mark
    led.sample(now=1.0)
    assert led.peak_mb() == pytest.approx(16.0)
    assert metrics.get("cgx.mem.peak_mb") == pytest.approx(16.0)
    assert metrics.get("cgx.mem.total_mb") == pytest.approx(1.0)
    # The bench harness's module-level hook sees the same number.
    assert memledger.peak_mb() == pytest.approx(16.0)


# ---------------------------------------------------------------------------
# Chaos acceptance: leak_page named before exhaustion.
# ---------------------------------------------------------------------------


def test_leak_page_chaos_detector_names_pool_before_exhaustion(monkeypatch):
    monkeypatch.setenv("CGX_FAULTS", "leak_page:1.0")
    faults.reset_injectors()
    led = _install_ledger(monkeypatch, flush_s=1.0, window=3)
    cache = kv_mod.PagedKvCache(max_pages=12, page_tokens=4)
    first_leak = None
    first_pressure = None
    exhausted_at = None
    for t in range(13):
        pid = cache.alloc(f"s{t}")
        if pid is None:
            exhausted_at = t
            break
        # Last reference drops -> the injected fault swallows the page.
        assert cache.free_seq(f"s{t}") == 0
        snap = led.sample(now=float(t))
        kinds = {f["kind"]: f for f in snap["findings"]}
        if "mem_leak" in kinds and first_leak is None:
            first_leak = t
            assert kinds["mem_leak"]["owner"] == "serve.kv_pool"
        if "mem_pressure" in kinds and first_pressure is None:
            assert kinds["mem_pressure"]["owner"].startswith("serve.kv_pool")
            first_pressure = t
    assert exhausted_at is not None  # the fault really drains the pool
    assert cache.pool_stats()["leaked_pages"] == 12
    # The detector names the owning site strictly before the wall...
    assert first_leak is not None and first_leak < exhausted_at
    # ...and the forecaster leads the wall by at least the lead window.
    assert first_pressure is not None
    assert exhausted_at - first_pressure >= 3
    assert metrics.get("cgx.faults.leak_page") == 12
    # invalidate() rebuilds the free list: chaos-leaked pages come back
    # and the release settles the ledger delta.
    cache.invalidate("chaos cleanup")
    assert cache.pool_stats()["leaked_pages"] == 0
    assert cache.free_pages == 12
    site = led.sample(now=99.0)["sites"]["serve.kv_pool"]
    assert site["outstanding"] == 0


# ---------------------------------------------------------------------------
# Health plumbing, reset cascade, inertness.
# ---------------------------------------------------------------------------


def test_note_mem_event_shape_and_kind_validation():
    eng = health.HealthEngine(0)
    ev = eng.note_mem("mem_leak", 5.0, 3.0, owner="serve.kv_pool", grew_by=5)
    assert ev is not None and ev.kind == "mem_leak"
    detail = dict(ev.detail)
    assert detail["owner"] == "serve.kv_pool" and detail["grew_by"] == 5
    assert ev.threshold == 3.0
    with pytest.raises(ValueError):
        eng.note_mem("straggler", 1.0, 1.0)
    assert "mem_leak" in health.EVENT_KINDS
    assert "mem_pressure" in health.EVENT_KINDS


def test_supervisor_cascade_resets_ledger(monkeypatch):
    from torch_cgx_tpu.robustness import supervisor

    led = _install_ledger(monkeypatch)
    memledger.note_alloc("shm.arena", nbytes=4096)
    led.sample(now=0.0)
    assert led.sample(now=1.0)["sites"]
    supervisor.invalidate_trace_caches()
    snap = led.sample(now=2.0)
    assert snap["sites"] == {}  # pre-recovery history would fabricate leaks
    assert metrics.get("cgx.mem.resets") >= 1


def test_inert_when_unset(monkeypatch):
    monkeypatch.delenv("CGX_MEMLEDGER", raising=False)
    assert memledger.maybe_start(0) is None
    assert not memledger.active()
    assert memledger.peak_mb() is None
    # The hot-path hooks are a single global load, never an error.
    memledger.note_alloc("serve.kv_pool")
    memledger.note_release("serve.kv_pool")
    memledger.reset_ledger("noop")
    assert metrics.get("cgx.mem.samples") == 0


def test_maybe_start_first_wins_rank_rebind(monkeypatch):
    monkeypatch.setenv("CGX_MEMLEDGER", "1")
    led = memledger.maybe_start(None)
    assert led is not None and led.rank == 0
    assert memledger.maybe_start(3) is led
    assert led.rank == 3
    assert memledger.maybe_start(5) is led
    assert led.rank == 3  # first nonzero bind wins


# ---------------------------------------------------------------------------
# Snapshot flush -> CLI / report / cluster merge round-trips.
# ---------------------------------------------------------------------------


def test_flush_snapshot_and_cgx_mem_cli_roundtrip(
    monkeypatch, tmp_path, capsys
):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    led = _install_ledger(monkeypatch, window=3)
    cache = kv_mod.PagedKvCache(max_pages=4, page_tokens=4)
    cache.alloc("s")
    for _ in range(3):
        memledger.note_alloc("serve.kv_pool")  # force a leak finding
        led.flush()
    path = tmp_path / "mem-rank0.jsonl"
    assert path.exists()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(recs) == 3
    pools = {r["pool"] for r in recs[-1]["pools"]}
    assert any(p.startswith("serve.kv_pool") for p in pools)
    from tools import cgx_mem

    assert cgx_mem.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "owner tree" in out and "serve.kv_pool" in out
    assert "leak suspects" in out
    assert cgx_mem.main([str(tmp_path), "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["ranks"] == [0]
    assert "serve.kv_pool" in summary["leak_suspects"]
    # cgx_report folds the same files into its == memory == section.
    from tools import cgx_report

    mem = cgx_report._memory_summary(str(tmp_path))
    assert mem is not None and mem["ranks"] == [0]
    assert "serve.kv_pool" in mem["leak_suspects"]
    assert cgx_mem.main(["/nonexistent-dir"]) == 2


def test_cluster_merge_over_store(monkeypatch, tmp_path):
    monkeypatch.setenv("CGX_METRICS_DIR", str(tmp_path))
    store = FakeStore()
    led = _install_ledger(monkeypatch, rank=1)
    memledger.note_alloc("shm.arena", nbytes=1 << 20)
    led.sample(now=0.0)
    assert watch.aggregate_mem_over_store(store, 1, 2) is None  # follower
    led.rebind_rank(0)
    view = watch.aggregate_mem_over_store(store, 0, 2)
    assert view is not None
    assert view["ranks_reporting"] == [0, 1]
    assert view["missing_ranks"] == []
    assert view["world_size"] == 2
    lines = (tmp_path / "cluster-mem.jsonl").read_text().splitlines()
    assert json.loads(lines[-1])["ranks_reporting"] == [0, 1]
    # A rank that never published is named, not waited on forever.
    view3 = watch.aggregate_mem_over_store(store, 0, 3, round_id=1,
                                           timeout_s=0.2)
    assert view3["missing_ranks"] == [1, 2]


def test_merge_noop_without_ledger():
    assert memledger.get_ledger() is None
    assert watch.aggregate_mem_over_store(FakeStore(), 0, 1) is None


# ---------------------------------------------------------------------------
# Planner: memory envelope + staging budget.
# ---------------------------------------------------------------------------


def test_memory_envelope_scales_with_depth():
    from torch_cgx_tpu.parallel import planner

    cm = planner.CostModel()
    e1 = cm.memory_envelope(1 << 20, ws=8, bits=4, bucket=512, chunks=1)
    e4 = cm.memory_envelope(1 << 20, ws=8, bits=4, bucket=512, chunks=4)
    assert e1["fusion_bytes"] == e4["fusion_bytes"] == 4.0 * (1 << 20)
    # Deeper pipeline -> smaller frames -> smaller staging footprint.
    assert e4["frame_bytes"] == pytest.approx(e1["frame_bytes"] / 4)
    assert e4["staging_bytes"] < e1["staging_bytes"]
    assert e4["total_bytes"] < e1["total_bytes"]
    # Degenerate shapes cost nothing rather than dividing by zero.
    z = cm.memory_envelope(0, ws=8, bits=4, bucket=512)
    assert z["total_bytes"] == 0.0


def test_staging_budget_gates_plan_and_keys(monkeypatch):
    from torch_cgx_tpu.parallel import planner

    monkeypatch.delenv("CGX_MEMLEDGER", raising=False)
    assert planner._staging_budget() is None
    key_off = planner.cache_key_component()
    monkeypatch.setenv("CGX_MEMLEDGER", "1")
    monkeypatch.setenv("CGX_SHM_MAX_MB", "64")
    assert planner._staging_budget() == 64 << 20
    # The budget is part of the planner's trace-key contribution: a
    # toggle retraces instead of serving a stale plan.
    assert planner.cache_key_component() != key_off
    # A budget below every candidate's staging forces the min-staging
    # (deepest) fallback rather than an infeasible plan.
    from torch_cgx_tpu.config import CompressionConfig

    cm = planner.CostModel()
    n = 1 << 22
    cc = CompressionConfig(bits=4, bucket_size=512)
    c_open, t_open = planner._best_chunks(cm, n, 8, 4, cc, "staged")
    c_tight, t_tight = planner._best_chunks(
        cm, n, 8, 4, cc, "staged", staging_budget=1
    )
    deepest = max(planner._slice_candidates(n, 8, cc))
    assert c_tight == deepest  # smallest frames, soonest reclaim
    assert cm.memory_envelope(n, 8, 4, 512, chunks=c_tight)[
        "staging_bytes"
    ] <= cm.memory_envelope(n, 8, 4, 512, chunks=c_open)["staging_bytes"]
    # A budget that fits everything changes nothing.
    c_loose, t_loose = planner._best_chunks(
        cm, n, 8, 4, cc, "staged", staging_budget=1 << 40
    )
    assert (c_loose, t_loose) == (c_open, t_open)
