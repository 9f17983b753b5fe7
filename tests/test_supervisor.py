"""Recovery supervisor suite (ISSUE 5 tentpole).

Unit layers run single-process: the rendezvous protocol over an
in-memory store (threads as ranks), the snapshot/rollback substrate, the
generation-tagged shm headers with drain-on-epoch-bump, and the retry
rung healing a ``flap`` fault. The chaos soak spawns three real torch
bridge ranks, SIGKILLs one mid-training, and asserts the acceptance
criteria: training completes on the survivor set, the generation bumps
exactly once, the evicted rank is named in the flight-recorder dump, and
the post-rollback replayed steps are bit-identical to a fault-free
survivor-only run.
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
import sys
import tempfile
import threading
import time
import traceback

import numpy as np
import pytest

from torch_cgx_tpu import checkpoint as ckpt
from torch_cgx_tpu import config as cfg
from torch_cgx_tpu.robustness import (
    BridgeTimeoutError,
    EvictedError,
    RecoveryFailedError,
    StaleGenerationError,
    faults,
    rendezvous as rdz,
)
from torch_cgx_tpu.robustness.supervisor import (
    RecoveryPolicy,
    RecoverySupervisor,
    invalidate_trace_caches,
)
from torch_cgx_tpu.utils.logging import metrics

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _fresh():
    faults.reset_injectors()
    metrics.reset()
    cfg.clear_registry()
    yield
    faults.reset_injectors()
    cfg.clear_registry()


class FakeStore:
    """Minimal c10d-Store look-alike (same shape as test_faults')."""

    def __init__(self):
        self._d = {}
        self._lock = threading.Lock()

    def set(self, k, v):
        with self._lock:
            self._d[k] = bytes(v) if not isinstance(v, bytes) else v

    def get(self, k):
        with self._lock:
            if k not in self._d:
                raise KeyError(k)
            return self._d[k]

    def add(self, k, v):
        with self._lock:
            cur = int(self._d.get(k, b"0")) + int(v)
            self._d[k] = str(cur).encode()
            return cur

    def delete_key(self, k):
        with self._lock:
            self._d.pop(k, None)


# ---------------------------------------------------------------------------
# Policy plumbing.
# ---------------------------------------------------------------------------


def test_policy_from_env(monkeypatch):
    monkeypatch.setenv("CGX_RECOVERY_RETRIES", "3")
    monkeypatch.setenv("CGX_RECOVERY_BACKOFF_MS", "250")
    monkeypatch.setenv("CGX_RECOVERY_CORRUPT_THRESHOLD", "5")
    monkeypatch.setenv("CGX_SNAPSHOT_EVERY", "4")
    p = RecoveryPolicy.from_env()
    assert (p.retries, p.backoff_ms, p.corrupt_threshold, p.snapshot_every) \
        == (3, 250.0, 5, 4)


def test_policy_defaults_are_inert(monkeypatch):
    for k in ("CGX_RECOVERY_RETRIES", "CGX_RECOVERY_BACKOFF_MS",
              "CGX_SNAPSHOT_EVERY"):
        monkeypatch.delenv(k, raising=False)
    p = RecoveryPolicy.from_env()
    assert p.retries == 0 and p.snapshot_every == 0


# ---------------------------------------------------------------------------
# Generation rendezvous over the store.
# ---------------------------------------------------------------------------


def _negotiate_concurrently(store, calls):
    """Run several negotiate() calls as threads; returns {rank: outcome}
    where outcome is a Decision or a raised exception."""
    out = {}

    def run(kw):
        try:
            out[kw["me"]] = rdz.negotiate(store, **kw)
        except Exception as e:  # noqa: BLE001 — the outcome IS the assert
            out[kw["me"]] = e

    threads = [
        threading.Thread(target=run, args=(kw,), daemon=True) for kw in calls
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    return out


def test_rendezvous_evicts_the_suspect():
    store = FakeStore()
    base = dict(generation=1, participants=[0, 1, 2], timeout_s=10.0,
                poll_s=0.01)
    out = _negotiate_concurrently(store, [
        dict(base, me=0, suspects=[1]),
        dict(base, me=2, suspects=[1]),
    ])
    for r in (0, 2):
        d = out[r]
        assert isinstance(d, rdz.Decision), d
        assert d.survivors == (0, 2)
        assert d.evicted == (1,)
        assert d.generation == 1
        assert not d.degrade


def test_rendezvous_merges_partial_suspect_views():
    # Only ONE survivor's heartbeat window saw the corpse; the other rank
    # timed out anonymously. The union of votes must still evict.
    store = FakeStore()
    base = dict(generation=1, participants=[0, 1, 2], timeout_s=10.0,
                poll_s=0.01)
    out = _negotiate_concurrently(store, [
        dict(base, me=0, suspects=[1]),
        dict(base, me=2, suspects=[]),
    ])
    assert out[0].survivors == (0, 2)
    assert out[2].survivors == (0, 2)


def test_rendezvous_degrade_vote_propagates():
    store = FakeStore()
    base = dict(generation=2, participants=[0, 1], timeout_s=10.0,
                poll_s=0.01)
    out = _negotiate_concurrently(store, [
        dict(base, me=0, degrade=True),
        dict(base, me=1),
    ])
    assert out[0].degrade and out[1].degrade
    assert out[0].survivors == (0, 1) and out[0].evicted == ()


def test_rendezvous_late_arrival_adopts_decision_and_gets_evicted():
    store = FakeStore()
    base = dict(generation=1, participants=[0, 1, 2], timeout_s=10.0,
                poll_s=0.01)
    out = _negotiate_concurrently(store, [
        dict(base, me=0, suspects=[1]),
        dict(base, me=2, suspects=[1]),
    ])
    assert isinstance(out[0], rdz.Decision)
    # The falsely-suspected rank shows up late and alive: it must adopt
    # the published decision and learn of its own eviction.
    with pytest.raises(EvictedError):
        rdz.negotiate(
            store, generation=1, me=1, participants=[0, 1, 2],
            timeout_s=5.0, poll_s=0.01,
        )


def test_rendezvous_agrees_on_min_snapshot_step():
    # Survivors can drift whole steps apart around a fault (a send-only
    # rank never blocks on the dead peer): the decision must pin the
    # replay step to the MINIMUM of the survivor votes so everyone
    # replays the same steps.
    store = FakeStore()
    base = dict(generation=1, participants=[0, 1, 2], timeout_s=10.0,
                poll_s=0.01)
    out = _negotiate_concurrently(store, [
        dict(base, me=0, suspects=[1], snapshot_step=6),
        dict(base, me=2, suspects=[1], snapshot_step=4),
    ])
    assert out[0].replay_step == 4
    assert out[2].replay_step == 4
    # No survivor holds a snapshot -> no agreed replay point.
    store2 = FakeStore()
    out2 = _negotiate_concurrently(store2, [
        dict(base, me=0, suspects=[1]),
        dict(base, me=2, suspects=[1]),
    ])
    assert out2[0].replay_step is None


def test_rendezvous_times_out_without_quorum():
    store = FakeStore()
    with pytest.raises(RecoveryFailedError, match="did not converge"):
        rdz.negotiate(
            store, generation=1, me=0, participants=[0, 1],
            timeout_s=0.3, poll_s=0.01,
        )
    assert metrics.get("cgx.recovery.rendezvous_failed") == 1


# ---------------------------------------------------------------------------
# Snapshot / rollback substrate.
# ---------------------------------------------------------------------------


def test_memory_snapshot_roundtrip_with_registry():
    cfg.register_layer(0, 0, 128, 4, 64)
    tree = {"w": np.arange(8.0, dtype=np.float32), "step": np.int64(5)}
    snap = ckpt.snapshot_in_memory(tree, 6)
    tree["w"][:] = -1.0  # post-snapshot mutation must not leak in
    cfg.clear_registry()
    assert cfg.registered_layer_sizes(0) is None
    out = ckpt.restore_in_memory(snap)
    np.testing.assert_array_equal(out["w"], np.arange(8.0, dtype=np.float32))
    assert cfg.registered_layer_sizes(0) == [128]
    # the restored tree is a fresh copy: mutate and restore again
    out["w"][:] = 9.0
    out2 = ckpt.restore_in_memory(snap)
    np.testing.assert_array_equal(out2["w"], np.arange(8.0, dtype=np.float32))


class _StubGroup:
    generation = 0
    global_rank = 0
    global_ranks = [0]


def test_supervisor_snapshot_rollback():
    sup = RecoverySupervisor(FakeStore(), _StubGroup(),
                             policy=RecoveryPolicy(snapshot_every=2))
    state = np.ones(4, np.float32)
    sup.take_snapshot(3, state)
    state *= 7.0
    step, back = sup.rollback()
    assert step == 3
    np.testing.assert_array_equal(back, np.ones(4, np.float32))
    assert metrics.get("cgx.recovery.snapshots") == 1
    assert metrics.get("cgx.recovery.rollbacks") == 1


def test_supervisor_snapshot_ring_and_agreed_step_rollback():
    # The ring retains snapshot_keep points so the rendezvous can pin
    # the replay step BEHIND this rank's newest snapshot; an agreed step
    # outside the ring returns None (run_steps then dies loudly).
    sup = RecoverySupervisor(
        FakeStore(), _StubGroup(),
        policy=RecoveryPolicy(snapshot_every=1, snapshot_keep=3),
    )
    for s in range(6):
        sup.take_snapshot(s, np.full(2, float(s), np.float32))
    assert sup.last_snapshot.step == 5
    step, back = sup.rollback(4)  # behind newest, inside the ring
    assert step == 4
    np.testing.assert_array_equal(back, np.full(2, 4.0, np.float32))
    assert sup.rollback(1) is None  # aged out (keep=3 -> steps 3,4,5)
    step, _ = sup.rollback()  # no agreed step: newest
    assert step == 5


def test_invalidate_trace_caches_bumps_registry_version():
    v0 = cfg.registry_version()
    invalidate_trace_caches()
    assert cfg.registry_version() == v0 + 1


def test_invalidate_trace_caches_empties_serving_programs():
    """The cascade reaches the serving plane's compiled-program LRU and
    says so in ``cgx.serve.program_invalidations``."""
    from torch_cgx_tpu.serving import scheduler as sched_mod
    from torch_cgx_tpu.utils.logging import metrics

    sched_mod._PROGRAM_CACHE[("sentinel",)] = object()
    before = metrics.get("cgx.serve.program_invalidations")
    invalidate_trace_caches()
    assert len(sched_mod._PROGRAM_CACHE) == 0
    assert metrics.get("cgx.serve.program_invalidations") == before + 1


def test_invalidate_trace_caches_resets_qerr_sampling():
    # ISSUE 6 satellite: the flightrec qerr subsample cadence
    # (allreduce._QERR_SEEN) must restart with the registry-version bump —
    # post-recovery programs are a NEW qerr stream, and a stale per-layer
    # counter would skip its first observations on the dead generation's
    # phase.
    from torch_cgx_tpu.parallel import allreduce as ar

    ar._QERR_SEEN.clear()
    ar._QERR_SEEN.update({"layer0/w": 17, "layer1/b": 3})
    invalidate_trace_caches()
    assert ar._QERR_SEEN == {}


# ---------------------------------------------------------------------------
# Generation-tagged shm headers + drain-on-epoch-bump.
# ---------------------------------------------------------------------------


def _channel_pair(store, tmp_path):
    from torch_cgx_tpu.torch_backend.shm import ShmChannel

    writer = ShmChannel(store, rank=0, directory=str(tmp_path))
    reader = ShmChannel(store, rank=1, directory=str(tmp_path))
    return writer, reader


def test_epoch0_header_format_unchanged(tmp_path):
    # Bit-identity guard: with recovery never engaged the wire header
    # keeps the legacy 5-field format, byte for byte.
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("k", b"x" * 256)
        hdr = bytes(store.get("cgxshm/k")).decode()
        assert len(hdr.rsplit(":", 5)) == 5  # only 4 separators
        assert not hdr.rsplit(":", 1)[1].startswith("e")
        out = reader.take("k")
        assert out.tobytes() == b"x" * 256
    finally:
        writer.close()
        reader.close()


def test_stale_epoch_message_discarded(tmp_path):
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("old", b"a" * 128)  # framed at epoch 0
        reader.bump_epoch(1)
        with pytest.raises(StaleGenerationError, match="generation 0"):
            reader.take("old")
        assert metrics.get("cgx.recovery.stale_discards") == 1
        # post-bump traffic flows: writer joins the new generation
        writer.bump_epoch(1)
        writer.put("new", b"b" * 128)
        hdr = bytes(store.get("cgxshm/new")).decode()
        assert hdr.rsplit(":", 1)[1] == "e1"
        assert reader.take("new").tobytes() == b"b" * 128
    finally:
        writer.close()
        reader.close()


def test_epoch_bump_abandons_pending_regions(tmp_path):
    store = FakeStore()
    from torch_cgx_tpu.torch_backend.shm import ShmChannel

    writer = ShmChannel(store, rank=0, directory=str(tmp_path))
    try:
        for i in range(4):
            writer.put(f"k{i}", b"z" * 1024)  # never taken, never acked
        assert len(writer._arena._pending) == 4
        writer.bump_epoch(3)
        assert writer._arena._pending == []  # drained
        assert metrics.get("cgx.recovery.epoch_bumps") == 1
    finally:
        writer.close()


def test_flap_heals_via_retry_rung(tmp_path, monkeypatch):
    # Rung 1 acceptance: a transiently-dropped header (published late) is
    # absorbed by the re-armed bounded wait — no escalation, data intact.
    monkeypatch.setenv("CGX_FAULTS", "flap:400ms@step=0")
    monkeypatch.setenv("CGX_BRIDGE_TIMEOUT_MS", "150")
    monkeypatch.setenv("CGX_RECOVERY_RETRIES", "4")
    monkeypatch.setenv("CGX_RECOVERY_BACKOFF_MS", "30")
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        payload = np.arange(2048, dtype=np.uint8).tobytes()
        writer.put("k", payload)
        assert metrics.get("cgx.faults.flap") == 1
        out = reader.take("k")  # first wait expires; a retry lands it
        assert out.tobytes() == payload
        assert metrics.get("cgx.recovery.retries") >= 1
        assert metrics.get("cgx.bridge_timeout") == 0
    finally:
        writer.close()
        reader.close()


def test_flap_without_retries_still_times_out(tmp_path, monkeypatch):
    # With the retry rung unarmed the old semantics hold exactly.
    monkeypatch.setenv("CGX_FAULTS", "flap:600ms@step=0")
    monkeypatch.setenv("CGX_BRIDGE_TIMEOUT_MS", "150")
    monkeypatch.delenv("CGX_RECOVERY_RETRIES", raising=False)
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("k", b"q" * 512)
        with pytest.raises(BridgeTimeoutError):
            reader.take("k")
    finally:
        writer.close()
        reader.close()


def test_slow_rank_injector_delay():
    inj = faults.FaultInjector(
        faults.parse_faults("slow_rank:0@120ms"), seed=0, rank=0
    )
    t0 = time.monotonic()
    inj.delay("slow_rank")
    assert time.monotonic() - t0 >= 0.12
    other = faults.FaultInjector(
        faults.parse_faults("slow_rank:1@120ms"), seed=0, rank=0
    )
    t0 = time.monotonic()
    other.delay("slow_rank")  # rank gate: not this rank
    assert time.monotonic() - t0 < 0.1


# ---------------------------------------------------------------------------
# JAX-side rollback hook (make_train_step snapshot_every).
# ---------------------------------------------------------------------------


def test_make_train_step_snapshot_hook(monkeypatch):
    """``make_train_step(snapshot_every=2)``: the wrapper host-copies the
    step INPUTS every 2nd step; ``step.rollback()`` re-installs them and
    replaying from there is bit-identical to the uninterrupted run."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from torch_cgx_tpu.parallel import make_train_step, replicate, shard_batch

    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "64")
    mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("dp",))
    rng = np.random.default_rng(0)
    Wt = rng.normal(size=(16, 4)).astype(np.float32)
    batches = []
    for _ in range(4):
        x = rng.normal(size=(32, 16)).astype(np.float32)
        batches.append((x, x @ Wt))

    def loss_fn(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2)

    opt = optax.adam(1e-2)
    step = make_train_step(
        loss_fn, opt, mesh, donate=False, snapshot_every=2
    )
    params = replicate({"w": jnp.zeros((16, 4), jnp.float32)}, mesh)
    opt_state = replicate(opt.init({"w": jnp.zeros((16, 4), jnp.float32)}), mesh)
    p, s = params, opt_state
    for i, (x, y) in enumerate(batches):
        b = shard_batch((x, y), mesh)
        p, s, _ = step(p, s, b, jnp.int32(i))
    final = np.asarray(p["w"])
    snap = step.last_snapshot()
    assert snap is not None and snap.step == 2
    assert metrics.get("cgx.recovery.snapshots") == 2  # steps 0 and 2
    # rollback and replay steps 2..3: bit-identical to the straight run
    rb_step, (p2, s2) = step.rollback()
    assert rb_step == 2
    for i in (2, 3):
        b = shard_batch(batches[i], mesh)
        p2, s2, _ = step(p2, s2, b, jnp.int32(i))
    np.testing.assert_array_equal(final, np.asarray(p2["w"]))


def test_make_train_step_no_snapshots_by_default(monkeypatch):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from torch_cgx_tpu.parallel import make_train_step, replicate, shard_batch

    monkeypatch.delenv("CGX_SNAPSHOT_EVERY", raising=False)
    mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("dp",))

    def loss_fn(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2)

    opt = optax.adam(1e-2)
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    x = np.ones((32, 16), np.float32)
    y = np.ones((32, 4), np.float32)
    params = replicate({"w": jnp.zeros((16, 4), jnp.float32)}, mesh)
    opt_state = replicate(opt.init({"w": jnp.zeros((16, 4), jnp.float32)}), mesh)
    step(params, opt_state, shard_batch((x, y), mesh), jnp.int32(0))
    assert step.last_snapshot() is None
    assert step.rollback() is None
    assert metrics.get("cgx.recovery.snapshots") == 0


# ---------------------------------------------------------------------------
# Chaos soak: kill a rank mid-training, survive, replay bit-identically.
# ---------------------------------------------------------------------------

_SOAK_WS = 3
_SOAK_STEPS = 12
# Kill OFF the snapshot cadence (snapshots at 0,2,4,... — kill at 5) so
# the rollback has real distance: step 4 completed at ws=3, is rolled
# back over, and replays at ws=2.
_SOAK_KILL_STEP = 5
_SOAK_NUMEL = 8192


def _soak_grad(global_rank: int, step: int) -> np.ndarray:
    """Deterministic per-(GLOBAL rank, step) gradient — the survivor-only
    control run regenerates the identical contributions."""
    rng = np.random.default_rng(1000 * (global_rank + 1) + step)
    return rng.normal(size=_SOAK_NUMEL).astype(np.float32)


def _soak_step_fn(states):
    import torch

    def step_fn(group, state, idx):
        states[idx] = state.copy()
        t = torch.from_numpy(_soak_grad(group.global_rank, idx).copy())
        group.allreduce([t]).wait()
        return state - 0.01 * t.numpy()

    return step_fn


def _soak_main(rank: int, ws: int, initfile: str, mdir: str, q) -> None:
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, _REPO)
        os.environ["CGX_BRIDGE_TIMEOUT_MS"] = "2500"
        os.environ["CGX_RECOVERY_RETRIES"] = "1"
        os.environ["CGX_RECOVERY_BACKOFF_MS"] = "50"
        os.environ["CGX_SNAPSHOT_EVERY"] = "2"
        os.environ["CGX_METRICS_DIR"] = mdir
        os.environ["CGX_COMPRESSION_QUANTIZATION_BITS"] = "4"
        os.environ["CGX_FAULTS"] = f"kill_rank:1@step={_SOAK_KILL_STEP}"
        import datetime

        import torch.distributed as dist

        from torch_cgx_tpu.torch_backend.backend import ProcessGroupCGX
        from torch_cgx_tpu.robustness.supervisor import RecoverySupervisor
        from torch_cgx_tpu.robustness import faults as faults_mod
        from torch_cgx_tpu.utils.logging import metrics as m

        store = dist.FileStore(initfile, ws)
        pg = ProcessGroupCGX(
            store, rank, ws, datetime.timedelta(seconds=60)
        )
        sup = RecoverySupervisor(store, pg)
        states: dict = {}
        final = sup.run_steps(
            np.zeros(_SOAK_NUMEL, np.float32), _SOAK_STEPS,
            _soak_step_fn(states),
        )
        problems = []
        if sup.generation != 1:
            problems.append(f"generation {sup.generation} != 1")
        if sup.survivors != [0, 2]:
            problems.append(f"survivors {sup.survivors} != [0, 2]")
        rb = sup.last_rollback_step
        if rb is None or rb > _SOAK_KILL_STEP:
            problems.append(f"bad rollback step {rb}")
        if m.get("cgx.recovery.evictions") != 1:
            problems.append(
                f"evictions counter {m.get('cgx.recovery.evictions')}"
            )
        if m.get("cgx.recovery.replayed_steps") < 1:
            problems.append("no replayed steps counted")
        # -- control: fault-free survivor-only run from the rollback
        # point, on a FRESH generation-namespaced group. Bit-identity of
        # the final parameters proves the replayed steps matched.
        os.environ.pop("CGX_FAULTS", None)
        faults_mod.reset_injectors()
        survivors = sup.survivors
        pg2 = ProcessGroupCGX(
            store, survivors.index(pg.global_rank), len(survivors),
            datetime.timedelta(seconds=60),
            generation=500, global_ranks=survivors,
        )
        control = states[rb].copy()
        fn = _soak_step_fn({})
        for idx in range(rb, _SOAK_STEPS):
            control = fn(pg2, control, idx)
        bit_identical = bool(np.array_equal(final, control))
        if not bit_identical:
            problems.append(
                "replayed run differs from fault-free survivor-only run "
                f"(max abs diff {np.abs(final - control).max()})"
            )
        pg.shutdown()
        pg2.shutdown()
        q.put((rank, "; ".join(problems) or None))
    except Exception:
        q.put((rank, traceback.format_exc()))


@pytest.mark.torch_bridge
def test_chaos_soak_kill_rank_recovers_and_replays(tmp_path):
    """ISSUE 5 chaos acceptance: a 3-rank run loses rank 1 to SIGKILL
    mid-training and completes on the survivors — generation bumped
    exactly once, evicted rank named in the flight-recorder dump,
    post-rollback replay bit-identical to a fault-free survivor-only
    run, ``cgx.recovery.*`` counters emitted, and the report CLI renders
    the recovery section."""
    mdir = str(tmp_path / "metrics")
    initfile = tempfile.mktemp(prefix="cgx_sup_store_")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_soak_main, args=(r, _SOAK_WS, initfile, mdir, q)
        )
        for r in range(_SOAK_WS)
    ]
    for p in procs:
        p.start()
    results = {}
    for _ in range(2):  # rank 1 dies by design and never reports
        rank, err = q.get(timeout=240)
        results[rank] = err
    for p in procs:
        p.join(timeout=60)
        if p.is_alive():
            p.terminate()
    assert sorted(results) == [0, 2], results
    for rank, err in sorted(results.items()):
        assert err is None, f"rank {rank}: {err}"
    from torch_cgx_tpu.robustness.faults import KILL_EXIT_CODE

    assert procs[1].exitcode == KILL_EXIT_CODE, procs[1].exitcode
    if os.path.exists(initfile):
        os.unlink(initfile)
    # -- flight-recorder acceptance: the eviction left an audit trail --
    path = os.path.join(mdir, "flightrec-rank0.jsonl")
    assert os.path.exists(path), (
        os.listdir(mdir) if os.path.isdir(mdir) else "no metrics dir"
    )
    events = [json.loads(line) for line in open(path)]
    rec = [e for e in events if e.get("kind") == "recovery"]
    assert any(
        e.get("phase") == "evicted_peers" and e.get("evicted") == [1]
        for e in rec
    ), rec
    assert any(e.get("phase") == "reconfigure" for e in rec)
    assert any(e.get("phase") == "rollback" for e in rec)
    # -- report CLI renders the recovery section --
    import subprocess as sp

    proc = sp.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"),
         mdir, "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    js = json.loads(proc.stdout)
    assert js.get("recovery"), js.keys()
    assert js["recovery"]["generation"] >= 1
    assert 1 in js["recovery"]["evicted"]
    # counters fold per-rank maxima then SUM across the two survivors
    assert js["recovery"]["counters"].get("cgx.recovery.evictions", 0) >= 1
    text = sp.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"), mdir],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert text.returncode == 0
    assert "== recovery" in text.stdout


# ---------------------------------------------------------------------------
# slow_rank absorbed by the retry rung through the real bridge.
# ---------------------------------------------------------------------------


def _slow_main(rank: int, ws: int, initfile: str, q) -> None:
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, _REPO)
        os.environ["CGX_BRIDGE_TIMEOUT_MS"] = "700"
        os.environ["CGX_RECOVERY_RETRIES"] = "3"
        os.environ["CGX_RECOVERY_BACKOFF_MS"] = "50"
        os.environ["CGX_COMPRESSION_QUANTIZATION_BITS"] = "4"
        # rank 1 sleeps 1.2 s at its first collective entry: longer than
        # one bounded wait, far shorter than the retry budget.
        os.environ["CGX_FAULTS"] = "slow_rank:1@1200ms@step=0"
        import datetime

        import torch
        import torch.distributed as dist

        from torch_cgx_tpu.torch_backend.backend import ProcessGroupCGX
        from torch_cgx_tpu.utils.logging import metrics as m

        store = dist.FileStore(initfile, ws)
        pg = ProcessGroupCGX(store, rank, ws, datetime.timedelta(seconds=30))
        t = torch.full((4096,), float(rank + 1))
        pg.allreduce([t]).wait()
        expect = sum(float(r + 1) for r in range(ws))
        ok = bool(torch.allclose(t, torch.full((4096,), expect), atol=0.5))
        retries = m.get("cgx.recovery.retries")
        pg.shutdown()
        q.put((rank, None if ok else "wrong reduction", retries))
    except Exception:
        q.put((rank, traceback.format_exc(), 0))


@pytest.mark.torch_bridge
def test_slow_rank_absorbed_by_retry_rung(tmp_path):
    """A straggler (alive heartbeat, 1.2 s stall vs a 0.7 s wait bound)
    must NOT be evicted: the fast rank's expired wait re-arms and the
    collective completes with the correct reduction."""
    initfile = tempfile.mktemp(prefix="cgx_slow_store_")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_slow_main, args=(r, 2, initfile, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    results = {}
    for _ in range(2):
        rank, err, retries = q.get(timeout=120)
        results[rank] = (err, retries)
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
    if os.path.exists(initfile):
        os.unlink(initfile)
    for rank, (err, _r) in sorted(results.items()):
        assert err is None, f"rank {rank}: {err}"
    # the fast rank's wait expired at least once and was re-armed
    assert results[0][1] >= 1, results
