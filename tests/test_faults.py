"""Chaos suite: drive every CGX_FAULTS injector mode through the hardened
data plane and assert the matching defense fires (ISSUE 1 tentpole).

Single-process tests exercise :class:`ShmChannel` directly over an
in-memory store; the kill test spawns real torch ranks (the
test_torch_backend custom-launch pattern — a pool would die with the
killed rank). The JAX tests drive ``make_train_step``'s non-finite guard
on the virtual 8-device mesh.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import sys
import tempfile
import threading
import time
import traceback

import numpy as np
import pytest

from torch_cgx_tpu.robustness import (
    BridgeTimeoutError,
    FaultSpec,
    WireCorruptionError,
    faults,
    heartbeat,
    parse_faults,
)
from torch_cgx_tpu.utils.logging import metrics

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset_injectors()
    metrics.reset()
    yield
    faults.reset_injectors()


# ---------------------------------------------------------------------------
# Grammar + determinism.
# ---------------------------------------------------------------------------


def test_fault_grammar_full_spec():
    specs = parse_faults(
        "drop_put:0.1,delay_take:50ms,corrupt_wire:step=7,"
        "kill_rank:2@step=5,nan_grad:step=3,stall_ack:1.0"
    )
    by_mode = {s.mode: s for s in specs}
    assert by_mode["drop_put"].prob == pytest.approx(0.1)
    assert by_mode["delay_take"].delay_ms == pytest.approx(50.0)
    assert by_mode["corrupt_wire"].step == 7
    assert by_mode["kill_rank"] == FaultSpec(
        mode="kill_rank", rank=2, step=5
    )
    assert by_mode["nan_grad"].step == 3
    assert by_mode["stall_ack"].prob == 1.0
    # durations in seconds, explicit rank=
    (s,) = parse_faults("delay_take:2s@rank=1")
    assert s.delay_ms == 2000.0 and s.rank == 1


def test_fault_grammar_slow_rank_and_flap():
    # ISSUE 5 satellite: the retry rung's rehearsal faults — a straggler
    # (slow_rank) and a transient drop-then-recover (flap).
    specs = parse_faults("slow_rank:1@800ms,flap:120ms@step=2")
    by_mode = {s.mode: s for s in specs}
    assert by_mode["slow_rank"] == FaultSpec(
        mode="slow_rank", rank=1, delay_ms=800.0
    )
    assert by_mode["flap"].delay_ms == pytest.approx(120.0)
    assert by_mode["flap"].step == 2
    # bare-int rank shorthand works for slow_rank like kill_rank
    (s,) = parse_faults("slow_rank:3@250ms")
    assert s.rank == 3 and s.delay_ms == pytest.approx(250.0)
    # both modes ARE their delay: omitting the duration would inject
    # nothing, so the parser fails loud instead of going vacuously green
    with pytest.raises(ValueError):
        parse_faults("slow_rank:3")
    with pytest.raises(ValueError):
        parse_faults("flap:step=2")


def test_flap_delay_helper_fires_on_its_step():
    inj = faults.FaultInjector(
        parse_faults("flap:50ms@step=1"), seed=0, rank=0
    )
    assert inj.flap_delay() is None  # event 0: gated off
    assert inj.flap_delay() == pytest.approx(0.05)  # event 1 fires
    assert inj.flap_delay() is None  # event 2: gated off again
    assert metrics.get("cgx.faults.flap") == 1


def test_fault_grammar_leak_page():
    # ISSUE 18 satellite: the memory plane's chaos fault — a KV page
    # whose last reference drops never reaches the free list. Prob and
    # step gates both parse; no extra fields are required (the fault IS
    # the suppressed release).
    (s,) = parse_faults("leak_page:1.0")
    assert s.mode == "leak_page" and s.prob == 1.0
    (s,) = parse_faults("leak_page:step=4")
    assert s.step == 4
    inj = faults.FaultInjector(parse_faults("leak_page:1.0"), seed=0, rank=0)
    assert inj.fire("leak_page")
    assert metrics.get("cgx.faults.leak_page") == 1


def test_fault_grammar_rejects_junk():
    with pytest.raises(ValueError):
        parse_faults("explode_randomly:1.0")  # unknown mode
    with pytest.raises(ValueError):
        parse_faults("drop_put:bogus")  # unparseable token
    with pytest.raises(ValueError):
        parse_faults("drop_put:1.5")  # probability out of range


def test_injector_seeded_determinism():
    a = faults.FaultInjector(parse_faults("drop_put:0.5"), seed=7, rank=0)
    b = faults.FaultInjector(parse_faults("drop_put:0.5"), seed=7, rank=0)
    c = faults.FaultInjector(parse_faults("drop_put:0.5"), seed=8, rank=0)
    seq_a = [a.fire("drop_put") for _ in range(64)]
    seq_b = [b.fire("drop_put") for _ in range(64)]
    seq_c = [c.fire("drop_put") for _ in range(64)]
    assert seq_a == seq_b  # same seed replays exactly
    assert seq_a != seq_c  # different seed is a different schedule
    assert any(seq_a) and not all(seq_a)


def test_injector_step_and_rank_gates():
    inj = faults.FaultInjector(
        parse_faults("corrupt_wire:step=2"), seed=0, rank=0
    )
    assert [inj.fire("corrupt_wire") for _ in range(4)] == [
        False, False, True, False,
    ]
    other = faults.FaultInjector(
        parse_faults("kill_rank:1@step=0"), seed=0, rank=0
    )
    assert not other.fire("kill_rank")  # rank gate: not this rank


# ---------------------------------------------------------------------------
# ShmChannel over an in-memory store.
# ---------------------------------------------------------------------------


class FakeStore:
    """Minimal c10d-Store look-alike: set/get/add/delete_key, get raises
    when the key is missing (like TCPStore on timeout)."""

    def __init__(self):
        self._d = {}
        self._lock = threading.Lock()

    def set(self, k, v):
        with self._lock:
            self._d[k] = bytes(v)

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


def _channel_pair(store, tmp_path):
    from torch_cgx_tpu.torch_backend.shm import ShmChannel

    writer = ShmChannel(store, rank=0, directory=str(tmp_path))
    reader = ShmChannel(store, rank=1, directory=str(tmp_path))
    return writer, reader


def test_checksum_roundtrip_clean(tmp_path, monkeypatch):
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        payload = np.arange(100_000, dtype=np.uint8).tobytes()
        writer.put("k", payload)
        out = reader.take("k")
        assert out.tobytes() == payload
        assert metrics.get("cgx.wire_corrupt") == 0
        # the header really carries a crc (5th field, non-negative)
        hdr = bytes(store.get("cgxshm/k")).decode()
        assert int(hdr.rsplit(":", 4)[4]) >= 0
    finally:
        writer.close()
        reader.close()


def test_corrupt_wire_raises_after_one_retry(tmp_path, monkeypatch):
    # Acceptance (b): corrupted payload -> WireCorruptionError after one
    # re-read, cgx.wire_corrupt incremented.
    monkeypatch.setenv("CGX_FAULTS", "corrupt_wire:step=0")
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("k", np.ones(4096, np.uint8).tobytes())
        with pytest.raises(WireCorruptionError, match="checksum mismatch"):
            reader.take("k")
        assert metrics.get("cgx.wire_corrupt") == 1
        assert metrics.get("cgx.faults.corrupt_wire") == 1
        assert metrics.get("cgx.wire_reread_ok") == 0
    finally:
        writer.close()
        reader.close()


def test_transient_corruption_heals_on_reread(tmp_path, monkeypatch):
    # A stale cached mapping (not arena damage) must be cured by the one
    # fresh re-read, counted under cgx.wire_reread_ok, and return clean
    # bytes.
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        payload = np.arange(4096, dtype=np.uint8).tobytes()
        writer.put("k", payload)
        real_read = reader._read
        flipped = {"done": False}

        def flaky_read(path, off, size, refresh=False):
            out = real_read(path, off, size, refresh=refresh)
            if not flipped["done"]:
                flipped["done"] = True
                out = out.copy()
                out[0] ^= 0xFF
            return out

        monkeypatch.setattr(reader, "_read", flaky_read)
        out = reader.take("k")
        assert out.tobytes() == payload
        assert metrics.get("cgx.wire_corrupt") == 1
        assert metrics.get("cgx.wire_reread_ok") == 1
    finally:
        writer.close()
        reader.close()


def test_take_timeout_bounded_and_named(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_BRIDGE_TIMEOUT_MS", "300")
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        t0 = time.monotonic()
        with pytest.raises(BridgeTimeoutError, match="never-posted") as ei:
            reader.take("never-posted")
        assert time.monotonic() - t0 < 5.0  # bounded, not a hang
        assert ei.value.key == "cgxshm/never-posted"
        assert metrics.get("cgx.bridge_timeout") == 1
    finally:
        writer.close()
        reader.close()


def test_drop_put_surfaces_as_reader_timeout(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_FAULTS", "drop_put:1.0")
    monkeypatch.setenv("CGX_BRIDGE_TIMEOUT_MS", "300")
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("k", b"x" * 1024)  # payload written, header dropped
        assert metrics.get("cgx.faults.drop_put") == 1
        with pytest.raises(BridgeTimeoutError):
            reader.take("k")
    finally:
        writer.close()
        reader.close()


def test_delay_take_injects_latency(tmp_path, monkeypatch):
    monkeypatch.setenv("CGX_FAULTS", "delay_take:80ms")
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("k", b"y" * 64)
        t0 = time.monotonic()
        out = reader.take("k")
        assert time.monotonic() - t0 >= 0.08
        assert out.tobytes() == b"y" * 64
        assert metrics.get("cgx.faults.delay_take") == 1
    finally:
        writer.close()
        reader.close()


def test_arena_pressure_bounded_not_unbounded_growth(tmp_path, monkeypatch):
    # A dead/stalled reader (stall_ack) + the CGX_SHM_MAX_MB cap: puts back
    # off, then fail with the stalled ack key named — instead of growing
    # tmpfs forever.
    monkeypatch.setenv("CGX_FAULTS", "stall_ack:1.0")
    monkeypatch.setenv("CGX_SHM_MAX_MB", "1")
    monkeypatch.setenv("CGX_BRIDGE_TIMEOUT_MS", "300")
    store = FakeStore()
    from torch_cgx_tpu.torch_backend.shm import ShmChannel

    writer = ShmChannel(store, rank=0, directory=str(tmp_path))
    try:
        chunk = b"z" * (512 * 1024)
        t0 = time.monotonic()
        with pytest.raises(BridgeTimeoutError, match="un-acked") as ei:
            for i in range(64):
                writer.put(f"k{i}", chunk)
        assert time.monotonic() - t0 < 10.0
        assert ei.value.key.endswith("/ack")
        assert metrics.get("cgx.arena_pressure_waits") > 0
    finally:
        writer.close()


def test_peer_death_reaped_arena_names_sender(tmp_path):
    # Satellite: a reaped writer arena (the crash-path hygiene deleted the
    # gen file) surfaces as the existing "sending rank died" RuntimeError —
    # immediately, not after a hang.
    store = FakeStore()
    writer, reader = _channel_pair(store, tmp_path)
    try:
        writer.put("k", b"q" * 4096)
        for gen in list(writer._arena._gens):
            os.unlink(writer._arena.path_of(gen))
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="sending rank died"):
            reader.take("k")
        assert time.monotonic() - t0 < 5.0
    finally:
        writer.close()
        reader.close()


# ---------------------------------------------------------------------------
# Heartbeat liveness.
# ---------------------------------------------------------------------------


def test_heartbeat_live_then_stale(tmp_path):
    me = os.getpid()
    hb = heartbeat.Heartbeat(str(tmp_path), me, interval_s=0.05).start()
    try:
        assert heartbeat.suspect_dead_pids(str(tmp_path), [me]) == []
        # a pid that never heartbeat is suspect
        assert heartbeat.suspect_dead_pids(str(tmp_path), [me, 999999]) == [
            999999
        ]
    finally:
        hb.stop(unlink=False)
    # age the file artificially: stale -> suspected
    old = time.time() - 60
    os.utime(hb.path, (old, old))
    assert heartbeat.suspect_dead_pids(str(tmp_path), [me]) == [me]


def test_heartbeat_process_singleton(tmp_path):
    a = heartbeat.ensure_heartbeat(str(tmp_path))
    b = heartbeat.ensure_heartbeat(str(tmp_path))
    assert a is b  # one thread/file per (process, directory)
    assert os.path.exists(a.path)
    assert heartbeat.suspect_dead_pids(str(tmp_path), [os.getpid()]) == []


# ---------------------------------------------------------------------------
# kill_rank through the real torch bridge (acceptance a).
# ---------------------------------------------------------------------------


def _kill_rank_main(rank: int, ws: int, initfile: str, mdir: str, q) -> None:
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, _REPO)
        os.environ["CGX_BRIDGE_TIMEOUT_MS"] = "6000"
        os.environ["CGX_FAULTS"] = "kill_rank:1@step=0"
        os.environ["CGX_METRICS_DIR"] = mdir  # acceptance: black-box dump
        os.environ["CGX_COMPRESSION_QUANTIZATION_BITS"] = "4"
        import torch
        import torch.distributed as dist
        import torch_cgx_tpu.torch_backend  # noqa: F401 — registers "cgx"
        from torch_cgx_tpu.robustness import BridgeTimeoutError as BTE

        dist.init_process_group(
            "cgx", init_method=f"file://{initfile}", rank=rank,
            world_size=ws,
        )
        # rank 1 dies inside this collective (kill_rank fires on its first
        # dequeued work item — an os._exit, no abort, no atexit).
        t = torch.full((8192,), float(rank + 1))
        t0 = time.monotonic()
        try:
            dist.all_reduce(t)
            q.put((rank, "collective succeeded despite the killed peer"))
            return
        except BTE as e:
            elapsed = time.monotonic() - t0
            msg = str(e)
            problems = []
            if "timed out" not in msg:
                problems.append(f"no timeout wording: {msg!r}")
            if 1 not in e.suspects or "1" not in msg:
                problems.append(f"dead rank 1 not named: {msg!r}")
            if elapsed > 30:
                problems.append(f"took {elapsed:.1f}s (budget was 6s)")
            q.put((rank, "; ".join(problems) or None))
    except Exception:
        q.put((rank, traceback.format_exc()))


@pytest.mark.torch_bridge
def test_kill_rank_produces_named_timeout(tmp_path):
    """A SIGKILL-style peer death mid-collective surfaces on the survivor
    as BridgeTimeoutError naming rank 1, within CGX_BRIDGE_TIMEOUT_MS —
    and (ISSUE 2 acceptance) with CGX_METRICS_DIR set the survivor leaves
    a flight-recorder dump identifying the failed collective and the
    suspected dead rank, which tools/cgx_report.py renders."""
    import json
    import subprocess

    mdir = str(tmp_path / "metrics")
    initfile = tempfile.mktemp(prefix="cgx_faults_store_")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_kill_rank_main, args=(r, 2, initfile, mdir, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    # Only rank 0 reports; rank 1 dies by design.
    rank, err = q.get(timeout=180)
    assert rank == 0 and err is None, f"rank {rank}: {err}"
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
    from torch_cgx_tpu.robustness.faults import KILL_EXIT_CODE

    assert procs[1].exitcode == KILL_EXIT_CODE, procs[1].exitcode
    if os.path.exists(initfile):
        os.unlink(initfile)
    # -- flight-recorder acceptance: the evidence survived the failure --
    path = os.path.join(mdir, "flightrec-rank0.jsonl")
    assert os.path.exists(path), (
        os.listdir(mdir) if os.path.isdir(mdir) else "no metrics dir"
    )
    lines = [json.loads(line) for line in open(path)]
    assert lines[0]["kind"] == "dump"
    failures = [e for e in lines[1:] if e["kind"] == "failure"]
    assert failures, "no failure event in the survivor's dump"
    assert any(f["error"] == "BridgeTimeoutError" for f in failures)
    # the failed collective is named...
    assert any(f.get("op") == "allreduce" for f in failures)
    # ...and so is the suspected dead peer
    assert any(1 in (f.get("suspects") or []) for f in failures)
    # the report CLI renders the chaos dir without error (text + json)
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"), mdir],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    # (kill_rank itself fired on the DEAD rank — an os._exit leaves no
    # dump, by design; the survivor's evidence is the named timeout.)
    assert "BridgeTimeoutError" in proc.stdout
    assert "suspected dead" in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"),
         mdir, "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0
    js = json.loads(proc.stdout)
    assert js["failures"]
    assert any(f.get("op") == "allreduce" for f in js["failures"])
    assert any(1 in (f.get("suspects") or []) for f in js["failures"])


# ---------------------------------------------------------------------------
# nan_grad + the non-finite guard (acceptance c).
# ---------------------------------------------------------------------------


def _guard_harness():
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from torch_cgx_tpu.parallel import make_train_step, replicate, shard_batch

    mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("dp",))
    rng = np.random.default_rng(0)
    Wt = rng.normal(size=(16, 4)).astype(np.float32)
    batches = []
    for _ in range(4):
        x = rng.normal(size=(32, 16)).astype(np.float32)
        batches.append((x, x @ Wt))

    def loss_fn(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2)

    def run(batch_list, guard, faults_env=None, idxs=None):
        os.environ.pop("CGX_FAULTS", None)
        if faults_env:
            os.environ["CGX_FAULTS"] = faults_env
        faults.reset_injectors()
        try:
            params = {"w": jnp.zeros((16, 4), jnp.float32)}
            opt = optax.adam(1e-2)
            step = make_train_step(
                loss_fn, opt, mesh, donate=False, nonfinite_guard=guard
            )
            p = replicate(params, mesh)
            s = replicate(opt.init(params), mesh)
            for i, (x, y) in enumerate(batch_list):
                b = shard_batch((x, y), mesh)
                si = idxs[i] if idxs is not None else i
                p, s, _loss = step(p, s, b, jnp.int32(si))
            return np.asarray(p["w"])
        finally:
            os.environ.pop("CGX_FAULTS", None)

    return batches, run


def test_nan_grad_skip_resumes_bit_identically(monkeypatch):
    """Acceptance (c): under nan_grad injection with guard="skip", the
    poisoned step is dropped (cgx.nonfinite_steps == 1), parameters stay
    finite, and training from there is bit-identical to a run that never
    saw the poisoned batch."""
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "64")
    batches, run = _guard_harness()
    w_faulted = run(batches, "skip", faults_env="nan_grad:step=1")
    assert np.isfinite(w_faulted).all()
    assert metrics.get("cgx.nonfinite_steps") == 1
    # control: same schedule minus the poisoned batch (step idx preserved
    # so the trace-identical program runs on the same inputs)
    control = [batches[0], batches[2], batches[3]]
    w_control = run(control, "skip", idxs=[0, 2, 3])
    np.testing.assert_array_equal(w_faulted, w_control)


def test_nan_grad_unguarded_poisons_everything(monkeypatch):
    """The failure mode the guard exists for: with the guard off, one NaN
    gradient element destroys the max-min wire for every parameter."""
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "64")
    batches, run = _guard_harness()
    w = run(batches[:2], "off", faults_env="nan_grad:step=1")
    assert not np.isfinite(w).all()


def test_nan_grad_probabilistic(monkeypatch):
    """A ``nan_grad:<prob>`` spec poisons ~that fraction of steps (a
    per-step Bernoulli seeded by CGX_FAULTS_SEED — deterministic replay),
    not every step."""
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "64")
    monkeypatch.setenv("CGX_FAULTS_SEED", "3")
    batches, run = _guard_harness()
    # 12 steps at p=0.5: some but not all must fault (p(all-or-none) ~ 2^-11)
    sched = (batches * 3)[:12]
    w = run(sched, "skip", faults_env="nan_grad:0.5")
    n_bad = metrics.get("cgx.nonfinite_steps")
    assert 0 < n_bad < 12, n_bad
    assert np.isfinite(w).all()
    # deterministic replay: same seed -> same fault schedule
    metrics.reset()
    run(sched, "skip", faults_env="nan_grad:0.5")
    assert metrics.get("cgx.nonfinite_steps") == n_bad


def test_nan_grad_exact_fallback_applies_the_step(monkeypatch):
    """guard="exact": the poisoned step still applies an update — from the
    uncompressed psum of the sanitized gradients — and params stay finite;
    fault-free runs match guard="off" to float round-off (the guard's
    staged selects + fallback psum make it a different XLA program, whose
    fusion of the adam update may differ in the last ulp: 3.7e-9 on jax
    0.9.0)."""
    monkeypatch.setenv("CGX_COMPRESSION_QUANTIZATION_BITS", "4")
    monkeypatch.setenv("CGX_COMPRESSION_BUCKET_SIZE", "64")
    batches, run = _guard_harness()
    w_exact = run(batches, "exact", faults_env="nan_grad:step=1")
    assert np.isfinite(w_exact).all()
    assert metrics.get("cgx.nonfinite_steps") == 1
    w_skip = run(batches, "skip", faults_env="nan_grad:step=1")
    assert not np.array_equal(w_exact, w_skip)  # the step was applied
    # value identity on clean runs
    w_off = run(batches, "off")
    w_exact_clean = run(batches, "exact")
    np.testing.assert_allclose(w_off, w_exact_clean, rtol=1e-6, atol=1e-8)
