"""Fault-tolerant socket transport tests (PR 20).

Three layers:

- **Unit** (no torch): the ``SocketTransport`` plane pair over a fake
  c10d store — roundtrip bit-identity, crc framing, bounded fetch,
  the reconnect/replay ladder under injected ``conn_reset`` /
  ``partial_write``, degrade-to-store under ``partition``, the
  ``TransportStore`` routing shim, the ``maybe_wrap_store`` identity
  pin, and the cross-host store-counter liveness judge.
- **Grammar**: the CGX_FAULTS network modes parse (and reject junk —
  a typo silently injecting nothing makes a chaos run vacuously
  green).
- **Bridge** (multi-process, ``torch_bridge``-marked): the real
  ``"cgx"`` backend with ``CGX_TRANSPORT=socket`` — bit-identity
  against the legacy store path, the conn_reset replay soak, the
  partition degrade (strictly before CGX_BRIDGE_TIMEOUT_MS, training
  continues), SIGKILL eviction naming, and the two-"hosts" heartbeat
  regression.
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time
import traceback

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from torch_cgx_tpu import config as cfg  # noqa: E402
from torch_cgx_tpu.robustness import faults  # noqa: E402
from torch_cgx_tpu.robustness import heartbeat as hb  # noqa: E402
from torch_cgx_tpu.torch_backend import transport as tp  # noqa: E402
from torch_cgx_tpu.utils.logging import metrics  # noqa: E402


@pytest.fixture(autouse=True)
def _reset():
    faults.reset_injectors()
    metrics.reset()
    yield
    faults.reset_injectors()


class FakeStore:
    """Minimal c10d-Store look-alike with the wait/check surface the
    transport's store fallback uses."""

    def __init__(self):
        self._d = {}
        self._lock = threading.Lock()

    def set(self, k, v):
        with self._lock:
            self._d[k] = v if isinstance(v, bytes) else bytes(v)

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

    def check(self, keys):
        with self._lock:
            return all(k in self._d for k in keys)

    def wait(self, keys, *a):
        deadline = time.monotonic() + 0.2
        while time.monotonic() < deadline:
            if self.check(keys):
                return
            time.sleep(0.01)
        raise RuntimeError(f"wait timeout {keys}")

    def delete_key(self, k):
        with self._lock:
            return self._d.pop(k, None) is not None

    def keys(self):
        with self._lock:
            return list(self._d)


def _mk_plane(store, my_id, rank=None, **kw):
    kw.setdefault("io_timeout_s", 2.0)
    kw.setdefault("ping_s", 0.2)
    return tp.SocketTransport(
        store, my_id=my_id, addr_key=lambda p: f"tpaddr/{p}",
        rank=rank, **kw,
    )


# ---------------------------------------------------------------------------
# CGX_FAULTS network grammar
# ---------------------------------------------------------------------------


def test_net_fault_grammar():
    specs = {
        s.mode: s for s in faults.parse_faults(
            "conn_reset:400ms@rank=1,partial_write,"
            "slow_link:200ms@edge=tcp,partition:1s@ranks=0,1"
        )
    }
    assert set(specs) == set(faults.NET_MODES)
    assert specs["conn_reset"].delay_ms == 400.0
    assert specs["conn_reset"].rank == 1
    # An ungated partial_write would truncate EVERY frame: defaults to
    # the first send event.
    assert specs["partial_write"].step == 0
    # slow_link IS an edge fault — the edge defaults even unspelled.
    assert faults.parse_faults("slow_link:200ms")[0].edge == "tcp"
    assert specs["partition"].ranks == (0, 1)
    assert specs["partition"].delay_ms == 1000.0


@pytest.mark.parametrize(
    "raw",
    [
        "conn_reset",  # window modes need a duration
        "slow_link@edge=tcp",
        "partition:10s",  # partition needs endpoints
        "partition:10s@ranks=0,1,2",  # exactly two
        "partition@ranks=0,1",  # and a duration
        "slow_link:200ms@edge=dcn",  # tcp-only edge
        "conn_reset:1s@ranks=0,1",  # ranks= is partition-only
    ],
)
def test_net_fault_grammar_rejects(raw):
    with pytest.raises(ValueError):
        faults.parse_faults(raw)


def test_partition_window_gates_on_pair(monkeypatch):
    monkeypatch.setenv("CGX_FAULTS", "partition:10s@ranks=0,1")
    inj0 = faults.get_injector(0)
    inj2 = faults.get_injector(2)
    assert inj0.window("partition", peer=1)  # opens + holds
    assert inj0.window("partition", peer=1)
    assert not inj0.window("partition", peer=2)  # wrong pair
    assert not inj2.window("partition", peer=3)  # rank outside the pair
    assert not inj0.window("conn_reset")  # un-specced mode


def test_conn_reset_window_expires(monkeypatch):
    monkeypatch.setenv("CGX_FAULTS", "conn_reset:100ms")
    inj = faults.get_injector(0)
    assert inj.window("conn_reset")
    time.sleep(0.15)
    assert not inj.window("conn_reset")


# ---------------------------------------------------------------------------
# SocketTransport plane pair (unit)
# ---------------------------------------------------------------------------


def test_socket_roundtrip_bit_identical():
    store = FakeStore()
    a = _mk_plane(store, "0")
    b = _mk_plane(store, "1")
    try:
        small = b"\x00\x01hello\xff"
        big = bytes(os.urandom(1 << 20))
        a.post("k/small", small, to=["1"])
        a.post("k/big", big, to=["1"])
        assert b.fetch("k/small", timeout_s=5.0) == small
        assert b.fetch("k/big", timeout_s=5.0) == big
        # Mailbox entries pop on fetch — a second fetch times out.
        with pytest.raises(tp.TransportTimeout):
            b.fetch("k/small", timeout_s=0.3)
        snap = metrics.snapshot()
        assert snap.get("cgx.transport.posts", 0) >= 2
        assert snap.get("cgx.transport.frames_rx", 0) >= 2
        assert snap.get("cgx.transport.link_down", 0) == 0
    finally:
        a.close()
        b.close()


def test_fetch_bounded_and_abortable():
    store = FakeStore()
    b = _mk_plane(store, "9")
    try:
        t0 = time.monotonic()
        with pytest.raises(tp.TransportTimeout) as ei:
            b.fetch("never/posted", timeout_s=0.3)
        assert time.monotonic() - t0 < 2.0  # bounded, not a hang
        assert "never/posted" in str(ei.value)

        class Poison(RuntimeError):
            pass

        def boom():
            raise Poison("aborted")

        with pytest.raises(Poison):
            b.fetch("never/posted", timeout_s=5.0, abort_check=boom)
    finally:
        b.close()


def test_fetch_store_fallback_probe():
    """A key only the plain store has (a degraded WRITER's flush) is
    still delivered by the dual-probe fetch."""
    store = FakeStore()
    b = _mk_plane(store, "9")
    try:
        store.set("deg/key", b"from-the-store")
        assert b.fetch("deg/key", timeout_s=5.0) == b"from-the-store"
        assert b.poll("deg/key")  # store side of poll
        assert metrics.snapshot().get("cgx.transport.store_fetches", 0) >= 1
    finally:
        b.close()


def test_conn_reset_replay_bit_identical(monkeypatch):
    """A reconnect ladder that outlasts the reset window replays the
    resend ring: same seq, same bytes, no degrade."""
    monkeypatch.setenv("CGX_FAULTS", "conn_reset:300ms@rank=0")
    store = FakeStore()
    a = _mk_plane(store, "0", rank=0, retries=20, backoff_ms=50)
    b = _mk_plane(store, "1", rank=1)
    try:
        payload = bytes(os.urandom(64 * 1024))
        a.post("replay/k0", payload, to=["1"])
        assert b.fetch("replay/k0", timeout_s=15.0) == payload
        lk = a.link("1")
        deadline = time.monotonic() + 5.0
        while (
            lk.resends < 1 and lk.reconnects < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert lk.state == tp._ST_CONNECTED
        assert lk.resends >= 1 or lk.reconnects >= 1, lk.snapshot()
        snap = metrics.snapshot()
        assert snap.get("cgx.transport.link_down", 0) == 0
        assert snap.get("cgx.transport.degraded_posts", 0) == 0
        # After the window: plain traffic flows on the same link.
        a.post("replay/k1", b"post-window", to=["1"])
        assert b.fetch("replay/k1", timeout_s=10.0) == b"post-window"
    finally:
        a.close()
        b.close()


def test_partition_degrades_to_store(monkeypatch):
    """An exhausted ladder degrades the edge: the ring flushes to the
    store under the SAME keys with bit-identical bytes, the reader's
    store probe delivers, and the health callback names the peer."""
    monkeypatch.setenv("CGX_FAULTS", "partition:30s@ranks=0,1")
    store = FakeStore()
    downs = []
    a = _mk_plane(
        store, "0", rank=0, retries=2, backoff_ms=20, io_timeout_s=0.5,
        on_link_down=lambda peer, peer_rank: downs.append(
            (peer, peer_rank)
        ),
    )
    b = _mk_plane(store, "1", rank=1)
    try:
        payload = bytes(os.urandom(4096))
        a.post("part/k0", payload, to=["1"])
        assert b.fetch("part/k0", timeout_s=15.0) == payload
        deadline = time.monotonic() + 10.0
        while a.down_peers() != ["1"] and time.monotonic() < deadline:
            time.sleep(0.05)
        assert a.down_peers() == ["1"]
        assert downs == [("1", 1)]
        snap = metrics.snapshot()
        assert snap.get("cgx.transport.link_down", 0) >= 1
        assert snap.get("cgx.transport.degraded_posts", 0) >= 1
        # Degraded edge: later posts go straight to the store path,
        # same key, same bytes.
        a.post("part/k1", b"still-delivered", to=["1"])
        assert b.fetch("part/k1", timeout_s=10.0) == b"still-delivered"
        assert store.get("part/k1") == b"still-delivered"
    finally:
        a.close()
        b.close()


def test_partial_write_torn_frame_resent(monkeypatch):
    """A torn first frame (header+body truncated mid-wire) is discarded
    by the receiver and redelivered intact by the replay."""
    monkeypatch.setenv("CGX_FAULTS", "partial_write")
    store = FakeStore()
    a = _mk_plane(store, "0", rank=0, retries=10, backoff_ms=30)
    b = _mk_plane(store, "1", rank=1, io_timeout_s=0.5)
    try:
        payload = bytes(os.urandom(32 * 1024))
        a.post("torn/k0", payload, to=["1"])
        assert b.fetch("torn/k0", timeout_s=15.0) == payload
        # The replay's ``resends`` bump races the delivery by a few
        # instructions (sender-thread bookkeeping) — poll briefly.
        lk = a.link("1")
        deadline = time.monotonic() + 5.0
        while lk.resends < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert lk.resends >= 1, lk.snapshot()
        assert metrics.snapshot().get("cgx.transport.link_down", 0) == 0
    finally:
        a.close()
        b.close()


def test_status_snapshot_shape():
    store = FakeStore()
    a = _mk_plane(store, "0")
    try:
        a.post("s/k", b"x", to=["1", "2"])
        rows = a.status()
        assert {r["peer"] for r in rows} == {"1", "2"}
        for r in rows:
            for col in (
                "state", "unacked", "queued", "reconnects", "resends",
                "last_send_age_s", "last_ack_age_s",
            ):
                assert col in r
    finally:
        a.close()


# ---------------------------------------------------------------------------
# TransportStore shim + identity pin
# ---------------------------------------------------------------------------


class _FakePlane:
    def __init__(self):
        self.posts = []
        self.box = {}

    def post(self, key, payload, to=()):
        self.posts.append((key, bytes(payload), tuple(to)))
        self.box[key] = bytes(payload)

    def poll(self, key):
        return key in self.box

    def fetch(self, key, timeout_s, abort_check=None, peer=None):
        if key not in self.box:
            raise tp.TransportTimeout(key, timeout_s)
        return self.box.pop(key)


def test_transport_store_routing_and_exclude():
    base = FakeStore()
    plane = _FakePlane()
    ts = tp.TransportStore(
        base, plane, peers=("rx",), prefixes=("cgxkv/s1/",),
        fetch_timeout_s=1.0, exclude=("/rereq/",),
    )
    # Routed payload key: framed post toward the construction peers,
    # never the base store.
    ts.set("cgxkv/s1/0001", b"page")
    assert plane.posts == [("cgxkv/s1/0001", b"page", ("rx",))]
    assert "cgxkv/s1/0001" not in base.keys()
    assert ts.check(["cgxkv/s1/0001"])
    assert bytes(ts.get("cgxkv/s1/0001")) == b"page"
    # Excluded control key under the routed prefix: plain store (its
    # reader set differs from the page stream's peers).
    ts.set("cgxkv/s1/rereq/0", b"3")
    assert plane.posts[1:] == []
    assert base.get("cgxkv/s1/rereq/0") == b"3"
    # Un-prefixed keys and counters pass through untouched.
    ts.set("other/key", b"v")
    assert base.get("other/key") == b"v"
    assert ts.add("cgxkv/s1/n", 2) == 2
    assert int(base.get("cgxkv/s1/n")) == 2
    # Routed delete is a no-op (mailbox pops on fetch).
    assert ts.delete_key("cgxkv/s1/0002") is True
    assert ts.delete_key("other/key") is True
    assert "other/key" not in base.keys()


def test_maybe_wrap_store_identity_pin(monkeypatch):
    """CGX_TRANSPORT unset (or any non-socket mode): the wrap is the
    identity — no plane, no address key, no behavioural delta."""
    base = FakeStore()
    for mode in (None, "", "store", "shm", "auto"):
        if mode is None:
            monkeypatch.delenv("CGX_TRANSPORT", raising=False)
        else:
            monkeypatch.setenv("CGX_TRANSPORT", mode)
        assert tp.maybe_wrap_store(
            base, endpoint="e", peers=("p",), prefixes=("cgxkv/",)
        ) is base
        assert base.keys() == []
    from torch_cgx_tpu.serving import transport as serving_tp

    monkeypatch.delenv("CGX_TRANSPORT", raising=False)
    assert serving_tp.maybe_socket_store(base, endpoint="kvrx") is base


def test_transport_mode_rejects_junk(monkeypatch):
    monkeypatch.setenv("CGX_TRANSPORT", "carrier-pigeon")
    with pytest.raises(ValueError):
        cfg.transport_mode()


def test_maybe_wrap_store_socket_roundtrip(monkeypatch):
    monkeypatch.setenv("CGX_TRANSPORT", "socket")
    base = FakeStore()
    rx = tp.maybe_wrap_store(
        base, endpoint="rx", peers=(), prefixes=("cgxkv/s/",),
        fetch_timeout_s=5.0,
    )
    txs = tp.maybe_wrap_store(
        base, endpoint="tx", peers=("rx",), prefixes=("cgxkv/s/",),
        fetch_timeout_s=5.0,
    )
    try:
        assert isinstance(rx, tp.TransportStore)
        payload = bytes(os.urandom(8192))
        txs.set("cgxkv/s/0", payload)
        assert bytes(rx.get("cgxkv/s/0")) == payload
        assert "cgxkv/s/0" not in base.keys()
        # The publish-after-write counters still live on the real store.
        txs.add("cgxkv/s/n", 1)
        assert rx.add("cgxkv/s/n", 0) == 1
    finally:
        txs.transport_plane.close()
        rx.transport_plane.close()


# ---------------------------------------------------------------------------
# Cross-host store-counter liveness (satellite 1)
# ---------------------------------------------------------------------------


def test_remote_liveness_convicts_stalled_counter():
    store = FakeStore()
    live_pid, dead_pid = 11111, 22222
    store.add(hb.store_heartbeat_key(live_pid), 1)
    store.add(hb.store_heartbeat_key(dead_pid), 1)
    judge = hb.RemoteLiveness(store, stale_s=0.15)
    # First probe can never convict: the judge needs its own history.
    assert judge.suspects([live_pid, dead_pid]) == []
    for _ in range(4):
        time.sleep(0.06)
        store.add(hb.store_heartbeat_key(live_pid), 1)  # keeps advancing
        judge.observe([live_pid, dead_pid])
    assert judge.suspects([live_pid, dead_pid]) == [dead_pid]
    assert (
        metrics.snapshot().get("cgx.heartbeat.remote_suspect_checks", 0)
        >= 1
    )


def test_attach_store_publishes_and_is_idempotent(tmp_path):
    store = FakeStore()
    hb.attach_store(str(tmp_path), store)
    key = hb.store_heartbeat_key(os.getpid())
    first = int(store.get(key))  # first bump lands before any wait
    assert first >= 1
    hb.attach_store(str(tmp_path), store)  # same store object: no dup
    deadline = time.monotonic() + 3.0
    while int(store.get(key)) == first and time.monotonic() < deadline:
        time.sleep(0.1)
    assert int(store.get(key)) > first  # the shared ticker advances it


def test_two_hosts_liveness_regression(tmp_path):
    """Two 'hosts' (distinct heartbeat dirs) sharing one store: the
    file-mtime judge can't see across, the counter judge can — and only
    convicts the host whose ticker stopped."""
    store = FakeStore()
    host_a, host_b = tmp_path / "a", tmp_path / "b"
    host_a.mkdir(), host_b.mkdir()
    pid_b = 54321

    class _B:
        """Host B's publisher, hand-cranked so the test can stop it."""

        def tick(self):
            store.add(hb.store_heartbeat_key(pid_b), 1)

    b = _B()
    b.tick()
    # Host A's real heartbeat publishes through the store.
    hb.attach_store(str(host_a), store)
    pid_a = os.getpid()
    # Host A's ticker is a thread of this process: under six loaded test
    # workers it has gone 0.3 s without a tick (ROADMAP C9), so the judge
    # allows it a second; what is judged is the same.
    judge = hb.RemoteLiveness(store, stale_s=1.0)
    judge.observe([pid_a, pid_b])
    for _ in range(5):
        time.sleep(0.1)
        b.tick()
        judge.observe([pid_a, pid_b])
    assert judge.suspects([pid_a, pid_b]) == []  # both alive
    # Host B stops ticking; host A's shared ticker keeps its counter
    # advancing — only B converts to a suspect.
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        time.sleep(0.1)
        if judge.suspects([pid_a, pid_b]) == [pid_b]:
            break
    assert judge.suspects([pid_a, pid_b]) == [pid_b]


# ---------------------------------------------------------------------------
# Bridge tests: the real "cgx" backend over the socket plane.
# ---------------------------------------------------------------------------


def _bridge_main(rank, ws, initfile, body_name, env, q):
    """Fresh-spawn bootstrap: CGX_* env must be set BEFORE backend
    construction (the transport engages at init_process_group time), so
    these tests cannot ride test_torch_backend's persistent pool."""
    sys.path.insert(0, _REPO)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.update(env)
    payload = None
    try:
        import torch.distributed as dist
        import torch_cgx_tpu.torch_backend  # noqa: F401

        dist.init_process_group(
            "cgx", init_method=f"file://{initfile}", rank=rank,
            world_size=ws,
        )
        payload = globals()[body_name](rank, ws)
        err = None
    except Exception:
        err = traceback.format_exc()
    finally:
        try:
            import torch.distributed as dist

            dist.destroy_process_group()
        except Exception:
            pass
        q.put((rank, err, payload))


def _run_bridge(body, ws, env, timeout=180.0, expect_dead=()):
    """Spawn ``ws`` fresh ranks; returns {rank: payload}. Ranks listed
    in ``expect_dead`` may die without reporting (SIGKILL chaos)."""
    import multiprocessing as mp

    initfile = tempfile.mktemp(prefix="cgx_tp_store_")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_bridge_main,
            args=(r, ws, initfile, body.__name__, dict(env), q),
        )
        for r in range(ws)
    ]
    for p in procs:
        p.start()
    errors, payloads = [], {}
    for _ in range(ws - len(expect_dead)):
        try:
            rank, err, payload = q.get(timeout=timeout)
        except Exception:
            errors.append("timeout waiting for a rank (hang?)")
            break
        if err is not None:
            errors.append(f"rank {rank}:\n{err}")
        payloads[rank] = payload
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
            p.join(timeout=10)
    if os.path.exists(initfile):
        os.unlink(initfile)
    assert not errors, "\n".join(errors)
    return payloads


def _body_collectives(rank, ws):
    """A few collectives whose results travel back for cross-mode
    bit-comparison."""
    import torch
    import torch.distributed as dist

    out = {}
    t = torch.arange(4096, dtype=torch.float32) * (rank + 1) / 7.0
    dist.all_reduce(t)
    out["allreduce"] = t.numpy().tobytes()
    b = torch.arange(512, dtype=torch.float32) * (rank * 3 + 1)
    dist.broadcast(b, src=0)
    out["broadcast"] = b.numpy().tobytes()
    gs = [torch.zeros(128) for _ in range(ws)]
    dist.all_gather(gs, torch.full((128,), float(rank + 1) / 3.0))
    out["allgather"] = b"".join(g.numpy().tobytes() for g in gs)
    dist.barrier()
    from torch_cgx_tpu.utils.logging import metrics as m

    out["metrics"] = {
        k: v for k, v in m.snapshot().items()
        if k.startswith("cgx.transport.")
    }
    return out


@pytest.mark.torch_bridge
def test_socket_bridge_bit_identical_vs_store_ws2():
    """CGX_TRANSPORT=socket produces byte-identical collective results
    to the legacy store path — and actually rides the socket plane."""
    legacy = _run_bridge(_body_collectives, 2, {"CGX_SHM": "0"})
    socketed = _run_bridge(
        _body_collectives, 2,
        {"CGX_SHM": "0", "CGX_TRANSPORT": "socket"},
    )
    for rank in (0, 1):
        for op in ("allreduce", "broadcast", "allgather"):
            assert socketed[rank][op] == legacy[rank][op], (rank, op)
        assert legacy[rank]["metrics"].get("cgx.transport.posts", 0) == 0
        assert socketed[rank]["metrics"].get("cgx.transport.posts", 0) > 0


def _body_conn_reset_soak(rank, ws):
    import torch
    import torch.distributed as dist

    for step in range(6):
        t = torch.full((2048,), float(rank + 1 + step))
        dist.all_reduce(t)
        want = float(sum(r + 1 + step for r in range(ws)))
        assert torch.equal(t, torch.full((2048,), want)), (step, t[:4])
    dist.barrier()
    from torch_cgx_tpu.utils.logging import metrics as m

    snap = m.snapshot()
    return {
        k: snap.get(k, 0)
        for k in (
            "cgx.transport.reconnects", "cgx.transport.resends",
            "cgx.transport.link_down", "cgx.transport.conn_errors",
        )
    }


@pytest.mark.torch_bridge
@pytest.mark.faults
def test_conn_reset_chaos_replays_bit_identical_ws2():
    """A 400 ms reset window on rank 0 with a ladder that outlasts it:
    the soak completes bit-identical via ring replay — no degrade."""
    payloads = _run_bridge(
        _body_conn_reset_soak, 2,
        {
            "CGX_SHM": "0",
            "CGX_TRANSPORT": "socket",
            "CGX_FAULTS": "conn_reset:400ms@rank=0",
            "CGX_TRANSPORT_RETRIES": "12",
            "CGX_TRANSPORT_BACKOFF_MS": "40",
        },
    )
    hit = payloads[0]
    assert hit["cgx.transport.conn_errors"] >= 1, hit
    assert (
        hit["cgx.transport.reconnects"] + hit["cgx.transport.resends"]
    ) >= 1, hit
    for rank in (0, 1):
        assert payloads[rank]["cgx.transport.link_down"] == 0, payloads


def _body_partition_degrade(rank, ws):
    import time as _t

    import torch
    import torch.distributed as dist

    steps = []
    for step in range(3):
        t0 = _t.monotonic()
        t = torch.full((1024,), float(rank + 1))
        dist.all_reduce(t)
        steps.append(_t.monotonic() - t0)
        want = float(sum(r + 1 for r in range(ws)))
        assert torch.equal(t, torch.full((1024,), want)), (step, t[:4])
    dist.barrier()
    from torch_cgx_tpu.utils.logging import metrics as m

    snap = m.snapshot()
    return {
        "steps_s": steps,
        "link_down": snap.get("cgx.transport.link_down", 0),
        "degraded_posts": snap.get("cgx.transport.degraded_posts", 0),
        "bridge_timeouts": snap.get("cgx.bridge_timeout", 0),
    }


@pytest.mark.torch_bridge
@pytest.mark.faults
def test_partition_degrades_before_bridge_timeout_ws2():
    """A 60 s partition across the only edge: the ladder exhausts in
    well under CGX_BRIDGE_TIMEOUT_MS, the edge degrades to the store
    (link_down fires), and training CONTINUES — no unbounded stall,
    no timeout error."""
    bridge_timeout_s = 20.0
    payloads = _run_bridge(
        _body_partition_degrade, 2,
        {
            "CGX_SHM": "0",
            "CGX_TRANSPORT": "socket",
            "CGX_FAULTS": "partition:60s@ranks=0,1",
            "CGX_TRANSPORT_RETRIES": "2",
            "CGX_TRANSPORT_BACKOFF_MS": "20",
            "CGX_TRANSPORT_IO_TIMEOUT_MS": "500",
            "CGX_BRIDGE_TIMEOUT_MS": str(int(bridge_timeout_s * 1000)),
        },
    )
    assert sum(p["link_down"] for p in payloads.values()) >= 1, payloads
    assert sum(p["degraded_posts"] for p in payloads.values()) >= 1
    for rank, p in payloads.items():
        assert p["bridge_timeouts"] == 0, (rank, p)
        # Degrade is detection, not a timeout: every step lands
        # strictly inside the bridge window.
        assert max(p["steps_s"]) < bridge_timeout_s, (rank, p)


def _body_sigkill_eviction(rank, ws):
    import signal

    import torch
    import torch.distributed as dist

    dist.barrier()
    if rank == 1:
        os.kill(os.getpid(), signal.SIGKILL)
    t = torch.full((256,), 1.0)
    try:
        dist.all_reduce(t)
    except RuntimeError as e:
        msg = str(e)
        assert "timed out" in msg, msg
        return {"error": msg}
    raise AssertionError("expected a bridge timeout")


@pytest.mark.torch_bridge
@pytest.mark.faults
def test_sigkill_peer_named_timeout_under_socket_ws2():
    """A SIGKILL'd peer under CGX_TRANSPORT=socket surfaces exactly as
    on the store path: a bounded BridgeTimeoutError — with the dead
    rank named via the degraded transport edge."""
    payloads = _run_bridge(
        _body_sigkill_eviction, 2,
        {
            "CGX_SHM": "0",
            "CGX_TRANSPORT": "socket",
            "CGX_TRANSPORT_RETRIES": "2",
            "CGX_TRANSPORT_BACKOFF_MS": "20",
            "CGX_TRANSPORT_IO_TIMEOUT_MS": "500",
            "CGX_BRIDGE_TIMEOUT_MS": "4000",
        },
        expect_dead=(1,),
    )
    msg = payloads[0]["error"]
    assert "socket transport" in msg, msg
    assert "suspected dead peer rank(s): [1]" in msg, msg


def _body_cross_host_heartbeat(rank, ws):
    import signal

    import torch
    import torch.distributed as dist

    dist.barrier()
    if rank == 1:
        os.kill(os.getpid(), signal.SIGKILL)
    t = torch.full((256,), 1.0)
    try:
        dist.all_reduce(t)
    except RuntimeError as e:
        msg = str(e)
        assert "timed out" in msg, msg
        return {"error": msg}
    raise AssertionError("expected a bridge timeout")


@pytest.mark.torch_bridge
@pytest.mark.faults
def test_two_hosts_heartbeat_names_dead_peer_ws2(tmp_path):
    """Two 'hosts' (distinct CGX_SHM_HOST_ID + heartbeat dirs): the
    file-mtime judge is blind across hosts, so naming the SIGKILL'd
    peer proves the store-counter liveness path (satellite 1). The
    recovery retry gives the counter judge the observation history a
    conviction needs."""
    dirs = [tmp_path / "hostA", tmp_path / "hostB"]
    for d in dirs:
        d.mkdir()
    env = {
        "CGX_BRIDGE_TIMEOUT_MS": "2600",
        "CGX_RECOVERY_RETRIES": "2",
        "CGX_RECOVERY_BACKOFF_MS": "100",
    }
    import multiprocessing as mp

    initfile = tempfile.mktemp(prefix="cgx_tp_store_")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = []
    for r in range(2):
        renv = dict(env)
        renv["CGX_SHM_HOST_ID"] = f"host{'AB'[r]}"
        renv["CGX_SHM_DIR"] = str(dirs[r])
        procs.append(
            ctx.Process(
                target=_bridge_main,
                args=(
                    r, 2, initfile, "_body_cross_host_heartbeat", renv, q,
                ),
            )
        )
    for p in procs:
        p.start()
    rank, err, payload = q.get(timeout=180)
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
    if os.path.exists(initfile):
        os.unlink(initfile)
    assert err is None, f"rank {rank}:\n{err}"
    assert rank == 0
    assert "suspected dead peer rank(s): [1]" in payload["error"], payload


# ---------------------------------------------------------------------------
# Operator surfaces: cgx_top link column + cgx_report transport section.
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"tp_test_{name}", os.path.join(_REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cgx_top_link_column(tmp_path):
    import json

    cgx_top = _load_tool("cgx_top")
    with open(tmp_path / "metrics-rank0.jsonl", "w") as f:
        f.write(json.dumps({
            "ts": 1000.0,
            "counters": {"cgx.transport.frames_tx": 12.0,
                         "cgx.transport.reconnects": 2.0},
            "gauges": {}, "histograms": {},
        }) + "\n")
    frame = cgx_top.render(str(tmp_path), {})
    assert "link" in frame
    assert "ok+r2" in frame
    # a degraded edge flips the cell to degN
    with open(tmp_path / "metrics-rank0.jsonl", "a") as f:
        f.write(json.dumps({
            "ts": 1002.0,
            "counters": {"cgx.transport.frames_tx": 20.0,
                         "cgx.transport.link_down": 1.0},
            "gauges": {"cgx.transport.degraded_edges": 1.0},
            "histograms": {},
        }) + "\n")
    assert "deg1" in cgx_top.render(str(tmp_path), {})
    # transport off (no cgx.transport.* traffic) renders '-'
    off = tmp_path / "off"
    off.mkdir()
    with open(off / "metrics-rank0.jsonl", "w") as f:
        f.write(json.dumps({
            "ts": 1000.0, "counters": {"cgx.step.count": 1.0},
            "gauges": {}, "histograms": {},
        }) + "\n")
    line = [
        ln for ln in cgx_top.render(str(off), {}).splitlines()
        if ln.strip().startswith("0 ")
    ]
    assert line, "rank row missing"


def test_cgx_report_transport_section(tmp_path):
    import json

    cgx_report = _load_tool("cgx_report")
    with open(tmp_path / "flightrec-rank0.jsonl", "w") as f:
        f.write(json.dumps({
            "kind": "transport_link_down", "peer": "1",
            "why": "retries exhausted", "flushed": 3, "retries": 2,
            "ts": 10.0,
        }) + "\n")
        f.write(json.dumps({
            "kind": "transport_reconnect", "peer": "1", "replay": 2,
            "ts": 5.0,
        }) + "\n")
    with open(tmp_path / "metrics-rank0.jsonl", "w") as f:
        f.write(json.dumps({
            "ts": 1000.0,
            "counters": {"cgx.transport.posts": 7.0,
                         "cgx.transport.frames_tx": 9.0,
                         "cgx.transport.frames_rx": 4.0,
                         "cgx.transport.bytes_tx": 2e6,
                         "cgx.transport.bytes_rx": 1e6,
                         "cgx.transport.resends": 2.0,
                         "cgx.transport.reconnects": 1.0,
                         "cgx.transport.link_down": 1.0,
                         "cgx.transport.degraded_posts": 3.0},
            "gauges": {"cgx.transport.degraded_edges": 1.0},
            "histograms": {},
        }) + "\n")
    summary = cgx_report.summarize(cgx_report.load_dir(str(tmp_path)))
    t = summary["transport"]
    assert t["posts"] == 7 and t["frames_tx"] == 9
    assert t["degraded_edges"] == 1 and t["degraded_posts"] == 3
    # events sorted by ts: reconnect (5.0) before link_down (10.0)
    assert [e["kind"] for e in t["events"]] == ["reconnect", "link_down"]
    # the gauge is a level — it must NOT leak into the summed counters
    assert "cgx.transport.degraded_edges" not in summary["counters"]
    text = cgx_report.render(summary)
    assert "== transport (supervised socket data plane) ==" in text
    assert "DEGRADED edges: 1" in text
    assert "retries exhausted" in text
    # a dir with no transport traffic has no transport section
    empty = tmp_path / "empty"
    empty.mkdir()
    s2 = cgx_report.summarize(cgx_report.load_dir(str(empty)))
    assert "transport" not in s2
