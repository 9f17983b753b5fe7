"""Elastic membership suite (ISSUE 16 tentpole).

Unit layers run single-process over an in-memory store: the preempt /
corrupt_join_page fault grammar, the snapshot pager (raw and quantized
round-trips, multi-donor striping, corruption re-request, deadline
abort), the join trigger claim/adoption, the decision's rank and donor
assignment, both abort paths (vote timeout, joiner-never-acks) leaving
survivors unharmed, a full commit round with a hand-rolled protocol
joiner proving received-state bit-identity, and the store-key hygiene
reaper across generation bumps.

The chaos soak spawns four real torch-bridge ranks, preempts rank 1
mid-training (SIGKILL-shaped death with a comeback notice and a
detached respawner), and asserts the ISSUE 16 acceptance: the respawned
rank rejoins at a bumped generation with zero checkpoint files on disk,
survivors never stall past the join bound, and every era of the run is
bit-identical to fault-free control replays — then rank 1 leaves again
(shrink -> grow -> shrink) and the final survivor era is verified the
same way.
"""

from __future__ import annotations

import glob
import json
import multiprocessing as mp
import os
import sys
import tempfile
import threading
import time
import traceback
import zlib

import numpy as np
import pytest

from torch_cgx_tpu import config as cfg
from torch_cgx_tpu.observability import health as health_mod
from torch_cgx_tpu.robustness import (
    JoinAbortedError,
    elastic,
    faults,
    rendezvous as rdz,
)
from torch_cgx_tpu.utils.logging import metrics

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _fresh():
    faults.reset_injectors()
    metrics.reset()
    cfg.clear_registry()
    health_mod.stop()
    yield
    faults.reset_injectors()
    cfg.clear_registry()
    health_mod.stop()


class FakeStore:
    """Minimal c10d-Store look-alike (same shape as test_supervisor's)."""

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

    def delete_key(self, k):
        # c10d's deleteKey returns whether a key was removed; the reap
        # counters depend on it.
        with self._lock:
            return self._d.pop(k, None) is not None

    def keys(self):
        with self._lock:
            return list(self._d)


class _StubGroup:
    """Just enough group surface for the survivor-side coordinator."""

    def __init__(self, global_rank, global_ranks, generation=0):
        self.global_rank = global_rank
        self.global_ranks = list(global_ranks)
        self.generation = generation
        self._shm = None
        self.reconfigures = []

    def reconfigure(self, members, generation, *, joiner_info=None):
        self.reconfigures.append((list(members), generation, joiner_info))
        self.global_ranks = list(members)
        self.generation = generation

    def degrade_to_store(self):  # pragma: no cover - consensus no-op path
        raise AssertionError("degrade must not fire with _shm is None")


class _StubSup:
    """Supervisor surface the coordinator binds to."""

    def __init__(self, store, group):
        self._store = store
        self.group = group
        self._elastic = None

    def attach_elastic(self, coordinator):
        self._elastic = coordinator

    @property
    def generation(self):
        return self.group.generation

    @property
    def survivors(self):
        return list(self.group.global_ranks)


def _tree(big_numel=3 * (1 << 19), seed=7):
    """A state tree with a multi-page float leaf, an int leaf and a
    scalar — exercises striping, raw int passthrough and 0-d arrays."""
    rng = np.random.default_rng(seed)
    return {
        "w": rng.normal(size=big_numel).astype(np.float32),
        "i": np.arange(17, dtype=np.int64),
        "s": np.float32(3.25),
    }


def _skeleton_like(state):
    import jax

    return jax.tree_util.tree_map(np.zeros_like, state)


def _tree_equal(a, b):
    import jax

    la, _ = jax.tree_util.tree_flatten(a)
    lb, _ = jax.tree_util.tree_flatten(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# Fault grammar.
# ---------------------------------------------------------------------------


def test_preempt_spec_parses_and_requires_duration():
    (s,) = faults.parse_faults("preempt:1500ms@rank=1@step=5")
    assert (s.mode, s.rank, s.step, s.delay_ms) == ("preempt", 1, 5, 1500.0)
    (s2,) = faults.parse_faults("preempt:2s@rank=3")
    assert (s2.mode, s2.rank, s2.delay_ms) == ("preempt", 3, 2000.0)
    with pytest.raises(ValueError):
        faults.parse_faults("preempt:rank=1@step=5")


def test_corrupt_join_payload_gates_on_page_ordinal(monkeypatch):
    monkeypatch.setenv("CGX_FAULTS", "corrupt_join_page:step=2")
    faults.reset_injectors()
    inj = faults.get_injector(0)
    payload = bytes(range(64))
    assert inj.corrupt_join_payload(payload, 0) == payload
    assert inj.corrupt_join_payload(payload, 1) == payload
    hit = inj.corrupt_join_payload(payload, 2)
    assert hit != payload
    assert sum(a != b for a, b in zip(hit, payload)) == 1
    assert inj.corrupt_join_payload(payload, 3) == payload


# ---------------------------------------------------------------------------
# Snapshot pager: encode -> donor stripes -> receiver -> decode.
# ---------------------------------------------------------------------------


def _ship_and_receive(store, state, bits, bucket, n_donors,
                      injector=None, timeout=30.0):
    wires, descs = elastic._encode_state(state, bits, bucket)
    meta = {
        "leaves": descs, "step": 7, "generation": 3, "registry": {},
        "bits": bits, "bucket": bucket, "n_donors": n_donors,
    }
    deadline = time.monotonic() + timeout
    streams = [elastic._stream_name(3, 9, di) for di in range(n_donors)]
    donors = [
        elastic._SnapshotDonor(
            store, streams[di], wires, descs,
            meta=meta if di == 0 else None, donor_idx=di,
            n_donors=n_donors, bits=bits, bucket=bucket,
            deadline=deadline, injector=injector if di == 0 else None,
        )
        for di in range(n_donors)
    ]
    for d in donors:
        d.start()
    meta_rx, bufs = elastic._SnapshotReceiver(
        store, streams, deadline).receive()
    out, step = elastic._decode_into_skeleton(
        _skeleton_like(state), meta_rx, bufs)
    for d in donors:
        d.join(10)
        assert d.done()
    return out, step


def test_snapshot_pager_raw_roundtrip_two_donors():
    store = FakeStore()
    state = _tree()  # 6 MiB leaf -> 6 pages, striped across 2 donors
    out, step = _ship_and_receive(store, state, 0, 0, n_donors=2)
    assert step == 7
    assert _tree_equal(out, state)
    assert metrics.get("cgx.elastic.pages_shipped") >= 7
    assert metrics.get("cgx.elastic.pages_received") >= 7


def test_snapshot_pager_quantized_roundtrip_matches_grid_snap():
    store = FakeStore()
    state = _tree(seed=11)
    out, step = _ship_and_receive(store, state, 8, 128, n_donors=2)
    assert step == 7
    # The lossy contract: both sides land on dequant(quant(original)) —
    # exactly what snap_state_to_grid produces from the original state.
    expected = elastic.snap_state_to_grid(state, 8, 128)
    assert _tree_equal(out, expected)
    # Non-float leaves ship raw even under a quantized edge config.
    assert np.array_equal(out["i"], state["i"])


def test_snapshot_page_corruption_is_rerequested(monkeypatch):
    monkeypatch.setenv("CGX_FAULTS", "corrupt_join_page:step=1")
    faults.reset_injectors()
    store = FakeStore()
    state = {"w": np.random.default_rng(3).normal(
        size=3 * (1 << 18)).astype(np.float32)}  # 3 MiB -> 3 pages
    out, _ = _ship_and_receive(
        store, state, 0, 0, n_donors=1, injector=faults.get_injector(0))
    assert _tree_equal(out, state)
    assert metrics.get("cgx.elastic.page_rereqs") >= 1
    assert metrics.get("cgx.elastic.page_reships") >= 1


def test_receiver_deadline_aborts_cleanly():
    store = FakeStore()
    rx = elastic._SnapshotReceiver(
        store, [elastic._stream_name(1, 5, 0)], time.monotonic() + 0.4)
    with pytest.raises(JoinAbortedError):
        rx.receive()
    assert metrics.get("cgx.elastic.join_aborts") >= 1


# ---------------------------------------------------------------------------
# Comeback notices.
# ---------------------------------------------------------------------------


def test_comeback_notice_roundtrip_and_expiry(monkeypatch):
    store = FakeStore()
    assert elastic.fresh_comeback(store, 2) is None
    elastic.publish_comeback(store, 2, 1.5)
    rec = elastic.fresh_comeback(store, 2)
    assert rec is not None and rec["rank"] == 2
    assert metrics.get("cgx.elastic.comebacks") == 1
    # Age the record past delay + grace: no longer fresh.
    stale = json.loads(rdz._read(store, elastic._comeback_key(2)))
    stale["ts"] = time.time() - (1.5 + elastic.REJOIN_GRACE_S + 1.0)
    rdz._publish(store, elastic._comeback_key(2),
                 json.dumps(stale, sort_keys=True))
    assert elastic.fresh_comeback(store, 2) is None


# ---------------------------------------------------------------------------
# Trigger claim / adoption and the decision.
# ---------------------------------------------------------------------------


def _coordinator(store, rank, ranks, generation=0):
    sup = _StubSup(store, _StubGroup(rank, ranks, generation))
    return elastic.ElasticCoordinator(store, sup), sup


def test_trigger_claimed_once_and_adopted(monkeypatch):
    monkeypatch.setenv("CGX_ELASTIC", "1")
    cfg.clear_registry()
    store = FakeStore()
    ca, _ = _coordinator(store, 0, [0, 1])
    cb, _ = _coordinator(store, 1, [0, 1])
    elastic.announce_join(store, global_rank=7, host="otherhost|9")
    s = np.zeros(4, np.float32)
    ca.on_step_boundary(s, 0)
    cb.on_step_boundary(s, 0)
    cb.on_step_boundary(s, 1)  # adopter picks the record up one step late
    assert ca._trigger is not None and cb._trigger is not None
    assert ca._trigger == cb._trigger
    assert ca._trigger["join_step"] == 2
    assert ca._trigger["generation"] == 1
    assert metrics.get("cgx.elastic.triggers") == 1


def test_elastic_disabled_is_inert(monkeypatch):
    monkeypatch.delenv("CGX_ELASTIC", raising=False)
    cfg.clear_registry()
    store = FakeStore()
    c, _ = _coordinator(store, 0, [0, 1])
    elastic.announce_join(store, global_rank=7, host="otherhost|9")
    s = np.zeros(4, np.float32)
    for step in range(4):
        assert c.on_step_boundary(s, step) is s
    assert c._trigger is None
    assert metrics.get("cgx.elastic.triggers") == 0


def test_decide_preserves_wanted_rank_and_ranks_donors(monkeypatch):
    monkeypatch.setenv("CGX_ELASTIC", "1")
    monkeypatch.setenv("CGX_JOIN_DONORS", "2")
    cfg.clear_registry()
    store = FakeStore()
    c, _ = _coordinator(store, 0, [0, 2, 3])
    k1 = elastic.announce_join(store, global_rank=1, host="ha|1")
    k2 = elastic.announce_join(store, global_rank=2, host="hb|2")  # taken
    trig = {"join_step": 12, "generation": 1, "n": k2,
            "key": elastic._trigger_key(0, 1)}
    votes = {
        0: {"load": 5.0, "host": "h0|10", "step": 10},
        2: {"load": 1.0, "host": "h2|12", "step": 10},
        3: {"load": 3.0, "host": "h3|13", "step": 10},
    }
    d = c._decide(10, trig, votes)
    assert d.generation == 1 and d.step == 10
    assert d.survivors == (0, 2, 3)
    # Wanted rank 1 is free -> preserved; wanted rank 2 is taken -> the
    # next free global rank past the survivors.
    assert d.joiners == (1, 4)
    assert d.intents == {1: k1, 4: k2}
    assert d.members == (0, 1, 2, 3, 4)
    # Donors: the two lowest-load survivors, lowest first (donor 0
    # ships the META frame).
    assert d.donors == (2, 3)
    assert d.hosts[1] == "ha|1" and d.hosts[4] == "hb|2"
    # Disagreeing votes can never admit: step -1 tells everyone to
    # consume the intents and move on.
    votes[3]["step"] = 9
    d2 = c._decide(10, trig, votes)
    assert d2.step == -1 and d2.joiners == ()


# ---------------------------------------------------------------------------
# Abort paths: survivors stay unharmed.
# ---------------------------------------------------------------------------


def test_vote_timeout_aborts_grow(monkeypatch):
    monkeypatch.setenv("CGX_ELASTIC", "1")
    monkeypatch.setenv("CGX_JOIN_TIMEOUT_MS", "500")
    cfg.clear_registry()
    store = FakeStore()
    c, sup = _coordinator(store, 0, [0, 1])  # rank 1 will never vote
    elastic.announce_join(store, global_rank=5, host="hx|5")
    s = np.arange(8, dtype=np.float32)
    c.on_step_boundary(s, 0)
    c.on_step_boundary(s, 1)
    out = c.on_step_boundary(s, 2)  # join step: admit runs, times out
    assert np.array_equal(out, s)
    assert rdz._read(store, "cgxjoin/g1/outcome") == "abort"
    assert sup.group.reconfigures == []
    assert c.consumed == 1
    assert metrics.get("cgx.elastic.join_aborts") >= 1
    # The consumed watermark holds: later boundaries never re-trigger.
    c.on_step_boundary(s, 3)
    assert c._trigger is None


def test_joiner_never_acks_aborts_and_survivors_carry_on(monkeypatch):
    monkeypatch.setenv("CGX_ELASTIC", "1")
    monkeypatch.setenv("CGX_JOIN_TIMEOUT_MS", "700")
    cfg.clear_registry()
    store = FakeStore()
    coords = {r: _coordinator(store, r, [0, 1]) for r in (0, 1)}
    elastic.announce_join(store, global_rank=4, host="hx|4")
    barrier = threading.Barrier(2, timeout=30)
    errs = {}

    def survivor(rank):
        try:
            c, _ = coords[rank]
            s = np.zeros(4, np.float32)
            for step in range(4):
                barrier.wait()
                c.on_step_boundary(s, step)
        except Exception:  # pragma: no cover - surfaced via errs
            errs[rank] = traceback.format_exc()

    ts = [threading.Thread(target=survivor, args=(r,)) for r in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
        assert not t.is_alive()
    assert errs == {}, errs
    assert rdz._read(store, "cgxjoin/g1/outcome") == "abort"
    for r in (0, 1):
        c, sup = coords[r]
        assert sup.group.reconfigures == []
        assert sup.generation == 0
        assert c.consumed == 1
    assert metrics.get("cgx.elastic.join_aborts") >= 1
    assert metrics.get("cgx.elastic.triggers") == 1  # no re-trigger


# ---------------------------------------------------------------------------
# Full commit round: hand-rolled protocol joiner, bit-identity, reaping.
# ---------------------------------------------------------------------------


def _grad(step):
    return np.float32(0.5) * np.arange(64, dtype=np.float32) + np.float32(step)


def test_full_join_round_is_bit_identical_and_reaps(monkeypatch):
    monkeypatch.setenv("CGX_ELASTIC", "1")
    monkeypatch.setenv("CGX_JOIN_TIMEOUT_MS", "20000")
    cfg.clear_registry()
    store = FakeStore()
    coords = {r: _coordinator(store, r, [0, 1]) for r in (0, 1)}
    barrier = threading.Barrier(2, timeout=30)
    n_steps, errs, finals = 6, {}, {}

    def survivor(rank):
        try:
            c, _ = coords[rank]
            state = np.arange(64, dtype=np.float32)
            for step in range(n_steps):
                barrier.wait()
                state = c.on_step_boundary(state, step)
                state = state + _grad(step)
            finals[rank] = state
        except Exception:  # pragma: no cover
            errs[rank] = traceback.format_exc()

    # Announced before the survivors start (as in the abort test above):
    # from its own thread, on a loaded machine, the announcement could come
    # after the survivors' six boundaries, and nobody was left to admit it.
    k = elastic.announce_join(store, global_rank=2, host="joinerhost|99")

    def joiner():
        try:
            akey = elastic._admit_key(k)
            deadline = time.monotonic() + 20
            while not rdz._flag_set(store, akey):
                assert time.monotonic() < deadline, "never admitted"
                time.sleep(0.01)
            admit = json.loads(rdz._read(store, akey))
            decision = elastic.JoinDecision.from_json(json.dumps(admit))
            me = int(admit["you"])
            jbase = f"{elastic.JOIN_PREFIX}/g{decision.generation}"
            store.add(f"{jbase}/jack", 1)
            while not rdz._flag_set(store, f"{jbase}/outcome"):
                assert time.monotonic() < deadline, "no outcome"
                time.sleep(0.01)
            assert rdz._read(store, f"{jbase}/outcome") == "commit"
            streams = [
                elastic._stream_name(decision.generation, me, di)
                for di in range(len(decision.donors))
            ]
            meta, bufs = elastic._SnapshotReceiver(
                store, streams, deadline).receive()
            state, step = elastic._decode_into_skeleton(
                np.zeros(64, np.float32), meta, bufs)
            rdz._publish(store, f"{jbase}/shmok{me}", "1")
            store.add(f"{jbase}/ready", 1)
            while int(store.add(f"{jbase}/ready", 0)) < len(decision.members):
                assert time.monotonic() < deadline, "ready barrier"
                time.sleep(0.01)
            for idx in range(step, n_steps):
                state = state + _grad(idx)
            finals["joiner"] = state
            finals["join_step"] = step
            finals["me"] = me
        except Exception:  # pragma: no cover
            errs["joiner"] = traceback.format_exc()

    ts = [threading.Thread(target=survivor, args=(r,)) for r in (0, 1)]
    ts.append(threading.Thread(target=joiner))
    for t in ts:
        t.start()
    for t in ts:
        t.join(40)
        assert not t.is_alive()
    assert errs == {}, errs
    assert finals["me"] == 2
    # Post-join state is bit-identical on every rank to a rank that was
    # never gone.
    assert np.array_equal(finals[0], finals[1])
    assert np.array_equal(finals["joiner"], finals[0])
    for r in (0, 1):
        c, sup = coords[r]
        assert sup.generation == 1
        assert sup.group.global_ranks == [0, 1, 2]
        (members, gen, joiner_info) = sup.group.reconfigures[0]
        assert (members, gen) == ([0, 1, 2], 1)
        assert joiner_info == {2: "joinerhost|99"}
    assert metrics.get("cgx.elastic.grows") >= 1
    assert metrics.get("cgx.elastic.joins") == 0  # hand-rolled joiner
    # Store-key hygiene: the NEXT generation bump retires every g1 join
    # key and the consumed intent/admit records.
    assert any(k.startswith("cgxjoin/g1/") for k in store.keys())
    rdz.reap_all(store, 1)
    leftovers = [
        k for k in store.keys()
        if k.startswith("cgxjoin/g1/")
        or k.startswith("cgxelastic/intents/1")
        or k.startswith("cgxelastic/admit/")
        or k.startswith("cgxelastic/trig/")
    ]
    assert leftovers == [], leftovers
    assert metrics.get("cgx.elastic.keys_reaped") > 0


def test_rendezvous_bumps_reap_join_keys_across_generations():
    """Satellite (b): counting keys across three generation bumps — the
    claim winner's reap cascades into the join namespace via the
    registered reaper."""
    store = FakeStore()
    # Plant a finished generation-0 join round.
    d = elastic.JoinDecision(
        generation=0, members=(0, 1), survivors=(0,), joiners=(1,),
        donors=(0,), hosts={0: "h|1", 1: "h|2"}, intents={1: 1},
        intents_n=1, step=4, bits=0, bucket=0,
        trigger_key=elastic._trigger_key(0, 0),
    )
    rdz._publish(store, "cgxjoin/g0/decision", d.to_json())
    rdz._publish(store, elastic._intent_key(1), "{}")
    rdz._publish(store, elastic._admit_key(1), "{}")
    rdz._publish(store, d.trigger_key, "{}")
    rdz._publish(store, "cgxjoin/g0/v0", "{}")
    store.add("cgxjoin/g0/jack", 1)
    for g in (1, 2, 3):
        rdz.negotiate(store, generation=g, me=0, participants=[0],
                      timeout_s=5.0, poll_s=0.01)
        stale = [
            k for k in store.keys()
            if k.startswith(f"cgxrdz/g{g - 1}/")
            or k.startswith(f"cgxjoin/g{g - 1}/")
        ]
        assert stale == [], (g, stale)
    assert not any(k.startswith("cgxelastic/intents/1") for k in store.keys())
    assert not any(k.startswith("cgxelastic/admit/") for k in store.keys())
    # Only the current generation's rendezvous keys remain.
    old = [k for k in store.keys()
           if k.startswith(("cgxrdz/g0/", "cgxrdz/g1/", "cgxrdz/g2/"))]
    assert old == []


# ---------------------------------------------------------------------------
# Chaos soak: 4 bridge ranks, preempt + rejoin + leave again.
# ---------------------------------------------------------------------------

_EL_WS = 4
_EL_NUMEL = 4096
# Preempt OFF the snapshot cadence (snapshots at even steps) so the
# shrink rollback has real distance, exactly like the ISSUE 5 soak.
_EL_KILL_STEP = 5
_EL_RESPAWN_S = 1.5
_EL_TAIL = 12       # steps everyone runs past the join step
_EL_PHASE_B = 10    # steps the survivors run after rank 1 leaves again
_EL_STEP_SLEEP = 0.2
_EL_MAX_STEPS = 200


def _el_grad(global_rank: int, step: int) -> np.ndarray:
    rng = np.random.default_rng(1000 * (global_rank + 1) + step)
    return rng.normal(size=_EL_NUMEL).astype(np.float32)


def _el_step_fn(states, gens, sleep_s):
    import torch

    def step_fn(group, state, idx):
        states[idx] = state.copy()
        gens[idx] = group.generation
        t = torch.from_numpy(_el_grad(group.global_rank, idx).copy())
        group.allreduce([t]).wait()
        if sleep_s:
            time.sleep(sleep_s)
        return state - 0.01 * t.numpy()

    return step_fn


def _el_env(mdir):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CGX_BRIDGE_TIMEOUT_MS"] = "2500"
    os.environ["CGX_RECOVERY_RETRIES"] = "1"
    os.environ["CGX_RECOVERY_BACKOFF_MS"] = "50"
    os.environ["CGX_SNAPSHOT_EVERY"] = "2"
    os.environ["CGX_METRICS_DIR"] = mdir
    os.environ["CGX_COMPRESSION_QUANTIZATION_BITS"] = "4"
    os.environ["CGX_ELASTIC"] = "1"
    os.environ["CGX_JOIN_TIMEOUT_MS"] = "20000"
    # The soak runs ~100 steps of collectives; the default 512-event
    # ring would age the mid-run grow/rejoin events out of the dump.
    os.environ["CGX_FLIGHTREC_CAP"] = "8192"


def _el_wait_crcs(store, tag, ranks, timeout_s=120.0):
    vals = {}
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for r in ranks:
            if r not in vals:
                try:
                    vals[r] = int(store.get(f"cgxtest/{tag}/{r}").decode())
                except Exception:
                    pass
        if len(vals) == len(ranks):
            return vals
        time.sleep(0.05)
    raise RuntimeError(f"crc exchange {tag}: only {sorted(vals)} of {ranks}")


def _el_main(rank: int, initfile: str, mdir: str, outfile: str, q) -> None:
    try:
        sys.path.insert(0, _REPO)
        _el_env(mdir)
        if rank == 1:
            os.environ["CGX_FAULTS"] = (
                f"preempt:{_EL_RESPAWN_S}s@rank=1@step={_EL_KILL_STEP}"
            )
            os.environ[
                "CGX_PREEMPT_RESPAWN"
            ] = (f"{sys.executable} {os.path.abspath(__file__)} "
                 f"--joiner-child {initfile} {outfile} {mdir}")
            # The detached respawner re-runs this file as a script whose
            # module-level imports need the repo on the path.
            os.environ["PYTHONPATH"] = os.pathsep.join(
                [_REPO] + [p for p in
                           os.environ.get("PYTHONPATH", "").split(os.pathsep)
                           if p]
            )
        import datetime

        import torch.distributed as dist

        from torch_cgx_tpu.robustness import elastic as el
        from torch_cgx_tpu.robustness import faults as faults_mod
        from torch_cgx_tpu.robustness.supervisor import RecoverySupervisor
        from torch_cgx_tpu.torch_backend.backend import ProcessGroupCGX
        from torch_cgx_tpu.utils.logging import metrics as m

        store = dist.FileStore(initfile, _EL_WS)
        pg = ProcessGroupCGX(
            store, rank, _EL_WS, datetime.timedelta(seconds=60)
        )
        sup = RecoverySupervisor(store, pg)
        el.ElasticCoordinator(store, sup)
        states: dict = {}
        gens: dict = {}
        fn = _el_step_fn(states, gens, _EL_STEP_SLEEP)
        state = np.zeros(_EL_NUMEL, np.float32)
        step, end, max_wall = 0, None, 0.0
        while True:
            t0 = time.monotonic()
            state = sup.run_steps(state, 1, fn, start_step=step)
            max_wall = max(max_wall, time.monotonic() - t0)
            step += 1
            if end is None and sup.generation >= 2:
                js = min(i for i, g in gens.items() if g >= 2)
                end = js + _EL_TAIL
            if end is not None and step >= end:
                break
            if step >= _EL_MAX_STEPS:
                raise RuntimeError(
                    f"rank {rank}: the joiner never arrived within "
                    f"{_EL_MAX_STEPS} steps (generation {sup.generation})"
                )
        problems = []
        js = min(i for i, g in gens.items() if g >= 2)
        rb1 = min(i for i, g in gens.items() if g == 1)
        if sup.generation != 2:
            problems.append(f"generation {sup.generation} != 2 after grow")
        if sorted(sup.survivors) != [0, 1, 2, 3]:
            problems.append(f"survivors {sup.survivors} != [0,1,2,3]")
        if rb1 > _EL_KILL_STEP:
            problems.append(f"rollback step {rb1} > kill step")
        if m.get("cgx.elastic.grows") < 1:
            problems.append("no grow counted")
        if m.get("cgx.recovery.rejoin_rungs") < 1:
            problems.append("rejoin rung never preferred for the suspect")
        # Survivors never stall longer than the join bound: the worst
        # single step covers one bridge timeout + the grow rendezvous,
        # both far under CGX_JOIN_TIMEOUT_MS.
        if max_wall > 15.0:
            problems.append(f"a step stalled {max_wall:.1f}s")
        endA = end
        if rank == 0:
            store.set("cgxtest/bounds", json.dumps(
                {"rb1": rb1, "js": js, "endA": endA}))
        store.set(f"cgxtest/crcA/{rank}", str(zlib.crc32(state.tobytes())))
        crcs = _el_wait_crcs(store, "crcA", [0, 1, 2, 3])
        if len(set(crcs.values())) != 1:
            problems.append(f"post-join state diverged across ranks: {crcs}")
        # -- control replays: fault-free era-by-era reruns chained on
        # the rolled-back anchor state. Gradients are state-independent,
        # so the joiner (whose history starts at the join step) can
        # participate in the ws-4 era's collectives from its own anchor;
        # every era starts at a reconfigure (fresh error feedback),
        # matching the fresh control groups.
        os.environ.pop("CGX_FAULTS", None)
        faults_mod.reset_injectors()
        cfn = _el_step_fn({}, {}, 0.0)
        # Only ranks 0/2/3 reach this point: rank 1 died at the preempt
        # and its respawn runs _joiner_child_main instead.
        pgA = ProcessGroupCGX(
            store, [0, 2, 3].index(rank), 3,
            datetime.timedelta(seconds=120),
            generation=600, global_ranks=[0, 2, 3],
        )
        control = states[rb1].copy()
        for idx in range(rb1, js):
            control = cfn(pgA, control, idx)
        pgB = ProcessGroupCGX(
            store, rank, _EL_WS, datetime.timedelta(seconds=120),
            generation=601, global_ranks=[0, 1, 2, 3],
        )
        for idx in range(js, endA):
            control = cfn(pgB, control, idx)
        if not np.array_equal(state, control):
            problems.append(
                "phase A state differs from fault-free control replay "
                f"(max abs diff {np.abs(state - control).max()})"
            )
        pgA.shutdown()
        pgB.shutdown()
        # -- phase B: rank 1 leaves again (its process exits after the
        # control); the survivors shrink back and finish.
        stateB = sup.run_steps(state, _EL_PHASE_B, fn, start_step=endA)
        if sup.generation != 3:
            problems.append(f"generation {sup.generation} != 3 after "
                            "second shrink")
        if sorted(sup.survivors) != [0, 2, 3]:
            problems.append(f"final survivors {sup.survivors} != [0,2,3]")
        rb3 = min(i for i, g in gens.items() if g == 3)
        pgC = ProcessGroupCGX(
            store, [0, 2, 3].index(rank), 3,
            datetime.timedelta(seconds=120),
            generation=602, global_ranks=[0, 2, 3],
        )
        controlB = states[rb3].copy()
        for idx in range(rb3, endA + _EL_PHASE_B):
            controlB = cfn(pgC, controlB, idx)
        if not np.array_equal(stateB, controlB):
            problems.append(
                "phase B state differs from fault-free control replay "
                f"(max abs diff {np.abs(stateB - controlB).max()})"
            )
        store.set(f"cgxtest/crcB/{rank}",
                  str(zlib.crc32(stateB.tobytes())))
        crcsB = _el_wait_crcs(store, "crcB", [0, 2, 3])
        if len(set(crcsB.values())) != 1:
            problems.append(f"final state diverged: {crcsB}")
        # Zero checkpoint files on disk: the whole lifecycle ran from
        # memory — nothing checkpoint-shaped may exist anywhere the run
        # writes.
        ckpt_files = [
            p for p in glob.glob(os.path.join(mdir, "**", "*"),
                                 recursive=True)
            if "ckpt" in os.path.basename(p).lower()
            or "checkpoint" in os.path.basename(p).lower()
        ]
        if ckpt_files:
            problems.append(f"checkpoint files on disk: {ckpt_files}")
        pgC.shutdown()
        pg.shutdown()
        q.put((rank, "; ".join(problems) or None))
    except Exception:
        q.put((rank, traceback.format_exc()))


def _joiner_child_main(initfile: str, outfile: str, mdir: str) -> None:
    """Entry point for the respawned rank 1 (CGX_PREEMPT_RESPAWN runs
    this file as a script). Reports through ``outfile`` — the detached
    process has no queue to the pytest parent."""
    report = {"problems": []}
    try:
        sys.path.insert(0, _REPO)
        os.environ.pop("CGX_FAULTS", None)
        os.environ.pop("CGX_PREEMPT_RESPAWN", None)
        _el_env(mdir)
        import datetime

        import torch.distributed as dist

        from torch_cgx_tpu.robustness import elastic as el
        from torch_cgx_tpu.robustness.supervisor import RecoverySupervisor
        from torch_cgx_tpu.torch_backend.backend import ProcessGroupCGX
        from torch_cgx_tpu.utils.logging import metrics as m

        store = dist.FileStore(initfile, _EL_WS)
        t0 = time.perf_counter()
        res = el.join(store, np.zeros(_EL_NUMEL, np.float32), global_rank=1)
        join_ms = (time.perf_counter() - t0) * 1000.0
        problems = report["problems"]
        if res.generation != 2:
            problems.append(f"joined at generation {res.generation} != 2")
        if res.members != [0, 1, 2, 3]:
            problems.append(f"members {res.members}")
        sup = RecoverySupervisor(store, res.group)
        el.ElasticCoordinator(store, sup,
                              consumed=res.decision.intents_n)
        states: dict = {}
        gens: dict = {}
        fn = _el_step_fn(states, gens, _EL_STEP_SLEEP)
        endA = res.step + _EL_TAIL
        final = sup.run_steps(res.state.copy(), endA - res.step, fn,
                              start_step=res.step)
        store.set("cgxtest/crcA/1", str(zlib.crc32(final.tobytes())))
        crcs = _el_wait_crcs(store, "crcA", [0, 1, 2, 3])
        if len(set(crcs.values())) != 1:
            problems.append(f"joiner diverged from survivors: {crcs}")
        bounds = json.loads(store.get("cgxtest/bounds").decode())
        if bounds["js"] != res.step:
            problems.append(
                f"survivors saw join step {bounds['js']}, joiner "
                f"resumed at {res.step}"
            )
        # The joiner's control: a fault-free replay of the ws-4 era from
        # its received state must reproduce its final state bit-for-bit
        # — the snapshot pages handed it exactly the state a rank that
        # was never gone would hold.
        pgB = ProcessGroupCGX(
            store, 1, _EL_WS, datetime.timedelta(seconds=120),
            generation=601, global_ranks=[0, 1, 2, 3],
        )
        cfn = _el_step_fn({}, {}, 0.0)
        control = res.state.copy()
        for idx in range(res.step, endA):
            control = cfn(pgB, control, idx)
        if not np.array_equal(final, control):
            problems.append(
                "joiner state differs from fault-free control "
                f"(max abs diff {np.abs(final - control).max()})"
            )
        if m.get("cgx.elastic.joins") < 1:
            problems.append("join counter not bumped")
        report.update(
            generation=res.generation, step=res.step, join_ms=join_ms,
            crc=crcs.get(1),
        )
        pgB.shutdown()
        # Leave WITHOUT ceremony: this exit IS the soak's second shrink.
    except Exception:
        report["problems"].append(traceback.format_exc())
    tmp = outfile + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f)
    os.rename(tmp, outfile)
    os._exit(1 if report["problems"] else 0)


# Slow tier: ~45 s of real-process soak on a 1-core box — the unit
# tests above cover every protocol leg in-process; run via -m faults
# or the full (unfiltered) sweep.
@pytest.mark.slow
@pytest.mark.torch_bridge
def test_chaos_soak_preempt_rejoin_shrink(tmp_path):
    """ISSUE 16 chaos acceptance: 4-rank bridge run, rank 1 SIGKILLed
    mid-training by ``preempt`` and respawned by the detached respawner
    — it rejoins at a bumped generation with zero checkpoint files on
    disk, survivors never stall past the join bound, every era is
    bit-identical to fault-free control replays, and when the rejoined
    rank leaves again the survivors shrink back and finish clean."""
    mdir = str(tmp_path / "metrics")
    outfile = str(tmp_path / "joiner.json")
    initfile = tempfile.mktemp(prefix="cgx_elastic_store_")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_el_main, args=(r, initfile, mdir, outfile, q))
        for r in range(_EL_WS)
    ]
    for p in procs:
        p.start()
    results = {}
    for _ in range(3):  # rank 1 preempts; its respawn reports via file
        rank, err = q.get(timeout=300)
        results[rank] = err
    for p in procs:
        p.join(timeout=60)
        if p.is_alive():
            p.terminate()
    assert sorted(results) == [0, 2, 3], results
    for rank, err in sorted(results.items()):
        assert err is None, f"rank {rank}: {err}"
    from torch_cgx_tpu.robustness.faults import KILL_EXIT_CODE

    assert procs[1].exitcode == KILL_EXIT_CODE, procs[1].exitcode
    # The detached joiner's report.
    deadline = time.monotonic() + 120
    while not os.path.exists(outfile) and time.monotonic() < deadline:
        time.sleep(0.2)
    assert os.path.exists(outfile), "the respawned joiner never reported"
    joiner = json.load(open(outfile))
    assert joiner["problems"] == [], joiner["problems"]
    assert joiner["generation"] == 2
    assert joiner["join_ms"] > 0
    if os.path.exists(initfile):
        os.unlink(initfile)
    # -- flight recorder: the whole membership story is audited --
    path = os.path.join(mdir, "flightrec-rank0.jsonl")
    assert os.path.exists(path), (
        os.listdir(mdir) if os.path.isdir(mdir) else "no metrics dir"
    )
    events = [json.loads(line) for line in open(path)]
    el_ev = [e for e in events if e.get("kind") == "elastic"]
    assert any(e.get("phase") == "grow" for e in el_ev), el_ev
    rec = [e for e in events if e.get("kind") == "recovery"]
    assert any(e.get("phase") == "rejoin_rung" for e in rec), \
        [e.get("phase") for e in rec]
    assert any(
        e.get("phase") == "evicted_peers" and e.get("evicted") == [1]
        for e in rec
    )
    # -- report CLI renders the membership section --
    import subprocess as sp

    proc = sp.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"),
         mdir, "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    js = json.loads(proc.stdout)
    assert js.get("membership"), js.keys()
    assert js["membership"]["grows"] >= 1
    assert js["membership"]["joiners"], js["membership"]
    text = sp.run(
        [sys.executable, os.path.join(_REPO, "tools", "cgx_report.py"), mdir],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert text.returncode == 0
    assert "== membership" in text.stdout


if __name__ == "__main__":
    if len(sys.argv) >= 5 and sys.argv[1] == "--joiner-child":
        _joiner_child_main(sys.argv[2], sys.argv[3], sys.argv[4])
    else:  # pragma: no cover
        sys.exit(f"usage: {sys.argv[0]} --joiner-child "
                 "<initfile> <outfile> <mdir>")
