"""Self-healing recovery supervisor: the policy ladder over the bridge.

PR 1 gave the data plane *detection* — bounded waits, heartbeats, wire
checksums — but every detected fault was still terminal: a
``BridgeTimeoutError`` propagated out of the Work future and the job
died, exactly the all-or-nothing failure model the reference inherits
from MPI. This module turns those raises into a recoverable event. Per
rank, a :class:`RecoverySupervisor` drives training steps through a
four-rung policy ladder:

1. **Retry** (``CGX_RECOVERY_RETRIES`` / ``CGX_RECOVERY_BACKOFF_MS``) —
   lives INSIDE the data plane (``backend._wait_key`` /
   ``ShmChannel._bounded_get``): an expired bounded wait with no
   heartbeat-named suspect is re-armed with exponential backoff +
   jitter. Transient faults (``flap``, ``slow_rank``) heal locally, with
   zero cross-rank coordination and zero wire change.
2. **Degrade** (``CGX_RECOVERY_CORRUPT_THRESHOLD``) — repeated
   ``WireCorruptionError`` marks the shm byte plane untrustworthy; the
   supervisor's next rendezvous carries a degrade vote and every
   survivor drops to the store transport together.
3. **Evict + reconfigure** — on an unrecoverable timeout the suspects
   named by the heartbeat go into a store-based generation rendezvous
   (:mod:`.rendezvous`); the agreed survivor set rebuilds the group IN
   PLACE (:meth:`ProcessGroupCGX.reconfigure`) at a bumped generation:
   all store keys move to the ``g<N>/`` namespace, shm headers carry the
   epoch tag and stale traffic is discarded, SRA/Ring chunk splits
   re-derive from the shrunk world size, and the JAX-side layout/trace
   caches are invalidated through the registry version they key on.
4. **Rollback + replay** (``CGX_SNAPSHOT_EVERY``) — the step driver
   rolls the training state back to the **rendezvous-agreed** replay
   step (each vote carries the voter's newest snapshot step; the
   decision pins the minimum, because survivors can drift whole steps
   apart around a fault) and deterministically replays from the matching
   in-memory snapshot (``checkpoint.snapshot_in_memory``,
   compression-registry included); with stochastic rounding off the
   replayed steps are bit-identical to a fault-free survivor-only run
   (tested in ``tests/test_supervisor.py``).

With every recovery knob unset the supervisor is inert and nothing in
the data plane changes: generation stays 0 (legacy key/header bytes),
no snapshots are taken, failures raise exactly as in PR 1.
"""

from __future__ import annotations

import dataclasses
import sys
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .. import config as cfg
from ..observability import flightrec
from ..observability import health as health_mod
from ..observability import timeline
from ..utils.logging import get_logger, metrics
from . import rendezvous as rdz
from .errors import (
    BridgeTimeoutError,
    RecoveryFailedError,
    StaleGenerationError,
    WireCorruptionError,
)

log = get_logger()

RECOVERABLE = (BridgeTimeoutError, WireCorruptionError, StaleGenerationError)


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Knobs of the ladder (all env-derived by default)."""

    retries: int = 0
    backoff_ms: float = 100.0
    corrupt_threshold: int = 2
    snapshot_every: int = 0
    snapshot_keep: int = 4  # rollback points retained (see recover())
    max_generations: int = 8  # ladder depth bound: evictions per run
    rendezvous_timeout_s: Optional[float] = None  # None = derived

    @classmethod
    def from_env(cls) -> "RecoveryPolicy":
        return cls(
            retries=cfg.recovery_retries(),
            backoff_ms=cfg.recovery_backoff_ms(),
            corrupt_threshold=cfg.recovery_corrupt_threshold(),
            snapshot_every=cfg.snapshot_every(),
        )

    def derived_rendezvous_timeout_s(self) -> float:
        """Long enough for the slowest survivor to exhaust its own retry
        rung and reach the rendezvous: (retries + 1) bridge timeouts,
        doubled for scheduling slack, floor 10 s."""
        if self.rendezvous_timeout_s is not None:
            return self.rendezvous_timeout_s
        bt = cfg.bridge_timeout_ms()
        per_wait = (bt / 1000.0) if bt else 300.0
        return max(10.0, 2.0 * per_wait * (self.retries + 1) + 5.0)


def invalidate_trace_caches() -> None:
    """World-size shrink invalidation: bump the config registry version —
    the key every trace-time cache (``make_train_step``'s build cache,
    ``allreduce._tree_layout``'s LRU) already includes — and clear the
    layout LRU outright when the JAX side is loaded, along with the
    flightrec qerr subsample cadence (post-recovery programs are a new
    qerr stream; stale per-layer counters would subsample it on the dead
    generation's phase). Lazy: a torch-only bridge process must not
    import jax here."""
    cfg._bump_registry_version()
    if "torch_cgx_tpu.parallel.allreduce" in sys.modules:
        ar = sys.modules["torch_cgx_tpu.parallel.allreduce"]
        ar.invalidate_layout_cache("recovery reconfigure")
        ar.reset_qerr_sampling()
    elif "torch_cgx_tpu.parallel.schedule" in sys.modules:
        # allreduce.invalidate_layout_cache drops compiled schedules too;
        # this arm covers a process that loaded the schedule compiler
        # without the tree-allreduce layer (a stale chunk plan after a
        # reconfigure would wedge the pipelined in-flight window against
        # peers running the fresh world's plan).
        sys.modules["torch_cgx_tpu.parallel.schedule"].invalidate_schedule_cache(
            "recovery reconfigure"
        )
    # Step plans sit above the layout/schedule LRUs they were solved
    # for; the allreduce arm cascades into the planner already, so this
    # arm covers only a process that loaded the planner without the
    # tree-allreduce layer (the eager planned-program plane).
    if "torch_cgx_tpu.parallel.allreduce" not in sys.modules:
        planner = sys.modules.get("torch_cgx_tpu.parallel.planner")
        if planner is not None:
            planner.invalidate_plan_cache("recovery reconfigure")
    # Producer-fuse context: the configured mesh/axis name the dead
    # generation and stashed pre-quantized payloads hold retired traces'
    # tracers — deactivate and re-epoch so the first post-recovery build
    # reconfigures from the survivor mesh (the ISSUE 14 cascade pass
    # found this module unreachable from the ladder).
    fp = sys.modules.get("torch_cgx_tpu.ops.fused_producer")
    if fp is not None:
        fp.invalidate("recovery reconfigure")
    # The health engine's per-peer wait state is a pre-recovery stream
    # too: an evicted peer whose wait EWMA froze at the timeout value
    # would otherwise re-emit a phantom straggler event every cooldown
    # window for the rest of the run.
    health_mod.forget_peers()
    # Wire plane: derived per-edge state (resolution memo, the
    # dispatcher's numel/bits side table, EF zeroers and the closed-loop
    # controller's cadence) is a pre-recovery stream too — a stale edge
    # cadence after a reconfigure mirrors the qerr-cadence bug above.
    # Registered edge CONFIGS survive (they are configuration, not
    # state); config.reset_registries is the stronger reset.
    wire_edges = sys.modules.get("torch_cgx_tpu.wire.edges")
    if wire_edges is not None:
        wire_edges.reset_edge_state("recovery reconfigure")
    # Serving plane (PR 15): the decode-program LRU bakes page-pool
    # geometry and per-layer kv_page wire specs, and every live
    # PagedKvCache's page tables map sequences onto pool rows — both are
    # dead-generation state after a reconfigure. The generation bump the
    # page-table invalidation performs is what forces the scheduler to
    # drop its lanes and re-prefill (a stale page mapping must never be
    # gathered into a post-recovery decode step).
    serving_sched = sys.modules.get("torch_cgx_tpu.serving.scheduler")
    if serving_sched is not None:
        serving_sched.invalidate_decode_cache("recovery reconfigure")
    serving_kv = sys.modules.get("torch_cgx_tpu.serving.kv_cache")
    if serving_kv is not None:
        serving_kv.invalidate_page_tables("recovery reconfigure")
    # Topology classification memo: keyed on (mesh, axes, classifier fn),
    # none of which move when an eviction shrinks the world under an
    # unchanged mesh object — a stale hit can name an evicted rank as a
    # cross-slice leader (the PR 13 regression class).
    topo = sys.modules.get("torch_cgx_tpu.parallel.topology")
    if topo is not None:
        topo.invalidate_classification_cache("recovery reconfigure")
    # Async cross-slice plane: per-peer round bookkeeping and the pending
    # delta buffer describe the dead generation's membership — the plane
    # re-derives slice leaders from the survivor host map at the bumped
    # generation on its next outer boundary.
    async_plane = sys.modules.get("torch_cgx_tpu.parallel.async_plane")
    if async_plane is not None:
        async_plane.reset_planes("recovery reconfigure")
    # Critical-path analysis memo (ISSUE 17): a cached DAG attributes
    # against the dead generation's tracks — post-recovery spans land at
    # a bumped generation tag and must re-analyze from scratch.
    critpath = sys.modules.get("torch_cgx_tpu.observability.critpath")
    if critpath is not None:
        critpath.invalidate_critpath_cache("recovery reconfigure")
    # Memory ledger (ISSUE 18): the alloc/release window streams and
    # pool free-level trends describe the dead generation's regime —
    # carrying them across the epoch bump would fabricate a leak (the
    # abandoned arena regions release in a burst) or a phantom
    # exhaustion trend out of the reconfigure itself.
    mem = sys.modules.get("torch_cgx_tpu.observability.memledger")
    if mem is not None:
        mem.reset_ledger("recovery reconfigure")
    metrics.add("cgx.recovery.trace_cache_invalidations")


# Live supervisors, for the memory ledger's snapshot-ring sampler (the
# ledger never holds a strong ref — a torn-down supervisor must stay
# collectable). Dead supervisors self-evict.
# cgx-analysis: allow(orphan-memo) — weak liveness set: each member's snapshot ring is bounded by policy.snapshot_keep and drains with its owner; clearing the set itself would only blind the memory ledger to live rings
_LIVE_SUPERVISORS: "weakref.WeakSet" = weakref.WeakSet()


class RecoverySupervisor:
    """Per-rank recovery state machine layered over one
    :class:`~..torch_backend.backend.ProcessGroupCGX`.

    The supervisor owns the group handle (``.group``) because recovery
    can rebuild it; user code addresses peers by GLOBAL rank
    (``.global_rank``, ``.survivors``) which is stable across
    reconfigurations. Collectives must be driven synchronously through
    :meth:`run_steps` (one step's collectives complete before the next
    is issued) — the reconfiguration contract of
    ``ProcessGroupCGX.reconfigure``.
    """

    def __init__(
        self,
        store,
        group,
        *,
        policy: Optional[RecoveryPolicy] = None,
    ):
        self._store = store
        self._group = group
        self._policy = policy or RecoveryPolicy.from_env()
        self._corruptions = 0
        self._degraded = False
        # step -> checkpoint.MemorySnapshot, insertion-ordered, bounded
        # to policy.snapshot_keep. More than one is retained because the
        # rendezvous may pin the group's replay step BEHIND this rank's
        # newest snapshot (a rank whose collectives were all send-side
        # can run whole steps past a dead peer before anything blocks).
        self._snapshots: Dict[int, Any] = {}
        self._last_rollback_step: Optional[int] = None
        # Live health plane (PR 6): sustained straggler scores arrive as
        # suspect *hints* — evidence gathered BEFORE any bridge timeout
        # fires, merged into the eviction vote when the ladder runs.
        # global rank -> (monotonic receive time, score).
        self._suspect_hints: Dict[int, Tuple[float, float]] = {}
        # Elastic join plane (robustness/elastic.py): when a coordinator
        # is attached, run_steps gives it every step boundary — grow
        # decisions are step-synchronized across survivors.
        self._elastic = None
        health_mod.add_consumer(self.note_health_event)
        _LIVE_SUPERVISORS.add(self)

    # -- introspection ----------------------------------------------------

    @property
    def group(self):
        return self._group

    @property
    def policy(self) -> RecoveryPolicy:
        return self._policy

    @property
    def generation(self) -> int:
        return self._group.generation

    @property
    def global_rank(self) -> int:
        return self._group.global_rank

    @property
    def survivors(self) -> List[int]:
        return self._group.global_ranks

    @property
    def last_snapshot(self):
        if not self._snapshots:
            return None
        return self._snapshots[max(self._snapshots)]

    @property
    def last_rollback_step(self) -> Optional[int]:
        return self._last_rollback_step

    def attach_elastic(self, coordinator) -> None:
        """Hook an :class:`~.elastic.ElasticCoordinator` into the step
        loop (called by its constructor)."""
        self._elastic = coordinator

    # -- health hints (the observability→control handoff, PR 6) -----------

    HINT_TTL_S = 60.0

    def note_health_event(self, event) -> None:
        """Health-engine consumer (registered in ``__init__`` when the
        engine is running): a sustained straggler score against a peer —
        or an ``async_lag`` event naming a slice leader whose outer
        rounds stopped arriving (PR 13) — becomes suspect evidence for
        the next rendezvous, recorded in the black box the moment it
        arrives, which is typically long before any bounded wait expires
        (for async_lag, before any wait even EXISTS: the async plane
        never blocks on DCN)."""
        if getattr(event, "kind", None) not in ("straggler", "async_lag"):
            return
        suspect = getattr(event, "suspect", None)
        if suspect is None or suspect == self.global_rank:
            return
        self._suspect_hints[int(suspect)] = (
            time.monotonic(), float(event.value),
        )
        metrics.add("cgx.recovery.health_hints")
        flightrec.record(
            "recovery", phase="health_hint", suspect=int(suspect),
            score=float(event.value), generation=self.generation,
        )

    @property
    def suspect_hints(self) -> Dict[int, float]:
        """Fresh (within HINT_TTL_S) straggler hints: global rank ->
        score."""
        now = time.monotonic()
        # list(): the health evaluator thread inserts concurrently, and a
        # mid-iteration insert would raise exactly when a straggler event
        # fires during an active recovery vote.
        return {
            g: score for g, (t, score) in list(self._suspect_hints.items())
            if now - t <= self.HINT_TTL_S
        }

    # -- snapshots (rung 4 substrate) -------------------------------------

    def take_snapshot(self, step: int, state: Any) -> None:
        """Host-copy ``state`` as a rollback point (registry snapshot
        included — ``checkpoint.snapshot_in_memory``). The newest
        ``policy.snapshot_keep`` points are retained so a rendezvous can
        pin the replay step behind this rank's latest."""
        from .. import checkpoint as ckpt

        self._snapshots[int(step)] = ckpt.snapshot_in_memory(state, step)
        while len(self._snapshots) > max(self._policy.snapshot_keep, 1):
            del self._snapshots[min(self._snapshots)]
        metrics.add("cgx.recovery.snapshots")

    def rollback(self, to_step: Optional[int] = None):
        """(step, state) restored from a retained snapshot — the newest
        one, or exactly ``to_step`` when given (the rendezvous-agreed
        replay step); the registry snapshot is re-installed. Returns None
        when no matching snapshot exists."""
        if to_step is None:
            if not self._snapshots:
                return None
            snap = self._snapshots[max(self._snapshots)]
        else:
            snap = self._snapshots.get(int(to_step))
            if snap is None:
                return None
        from .. import checkpoint as ckpt

        state = ckpt.restore_in_memory(snap)
        metrics.add("cgx.recovery.rollbacks")
        return snap.step, state

    # -- the ladder -------------------------------------------------------

    def recover(self, exc: BaseException) -> rdz.Decision:
        """Walk rungs 2-3 for one detected failure: decide degrade vs
        evict, converge through the generation rendezvous, and
        reconfigure the group. (Rung 1 already ran inside the data plane;
        rung 4 is the caller's rollback to the returned decision's
        ``replay_step``, see :meth:`run_steps`.) Raises
        :class:`RecoveryFailedError` / :class:`EvictedError` when the
        group is beyond saving or this rank was voted out."""
        if self.generation + 1 > self._policy.max_generations:
            raise RecoveryFailedError(
                f"recovery ladder exhausted: {self.generation} generations "
                f"already spent (max_generations={self._policy.max_generations})"
            ) from exc
        suspects_local = list(getattr(exc, "suspects", ()) or ())
        globals_now = self._group.global_ranks
        suspects = [
            globals_now[r] for r in suspects_local if 0 <= r < len(globals_now)
        ]
        # Health-plane evidence: fresh sustained-straggler hints join the
        # vote — crucially covering the case where the timeout names no
        # suspect at all (cross-host peers have no heartbeat file).
        for g in sorted(self.suspect_hints):
            if g in globals_now and g not in suspects:
                suspects.append(g)
                metrics.add("cgx.recovery.health_hint_votes")
        # Rejoin rung (preferred over a bare evict when the suspect says
        # it is coming back): a preempted rank publishes a comeback
        # notice before dying. The shrink still proceeds — the group
        # cannot wait out a respawn — but the membership policy reserves
        # the rank's identity and the ladder records the softer rung, so
        # the respawned process re-enters through the elastic join at a
        # later generation instead of being forgotten.
        if cfg.elastic_enabled() and suspects:
            from . import elastic as elastic_mod

            rejoining = []
            for g in suspects:
                cb = elastic_mod.fresh_comeback(self._store, g)
                if cb is not None:
                    rejoining.append(g)
                    health_mod.membership_policy().expect_rejoin(
                        g,
                        float(cb.get("delay_s", 0.0))
                        + elastic_mod.REJOIN_GRACE_S,
                    )
            if rejoining:
                metrics.add("cgx.recovery.rejoin_rungs")
                flightrec.record(
                    "recovery", phase="rejoin_rung", suspects=rejoining,
                    generation=self.generation,
                )
                log.warning(
                    "recovery: suspect(s) %s announced a comeback — "
                    "shrinking now, rank reserved for rejoin", rejoining,
                )
        degrade_vote = False
        if isinstance(exc, WireCorruptionError):
            self._corruptions += 1
            degrade_vote = (
                not self._degraded
                and self._corruptions >= self._policy.corrupt_threshold
            )
        new_gen = self.generation + 1
        flightrec.record(
            "recovery", phase="detect", error=type(exc).__name__,
            generation=self.generation, suspects=suspects,
            degrade_vote=degrade_vote, message=str(exc)[:160],
        )
        t0 = time.perf_counter()
        decision = rdz.negotiate(
            self._store,
            generation=new_gen,
            me=self.global_rank,
            participants=globals_now,
            suspects=suspects,
            degrade=degrade_vote,
            snapshot_step=max(self._snapshots) if self._snapshots else None,
            timeout_s=self._policy.derived_rendezvous_timeout_s(),
        )
        timeline.record(
            "recovery.rendezvous", timeline.CAT_RECOVERY, t0,
            time.perf_counter() - t0, generation=new_gen,
            survivors=list(decision.survivors),
        )
        if decision.degrade and not self._degraded:
            self._group.degrade_to_store()
            self._degraded = True
        t1 = time.perf_counter()
        if decision.evicted:
            metrics.add("cgx.recovery.evictions", float(len(decision.evicted)))
        self._group.reconfigure(list(decision.survivors), new_gen)
        invalidate_trace_caches()
        # Hints served their purpose in this vote; the new generation's
        # evidence must come from post-recovery observations (an evicted
        # rank's hint would otherwise linger for HINT_TTL_S).
        self._suspect_hints.clear()
        timeline.record(
            "recovery.reconfigure", timeline.CAT_RECOVERY, t1,
            time.perf_counter() - t1, generation=new_gen,
            ws=len(decision.survivors),
        )
        if decision.evicted:
            # The black box is the eviction's audit trail: who was voted
            # out, by which generation, with what evidence before it.
            flightrec.record(
                "recovery", phase="evicted_peers",
                evicted=list(decision.evicted), generation=new_gen,
                survivors=list(decision.survivors),
            )
            flightrec.dump(reason="eviction")
        return decision

    def run_steps(
        self,
        state: Any,
        n_steps: int,
        step_fn: Callable[[Any, Any, int], Any],
        *,
        start_step: int = 0,
    ) -> Any:
        """Drive ``step_fn(group, state, step_idx) -> state`` for steps
        ``start_step .. start_step + n_steps`` through the full ladder.

        ``step_fn`` must treat ``state`` as read-only input and return the
        next state (on a failed step the returned value is discarded and
        the step re-runs from the rollback snapshot — in-place mutation
        would leak the failed attempt into the replay). Snapshots are
        taken every ``policy.snapshot_every`` steps, before the step runs.
        """
        step = start_step
        end = start_step + n_steps
        every = self._policy.snapshot_every
        while step < end:
            try:
                if self._elastic is not None:
                    # Elastic grow point: runs BEFORE the snapshot so a
                    # commit's grid-snapped state is what gets retained,
                    # and inside the try so a post-commit ready-barrier
                    # wedge walks the normal ladder (the joiners become
                    # the suspects).
                    state = self._elastic.on_step_boundary(state, step)
                # Cadence on the ABSOLUTE step index: a joiner's
                # run_steps starts mid-run (start_step = the join step),
                # and the rendezvous pins replay to the MINIMUM voted
                # snapshot step — survivors and joiners must snapshot
                # the same steps or a post-join recovery pins a point
                # the joiner never took.
                if every and step % every == 0:
                    self.take_snapshot(step, state)
                state = step_fn(self._group, state, step)
            except RECOVERABLE as e:
                log.warning(
                    "recovery: step %d failed with %s — running the "
                    "ladder", step, type(e).__name__,
                )
                decision = self.recover(e)
                target = decision.replay_step
                rb = self.rollback(target)
                if rb is None and target is not None:
                    # The survivors agreed to replay from `target` but
                    # this rank no longer retains that snapshot (it ran
                    # whole steps past the fault — send-only collectives
                    # never blocked — and aged the point out of the
                    # ring). Replaying from anywhere else would pair
                    # wrong-step payloads under identical post-recovery
                    # keys: die loudly instead.
                    raise RecoveryFailedError(
                        f"survivors agreed to replay from step {target} "
                        f"but this rank retains snapshots "
                        f"{sorted(self._snapshots) or 'none'} — "
                        "deterministic replay is impossible (raise "
                        "snapshot_keep or CGX_SNAPSHOT_EVERY cadence)"
                    ) from e
                if rb is not None:
                    replay_from, state = rb
                    self._last_rollback_step = replay_from
                    metrics.add(
                        "cgx.recovery.replayed_steps",
                        float(step - replay_from),
                    )
                    flightrec.record(
                        "recovery", phase="rollback", from_step=step,
                        to_step=replay_from, generation=self.generation,
                    )
                    timeline.instant(
                        "recovery.rollback", from_step=step,
                        to_step=replay_from, generation=self.generation,
                    )
                    step = replay_from
                else:
                    flightrec.record(
                        "recovery", phase="resume_no_snapshot",
                        step=step, generation=self.generation,
                    )
                continue
            step += 1
        return state
