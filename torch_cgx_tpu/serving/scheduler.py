"""Continuous-batching decode scheduler over the paged quantized KV pool.

The scheduler serves a model adapter (``serving/adapter.py``) through the
compiled programs built for it (``serving/programs.py``): it decides which
request holds which lane and which pool rows, dispatches the programs over
the state they donate to one another, and reads what they produced. Nothing
here knows what a stream means or what a program computes. Admission and
eviction happen per step around the one decode program (continuous
batching): completed lanes free their pages back to the refcounted pool and
a waiting request takes the lane on the next step, so the batch never drains
to refill.

A tick queues everything before it reads anything. The programs are
ordered on the device by the state they donate to one another, so the
host dispatches prefill, lane write, the next prefill, its lane write,
the commit of full tails (decided from tail lengths the host counts
itself: set at the lane write, plus one a step, zero at a commit; the
program takes the few lanes that filled by index, ``ServeConfig.commit_lanes``
a call, and quantizes their rows alone) and the
decode step, and only then reads: the first tokens in admission order,
then the step's tokens. A first token is stamped, and can finish its
request, when the host holds it. When no lane is free and none produces
its last token at the step just dispatched, nothing could be admitted
before the next step whatever arrives, so that step is dispatched too,
before the read: emit, the caller's work between ticks and the next
dispatch then run under a step, not between two
(:meth:`ContinuousBatchScheduler._runs_ahead`; never more than one step
beyond the one being read). With ``eos_token`` set a lane can finish
unannounced under a step so queued: the token that step decodes for it is
dropped (``cgx.serve.decode.discarded_tokens``).

An adapter whose step runs a block of ``L`` positions a lane
(``adapter.block_tokens``, docs/SERVING.md "Blocks") yields 0 tokens a lane
a step or ``L``, and the device decides which. The host's counts follow what
each read says: beside the tokens a lane emitted the step's output names the
lanes whose block is all known now, which are the lanes that store at the
NEXT step, so the tail lengths and the tokens left are counted a step before
the store is read (:meth:`ContinuousBatchScheduler._account_block_step`), and
the commit of full tails and the step queued ahead stay what they are. With
``block_tokens`` 1 none of that runs.

The tick is cut where the host stops: a ``trace_span`` at every dispatch
(``serve.prefill.forward``, ``serve.admit_lane``, ``serve.dispatch.commit``,
``serve.dispatch.step``) and at every blocking read
(``serve.prefill.first_token``, ``serve.wait.step``). Four accounts are read
from the cuts (docs/OBSERVABILITY.md): the tick's (with the caller's time
between two ticks it adds up to the loop's wall time, and a tick far over
what its own programs usually cost leaves one warning line that says where
its time went: :meth:`ContinuousBatchScheduler._close_tick`), the first
token's (what was dispatched between a request's lane write and the read of
its token, ``cgx.serve.ttft_behind_s``), the unfed device's (from a read that
leaves nothing queued to the next dispatch that carries work,
``cgx.serve.device_unfed_s``) and the device's (every dispatch leaves a
record of what the device owes, every read that blocked closes the interval
since the last one, and an interval that held one program is that program's
time on the device, with no profiler: ``cgx.serve.device.*``,
:meth:`ContinuousBatchScheduler._note_read`).

Requests arrive with their KV either computed here (local prefill — the
colocated mode, also the FAILOVER path) or shipped by a disaggregated
prefill worker over the :mod:`.transport` counter streams; decode polls
those streams without ever blocking, and a stream that stalls past
``CGX_SERVE_PREFILL_TIMEOUT_MS`` fails over to local prefill instead of
wedging admission (``cgx.serve.prefill_failovers`` — the serving plane's
recovery-ladder rung; docs/SERVING.md).

The compiled decode/commit/prefill programs live in a module-level LRU
(``_PROGRAM_CACHE``) keyed by :func:`_program_key` — the adapter's kind
and model geometry, serve geometry, the per-layer resolved ``kv_page`` wire configs
(registry-versioned) and ``config.trace_knob_fingerprint()``, so a knob
flip or an SLO-controller re-solve can never hit a stale staged decode
step (the ISSUE 14/15 knob→cache-key completeness contract; the cache is
a declared analyzer surface). ``supervisor.invalidate_trace_caches``
cascades into :func:`invalidate_decode_cache` and the page-table
invalidation (``kv_cache.invalidate_page_tables``); the scheduler
detects a bumped cache generation at the next step and re-derives every
lane (running requests re-prefill — a stale page mapping is never
served).
"""

from __future__ import annotations

import dataclasses
import resource
import time
from collections import OrderedDict, deque
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import config as cfg_mod
from ..ops import codec_host
from ..ops import paged_kv
from ..observability import memledger, timeline
from ..utils.logging import get_logger, metrics
from ..utils.tracing import (
    install_compile_listener,
    install_gc_hook,
    observe_span,
    trace_span,
)
from ..wire import dispatch as wire_dispatch
from . import kv_cache as kv_mod
from . import programs
from . import transport as tp

log = get_logger()

_TPS_EWMA = 0.2  # tokens/s gauge smoothing
# Admissions dispatched and not read that one more may be queued behind:
# one on the device and one waiting for it keep the device busy through a
# burst, and each one queued holds its prefill's outputs (a lane's tails and
# recurrent state) on the device from its dispatch, whenever it runs.
_ADMISSIONS_IN_FLIGHT = 2
# A stall: a tick, with the caller's time before it, longer than both; a
# scheduler's first ticks record none (its programs compile there, and the
# mean they are held against, which forgets at the same rate, is not yet one).
_STALL_MIN_S = 0.5
_STALL_OVER_MEAN = 8.0
_STALL_WARMUP_TICKS = 32
# The device's account. A read shorter than this did not block: the host came
# after the device, and what it took was the copy of a buffer that was ready
# (0.29-0.55 ms on the chip's host, one in a hundred over 1 ms; a read that
# waited for a program took 3.8 ms and more: PERF.md section 5, PR 50).
_READ_BLOCKED_S = 1e-3
# The programs that carry work, as ``cgx.serve.device_unfed_s`` has it, and
# those that may ride in an interval of a clean class (a lane write, a
# release: 0.002-0.54 ms of the device each).
_WORK = frozenset(("prefill_pages", "commit", "decode_step"))
_RIDERS = frozenset(("admit_lane", "release_lanes"))
# The account's classes, by the program an interval of the class is a sample
# of: the histogram under ``cgx.serve.device.`` that takes its clean samples.
_CLASS_HIST = {"decode_step": "step", "commit": "commit_step",
               "prefill_pages": "prefill"}
# The tick's account: where a stalled tick's time went, by the histogram
# under ``cgx.serve.`` whose ``.sum`` is read at the tick's two ends. The
# tick itself, then the spans that lie side by side inside it, then what
# can lie inside any of those.
_TICK_SPANS = {
    "dispatch.step": "dispatch_step_s",
    "dispatch.commit": "dispatch_commit_s",
    "prefill.forward": "prefill_forward_s",
    "admit_lane": "admit_lane_s",
    "wait.step": "wait_step_s",
    "first_token": "prefill_first_token_s",
    "emit": "decode_emit_s",
}
_TICK_WITHIN = {"gc": "host_gc_s", "compile": "compile_s"}
_TICK_ACCOUNT = tuple(
    f"cgx.serve.{hist}"
    for hist in ("step_s", *_TICK_SPANS.values(), *_TICK_WITHIN.values())
)


# ---------------------------------------------------------------------------
# Request surface.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One generation request."""

    id: str
    tokens: List[int]  # prompt
    max_new_tokens: int = 16
    # -- filled by the scheduler --
    output: List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done: bool = False
    # An adapter that generates by diffusion over blocks: the denoising step
    # of its block at which each token of ``output`` was unmasked.
    unmask_step: List[int] = dataclasses.field(default_factory=list)



# ---------------------------------------------------------------------------
# The compiled-program LRU.
# ---------------------------------------------------------------------------


def _program_key(server) -> Tuple:
    """Everything the compiled serving programs bake in: the adapter's
    kind and model geometry, serve geometry, the per-layer resolved cache
    streams (covering the edge registry through both the resolved values
    AND the registry version — a re-registration that resolves identically
    keeps the key), the per-layer state streams, and the trace-affecting
    env knobs
    (``trace_knob_fingerprint`` carries the CGX_KV_*/CGX_SERVE_* serving
    subset plus the codec-lowering knobs the staged dequantize
    consumes)."""
    return (
        server.kind,
        server.geometry,
        (server.serve.page_tokens, server.serve.max_batch,
         server.serve.max_pages, server.serve.max_seq),
        programs._resolved_streams(server),
        programs._resolved_state_streams(server),
        programs._resolved_windows(server),
        cfg_mod.registry_version(),
        cfg_mod.trace_knob_fingerprint(),
    )


_PROGRAM_CACHE: "OrderedDict" = OrderedDict()
_PROGRAM_CACHE_MAX = 8


def invalidate_decode_cache(reason: str = "reconfigure") -> None:
    """Invalidation entry point — cascaded from
    ``supervisor.invalidate_trace_caches``: compiled decode/commit/
    prefill programs bake page-pool geometry and wire specs that a
    recovery reconfiguration may have replaced."""
    _PROGRAM_CACHE.clear()
    metrics.add("cgx.serve.program_invalidations")
    log.info("serving decode-program cache invalidated (%s)", reason)


def _decode_program(server) -> SimpleNamespace:
    """The compiled serving programs for this server's current key —
    from the LRU, building on miss."""
    key = _program_key(server)
    prog = _PROGRAM_CACHE.get(key)
    if prog is not None:
        _PROGRAM_CACHE.move_to_end(key)
        return prog
    prog = _build_programs(server)
    _PROGRAM_CACHE[key] = prog
    while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
        _PROGRAM_CACHE.popitem(last=False)
    return prog


# What a cache miss calls, looked up through this module's global (a test
# that alters a program patches this name).
_build_programs = programs.build


# ---------------------------------------------------------------------------
# The scheduler.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Ready:
    """A request whose KV is fully ingested, waiting for a lane."""

    req: Request
    page_ids: List[int]
    # {stream: (L, page_tokens, H * Dh) f32}, a position a row as the
    # state keeps them, rows from ``tail_len`` on zero: device arrays from
    # the local prefill, host arrays from a page stream — the
    # ``admit_lane`` program takes either.
    tails: Dict[str, Union[jax.Array, np.ndarray]]
    tail_len: int
    # A scalar still on the device from the local prefill (nothing waits
    # for it until the tick's read phase), an int from a page stream.
    first_token: Union[int, jax.Array]
    pos: int
    # {state stream: (its layers, *shape)}: the lane's recurrent state after
    # the prompt's last token, left on the device by the local prefill;
    # empty for a model without state streams.
    states: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    # Its ring of the window layers' pools (``kv_cache.alloc_ring``), for a
    # model with window layers.
    ring: Optional[int] = None
    # End of the prefill (or ingest) that built it, on ``submitted_at``'s
    # clock: ``cgx.serve.ready_wait_s`` counts from here to the lane write.
    ready_at: float = dataclasses.field(default_factory=time.monotonic)
    # A local prefill's open ``serve.prefill.local`` span, ``(start, its
    # fields)``: closed at the read of the first token.
    span: Optional[Tuple[float, Dict]] = None
    # ``CGX_QERR_STATS``: the rows ``prefill_pages`` quantized, by layer,
    # on the device until that read.
    qerr_rows: Dict[int, jax.Array] = dataclasses.field(default_factory=dict)
    # The return of its ``admit_lane`` dispatch (``time.perf_counter()``):
    # ``cgx.serve.ttft_behind_s`` counts from here to the read.
    admitted_at: float = 0.0
    # The number of its ``prefill_pages`` in what the device owes
    # (:meth:`ContinuousBatchScheduler._owe`): the read of its first token
    # waits for that program and nothing behind it. 0 from a page stream,
    # whose first token is on the host.
    owed: int = 0
    # An adapter whose step runs a block: the lane's first block ``(L,)``,
    # the prompt's tokens past its last whole block, -1 where masked.
    block: Optional[np.ndarray] = None


@dataclasses.dataclass
class _Step:
    """A decode step dispatched and not read yet: its tokens (then the
    adapter's counters) on the device, and the request each lane's token
    belongs to. A lane is struck out when its request leaves it under the
    step. ``owed`` is the step's number in what the device owes."""

    tokens: jax.Array
    lanes: Dict[int, Request]
    owed: int
    # A block step: whether the host has counted its stores
    # (:meth:`ContinuousBatchScheduler._account_block_step`).
    accounted: bool = True


class ContinuousBatchScheduler:
    """Admit/evict-per-step decode over one model adapter
    (``adapter.Adapter``: ``gpt2.GPT2Server``, ``latent.LatentMoEServer``).

    ``receiver`` (optional :class:`~.transport.KvPageReceiver`) is the
    disaggregated mode: ``submit(req, remote=True)`` registers the
    request's page stream and admission waits (without blocking — the
    poll is a counter read) for the prefill worker's frames. Without a
    receiver — or when a stream stalls past the failover bound — the
    scheduler prefills locally. ``step()`` never blocks; ``run()`` is
    the bounded convenience loop.
    """

    def __init__(
        self,
        server,
        *,
        receiver: Optional[tp.KvPageReceiver] = None,
    ):
        self.server = server
        sv = server.serve
        if receiver is not None:
            tp.require_kv_streams(server)
        self._receiver = receiver
        # A pure-serving process never touches the train paths that
        # start the memory ledger, yet its KV pool is a primary ledger
        # owner — arm it here too (no-op when CGX_MEMLEDGER is unset).
        memledger.maybe_start()
        self._gc_pauses = install_gc_hook()
        install_compile_listener()
        self._prog = _decode_program(server)
        self._prog_key = _program_key(server)
        # A ring a lane for the window layers, where the model has any.
        self.cache = kv_mod.PagedKvCache(
            sv.max_pages, sv.page_tokens,
            rings=sv.max_batch if self._prog.ring else 0,
        )
        self._cache_gen = self.cache.generation
        self._state_bytes = 0  # the recurrent state held (memledger owner)
        self._window_bytes = 0  # the window layers' pools (memledger owner)
        self._pass_bytes = 0  # a looped adapter's pools and tails (the same)
        self._state = self._fresh_state()
        self._lanes: List[Optional[Request]] = [None] * sv.max_batch
        self._waiting: List[Request] = []  # local-prefill queue
        self._remote: "OrderedDict[str, Request]" = OrderedDict()
        self._ready: List[_Ready] = []
        self._frames: Dict[str, List[tp.PageFrame]] = {}
        self._done: List[Request] = []
        self._released: List[int] = []  # lanes awaiting _release_lanes
        # What the host counts for itself, a lane each: the state's
        # ``tail_len`` (written with the lane, plus one a dispatched step
        # while the lane is held, zero at a commit and at a release), and
        # the decode steps the lane's request may still be dispatched (0
        # for a free lane).
        self._tail_len = np.zeros((sv.max_batch,), np.int64)
        self._left = np.zeros((sv.max_batch,), np.int64)
        # The state's ``n_pages`` a lane, counted here like the tail lengths
        # (the live pages a step reads, the ring slot a commit writes), and
        # with window layers the lane's ring.
        self._n_pages = np.zeros((sv.max_batch,), np.int64)
        self._ring_of = np.zeros((sv.max_batch,), np.int64)
        # An adapter whose step runs a block: the lanes that store at the
        # step after the last one read (the device says so, a read early),
        # and the prompt tokens in a lane's first block, which its first
        # store does not emit.
        self._store_next = np.zeros((sv.max_batch,), bool)
        self._block_skip = np.zeros((sv.max_batch,), np.int64)
        # Dispatched and unread, in device order: lane writes whose first
        # token nobody has read, and decode steps (two at most, the second
        # only by :meth:`_runs_ahead`).
        self._unread: List[Tuple[int, _Ready]] = []
        self._steps: "deque[_Step]" = deque()
        self._rekey_pending = False
        self._tokens_total = 0
        self._last_step_t: Optional[float] = None
        self._tps = 0.0
        # The tick's clock (``time.perf_counter()``): the end of the last
        # ``step()``; the ticks so far and the running mean of a tick with
        # the gap before it (the stall record's yardstick); and the instant
        # a blocking read left the device with nothing queued, until the
        # next program that carries work is dispatched.
        self._step_end: Optional[float] = None
        self._ticks = 0
        self._tick_mean = 0.0
        self._unfed_since: Optional[float] = None
        # The device's account. What was dispatched and no read has waited
        # for yet, in dispatch order: (number, kind, count, request, the
        # dispatch's return). The records of the open interval and its
        # start: the return of the read that closed the last one, or None,
        # and then the dispatch of its first program that carries work if
        # an unfed mark stood before it (``_since_unfed``), else there is
        # no interval (a caller's absence dropped it). What this tick's
        # reads waited for and the intervals of one class they closed,
        # which become samples, and move the running mean of the class
        # (decode step, commit call, a prefill's padded token by the length's
        # power of two: [samples, mean]), once the tick has been held
        # against it; and the process's
        # resource usage at the last tick's end (the stall line).
        self._owed: "deque[Tuple]" = deque()
        self._owed_n = 0
        self._held: List[Tuple] = []
        self._since: Optional[float] = None
        self._since_unfed = True  # nothing dispatched yet: the first feeds it
        self._tick_owed: List[Tuple] = []
        self._tick_closed: List[Tuple] = []
        self._usual: Dict = {}
        self._rusage = resource.getrusage(resource.RUSAGE_SELF)

    # -- state plumbing ----------------------------------------------------

    def _fresh_state(self) -> Dict:
        """An empty state (``programs.fresh_state``), and the account of
        the bytes it holds: the scheduler owns them."""
        b = self.server.serve.max_batch
        state = programs.fresh_state(self._prog, self.server.serve)
        if self._prog.ring:
            self._note_window_pools(state["pools"])
        if self._prog.passes > 1:
            self._note_pass_cache(state)
        state_bytes = sum(
            t.nbytes for name in self._prog.state_names
            for t in state[f"state_{name}"] if t is not None
        )
        metrics.set("cgx.serve.state.bytes", float(state_bytes))
        if state_bytes:
            if self._state_bytes:  # a rebuild drops the old state's arrays
                memledger.note_release(
                    "serve.state", n=b, nbytes=self._state_bytes
                )
            memledger.note_alloc("serve.state", n=b, nbytes=state_bytes)
        self._state_bytes = state_bytes
        return state

    def _note_window_pools(self, pools) -> None:
        """The bytes the pools hold by page class, as gauges
        (``cgx.serve.kv.pool_bytes.window`` / ``.global``, and ``.uniform``:
        what the window layers would hold with ``max_pages + 1`` rows like
        the others) and, the window layers', as the memory ledger's owner
        ``serve.kv.window``."""
        sv = self.server.serve
        held = {True: 0, False: 0}
        uniform = 0
        for layer, window in zip(pools, self._prog.windows):
            nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(layer))
            held[bool(window)] += nbytes
            if window:
                rows = sv.max_batch * self._prog.ring + 1
                uniform += nbytes // rows * (sv.max_pages + 1)
        for name, value in (("window", held[True]), ("global", held[False]),
                            ("uniform", uniform)):
            metrics.set(f"cgx.serve.kv.pool_bytes.{name}", float(value))
        if self._window_bytes:  # a rebuild drops the old pools
            memledger.note_release("serve.kv.window", n=sv.max_batch,
                                   nbytes=self._window_bytes)
        memledger.note_alloc("serve.kv.window", n=sv.max_batch,
                             nbytes=held[True])
        self._window_bytes = held[True]

    def _note_pass_cache(self, state) -> None:
        """What a looped adapter's pass dimension holds: the pools (every
        pass's rows, ``cgx.serve.kv.pool_bytes.global``) and the raw tails
        (every pass's, ``cgx.serve.kv.tail_bytes``) as gauges, and both
        together as the memory ledger's owner ``serve.kv.passes``, ``n``
        the passes."""
        held = {
            "pool_bytes.global": sum(
                a.nbytes for a in jax.tree_util.tree_leaves(state["pools"])),
            "tail_bytes": sum(
                t.nbytes for name in self._prog.names
                for t in state[f"tail_{name}"] if t is not None),
        }
        for name, value in held.items():
            metrics.set(f"cgx.serve.kv.{name}", float(value))
        passes, nbytes = self._prog.passes, sum(held.values())
        if self._pass_bytes:  # a rebuild drops the old pools and tails
            memledger.note_release("serve.kv.passes", n=passes,
                                   nbytes=self._pass_bytes)
        memledger.note_alloc("serve.kv.passes", n=passes, nbytes=nbytes)
        self._pass_bytes = nbytes

    def _maybe_rebuild(self) -> None:
        """Program-era and cache-generation checks, once per step.

        A cache-generation bump (the recovery cascade) drops every lane
        IMMEDIATELY — page mappings from the old generation must never
        be gathered again, whatever it costs the in-flight requests.

        A program re-key (knob flip / SLO re-solve) adopts at a DRAIN
        point instead: admission pauses, active lanes finish their
        generations under the old program, and only then do the pools
        and programs rebuild — pages quantized at two widths never mix
        inside one sequence, and no lane loses generated tokens to a
        bit-budget move (the slo.py adoption contract)."""
        if self.cache.generation != self._cache_gen:
            self._cache_gen = self.cache.generation
            self._rekey_pending = False
            self._evict_all_to_queue("cache generation bump")
        key = _program_key(self.server)
        if key != self._prog_key:
            if any(r is not None for r in self._lanes):
                if not self._rekey_pending:
                    self._rekey_pending = True
                    metrics.add("cgx.serve.rekey_drains")
                    log.info(
                        "serving scheduler: program re-key pending — "
                        "draining active lanes before adoption"
                    )
                return
            self._rekey_pending = False
            self._prog_key = key
            self._prog = _decode_program(self.server)
            self._evict_all_to_queue("program re-key")
            metrics.add("cgx.serve.bits_adoptions")
        else:
            self._rekey_pending = False

    def _requeue(self, req: Request) -> None:
        """Return a request to the waiting queue for a full re-prefill,
        releasing its pool pages (free_seq is a no-op when the cache
        generation bump already dropped the tables)."""
        self.cache.free_seq(req.id)
        req.output.clear()
        req.unmask_step.clear()
        req.first_token_at = None
        self._waiting.insert(0, req)

    def _evict_all_to_queue(self, reason: str) -> None:
        requeued = 0
        for lane, req in enumerate(self._lanes):
            if req is not None and not req.done:
                self._requeue(req)
                requeued += 1
            self._lanes[lane] = None
        for r in self._ready:
            if not r.req.done:
                self._requeue(r.req)
                requeued += 1
        self._ready.clear()
        self._frames.clear()
        for stream, req in list(self._remote.items()):
            # In-flight remote streams describe pool rows of the dead
            # era; fail them over to local prefill — and drop the
            # receiver's stream state, or its late frames would keep
            # accumulating (and costing poll round-trips) forever.
            if self._receiver is not None:
                self._receiver.drop_stream(stream)
            self._requeue(req)
            self._remote.pop(stream)
            requeued += 1
        self._state = self._fresh_state()
        self._released.clear()
        # What was in flight computed over the state just dropped.
        self._unread.clear()
        self._steps.clear()
        self._tail_len[:] = 0
        self._left[:] = 0
        self._n_pages[:] = 0
        self._store_next[:] = False
        if requeued:
            log.info(
                "serving scheduler reset (%s): %d request(s) requeued "
                "for re-prefill", reason, requeued,
            )

    # -- submission --------------------------------------------------------

    def submit(self, req: Request, *, remote: bool = False) -> None:
        """Queue a request. ``remote=True`` expects a prefill worker to
        ship the KV stream named by ``req.id`` (requires a receiver);
        otherwise the scheduler prefills locally at admission."""
        req.submitted_at = time.monotonic()
        # Request attribution anchor (ISSUE 17): the critical-path
        # engine's TTFT decomposition starts every request at this
        # instant and joins the rest of the flow by ``req``.
        timeline.instant(
            "serve.submit", cat=timeline.CAT_TRACE, req=req.id,
            remote=bool(remote),
        )
        if remote:
            if self._receiver is None:
                raise ValueError(
                    "remote submission needs a KvPageReceiver"
                )
            self._receiver.add_stream(req.id)
            self._remote[req.id] = req
        else:
            self._waiting.append(req)

    def outstanding(self) -> int:
        return (
            len(self._waiting)
            + len(self._remote)
            + len(self._ready)
            + sum(1 for r in self._lanes if r is not None)
            + len(self._steps)  # a step in flight is read before the end
        )

    @property
    def completed(self) -> List[Request]:
        return list(self._done)

    # -- the per-step pipeline --------------------------------------------

    def step(self) -> bool:
        """One scheduler tick: drain transport, fail over stalled
        streams, dispatch the admissions, the commit of full tails and a
        decode step for every active lane, then read what they produced
        (first tokens, the step's tokens) and evict completed lanes.
        Returns whether anything progressed (the run loop's idle-sleep
        signal). Waits for nothing but the device's own programs.

        Around the tick: ``cgx.serve.between_steps_s`` observes the
        caller's time since the last ``step()`` returned (with
        ``cgx.serve.step_s`` it adds up to the loop's wall time), and
        :meth:`_close_tick` holds the two against their running mean. A
        gap that is a stall by itself is the caller's absence, not its
        turn-around: the stall record has it, and the loop's accounts
        (the time between steps, the unfed device, the device's open
        interval) leave it out."""
        before = metrics.sums(_TICK_ACCOUNT)
        between, away = 0.0, False
        self._tick_owed.clear()
        if self._step_end is not None:
            between = time.perf_counter() - self._step_end
            away = self._is_stall(between)
            if away:
                self._unfed_since = None  # nobody was there to feed it
                self._since, self._since_unfed = None, False
                self._held.clear()
            else:
                metrics.observe("cgx.serve.between_steps_s", between)
        with trace_span("serve.step"):
            self._maybe_rebuild()
            progressed = self._drain_transport()
            progressed |= self._failover_stalled()
            progressed |= self._admit()
            progressed |= self._decode()
        self._step_end = time.perf_counter()
        self._close_tick(between, away, before)
        return progressed

    def _is_stall(self, seconds: float,
                  usual: Optional[float] = None) -> bool:
        """Whether the tick about to end (or the gap before it) is one,
        past the scheduler's first ticks: over ``_STALL_MIN_S``, and by
        that much over the ``usual`` device time of the programs its reads
        waited for; where nothing says what those usually cost, over
        ``_STALL_OVER_MEAN`` times the running mean of the ticks."""
        if self._ticks < _STALL_WARMUP_TICKS or seconds < _STALL_MIN_S:
            return False
        if usual is None:
            return seconds >= _STALL_OVER_MEAN * self._tick_mean
        return seconds - usual >= _STALL_MIN_S

    def _usual_s(self) -> Optional[float]:
        """What the programs this tick's reads waited for usually cost the
        device: the running means of their classes (a step's, a commit
        call's, a prefill's padded token's) times what each holds, summed
        (a rider nothing). None where the tick's reads waited for no
        program, or for one whose class has closed fewer than three clean
        intervals; a prefill of such a class is held against the token of
        the nearest class that has (a first 16k-token prompt against the
        8k-token ones)."""
        total = None
        for _, kind, n, _, _ in self._tick_owed:
            if kind in _RIDERS:
                continue
            key = _class_key(kind, n)
            cell = self._usual.get(key)
            if kind == "prefill_pages" and (cell is None or cell[0] < 3):
                near = [(abs(k[1] - key[1]), c) for k, c in self._usual.items()
                        if isinstance(k, tuple) and c[0] >= 3]
                cell = min(near)[1] if near else None
            if cell is None or cell[0] < 3:
                return None
            total = (total or 0.0) + cell[1] * (n or 1)
        return total

    def _close_tick(self, between: float, away: bool,
                    before: Tuple[float, ...]) -> None:
        """The stall record. A tick, with the ``between`` seconds the
        caller took before it, that :meth:`_is_stall` finds too long for
        what the device owed under its reads counts in
        ``cgx.serve.stalls`` and leaves one warning line that says where
        its time went: the growth of each histogram of ``_TICK_ACCOUNT``
        since ``before``, which ``step()`` read as the tick began; then
        the programs its reads waited for, in dispatch order, and their
        usual device time; then what the process did meanwhile, the CPU
        seconds of all its threads and the times it was taken off a core
        (a wait with neither is the device or the runtime late, one with
        switches a host that was not scheduled). ``cgx.serve.stall_s``
        observes it unless the caller was ``away`` (the gap alone was the
        stall: no tick of this loop was slow). Those histograms are the
        process's: two schedulers ticking in one process read each other's
        time. Full collections are published here, so that a pause lands
        in the tick it lengthened."""
        self._gc_pauses.publish()
        grown = [b - a for a, b in
                 zip(before, metrics.sums(_TICK_ACCOUNT))]
        whole = between + grown[0]
        usual = self._usual_s() if whole >= _STALL_MIN_S else None
        stalled, mean = self._is_stall(whole, usual), self._tick_mean
        compiled = grown[-1] > 0  # its time is no program's usual cost
        self._settle(stalled or compiled,
                     unknown=usual is None and not compiled)
        self._ticks += 1
        self._tick_mean += ((grown[0] if away else whole) - mean) / min(
            self._ticks, _STALL_WARMUP_TICKS)
        was, now = self._rusage, resource.getrusage(resource.RUSAGE_SELF)
        self._rusage = now
        if not stalled:
            return
        metrics.add("cgx.serve.stalls")
        if not away:
            metrics.observe("cgx.serve.stall_s", whole)
        spans = grown[1:1 + len(_TICK_SPANS)]
        parts = [("between_steps", between), *zip(_TICK_SPANS, spans),
                 ("other", grown[0] - sum(spans))]
        within = zip(_TICK_WITHIN, grown[1 + len(_TICK_SPANS):])
        owed = [kind + (f"[{n}]" if kind == "prefill_pages" else
                        f" x{n}" if kind == "commit" else "")
                for _, kind, n, _, _ in self._tick_owed]
        log.warning(
            "serving: stalled tick %d: %.3f s, the running mean %.4f s: %s"
            " | inside those: %s | device owed: %s (usual %s)"
            " | process: cpu=%.3f nivcsw=%d",
            self._ticks, whole, mean,
            " ".join(f"{name}={v:.3f}" for name, v in parts),
            " ".join(f"{name}={v:.3f}" for name, v in within),
            " ".join(owed) or "nothing",
            "unknown" if usual is None else f"{usual:.4f}",
            now.ru_utime + now.ru_stime - was.ru_utime - was.ru_stime,
            now.ru_nivcsw - was.ru_nivcsw,
        )

    def run(self, *, deadline_s: float = 120.0,
            idle_sleep_s: float = 0.002) -> bool:
        """Bounded convenience loop: step until every submitted request
        completes or the deadline passes (False = timed out with work
        outstanding — the caller decides whether that is an error)."""
        deadline = time.monotonic() + deadline_s
        while self.outstanding() and time.monotonic() < deadline:
            if not self.step():
                time.sleep(idle_sleep_s)
        return not self.outstanding()

    # -- transport ingest --------------------------------------------------

    def _drain_transport(self) -> bool:
        if self._receiver is None:
            return False
        progressed = False
        for stream, frame in self._receiver.poll():
            self._frames.setdefault(stream, []).append(frame)
            progressed = True
        for stream in [s for s in self._remote if
                       self._receiver.complete(s)]:
            req = self._remote.pop(stream)
            frames = self._frames.pop(stream, [])
            meta = self._receiver.meta(stream) or {}
            self._receiver.drop_stream(stream)
            try:
                with trace_span(
                    "serve.ingest", req=req.id, frames=len(frames)
                ):
                    self._ingest_stream(req, meta, frames)
            except Exception as e:
                metrics.add("cgx.serve.ingest_errors")
                log.warning(
                    "serving: stream %s ingest failed (%s); failing over "
                    "to local prefill", stream, e,
                )
                # Pages allocated before the failure must not stay
                # mapped to a sequence that will re-prefill from scratch
                # (free_seq is a no-op when nothing was allocated).
                self.cache.free_seq(req.id)
                self._waiting.insert(0, req)
            progressed = True
        return progressed

    def _failover_stalled(self) -> bool:
        if self._receiver is None or not self._remote:
            return False
        timeout_s = cfg_mod.serve_prefill_timeout_ms() / 1e3
        progressed = False
        for stream in [s for s in self._remote
                       if self._receiver.stalled(s, timeout_s)]:
            req = self._remote.pop(stream)
            self._frames.pop(stream, None)
            self._receiver.drop_stream(stream)
            metrics.add("cgx.serve.prefill_failovers")
            timeline.instant(
                "serve.failover", cat=timeline.CAT_TRACE, req=stream,
            )
            from ..observability import flightrec

            flightrec.record(
                "serve_prefill_failover", stream=stream,
                timeout_ms=timeout_s * 1e3,
            )
            log.warning(
                "serving: prefill stream %s stalled > %.0f ms — failing "
                "over to local prefill (degraded, not wedged)",
                stream, timeout_s * 1e3,
            )
            self._waiting.insert(0, req)
            progressed = True
        return progressed

    def _ingest_stream(self, req: Request, meta: Dict,
                       frames: Sequence[tp.PageFrame]) -> None:
        """Turn a completed page stream into a ready lane payload: pool
        rows written in one batched scatter per layer, tail + first
        token from the META frame. The transport's frames are K and V
        pages and tails (``tp.STREAM_OF_KIND``); an adapter with other
        streams never gets a receiver (``tp.require_kv_streams``)."""
        streams = self._prog.streams
        n_layer = self.server.n_layer
        pt = self.server.serve.page_tokens
        n_pages = int(meta["pages"])
        if int(meta.get("page_tokens", pt)) != pt:
            raise ValueError(
                f"stream page_tokens {meta.get('page_tokens')} != "
                f"serving {pt}"
            )
        page_ids: List[int] = []
        for _ in range(n_pages):
            pid = self.cache.alloc(req.id)
            if pid is None:
                self.cache.free_seq(req.id)
                raise RuntimeError("KV pool exhausted during ingest")
            page_ids.append(pid)
        rows = [
            {name: [None] * n_pages for name, _ in streams[layer]}
            for layer in range(n_layer)
        ]
        tails = {
            name: np.zeros(
                (n_layer, pt, spec.n_head * spec.d_head), np.float32
            )
            for name, spec in streams[0]
        }
        tail_len = int(meta.get("tail_tokens", 0))
        spec_of = [dict(layer) for layer in streams]
        for f in frames:
            if f.is_meta:
                continue
            name, is_page = tp.STREAM_OF_KIND[f.kind]
            spec = spec_of[f.layer][name]
            if is_page:
                if f.bits != spec.bits or (
                    spec.quantized and f.bucket != spec.bucket_size
                ):
                    raise ValueError(
                        f"stream layer {f.layer} page wire spec "
                        f"(bits={f.bits}, bucket={f.bucket}) does not "
                        f"match the serving spec (bits={spec.bits}, "
                        f"bucket={spec.bucket_size}) — prefill and "
                        "decode must resolve the same kv_page configs"
                    )
                rows[f.layer][name][f.page_idx] = _decode_page_payload(
                    f, spec
                )
            else:  # tail
                vals = np.frombuffer(f.payload, np.float16).astype(
                    np.float32
                ).reshape(-1, spec.n_head * spec.d_head)
                tails[name][f.layer, : vals.shape[0]] = vals
        if n_pages:
            layer_rows = [
                {name: _stack_rows(rows[layer][name], spec)
                 for name, spec in streams[layer]}
                for layer in range(n_layer)
            ]
            ids = jnp.asarray(page_ids, jnp.int32)
            self._state = dict(
                self._state,
                pools=self._prog.ingest(
                    self._state["pools"], layer_rows, ids
                ),
            )
            self._owe("ingest", req=req.id)
        self._note_pages(n_pages)
        metrics.add("cgx.serve.pages_ingested", float(n_pages))
        self._ready.append(_Ready(
            req=req,
            page_ids=page_ids,
            tails=tails,
            tail_len=tail_len,
            first_token=int(meta["first_token"]),
            pos=int(meta["prompt_tokens"]),
        ))

    def _note_pages(self, n_pages: int) -> None:
        """``n_pages`` pages of every stream of every layer went into the
        pools (of a window layer no more than its ring keeps; of a looped
        adapter's layer one a pass): the wire plane's ``kv_page``
        accounting."""
        if not n_pages:
            return
        for layer, layer_streams in enumerate(self._prog.streams):
            kept = (min(n_pages, self._prog.ring)
                    if self._prog.windows[layer]
                    else n_pages * self._prog.passes)
            for _, spec in layer_streams:
                _account_pages(self.server.layer_name(layer), spec, kept)

    # -- local prefill (colocated mode + the failover rung) ---------------

    def _local_prefill(self, req: Request) -> Optional[_Ready]:
        sv = self.server.serve
        prompt = np.asarray(req.tokens, np.int32)
        s = prompt.shape[0]
        block = self._prog.block
        # A lane of a block adapter holds whole blocks: its last one too.
        if s < 1 or -(-(s + req.max_new_tokens) // block) * block > sv.max_seq:
            raise ValueError(
                f"request {req.id!r}: prompt {s} + max_new "
                f"{req.max_new_tokens} exceeds CGX_SERVE_MAX_SEQ "
                f"{sv.max_seq}"
            )
        pids: List[int] = []
        for _ in range(s // block * block // sv.page_tokens):
            pid = self.cache.alloc(req.id)
            if pid is None:
                self.cache.free_seq(req.id)
                return None  # pool pressure: stay queued
            pids.append(pid)
        ring = None
        if self._prog.ring:
            ring = self.cache.alloc_ring(req.id)
            if ring is None:
                self.cache.free_seq(req.id)
                return None  # every ring is held: stay queued
        try:
            return self._local_prefill_compute(req, prompt, pids, ring)
        except BaseException:
            # A prefill failure (jit error, bad prompt) must release the
            # pages it reserved — the request re-enters the queue or
            # errors out, either way without pinning pool rows.
            self.cache.free_seq(req.id)
            raise

    def _local_prefill_compute(
        self, req: Request, prompt: np.ndarray, pids: List[int],
        ring: Optional[int] = None,
    ) -> _Ready:
        """The dispatch of one request's prefill, its full pages ``pids``
        (and, for a model with window layers, its ``ring``) reserved: one
        call of the ``prefill_pages`` program, which leaves
        the pages in the pools and the tails and the first token on the
        device. Nothing is read here: the ``serve.prefill.local`` span
        opened now is closed by :meth:`_read_first_tokens`."""
        sv = self.server.serve
        pt = sv.page_tokens
        opens, padded_len = None, 0
        if self._prog.block > 1:
            # The prefill covers the prompt's whole blocks, padded as the
            # whole prompt would be (a prompt length has one padded length
            # whatever its remainder; zeros past the whole blocks are later
            # blocks, which nothing real sees); the tokens past them open
            # the lane's first block as known tokens.
            whole = prompt.shape[0] // self._prog.block * self._prog.block
            opens = np.full((self._prog.block,), -1, np.int32)
            opens[: prompt.shape[0] - whole] = prompt[whole:]
            padded_len = _pad_prompt(prompt, pt).shape[0]
            prompt = prompt[:whole]
        s, n_full = prompt.shape[0], len(pids)
        tail_len = s - n_full * pt
        start = time.perf_counter()
        queue_wait = time.monotonic() - req.submitted_at
        metrics.observe("cgx.serve.queue_wait_s", queue_wait)
        fields = dict(req=req.id, prompt_tokens=int(s),
                      queue_wait_ms=round(queue_wait * 1e3, 3))
        try:
            with trace_span(
                "serve.prefill.forward",
                hist="cgx.serve.prefill_forward_s", req=req.id,
            ):
                padded = (np.pad(prompt, (0, padded_len - s)) if padded_len
                          else _pad_prompt(prompt, pt))
                # A last page that is a tail goes to the scratch row.
                ids = np.full((padded.shape[0] // pt,), sv.max_pages,
                              np.int32)
                ids[:n_full] = pids
                first, pools, tails, qerr_rows, states = (
                    self._prog.prefill_pages(
                        self.server.p, self._state["pools"], padded[None],
                        np.arange(padded.shape[0], dtype=np.int32)[None],
                        np.int32(max(s - 1, 0)), ids, np.int32(tail_len),
                        *self._ring_rows(ring, n_full, len(ids)),
                    )
                )
                self._state["pools"] = pools
                owed = self._owe("prefill_pages", int(padded.shape[0]),
                                 req.id)
        except BaseException:
            self._close_prefill_span((start, fields), ok=False)
            raise
        self._note_pages(n_full)
        metrics.add("cgx.serve.local_prefills")
        return _Ready(
            req=req, page_ids=pids, tails=tails,
            tail_len=tail_len, first_token=first, pos=s,
            states=states, span=(start, fields), qerr_rows=qerr_rows,
            ring=ring, owed=owed, block=opens,
        )

    def _slot_rows(self, ring_id, pages):
        """The window pools' rows of pages ``pages`` of the sequence that
        holds ring ``ring_id`` (arrays or numbers): page ``n`` lies in slot
        ``n % ring`` of its ring."""
        return ring_id * self._prog.ring + pages % self._prog.ring

    def _ring_rows(self, ring_id: Optional[int], n_full: int,
                   n_padded: int):
        """``prefill_pages``' last operand for a model with window layers
        (nothing for one without): the window pools' row of each of the
        prompt's last ``ring + 1`` padded pages. Page ``n`` of the ``ring``
        newest full ones goes to slot ``n % ring`` of the request's ring;
        an older one has slid out and a last page that is a tail is no
        page, and both go to the scratch row."""
        ring = self._prog.ring
        if not ring:
            return ()
        pages = np.arange(max(n_padded - ring - 1, 0), n_padded)
        kept = (pages >= n_full - ring) & (pages < n_full)
        scratch = self.server.serve.max_batch * ring
        return (np.where(kept, self._slot_rows(ring_id, pages),
                         scratch).astype(np.int32),)

    @staticmethod
    def _close_prefill_span(span: Optional[Tuple[float, Dict]],
                            ok: bool) -> None:
        if span is not None:  # a page stream's request has none
            start, fields = span
            observe_span("serve.prefill.local", start,
                         hist="cgx.serve.prefill_s", ok=ok, **fields)

    # -- admission / eviction ---------------------------------------------

    def _free_lanes(self) -> List[int]:
        return [i for i, r in enumerate(self._lanes) if r is None]

    def _admit(self) -> bool:
        """The dispatch phase of the tick's admissions; their first
        tokens are read after the decode step is queued
        (:meth:`_read_first_tokens`)."""
        if self._rekey_pending:
            return False  # draining toward a program re-key: no admits
        progressed = False
        free = self._free_lanes()
        while True:
            # A ready request takes a free lane at once: its first token
            # does not wait for the prefills of the requests behind it
            # (prefill all, then write all lanes, made every admission of
            # a tick as late as the last one).
            while free and self._ready:
                self._admit_lane(free.pop(0), self._ready.pop(0))
                progressed = True
            # Prefill-ahead is bounded by the lanes that could actually
            # take the result this step: one free lane must not trigger a
            # whole-queue prefill burst (which would hold pool pages for
            # requests that cannot run yet and inflate every TTFT behind
            # the forwards queued in front of theirs).
            if not (self._waiting and free):
                break
            # A burst reads as it goes: with every lane free at once, the
            # prefills queued would else hold a lane's state each.
            self._read_first_tokens(keep=_ADMISSIONS_IN_FLIGHT - 1)
            req = self._waiting.pop(0)
            try:
                ready = self._local_prefill(req)
            except Exception as e:
                self._fail(req, e)
                progressed = True
                continue
            if ready is None:
                self._waiting.insert(0, req)  # pool pressure
                break
            self._ready.append(ready)
        return progressed

    def _fail(self, req: Request, error: Exception) -> None:
        """A request whose prefill raised, at its dispatch or at the read
        of its first token, errors alone; the caller has freed its pages."""
        metrics.add("cgx.serve.request_errors")
        log.warning("serving: request %s failed prefill: %s", req.id, error)
        req.done = True
        self._done.append(req)

    def _admit_lane(self, lane: int, ready: _Ready) -> None:
        """Dispatch the write of one ready request into a free lane. The
        first token goes in as it is, from the device or from the host;
        the request has it, and its TTFT, at :meth:`_read_first_tokens`."""
        sv = self.server.serve
        req = ready.req
        ready_wait = time.monotonic() - ready.ready_at
        metrics.observe("cgx.serve.ready_wait_s", ready_wait)
        with trace_span(
            "serve.admit_lane", req=req.id, lane=int(lane),
            ready_wait_ms=round(ready_wait * 1e3, 3),
        ):
            table_row = np.full((sv.pages_per_seq,), -1, np.int32)
            table_row[: len(ready.page_ids)] = ready.page_ids
            first = ready.first_token
            if ready.block is not None:
                token = ready.block  # no first token: the lane's first block
            else:
                token = np.int32(first) if isinstance(first, int) else first
            n_full, ring = len(ready.page_ids), self._prog.ring
            self._n_pages[lane] = n_full
            ring_row = ()
            if ring:  # the slots the prefill wrote (``_ring_rows``)
                pages = np.arange(max(n_full - ring, 0), n_full)
                ring_row = np.full((ring,), -1, np.int32)
                ring_row[pages % ring] = self._slot_rows(ready.ring, pages)
                ring_row = (ring_row,)
                self._ring_of[lane] = ready.ring
            self._state = self._prog.admit_lane(
                self._state, np.int32(lane), table_row,
                np.int32(n_full), np.int32(ready.tail_len),
                token, np.int32(ready.pos), ready.tails, ready.states,
                *ring_row,
            )
            self._owe("admit_lane", req=req.id)
            if ready.states:
                metrics.add("cgx.serve.state.lane_writes")
            self._lanes[lane] = req
            self._tail_len[lane] = ready.tail_len
            if ready.block is None:
                self._left[lane] = max(req.max_new_tokens - 1, 0)
            else:  # no first token: every token is a step's
                self._left[lane] = req.max_new_tokens
                self._store_next[lane] = False
                self._block_skip[lane] = int((ready.block >= 0).sum())
            self._unread.append((lane, ready))
            metrics.add("cgx.serve.requests_admitted")
        ready.admitted_at = time.perf_counter()

    def _read_first_tokens(self, keep: int = 0) -> bool:
        """The read phase of the admissions dispatched and not read (all
        but the newest ``keep``), in their order: each read waits for its
        own prefill and nothing behind it. The prefill's own argmax IS the
        first generated token — the disaggregated convention: TTFT is
        admission, not first decode — and the request has it, its stamp
        and its TTFT here, when the host does. A first token that is all
        the request asked for (or the end of sequence) finishes it here."""
        n = max(len(self._unread) - keep, 0)
        for _ in range(n):
            lane, ready = self._unread.pop(0)
            req = ready.req
            first = ready.first_token
            # What stood between its lane write and this read: the other
            # admissions, the commits and the steps dispatched since.
            behind = time.perf_counter() - ready.admitted_at
            metrics.observe("cgx.serve.ttft_behind_s", behind)
            try:
                if not isinstance(first, int):  # still on the device
                    with trace_span(
                        "serve.prefill.first_token",
                        hist="cgx.serve.prefill_first_token_s", req=req.id,
                        behind_ms=round(behind * 1e3, 3),
                    ):
                        began = time.perf_counter()
                        first = int(first)
                        ended = time.perf_counter()
                    self._note_read(ready.owed, began, ended, prefill=True)
                for layer, rows in ready.qerr_rows.items():
                    rows = np.asarray(rows)  # every pass's padded pages
                    _observe_page_qerr(
                        self.server.layer_name(layer),
                        self._prog.specs[layer],
                        rows.reshape(self._prog.passes, -1, rows.shape[-1])[
                            :, : len(ready.page_ids)],
                        already_host=True,
                    )
            except Exception as e:
                # The failed-prefill contract at the later read: the
                # request errors alone, its pages freed, its lane released.
                self._close_prefill_span(ready.span, ok=False)
                self.cache.free_seq(req.id)
                self._vacate(lane)
                self._fail(req, e)
                continue
            self._close_prefill_span(ready.span, ok=True)
            if ready.block is not None:
                continue  # what was read is the prefill's end, no token
            self._stamp_first_token(lane, req)
            self._emit(lane, req, first)
            self._note_tokens(1)
        self._release_lanes()  # a first token can finish its request
        return bool(n)

    def _stamp_first_token(self, lane: int, req: Request) -> None:
        """The host holds the request's first token (a block adapter's: its
        first block's store): its stamp and its TTFT."""
        now = time.monotonic()
        req.first_token_at = now
        ttft_ms = (now - req.submitted_at) * 1e3
        metrics.observe("cgx.serve.ttft_ms", ttft_ms)
        timeline.instant(
            "serve.admit", cat=timeline.CAT_TRACE, req=req.id,
            lane=int(lane), ttft_ms=round(ttft_ms, 3),
        )

    def _emit(self, lane: int, req: Request, token: int) -> None:
        """Hand a request the token the host has just read for it."""
        req.output.append(token)
        if len(req.output) >= req.max_new_tokens or (
            token == self.server.serve.eos_token
        ):
            self._finish_lane(lane)

    def _finish_lane(self, lane: int) -> None:
        """The host's half of a finished request; its lane's state is
        reset by the tick's one :meth:`_release_lanes`. No step in flight
        holds a token for a lane that finishes by count (``_left``), so
        one that does was finished by ``eos_token``, unannounced."""
        req = self._lanes[lane]
        assert req is not None
        self.cache.free_seq(req.id)
        req.done = True
        self._done.append(req)
        late = self._vacate(lane)
        if late and self._prog.block == 1:  # a block's are counted at emit
            metrics.add("cgx.serve.decode.discarded_tokens", float(late))
        metrics.add("cgx.serve.requests_completed")

    def _vacate(self, lane: int) -> int:
        """Take the lane from its request (finished, evicted or failed).
        Returns the tokens that steps in flight were decoding for it:
        struck out, nobody reads them."""
        self._lanes[lane] = None
        self._left[lane] = 0
        self._store_next[lane] = False
        self._released.append(lane)
        return sum(
            step.lanes.pop(lane, None) is not None for step in self._steps
        )

    def _release_lanes(self) -> None:
        """One ``release_lanes`` call for the lanes that finished or were
        evicted since the last one."""
        if not self._released:
            return
        mask = np.zeros((self.server.serve.max_batch,), bool)
        mask[self._released] = True
        self._released.clear()
        reset = programs._LANE_RESET
        self._tail_len[mask] = reset["tail_len"]
        self._n_pages[mask] = reset["n_pages"]
        names = (*reset,
                 *(programs._RING_RESET if self._prog.ring else ()))
        self._state.update(self._prog.release_lanes(
            {name: self._state[name] for name in names}, mask
        ))
        self._owe("release_lanes")

    # -- decode ------------------------------------------------------------

    def _decode(self) -> bool:
        """The decode half of the tick: dispatch (unless the last tick
        queued this one's step ahead) the commit of full tails and the
        step, dispatch the next step too where :meth:`_runs_ahead`
        allows, and only then read: the first tokens of the tick's
        admissions, then the tokens of the oldest step in flight."""
        sv = self.server.serve
        fresh = not self._steps
        if fresh and not self._left.any():
            # No lane has a token to come from a step: a first token can
            # be all a request asked for.
            return self._read_first_tokens()
        with trace_span(
            "serve.decode.prepare", hist="cgx.serve.decode_prepare_s"
        ):
            if fresh:
                self._commit_full_tails()
        if fresh and not self._left.any():  # the pool evicted them all
            self._read_first_tokens()
            return True
        with trace_span("serve.decode_step"):
            if fresh:
                self._dispatch_step()
            if len(self._steps) == 1 and self._runs_ahead():
                # Under the step just queued, not between two: the next
                # step's commit, from the host's counts, and its dispatch.
                self._commit_full_tails()
                self._dispatch_step()
                metrics.add("cgx.serve.decode.ahead")
            self._read_first_tokens()
            step = self._steps.popleft()
            with trace_span("serve.wait.step", hist="cgx.serve.wait_step_s"):
                began = time.perf_counter()
                nxt = np.asarray(step.tokens)
                ended = time.perf_counter()
            self._note_read(step.owed, began, ended)
        with trace_span("serve.decode.emit", hist="cgx.serve.decode_emit_s"):
            metrics.add("cgx.serve.decode_steps")
            # What the adapter counted this step, read with the tokens.
            if self._prog.block == 1:
                counts = nxt[sv.max_batch:]
            else:
                *block_out, counts = programs.split_block_step(
                    nxt, sv.max_batch, self._prog.block)
            for name, count in zip(self.server.step_counters, counts):
                metrics.add(f"cgx.serve.{name}", float(count))
            metrics.set(
                "cgx.serve.batch_occupancy", len(step.lanes) / sv.max_batch
            )
            if self._prog.block == 1:
                for lane, req in step.lanes.items():
                    self._emit(lane, req, int(nxt[lane]))
                emitted = len(step.lanes)
            else:
                emitted = self._emit_blocks(step, *block_out)
            self._release_lanes()
            self._note_tokens(emitted)
        return True

    def _emit_blocks(self, step: _Step, tokens, emitted, unmask,
                     stores) -> int:
        """The read of a block step (``programs.split_block_step``'s
        parts): a lane that stored
        hands its request the block's generated tokens (a first block's
        prompt tokens, whose unmask step is -1, are the prompt's), each with
        its unmask step, up to what the request asked for or its end of
        sequence; what the block holds past that is discarded
        (``cgx.serve.decode.discarded_tokens``). The lanes that store at
        the next step are noted, and the step queued ahead, whose stores
        are known only now, is counted. Returns the tokens emitted."""
        eos = self.server.serve.eos_token
        held = list(step.lanes)
        self._store_next[held] = stores[held] != 0
        total = 0
        for lane in held:
            if not emitted[lane]:
                continue
            req = step.lanes[lane]
            if req.first_token_at is None:
                self._stamp_first_token(lane, req)
            fresh = unmask[lane] >= 0
            assert emitted[lane] == fresh.sum(), (lane, emitted, unmask)
            new = [int(t) for t in tokens[lane][fresh]]
            take = new[: req.max_new_tokens - len(req.output)]
            if eos is not None and eos in take:
                take = take[: take.index(eos) + 1]
            req.output.extend(take)
            req.unmask_step.extend(
                int(u) for u in unmask[lane][fresh][: len(take)])
            total += len(take)
            if len(take) < len(new):
                metrics.add("cgx.serve.decode.discarded_tokens",
                            float(len(new) - len(take)))
            if len(req.output) >= req.max_new_tokens or take[-1] == eos:
                self._finish_lane(lane)
        for ahead in self._steps:
            if not ahead.accounted:
                self._account_block_step(ahead)
        return total

    def _account_block_step(self, step: _Step) -> None:
        """The host's counts for a dispatched block step, once the read of
        the step before it has named the lanes that store at it
        (``_store_next``): their tails grow by a block and their requests
        have a block's tokens fewer to come (a first block's prompt tokens
        are none of them). A step dispatched behind a read is counted at
        its dispatch, the step queued ahead at that read; either way before
        the next commit or dispatch, which therefore find every tail's
        length exact up to the step before: a tail that filled there is
        promoted before the lane's next store, queued ahead or not."""
        n = self._prog.block
        stores = [i for i in step.lanes if self._store_next[i]]
        self._tail_len[stores] += n
        self._left[stores] -= np.minimum(
            self._left[stores], n - self._block_skip[stores])
        self._block_skip[stores] = 0
        self._store_next[stores] = False
        step.accounted = True

    def _runs_ahead(self) -> bool:
        """Whether the next step may be dispatched before the last one is
        read. It may when no lane is free and no lane's request has its
        last token in the steps dispatched: whatever arrives meanwhile
        could not be admitted before the next step, so queueing it
        lengthens no TTFT. (A free lane has no steps left either.) The
        commit in front of it must find its pages: a lane evicted for want
        of one is a free lane."""
        if self._left.min() <= 0:
            return False
        full = int((self._tail_len >= self.server.serve.page_tokens).sum())
        return full <= self.cache.free_pages

    def _dispatch_step(self) -> None:
        """Queue one decode step over the state as the programs dispatched
        so far leave it. The step runs every lane the device holds active,
        which are the lanes held here (whatever vacates a lane releases it
        before the next dispatch); a token is read for the lanes whose
        request still has one to come."""
        with trace_span(
            "serve.dispatch.step", hist="cgx.serve.dispatch_step_s"
        ):
            self._state, tokens = self._prog.decode_step(
                self.server.p, self._state
            )
            owed = self._owe("decode_step")
        held = [i for i, r in enumerate(self._lanes) if r is not None]
        if self._prog.ring or self.server.guards_global_read:
            self._note_live_pages(held)
        if self._prog.block > 1:
            # Which lanes store is known once the step before is read: now,
            # unless this step is queued ahead of that read.
            step = _Step(tokens=tokens, owed=owed, accounted=False,
                         lanes={i: self._lanes[i] for i in held})
            if not self._steps:
                self._account_block_step(step)
            self._steps.append(step)
            return
        self._tail_len[held] += 1
        lanes = {i: self._lanes[i] for i in held if self._left[i] > 0}
        self._left[list(lanes)] -= 1
        self._steps.append(_Step(tokens=tokens, lanes=lanes, owed=owed))

    def _note_live_pages(self, held: List[int]) -> None:
        """The pages the step just dispatched has a visible key in, summed
        over the held lanes, by class and for one layer of the class, from
        the host's own counts: every committed page of a global layer; of a
        window layer those from the page that holds the oldest position the
        lane's token (at ``n_pages * page_tokens + tail_len``) can see. Those
        are the slots the step's read leaves open (``adapter.page_live`` and
        ``adapter.ring_live`` on the device; the tail's live rows are
        counted beside them, ``kv.live_tail_rows``); a guarded global
        read's are
        counted again as ``kv.decoded_pages.global``: what the read
        decodes, which was every slot of every lane's table
        (``kv.table_pages.global``) before the read had a guard."""
        sv = self.server.serve
        n_pages = self._n_pages[held]
        committed = float(n_pages.sum())
        metrics.add("cgx.serve.kv.live_pages.global", committed)
        # The tail positions the step reads beside them, its own token's
        # among them (the tail's mask: ``adapter.lane_masks``).
        metrics.add("cgx.serve.kv.live_tail_rows",
                    float((self._tail_len[held] + self._prog.block).sum()))
        if self.server.guards_global_read:
            metrics.add("cgx.serve.kv.decoded_pages.global", committed)
            metrics.add("cgx.serve.kv.table_pages.global",
                        float(sv.max_batch * sv.pages_per_seq))
        if not self._prog.ring:
            return
        pt, window = sv.page_tokens, self._prog.window
        oldest = np.maximum(
            n_pages * pt + self._tail_len[held] - window + 1, 0) // pt
        live = float(np.maximum(n_pages - oldest, 0).sum())
        metrics.add("cgx.serve.kv.live_pages.window", live)

    def _commit_full_tails(self) -> None:
        """Promote full tails into pool pages, so that every lane has
        tail room for the step dispatched next: which tails are full is
        the host's own count, nothing is read from the device. A lane the
        pool has no page for is evicted back to the queue. The ``commit``
        program takes ``ServeConfig.commit_lanes`` lanes by index, so the
        device quantizes the tails that filled and not every lane's; when
        more are full at once (the start of a run, a burst of equal
        prompts) it is dispatched again over the donated state for the
        next few, and every full tail is committed before this returns."""
        sv = self.server.serve
        full = [i for i, r in enumerate(self._lanes)
                if r is not None and self._tail_len[i] >= sv.page_tokens]
        if not full:
            return
        committed, pids = [], []
        for lane in full:
            req = self._lanes[lane]
            pid = self.cache.alloc(req.id)
            if pid is None:
                # Pool pressure mid-decode: evict this lane back to the
                # queue (it re-prefills when pages free up) rather than
                # stalling every other lane.
                metrics.add("cgx.serve.decode_evictions")
                self.cache.free_seq(req.id)
                req.output.clear()
                req.unmask_step.clear()
                req.first_token_at = None
                self._waiting.append(req)
                self._vacate(lane)
                continue
            committed.append(lane)
            pids.append(pid)
        self._release_lanes()
        if not committed:
            return
        if cfg_mod.qerr_stats():
            st = self._state
            for layer in range(self.server.n_layer):
                spec = self._prog.specs[layer]
                if spec is not None and spec.quantized:
                    # the layer's leading stream is the one the qerr
                    # telemetry watches
                    lead = self._prog.streams[layer][0][0]
                    tail = np.asarray(st[f"tail_{lead}"][layer])
                    rows = (tail[committed] if self._prog.passes == 1
                            else tail[:, committed])  # every pass's page
                    metrics.add("cgx.serve.host_reads")
                    _observe_page_qerr(
                        self.server.layer_name(layer), spec,
                        rows, already_host=True,
                    )
        k = sv.commit_lanes
        calls = -(-len(committed) // k)
        with trace_span(
            "serve.dispatch.commit", hist="cgx.serve.dispatch_commit_s"
        ):
            for at in range(0, len(committed), k):
                lanes, ids = committed[at:at + k], pids[at:at + k]
                pad = k - len(lanes)  # slots left over: the scratch row
                self._state = self._prog.commit(
                    self._state,
                    np.asarray(lanes + lanes[:1] * pad, np.int32),
                    np.asarray(ids + [sv.max_pages] * pad, np.int32),
                    *self._ring_slots(lanes, pad),
                )
                if not at:  # the device has work from the first call on
                    self._owe("commit", calls)
        metrics.add("cgx.serve.commit.calls", float(calls))
        metrics.add("cgx.serve.commit.rows", float(calls * k))
        metrics.add("cgx.serve.commit.lanes", float(len(committed)))
        self._tail_len[committed] = 0
        self._note_pages(len(committed))
        of_global, of_window = self._prog.class_streams
        metrics.add("cgx.serve.pages_committed",
                    float(of_global * len(committed)))
        if self._prog.ring:
            # A page written over one that slid out: the ring had turned.
            recycled = int((self._n_pages[committed] >= self._prog.ring).sum())
            metrics.add("cgx.serve.window.pages_committed",
                        float(of_window * len(committed)))
            metrics.add("cgx.serve.window.pages_recycled",
                        float(of_window * recycled))
        self._n_pages[committed] += 1

    def _ring_slots(self, lanes: List[int], pad: int):
        """``commit``'s last operand for a model with window layers
        (nothing for one without): the window pools' row each lane's full
        tail goes to, slot ``n_pages % ring`` of the lane's ring; the
        scratch row for a padded slot."""
        ring = self._prog.ring
        if not ring:
            return ()
        rows = self._slot_rows(self._ring_of[lanes], self._n_pages[lanes])
        scratch = self.server.serve.max_batch * ring
        return (np.asarray(list(rows) + [scratch] * pad, np.int32),)

    def _owe(self, kind: str, n: int = 0, req: Optional[str] = None) -> int:
        """A device program has just been dispatched: one record at the end
        of what the device owes, ``n`` a prefill's padded length or a
        commit's calls. Returns the record's number, by which the read of
        the program's output names it. Where the program carries work
        (``prefill_pages``, ``commit``, ``decode_step``; not a lane write
        or a release), ``cgx.serve.device_unfed_s`` observes how long the
        device had stood with nothing queued, the host's own estimate of
        its idle gap."""
        now = time.perf_counter()
        self._owed_n += 1
        self._owed.append((self._owed_n, kind, n, req, now))
        if kind in _WORK and self._unfed_since is not None:
            metrics.observe("cgx.serve.device_unfed_s",
                            now - self._unfed_since)
            self._unfed_since = None
        return self._owed_n

    def _note_read(self, target: int, began: float, ended: float,
                   prefill: bool = False) -> None:
        """A blocking copy from the device (of a first token if
        ``prefill``, else of a step's tokens) ran from ``began`` to
        ``ended``: count it, and keep the device's account. The device has
        finished the program the copy waited for, record ``target``, and
        every program dispatched before it: those leave what it owes and
        join the open interval. Where the copy blocked, the device finished
        at ``ended``, the interval closes there (:meth:`_close_interval`)
        and the next one opens: at ``ended`` where work is still queued
        (the device was fed through), else at the dispatch that next feeds
        it (the unfed mark, which any read that leaves nothing dispatched
        and unread sets). Where it did not block the host came late and the
        interval stays open, to be closed by the next read that blocks,
        unless nothing is left queued: then the device stood idle from an
        instant nobody saw, and the interval is dropped and counted
        (``cgx.serve.device.unsound``)."""
        metrics.add("cgx.serve.host_reads")
        owed, held = self._owed, self._held
        before = len(held)
        while owed and owed[0][0] <= target:
            held.append(owed.popleft())
        self._tick_owed += held[before:]
        unfed = not self._steps and not self._unread
        if ended - began >= _READ_BLOCKED_S and len(held) > before:
            self._close_interval(ended, prefill)
            self._since, self._since_unfed = (
                (None, True) if unfed else (ended, False))
        elif unfed:
            if self._since is not None or self._since_unfed:
                metrics.add("cgx.serve.device.unsound")
            held.clear()
            self._since, self._since_unfed = None, True
        if unfed:
            self._unfed_since = ended

    def _close_interval(self, ended: float, prefill: bool) -> None:
        """The open interval ends at ``ended``, the return of a read that
        blocked: the device was busy from its start with the programs it
        holds and nothing else, so its seconds are their device time
        (``cgx.serve.device.accounted_s``). Where it holds one class it is
        kept for the tick's end (:meth:`_settle`), with whether it is a
        clean sample of the class's histogram: ``step`` (fed through, one
        ``decode_step`` and nothing else), ``commit_step`` (fed through, a
        tick's ``commit`` calls and one ``decode_step``) or ``prefill``
        (one ``prefill_pages``, closed by the read of its first token),
        lane writes and releases riding in the last two. An interval that
        began at a dispatch is a sample of no histogram and still teaches
        the stall record what its class costs."""
        held, start = self._held, self._since
        fed = start is not None
        if not fed and self._since_unfed:
            start = next(r[4] for r in held if r[1] in _WORK)
        if start is not None:
            metrics.add("cgx.serve.device.accounted_s", ended - start)
            work = [r for r in held if r[1] not in _RIDERS]
            kinds = tuple(r[1] for r in work)
            if kinds == ("decode_step",):
                self._tick_closed.append(
                    ("decode_step", fed and len(held) == 1, start, ended,
                     0, None))
            elif kinds == ("prefill_pages",) and prefill:
                _, _, tokens, req, _ = work[0]
                self._tick_closed.append(
                    ("prefill_pages", True, start, ended, tokens, req))
            elif kinds == ("commit", "decode_step"):
                self._tick_closed.append(
                    ("commit", fed, start, ended, work[0][2], None))
        held.clear()

    def _settle(self, stalled: bool, unknown: bool) -> None:
        """The tick is over, and so is the judgement of it: the intervals
        its reads closed become samples. Unless the tick ``stalled`` or
        built a program (its time is the stall record's or the compiler's,
        not what a program costs the chip), a clean one is observed by its histogram, ``cgx.serve.device.step_s``,
        ``commit_step_s`` (``commit_calls`` adds its calls) or ``prefill_s``
        (``prefill_tokens`` adds its padded length; the timeline's record
        has it and the request), and each moves the running mean of its
        class by the rule of ``_tick_mean``: a step's, a prefill's padded
        token's (a class a power of two of padded length:
        :func:`_class_key`), and a commit call's, which is what its
        interval took over the step's mean. A prefill teaches from a stalled tick too
        where the tick was held against the ticks' mean, a class being
        ``unknown``: a long prompt's prefill is the one program that can
        take the stall's floor by itself, and its class would never be
        known else. The scheduler's first ticks teach nothing (its
        programs compile there)."""
        warm = self._ticks >= _STALL_WARMUP_TICKS
        for kind, clean, start, ended, n, req in self._tick_closed:
            seconds = ended - start
            if clean and not stalled:
                fields = {}
                if kind == "prefill_pages":
                    fields = dict(req=req, tokens=n)
                    metrics.add("cgx.serve.device.prefill_tokens", float(n))
                elif kind == "commit":
                    fields = dict(calls=n)
                    metrics.add("cgx.serve.device.commit_calls", float(n))
                name = _CLASS_HIST[kind]
                observe_span(f"serve.device.{name}", start, end=ended,
                             hist=f"cgx.serve.device.{name}_s", **fields)
            if not warm or (stalled and not (
                    unknown and kind == "prefill_pages")):
                continue
            if kind == "commit":
                step = self._usual.get("decode_step")
                if step is None or step[0] < 3:
                    continue
                seconds -= step[1]
            cell = self._usual.setdefault(_class_key(kind, n), [0, 0.0])
            seconds /= n or 1  # a commit's call, a prefill's padded token
            cell[0] += 1
            cell[1] += (seconds - cell[1]) / min(cell[0],
                                                 _STALL_WARMUP_TICKS)
        self._tick_closed.clear()

    def _note_tokens(self, n: int) -> None:
        self._tokens_total += n
        metrics.add("cgx.serve.tokens_generated", float(n))
        now = time.monotonic()
        if self._last_step_t is not None and n:
            dt = now - self._last_step_t
            if dt > 0:
                inst = n / dt
                self._tps = (
                    inst if not self._tps
                    else (1 - _TPS_EWMA) * self._tps + _TPS_EWMA * inst
                )
                metrics.set("cgx.serve.tokens_per_s", self._tps)
        self._last_step_t = now


# ---------------------------------------------------------------------------
# Shared page helpers (ingest + accounting).
# ---------------------------------------------------------------------------


def _class_key(kind: str, n: int):
    """The class whose running mean the stall record holds a program
    against: its kind, and for a prefill the power of two its padded length
    reaches up to (a mix of 16k-token prompts pads to four lengths between
    15,616 and 16,384, each seen a few times an hour: by exact length the
    classes starve, and a token's cost moves by a third from one power of
    two to the next)."""
    return (kind, (n - 1).bit_length()) if kind == "prefill_pages" else kind


def _pad_prompt(prompt: np.ndarray, page_tokens: int) -> np.ndarray:
    """Right-pad a prompt to the next page multiple so distinct lengths
    share one compiled prefill program. Causal attention makes the pad
    inert for every real position (see ``prefill_forward``); a recurrent
    layer's state would swallow it, so an adapter with state streams
    returns the state at ``last_idx``, not at the padded end
    (``models/granite_hybrid.mamba_prefill``)."""
    s = prompt.shape[0]
    padded_len = -(-s // page_tokens) * page_tokens
    if padded_len == s:
        return prompt
    return np.pad(prompt, (0, padded_len - s))


def _decode_page_payload(frame: tp.PageFrame, spec: paged_kv.PageSpec):
    """A page frame's payload in pool-row form: (words, meta) numpy
    pair for quantized specs (the host-codec wire words, reshaped to the
    pool's rows of 128, and the wire's (unit, minimum) pairs as the pool's
    two planes — zero re-encoding), or the raw f32 payload row."""
    if not spec.quantized:
        return np.frombuffer(frame.payload, np.float16).astype(
            np.float32
        )
    q = codec_host.from_bytes(
        np.frombuffer(frame.payload, np.uint8),
        spec.flat, spec.bits, spec.bucket_size, np.float32,
    )
    return (
        paged_kv.pool_words(np.asarray(q.packed), spec)[0],
        paged_kv.pool_meta(np.asarray(q.meta, np.float32)),
    )


def _stack_rows(rows: List, spec: paged_kv.PageSpec):
    """Stack per-page ingest rows into the batched scatter operands."""
    if any(r is None for r in rows):
        raise ValueError("incomplete page set in a completed stream")
    if not spec.quantized:
        return jnp.asarray(np.stack(rows))
    return (
        jnp.asarray(np.stack([r[0] for r in rows])),
        jnp.asarray(np.stack([r[1] for r in rows])),
    )


def _account_pages(name: str, spec: paged_kv.PageSpec, n_pages: int) -> None:
    """Wire-plane accounting for shipped/committed pages: the same
    ``cgx.wire.bytes_*.kv_page`` counters and controller side table every
    other edge feeds (``wire.dispatch.note_external_edge``)."""
    wire_dispatch.note_external_edge(
        "kv_page", name,
        numel=spec.flat, bits=spec.bits,
        raw_bytes=float(spec.raw_bytes() * n_pages),
        wire_bytes=float(spec.wire_bytes() * n_pages),
    )


def _observe_page_qerr(
    name: str, spec: paged_kv.PageSpec, rows, *, already_host: bool = False
) -> None:
    """CGX_QERR_STATS: the kv_page edge's relative-L2 round-trip error,
    observed into the same ``cgx.qerr.wire:kv_page:<layer>`` stream the
    SLO controller solves from (host-side — the pages travel a host
    transport, so no staged callback is needed)."""
    if not cfg_mod.qerr_stats():
        return
    rows_np = rows if already_host else np.asarray(rows)
    rows_np = rows_np.reshape(-1, spec.flat).astype(np.float32)
    for row in rows_np:
        q = codec_host.quantize(row, spec.bits, spec.bucket_size)
        rt = codec_host.dequantize(q, out_dtype=np.float32)
        denom = float(np.linalg.norm(row)) or 1.0
        rel = float(np.linalg.norm(row - rt)) / denom
        metrics.observe(
            f"cgx.qerr.{wire_dispatch.edge_label('kv_page', name)}", rel
        )
