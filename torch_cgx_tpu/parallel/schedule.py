"""Compiled collective schedules: chunked quantize->wire->epilogue
pipelining for the compressed allreduce planes.

The reference hides gradient communication behind backward compute via
Horovod-style fusion + DDP hook ordering (PAPER.md §0); our port still ran
each fused bucket as ONE monolithic quantize -> exchange -> epilogue
sequence — zero overlap, confirmed by the ``overlap_frac`` column of
``cgx_trace`` attribution. GC3 (arxiv 2201.11840) treats collective
schedules as compiled, cacheable programs; "Fused Computation-Collective
Operations" (arxiv 2305.06942) shows the remaining step time lives in
chunk-granular fusion of compute with in-flight collectives. This module
is the schedule *compiler*: from a fusion slice's (n, ws, config) it
derives a chunked pipeline —

    chunk k+1 quantizes  WHILE  chunk k is on the wire
                         WHILE  chunk k-1 runs the fused epilogue

— cached in a bounded LRU keyed like ``allreduce``'s layout cache plus
(route, chunking, chip), and executed on both planes:

* **staged XLA plane** (:func:`pipelined_quantized_allreduce`, routed via
  ``parallel/xla_allreduce.py``): the pipelined loop is compiled INTO the
  single staged program — per-chunk ``lax.all_to_all``/``ppermute``
  exchanges interleaved with the PR 4 fused epilogue kernel in software-
  pipeline emission order (chunk k+1's quantize+exchange is staged before
  chunk k's epilogue+allgather), giving XLA's latency-hiding scheduler
  independent collective/compute chains to overlap. Still zero host
  callbacks — this module is listed in ``xla_allreduce.STAGED_PURE`` and
  jaxpr-guarded by tests/test_schedule.py.
* **bridge plane** (``torch_backend/backend.py`` ``_qreduce_sra_pipelined``):
  a double-buffered in-flight window — an encoder thread runs chunk
  encode+put up to ``_BRIDGE_WINDOW`` chunks ahead of the worker thread's
  take/fold/requantize/decode, replacing the strict phase barriers of the
  monolithic path. The bridge keeps a dependency-light duplicate of
  :func:`chunk_table` (it must not import the parallel package);
  tests/test_schedule.py cross-checks the two.

**Bit-equality contract**: chunks are COLUMN blocks of the SRA wire
layout, not contiguous spans of the fused buffer. The monolithic SRA
views the slice as a (ws, chunk) matrix — row r is rank r's owned
span — and the own-chunk-raw rule keys off the row index; a contiguous
split would reassign ownership per element and change every decode sum.
A column block keeps row r owned by rank r in every chunk, and block
widths are rounded to ``lcm(bucket_size, LANE_GROUP)`` so the
quantization bucket grid WITHIN each row is unchanged (buckets restart
per quantize call at multiples of the width — an aligned width puts
every boundary back on the monolithic grid). With the accumulate
association pinned to the dispatcher's ``ordered_rowsum`` fold in both
forms, a deterministic (non-stochastic) pipelined SRA is bit-equal to
the monolithic SRA on ANY payload (``bench.py --schedule`` asserts this
before timing; tests/test_schedule.py pins it on random data).
Stochastic rounding draws per-chunk streams (keys fold in the chunk
index), so stochastic bytes differ between schedules — exactly as they
differ between any two fusion layouts. Only the SRA transport is
pipelined: Ring is already a hop pipeline by construction, and
all-to-all is the debug path — both stay monolithic.

``CGX_SCHEDULE`` unset ("auto") pipelines only on a real TPU backend, so
every CPU/CI path stays bit-identical: staged programs, store keys and
wire bytes unchanged (the grad_sync bit-identity suite pins it).
"""

from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import config as cfg_mod
from ..config import CompressionConfig
from ..observability import timeline
from ..ops import codec
from ..utils.logging import metrics
from . import reducers

# Double-buffered in-flight window of the bridge pipeline: how many chunks
# the encoder thread may run ahead of the worker thread's take/epilogue.
# 2 = classic double buffering — chunk k+1 encodes while chunk k is in
# flight; deeper windows only grow arena residency without adding overlap
# (there is one encoder thread and one epilogue thread to keep busy).
_BRIDGE_WINDOW = 2


def chunk_alignment(bucket_size: int) -> int:
    """Column-width alignment of schedule chunk boundaries:
    ``lcm(bucket_size, LANE_GROUP)``. Quantization buckets restart per
    quantize call, so a column block starting at a multiple of the
    bucket size (within its row) keeps every bucket boundary on the
    monolithic layout's grid — the bit-equality contract (module
    docstring)."""
    return math.lcm(max(1, bucket_size), codec.LANE_GROUP)


def chunk_table(
    width: int, chunks: int, bucket_size: int
) -> Tuple[Tuple[int, int], ...]:
    """(column offset, column width) chunk plan over one rank-chunk of
    ``width`` elements (the per-rank row of the SRA wire layout) at a
    target pipeline depth of ``chunks``.

    Every boundary is a multiple of :func:`chunk_alignment`; the last
    chunk absorbs the remainder. A row too narrow for the requested
    depth degrades to fewer chunks — down to ``((0, width),)``, the
    monolithic plan. Pure integer arithmetic: the bridge keeps a
    dependency-light duplicate (``backend._sched_chunk_table``) pinned to
    this by test."""
    if width <= 0:
        return ((0, max(width, 0)),) if width else ()
    align = chunk_alignment(bucket_size)
    chunks = max(1, int(chunks))
    # Aligned units available; each chunk needs at least one whole unit.
    units = width // align
    depth = min(chunks, units) if units else 1
    if depth <= 1:
        return ((0, width),)
    per = (units // depth) * align
    out = []
    off = 0
    for _ in range(depth - 1):
        out.append((off, per))
        off += per
    out.append((off, width - off))
    return tuple(out)


# ---------------------------------------------------------------------------
# The schedule LRU (GC3's compiled-schedule discipline, sibling of the
# layout LRU in allreduce.py and the program LRU in xla_allreduce.py).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CompiledSchedule:
    """One fusion slice's compiled pipeline plan: ``table`` is the
    column-block plan over the slice's per-rank wire row of ``chunk``
    elements (``reducers.chunk_layout(n, ws)[0]``)."""

    table: Tuple[Tuple[int, int], ...]  # (col offset, col width) per chunk
    n: int
    ws: int
    chunk: int  # per-rank row width of the (ws, chunk) wire layout
    cc: CompressionConfig

    @property
    def depth(self) -> int:
        return len(self.table)


_SCHED_CACHE: "OrderedDict" = OrderedDict()
_SCHED_CACHE_MAX = 128
_SCHED_STATS = {"hits": 0, "misses": 0}
# Cached "no pipeline for this key" marker — a stored bare None would be
# indistinguishable from a cache miss and re-derive (and re-count a miss)
# on every call.
_NO_SCHEDULE = object()


def schedule_cache_stats() -> Dict[str, int]:
    return dict(_SCHED_STATS)


def schedule_cache_clear() -> None:
    _SCHED_CACHE.clear()
    _SCHED_STATS.update(hits=0, misses=0)


def invalidate_schedule_cache(reason: str = "reconfigure") -> None:
    """Invalidation entry point — called alongside
    ``allreduce.invalidate_layout_cache`` (a PR 5 recovery reconfigure
    re-derives chunk layouts at the shrunk world size; serving a stale
    chunk table there would wedge the bridge's in-flight window against
    peers running the fresh plan)."""
    schedule_cache_clear()
    metrics.add("cgx.sched.cache_invalidations")
    from ..utils.logging import get_logger

    get_logger().info("schedule cache invalidated (%s)", reason)


def _chip_fingerprint() -> str:
    """The (backend, chip) component of the schedule key: a plan derived
    for one chip's crossover must not serve another's."""
    return f"{jax.default_backend()}/{jax.devices()[0].device_kind}"


def cache_key_component() -> Tuple:
    """The schedule component of trace-cache keys (``make_train_step``):
    everything that changes what the pipelined emission stages — resolved
    mode, target depth — so a ``CGX_SCHEDULE`` flip between calls forces
    a retrace, never a stale-schedule hit."""
    return (cfg_mod.schedule_mode(), cfg_mod.sched_chunks())


def _schedule_key(n, ws, dtype, cc, route, chunks) -> Tuple:
    return (
        int(n),
        int(ws),
        str(dtype),
        cc,
        route,
        int(chunks),
        _chip_fingerprint(),
        cfg_mod.registry_version(),
    )


def _engaged(route_staged: bool) -> bool:
    """Whether the schedule compiler may pipeline on the JAX plane under
    the current mode/backend: "on" anywhere, "auto" only on a real TPU
    backend (inert on every CPU/CI path — same discipline as
    ``CGX_XLA_ALLREDUCE=auto``), "off" never. ``route_staged`` is the
    topology router's verdict for the slice — the pipelined program is
    the staged program's sibling and rides the same routing."""
    del route_staged  # pipelining is mode-gated; routing picked the plane
    mode = cfg_mod.schedule_mode()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return jax.default_backend() == "tpu"


def engaged() -> bool:
    """Public mode probe for callers that only need the yes/no (e.g. the
    reverse-order group emission in ``allreduce_tree``): True when the
    current mode/backend would let the compiler pipeline at all."""
    return _engaged(True)


def compiled_schedule(
    n: int,
    ws: int,
    cc: CompressionConfig,
    *,
    reduction: str = cfg_mod.REDUCTION_SRA,
    dtype="float32",
    route: str = "staged",
    route_staged: bool = True,
    chunks: Optional[int] = None,
) -> Optional[CompiledSchedule]:
    """The compiled pipeline plan for one fusion slice, or ``None`` when
    pipelining does not engage (mode off/auto-on-CPU, compression off,
    ws == 1, a non-SRA reduction — Ring already pipelines hop-wise by
    construction, all-to-all is the debug path — or a payload too small
    to split). Plans come from the bounded LRU
    (``cgx.sched.cache_hits``/``cache_misses``).

    ``chunks``: an explicit depth decision from the step planner
    (``parallel/planner.py``). When given it REPLACES both the
    ``CGX_SCHED_CHUNKS`` knob and the mode gate — the planner's own
    engagement gate already decided this slice pipelines (the planner is
    the schedule compiler's front end, not a bypass: depth 1 still
    degrades to None/monolithic and every other gate above holds)."""
    if ws <= 1 or not cc.enabled or cfg_mod.dummy_compression():
        return None
    if reduction != cfg_mod.REDUCTION_SRA:
        return None
    if chunks is None:
        if not _engaged(route_staged):
            return None
        chunks = cfg_mod.sched_chunks()
    key = _schedule_key(n, ws, dtype, cc, route, chunks)
    hit = _SCHED_CACHE.get(key)
    if hit is not None:
        _SCHED_CACHE.move_to_end(key)
        _SCHED_STATS["hits"] += 1
        metrics.add("cgx.sched.cache_hits")
        return None if hit is _NO_SCHEDULE else hit
    _SCHED_STATS["misses"] += 1
    metrics.add("cgx.sched.cache_misses")
    chunk = reducers.chunk_layout(n, ws)[0]
    table = chunk_table(chunk, chunks, cc.bucket_size)
    sched: Optional[CompiledSchedule] = None
    if len(table) >= 2:
        sched = CompiledSchedule(table=table, n=n, ws=ws, chunk=chunk, cc=cc)
        metrics.add("cgx.sched.compiled")
    # Cache the negative result too (single-chunk payloads would re-probe
    # every call otherwise) — as the _NO_SCHEDULE sentinel, since a bare
    # None stored in the cache is indistinguishable from a miss.
    _SCHED_CACHE[key] = sched if sched is not None else _NO_SCHEDULE
    if len(_SCHED_CACHE) > _SCHED_CACHE_MAX:
        _SCHED_CACHE.popitem(last=False)
    return sched


# ---------------------------------------------------------------------------
# Staged-plane executor: the software-pipelined loop, compiled into the
# single XLA program. Staged-pure — no host callbacks, no blocking device
# syncs (tools/lint.py enforces both; the jaxpr guard re-checks at trace
# time).
# ---------------------------------------------------------------------------


def _note_pipeline(sched: CompiledSchedule, reduction: str) -> None:
    """Trace-time accounting (once per compiled program — runtime hooks
    would need a host callback the staged program must not contain)."""
    metrics.add("cgx.sched.pipelined_slices")
    metrics.add("cgx.sched.chunks_staged", float(sched.depth))
    timeline.instant(
        "sched_pipeline",
        cat=timeline.CAT_COLLECTIVE,
        elems=int(sched.n),
        ws=int(sched.ws),
        chunks=int(sched.depth),
        bits=int(sched.cc.bits),
        reduction=reduction,
    )


def pipelined_quantized_allreduce(
    x: jax.Array,
    axis_name: str,
    ws: int,
    cc: CompressionConfig,
    reduction: str,
    key: Optional[jax.Array],
    sched: CompiledSchedule,
    *,
    with_wire: bool = False,
    pre=None,
):
    """Software-pipelined SRA allreduce of one fusion slice (inside
    shard_map): the slice's (ws, chunk) wire layout is split into the
    schedule's column blocks — rank r keeps row r in every block, so the
    own-chunk-raw rule and the bucket grid match the monolithic layout
    exactly (bit-equality contract, module docstring) — and each block
    runs the same quantize -> ``lax.all_to_all`` -> fused epilogue ->
    ``lax.all_gather`` -> decode composition as
    ``reducers.sra_allreduce``, EMITTED in pipeline order: block k+1's
    quantize + exchange is staged before block k's epilogue + allgather +
    decode, so the XLA latency-hiding scheduler sees independent
    collective/compute chains it can overlap (block k+1 on the wire
    while block k's epilogue kernel runs).

    ``with_wire=True`` also returns this device's wire decode (the EF
    residual base — same quantize-once payload sharing as
    ``sra_allreduce_with_wire``), assembled from the per-block stage-1
    payloads.

    ``pre``: a producer-staged payload (``ops.fused_producer.Produced``)
    whose ``q_blocks`` were quantized per column block against THIS
    schedule's table (the consumer verifies the tables match before
    routing here): each block's quantize is skipped and the raw own
    chunk comes from ``pre.raw_row`` slices — the f32 buffer is never
    read."""
    if reduction != cfg_mod.REDUCTION_SRA:
        raise ValueError(
            f"pipelined schedules cover the SRA transport only, got "
            f"{reduction!r} (compiled_schedule should have returned None)"
        )
    if pre is not None and (
        pre.q_blocks is None or len(pre.q_blocks) != sched.depth
    ):
        raise ValueError(
            "producer-staged payload's block plan does not match the "
            "compiled schedule (consumer-side table check missed?)"
        )
    _note_pipeline(sched, reduction)
    depth = sched.depth
    n = x.shape[0]
    xs = (
        reducers._pad_rows(x, ws, sched.chunk) if pre is None else None
    )  # (ws, chunk), monolithic
    own_idx = lax.axis_index(axis_name)
    own = (jnp.arange(ws) == own_idx)[:, None]
    exchanged: list = [None] * depth
    outs: list = [None] * depth
    rts: list = [None] * depth

    def _block_key(c: int):
        # Per-block stochastic stream (the fusion-slice convention):
        # blocks of one slice must not share fold sequences.
        return jax.random.fold_in(key, c) if key is not None else None

    def _raw_c(c: int):
        """Block c's slice of the producer raw own row."""
        off, w = sched.table[c]
        return lax.slice(pre.raw_row, (off,), (off + w,))

    def start(c: int) -> None:
        """Stage 1 of block c: quantize its columns + put on the wire."""
        off, w = sched.table[c]
        kc = _block_key(c)
        if pre is not None:
            q = pre.q_blocks[c]
            xs_c = None
        else:
            xs_c = lax.slice(xs, (0, off), (ws, off + w))
            q = reducers._quantize_rows(
                xs_c, cc, reducers._phase_key(kc, 1, axis_name)
            )
        q_recv = jax.tree.map(
            lambda a: lax.all_to_all(a, axis_name, 0, 0), q
        )
        exchanged[c] = (kc, q, q_recv, xs_c)

    def finish(c: int) -> None:
        """Stages 2+3 of block c: fused epilogue + allgather + decode."""
        kc, q, q_recv, xs_c = exchanged[c]
        q_own = reducers._sra_epilogue_q(
            q_recv, xs_c, own_idx, axis_name, cc, kc, x.dtype,
            raw_row=_raw_c(c) if pre is not None else None,
        )
        gathered = reducers._gather_rows(q_own, axis_name)
        outs[c] = reducers._dequantize_rows(gathered)  # (ws, w)
        if with_wire:
            rt_rows = reducers._dequantize_rows(q)
            raw_b = xs_c if pre is None else _raw_c(c)[None]
            rts[c] = jnp.where(own, raw_b.astype(rt_rows.dtype), rt_rows)
        exchanged[c] = None  # release the traced intermediates

    # The software pipeline: fill one block ahead, then steady-state.
    start(0)
    for c in range(depth):
        if c + 1 < depth:
            start(c + 1)
        finish(c)
    out = jnp.concatenate(outs, axis=1).reshape(-1)[:n].astype(x.dtype)
    if not with_wire:
        return out
    rt = (
        jnp.concatenate(rts, axis=1).reshape(-1)[:n].astype(x.dtype)
    )
    return out, rt


def dispatch_order(n_groups: int) -> Tuple[int, ...]:
    """Emission order of fused gradient groups in ``allreduce_tree`` when
    the schedule is engaged: REVERSED — backward produces the LAST
    layers' gradients first, so emitting tail groups' collectives first
    lets XLA start their exchanges while earlier layers' gradients are
    still being computed (the reference's DDP-hook bucket ordering,
    PAPER.md §0, re-expressed as emission order for the latency-hiding
    scheduler). Values are order-invariant — each group's stochastic key
    folds its ORIGINAL index — so this changes schedule, never bytes."""
    return tuple(reversed(range(n_groups)))
