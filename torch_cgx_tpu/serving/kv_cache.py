"""Paged KV-cache allocator: block pool, page tables, refcounted frees.

The allocator is HOST-side bookkeeping over a device-resident pool
(``ops/paged_kv.py`` owns the pool arrays and their codec): pages are
fixed-size blocks identified by integer ids, a sequence's cache is an
ordered page-id list (its page table), and a page is returned to the
free list only when its refcount drains — shared-prefix sequences
(``fork``) retain the same physical pages, the standard paged-attention
economy (vLLM's PagedAttention, applied here to *quantized* pages so the
pool and the prefill→decode wire share one byte layout).

Wire treatment resolves through the unified wire plane's edge registry
under the ``kv_page`` kind: a registered ``(kv_page, pattern)`` config —
the serving SLO controller's write target — wins per layer; otherwise
``CGX_KV_BITS`` is the env default (0 = raw f16 pages, the shipping
baseline). ``CGX_WIRE=off`` forces every page raw, the same one-knob
bisection story as every other edge kind.

Two page classes (docs/SERVING.md, "Window and global pages"). A *global*
page is what the above describes: a sequence holds as many as it is long.
A sliding-window layer's pages are a *ring*: a sequence never needs more
than ``ring`` of them, so it is given a ring whole (``alloc_ring``; ring
``g`` is the ``ring`` consecutive rows from ``g * ring`` of the window
layers' pools) and writes page ``n`` over page ``n - ring`` in slot ``n %
ring``. A ring is returned with the sequence's pages (``free_seq``,
``invalidate``), so no window page outlives its sequence, and it is never
forked.

Recovery cascade (ISSUE 15 satellite): live caches register in a module
WeakSet; ``supervisor.invalidate_trace_caches`` reaches
:func:`invalidate_page_tables`, which bumps every live cache's
generation and drops its page tables — a post-eviction scheduler can
never serve a stale page mapping (the analyzer's cache-reachability
pass proves the cascade edge).
"""

from __future__ import annotations

import dataclasses
import threading
import weakref
from typing import Dict, List, Optional

from .. import config as cfg_mod
from ..config import CompressionConfig
from ..observability import memledger
from ..robustness import faults as faults_mod
from ..utils.logging import get_logger, metrics
from ..wire import edges

log = get_logger()

# Live caches, for the recovery cascade. Dead caches self-evict; each
# member's page tables/generation reset through invalidate_page_tables.
# cgx-analysis: allow(orphan-memo) — weak liveness set: the cascade resets every MEMBER's derived state (invalidate_page_tables below, reached from supervisor.invalidate_trace_caches); clearing the set itself would only disconnect live caches from future cascades
_LIVE: "weakref.WeakSet" = weakref.WeakSet()


def resolve_kv_config(layer_name: str) -> Optional[CompressionConfig]:
    """The wire treatment of this layer's KV pages, or None (raw f16).

    Resolution order: ``CGX_WIRE=off`` -> raw (the bisection knob);
    a registered ``kv_page`` edge config matching ``layer_name`` (the
    SLO controller's write surface) -> its quantize cc; else the
    ``CGX_KV_BITS`` env default (0 -> raw). Quantize-only, like the
    all_to_all edges: low-rank/sparse peer compressors have no
    cross-step structure to exploit in a one-shot page."""
    if cfg_mod.wire_mode() == "off":
        return None
    ec = edges.resolve_edge(edges.EDGE_KV_PAGE, layer_name)
    if ec is not None:
        if ec.compressor != edges.COMPRESSOR_QUANTIZE:
            raise ValueError(
                f"edge ('kv_page', {layer_name!r}): compressor "
                f"{ec.compressor!r} is unsupported; KV pages quantize only"
            )
        return ec.cc if ec.cc.enabled else None
    bits = cfg_mod.kv_bits()
    if not bits:
        return None
    return CompressionConfig(bits=bits, bucket_size=0).merged_with_default(
        cfg_mod.default_compression_config()
    )


@dataclasses.dataclass
class _SeqEntry:
    pages: List[int]
    tokens: int  # committed tokens (pages * page_tokens of the owner)


class PagedKvCache:
    """Page-id allocator + per-sequence page tables (thread-safe).

    ``max_pages`` bounds the pool; ``page_tokens`` is the block
    granularity. The pool ARRAYS live with the scheduler
    (``ops/paged_kv.py`` pools) — this class owns which rows mean what.
    """

    def __init__(self, max_pages: int, page_tokens: int, rings: int = 0):
        if max_pages < 1 or page_tokens < 1:
            raise ValueError(
                f"max_pages/page_tokens must be >= 1, got "
                f"{max_pages}/{page_tokens}"
            )
        self.max_pages = int(max_pages)
        self.page_tokens = int(page_tokens)
        self.rings = int(rings)  # window-class rings to hand out (0: none)
        self.generation = 0
        self._lock = threading.Lock()
        self._free: List[int] = list(range(max_pages - 1, -1, -1))
        self._refs: Dict[int, int] = {}
        self._seqs: Dict[str, _SeqEntry] = {}
        self._free_rings: List[int] = list(range(self.rings - 1, -1, -1))
        self._ring_of: Dict[str, int] = {}
        _LIVE.add(self)

    # -- introspection -----------------------------------------------------

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def live_pages(self) -> int:
        with self._lock:
            return len(self._refs)

    def refcount(self, page_id: int) -> int:
        with self._lock:
            return self._refs.get(int(page_id), 0)

    def pages_of(self, seq_id: str) -> List[int]:
        with self._lock:
            e = self._seqs.get(seq_id)
            return list(e.pages) if e is not None else []

    def committed_tokens(self, seq_id: str) -> int:
        with self._lock:
            e = self._seqs.get(seq_id)
            return e.tokens if e is not None else 0

    def has_seq(self, seq_id: str) -> bool:
        with self._lock:
            return seq_id in self._seqs

    def pool_stats(self) -> Dict[str, int]:
        """One consistent snapshot of the pool's truth (the memory
        ledger's sampler and the gauge publisher read this): dedup_pages
        counts fork-shared page *copies avoided* (sum of refcounts above
        1 — the shared-prefix economy, bytes that would exist without
        fork); leaked = pages in neither the free list nor any refcount
        (reachable only through ``invalidate``)."""
        with self._lock:
            return self._pool_stats_locked()

    def _pool_stats_locked(self) -> Dict[str, int]:
        live = len(self._refs)
        free = len(self._free)
        return {
            "max_pages": self.max_pages,
            "page_tokens": self.page_tokens,
            "free_pages": free,
            "live_pages": live,
            "dedup_pages": sum(r - 1 for r in self._refs.values() if r > 1),
            "leaked_pages": self.max_pages - free - live,
            "seqs": len(self._seqs),
            "generation": self.generation,
        }

    def publish_pool_gauges(self) -> Dict[str, int]:
        """Refresh the ``cgx.serve.pool_*`` gauges from the pool's
        current truth. Mutators call this inline; the memory ledger
        calls it every sample tick so Prometheus scrapes BETWEEN decode
        steps see live occupancy, not the value as of the last alloc."""
        with self._lock:
            return self._publish_gauges_locked()

    def _publish_gauges_locked(self) -> Dict[str, int]:
        st = self._pool_stats_locked()
        metrics.set("cgx.serve.pool_free", float(st["free_pages"]))
        metrics.set("cgx.serve.pool_dedup_pages", float(st["dedup_pages"]))
        return st

    # -- allocation --------------------------------------------------------

    def alloc(self, seq_id: str) -> Optional[int]:
        """Append one fresh page to ``seq_id``'s table (creating the
        sequence on first use). None when the pool is exhausted — the
        scheduler's admission backpressure, never an exception on the
        decode path (``cgx.serve.pool_exhausted`` counts it)."""
        with self._lock:
            if not self._free:
                metrics.add("cgx.serve.pool_exhausted")
                return None
            pid = self._free.pop()
            self._refs[pid] = 1
            e = self._seqs.setdefault(seq_id, _SeqEntry(pages=[], tokens=0))
            e.pages.append(pid)
            e.tokens += self.page_tokens
            metrics.add("cgx.serve.pages_allocated")
            self._publish_gauges_locked()
            memledger.note_alloc("serve.kv_pool")
            return pid

    def alloc_ring(self, seq_id: str) -> Optional[int]:
        """The window-class ring of ``seq_id`` (one a sequence, taken on
        first use). None when every ring is held: admission backpressure,
        like :meth:`alloc`."""
        with self._lock:
            ring = self._ring_of.get(seq_id)
            if ring is None:
                if not self._free_rings:
                    metrics.add("cgx.serve.pool_exhausted")
                    return None
                ring = self._ring_of[seq_id] = self._free_rings.pop()
            return ring

    @property
    def free_rings(self) -> int:
        with self._lock:
            return len(self._free_rings)

    def fork(self, src_seq: str, dst_seq: str) -> List[int]:
        """Share ``src_seq``'s committed pages into a new sequence
        (prefix reuse): every shared page's refcount bumps; the fork
        COPIES the table, so the two sequences diverge from here (a
        page appended to one never appears in the other)."""
        with self._lock:
            src = self._seqs.get(src_seq)
            if src is None:
                raise KeyError(f"unknown source sequence {src_seq!r}")
            if dst_seq in self._seqs:
                raise ValueError(f"sequence {dst_seq!r} already exists")
            for pid in src.pages:
                self._refs[pid] += 1
            self._seqs[dst_seq] = _SeqEntry(
                pages=list(src.pages), tokens=src.tokens
            )
            # Fork changes dedup truth without touching the free list —
            # the one mutator the old pool_free-only refresh missed.
            self._publish_gauges_locked()
            return list(src.pages)

    def free_seq(self, seq_id: str) -> int:
        """Release every page of ``seq_id`` (refcounted: shared pages
        return to the free list only when the last holder drops).
        Unknown sequences are a no-op (eviction paths race completion).
        Returns the number of pages actually returned to the pool."""
        with self._lock:
            ring = self._ring_of.pop(seq_id, None)
            if ring is not None:
                self._free_rings.append(ring)
            e = self._seqs.pop(seq_id, None)
            if e is None:
                return 0
            freed = 0
            injector = faults_mod.get_injector()
            for pid in e.pages:
                n = self._refs.get(pid)
                if n is None:
                    raise RuntimeError(
                        f"page {pid} of {seq_id!r} has no refcount — "
                        "double free (allocator corruption)"
                    )
                if n <= 1:
                    del self._refs[pid]
                    if injector is not None and injector.fire("leak_page"):
                        # Chaos leak: the page's last reference drops but
                        # the page never reaches the free list — lost to
                        # both the pool and the refcount map until an
                        # invalidate rebuilds the free list. The ledger's
                        # alloc−release delta for serve.kv_pool is what
                        # must catch this (no note_release here — that
                        # suppression IS the fault).
                        continue
                    self._free.append(pid)
                    freed += 1
                else:
                    self._refs[pid] = n - 1
            self._publish_gauges_locked()
            memledger.note_release("serve.kv_pool", n=freed)
            return freed

    # -- recovery ----------------------------------------------------------

    def invalidate(self, reason: str = "invalidate") -> None:
        """Drop every page table and refcount; bump the generation. The
        post-recovery contract: page ids handed out before the bump name
        pool rows whose contents a reconfigured group may have replaced,
        so every mapping must re-derive (admitted sequences re-prefill —
        the scheduler treats a generation bump as a full eviction)."""
        with self._lock:
            dropped = len(self._seqs)
            # Everything not on the free list comes back — including
            # chaos-leaked pages — so the ledger's outstanding delta for
            # this pool settles to zero here (the reset hook the
            # mem-ledger-pairing pass pairs with alloc's note_alloc).
            reclaimed = self.max_pages - len(self._free)
            self._seqs.clear()
            self._refs.clear()
            self._free = list(range(self.max_pages - 1, -1, -1))
            self._ring_of.clear()
            self._free_rings = list(range(self.rings - 1, -1, -1))
            self.generation += 1
            metrics.add("cgx.serve.cache_invalidations")
            self._publish_gauges_locked()
            memledger.note_release("serve.kv_pool", n=reclaimed)
        log.info(
            "serving kv-cache invalidated (%s): %d sequence(s) dropped, "
            "generation -> %d", reason, dropped, self.generation,
        )


def invalidate_page_tables(reason: str = "reconfigure") -> None:
    """Recovery-cascade entry point (``supervisor.invalidate_trace_caches``):
    every live cache's page tables drop and its generation bumps, so no
    scheduler can serve a pre-recovery page mapping."""
    for cache in list(_LIVE):
        cache.invalidate(reason)
