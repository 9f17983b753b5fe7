"""Disaggregated prefill→decode KV page transport.

One stream per request, over the same c10d-style store (plus, when both
ends share a host, the hardened/checksummed/traced :class:`ShmChannel`
byte plane) every bridge collective already rides. The wire protocol is
the PR 13 ``AsyncBridgeSender`` pattern applied to serving:

* ``cgxkv/<stream>/n`` — a store counter, bumped AFTER the payload key
  is readable (publish-after-write: a decode worker that observes seq
  ``k`` can fetch frame ``k`` without waiting — decode NEVER blocks on
  prefill);
* ``cgxkv/<stream>/<seq>`` — one framed message: a fixed struct header
  (layer, kind, page index, codec geometry, crc32) + the page's wire
  bytes — for quantized pages exactly the pool-row byte layout
  (``ops/codec_host.py`` wire format), so a received frame drops into
  the decode pool without re-encoding.

A stream opens with a META frame (expected page count, prompt length,
tail geometry) so the receiver knows completion without ever waiting; a
mid-stream prefill death therefore surfaces as a *stalled* stream — the
receiver's ``stalled()`` staleness probe, which the scheduler turns into
a bounded local-prefill failover (``cgx.serve.prefill_failovers``)
instead of a wedge.

The sender is a dedicated thread draining a post queue (prefill's
critical path never blocks on the store either); every wait in its body
is bounded (``tools/lint.py check_serve_scheduler_blocking``).
``throttle_gbps`` models a constrained interconnect for benches — the
sleep is proportional to FRAME bytes, which is precisely how a
bandwidth-bound link prices the quantized-vs-raw contrast
(``bench.py --serve``).
"""

from __future__ import annotations

import dataclasses
import json
import queue as _queue
import struct
import threading
import time
import zlib
from typing import Dict, List, Optional

from .. import config as cfg_mod
from ..observability import timeline
from ..utils.logging import get_logger, metrics

log = get_logger()

# Frame kinds.
K_PAGE = 0
V_PAGE = 1
K_TAIL = 2  # raw f16 tail block (the not-yet-full last page)
V_TAIL = 3
META = 4
# The frame kinds carry a K/V cache and nothing else: kind -> (the cache
# stream it belongs to, whether it is a whole page).
STREAM_OF_KIND = {
    K_PAGE: ("k", True), V_PAGE: ("v", True),
    K_TAIL: ("k", False), V_TAIL: ("v", False),
}


def require_kv_streams(server) -> None:
    """The disaggregated path ships K and V frames of every layer: refuse,
    up front and in plain words, an adapter whose cache streams are others
    (a latent cache), whose layers do not all leave K and V pages, or that
    keeps a recurrent state a lane (no frame kind ships one), or whose
    layers run several passes a token (a frame names a layer and a page and
    no pass), or whose step runs a block of positions (a stream brings a
    first token, a block adapter has none), instead of half-working."""
    refusal = (
        "the disaggregated prefill path ships K and V page frames "
        f"(serving/transport.py kinds); adapter {server.kind!r} "
    )
    if server.block_tokens > 1:
        raise ValueError(
            f"{refusal}runs a block of {server.block_tokens} positions a "
            "step and opens a lane with the prompt's remainder, where a "
            "stream's META frame carries one first token, and is served "
            "with local prefill only"
        )
    if server.cache_passes > 1:
        raise ValueError(
            f"{refusal}runs its layers {server.cache_passes} times a token "
            "and keeps a cache a pass, which a frame's layer and page "
            "cannot address, and is served with local prefill only"
        )
    for layer in range(server.n_layer):
        names = [name for name, _ in server.cache_streams(layer)]
        state = [name for name, _, _ in server.state_streams(layer)]
        window = server.page_window(layer)
        if window:
            raise ValueError(
                f"{refusal}keeps layer {layer}'s pages as a ring of its "
                f"window ({window} tokens), which a page stream "
                "cannot address, and is served with local prefill only"
            )
        if state:
            raise ValueError(
                f"{refusal}keeps the recurrent state {state} a lane at "
                f"layer {layer}, which no frame kind ships, and is served "
                "with local prefill only"
            )
        if names != ["k", "v"]:
            raise ValueError(
                f"{refusal}caches the streams {names} at layer {layer} and "
                "is served with local prefill only"
            )
# Elastic-join snapshot pages (robustness/elastic.py — the param_page
# wire edge): the `layer` field carries the flat LEAF index of the
# training-state tree, `page_idx` the page within that leaf.
P_PAGE = 5  # codec-compressed leaf page (HostQTensor wire bytes)
P_RAW = 6  # raw leaf page bytes (lossless — the bit-identity default)

# layer(u16) kind(u16) page_idx(u16) bits(u16) bucket(u32) numel(u32)
# crc(u32; the sentinel _NO_CRC = unchecked)
_FRAME = struct.Struct("<HHHHIII")

# Checksum-off sentinel. A real crc32 landing ON the sentinel (p = 2^-32)
# just skips that one frame's verify — safe, never a false corruption.
_NO_CRC = 0xFFFFFFFF

_TICK_S = 0.2
_SHIP_RETRIES = 3
_SHIP_BACKOFF_S = 0.05

DEFAULT_SHIP_DEPTH = 4


def maybe_socket_store(
    store, endpoint: str, peers=(), prefixes=("cgxkv/",), exclude=(),
):
    """Route this store's page-stream keys over the supervised socket
    plane when ``CGX_TRANSPORT=socket`` (PR 20). Lazy + best-effort by
    design: the torch_backend package (where the plane lives) is only
    imported once the knob asks for it, and any failure falls back to
    the plain store — serving must never lose a stream to a transport
    bootstrap problem. With the knob unset this returns ``store``
    unchanged (the byte-compatibility pin)."""
    if cfg_mod.transport_mode() != "socket":
        return store
    try:
        from ..torch_backend.transport import maybe_wrap_store

        return maybe_wrap_store(
            store, endpoint=endpoint, peers=tuple(peers),
            prefixes=tuple(prefixes), exclude=tuple(exclude),
        )
    except Exception as e:
        log.warning(
            "kv transport: socket plane unavailable (%s); store path", e
        )
        return store


class LinkThrottle:
    """Byte-proportional model of ONE shared bandwidth-bound link
    (bench.py --serve): every sender reserving through the same instance
    serializes its bytes at ``gbps``, so aggregate admission latency
    scales with total wire bytes — the quantized-vs-raw contrast a real
    constrained interconnect would price. Thread-safe; the reservation
    is taken under the lock, the sleep happens outside it."""

    def __init__(self, gbps: float):
        if gbps <= 0:
            raise ValueError(f"throttle gbps must be > 0, got {gbps}")
        self._bps = gbps * 1e9
        self._lock = threading.Lock()
        self._free_at = 0.0

    def acquire(self, n_bytes: int) -> None:
        now = time.monotonic()
        with self._lock:
            start = max(now, self._free_at)
            self._free_at = start + n_bytes / self._bps
            until = self._free_at
        if until > now:
            time.sleep(until - now)


@dataclasses.dataclass(frozen=True)
class PageFrame:
    """One decoded transport frame."""

    layer: int
    kind: int
    page_idx: int
    bits: int
    bucket: int
    numel: int
    payload: bytes

    @property
    def is_meta(self) -> bool:
        return self.kind == META


def frame_page(
    layer: int, kind: int, page_idx: int, bits: int, bucket: int,
    numel: int, payload: bytes, *, checksum: bool = True,
) -> bytes:
    crc = zlib.crc32(payload) if checksum else _NO_CRC
    return _FRAME.pack(
        layer, kind, page_idx, bits, bucket, numel, crc
    ) + payload


def unframe_page(buf: bytes) -> PageFrame:
    layer, kind, page_idx, bits, bucket, numel, crc = _FRAME.unpack_from(buf)
    payload = bytes(buf[_FRAME.size:])
    if crc != _NO_CRC and zlib.crc32(payload) != crc:
        from ..robustness.errors import WireCorruptionError

        raise WireCorruptionError(
            f"kv transport: frame checksum mismatch (layer {layer}, kind "
            f"{kind}, page {page_idx}) — the page payload is corrupted"
        )
    return PageFrame(layer, kind, page_idx, bits, bucket, numel, payload)


def peek_header(buf: bytes) -> PageFrame:
    """Decode a frame's fixed header WITHOUT verifying the payload crc,
    payload attached unverified. The snapshot receiver's re-request path
    needs the (leaf, page) identity of a frame whose checksum just
    failed — the header is outside the checksummed region, so it is
    still trustworthy enough to name the page to re-request (a corrupted
    header at worst re-requests the wrong page, which the donor serves
    idempotently)."""
    layer, kind, page_idx, bits, bucket, numel, _ = _FRAME.unpack_from(buf)
    return PageFrame(
        layer, kind, page_idx, bits, bucket, numel, bytes(buf[_FRAME.size:])
    )


def meta_frame(meta: Dict, *, checksum: bool = True) -> bytes:
    return frame_page(
        0, META, 0, 0, 0, 0, json.dumps(meta).encode(), checksum=checksum
    )


class KvPageSender:
    """Prefill side: enqueue frames, a dedicated thread ships them.

    ``stream`` names the request's key namespace; ``shm`` (optional
    :class:`~..torch_backend.shm.ShmChannel`) carries payload bytes over
    the same-host byte plane (checksummed + traced there too) with the
    store holding only headers; without it the frame bytes ride the
    store directly. ``depth`` frames ship per thread tick (the
    planner-picked pipelining granularity, ``CGX_KV_SHIP_DEPTH``);
    ``throttle_gbps`` models link bandwidth (benches). A ship failure
    retries bounded, then counts ``cgx.serve.ship_errors`` — staleness
    detection on the decode side is the recovery surface, exactly the
    async-plane contract.
    """

    def __init__(
        self,
        store,
        stream: str,
        *,
        shm=None,
        depth: Optional[int] = None,
        throttle: Optional[LinkThrottle] = None,
        throttle_gbps: Optional[float] = None,
        checksum: Optional[bool] = None,
    ):
        self._store = store
        self.stream = str(stream)
        self._shm = shm
        d = depth if depth is not None else (cfg_mod.kv_ship_depth() or 0)
        self.depth = int(d) if d else DEFAULT_SHIP_DEPTH
        # `throttle` shares one modeled link across streams (the bench's
        # shape); `throttle_gbps` is the private-link convenience.
        self._throttle = throttle or (
            LinkThrottle(throttle_gbps) if throttle_gbps else None
        )
        self._checksum = (
            cfg_mod.wire_checksum() if checksum is None else bool(checksum)
        )
        self._q: "_queue.Queue" = _queue.Queue()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._thread_lock = threading.Lock()
        self._seq = 0

    # -- keys --------------------------------------------------------------

    def _counter_key(self) -> str:
        return f"cgxkv/{self.stream}/n"

    def _payload_key(self, seq: int) -> str:
        return f"cgxkv/{self.stream}/{seq}"

    # -- producer side -----------------------------------------------------

    def post_meta(self, meta: Dict) -> None:
        # End-to-end request attribution (ISSUE 17): the stream name IS
        # the request id scheduler-side — stamp it into the META frame
        # so the decode side (and the critical-path engine) can join
        # the wire stream back to the request without the scheduler's
        # stream registry.
        if "request_id" not in meta:
            meta = dict(meta, request_id=self.stream)
        self._post(meta_frame(meta, checksum=self._checksum))

    def post_page(
        self, layer: int, kind: int, page_idx: int, bits: int, bucket: int,
        numel: int, payload: bytes,
    ) -> None:
        self._post(frame_page(
            layer, kind, page_idx, bits, bucket, numel, payload,
            checksum=self._checksum,
        ))

    def _post(self, buf: bytes) -> None:
        self._ensure_thread()
        # The seq is assigned ONCE per frame, here — a retried ship must
        # reuse it, or the publish counter walks past a key that was
        # never written and the receiver (which fetches densely) stalls
        # a stream the retry machinery actually saved.
        self._seq += 1
        self._q.put((self._seq, buf))

    def pending(self) -> int:
        return self._q.qsize()

    def _ensure_thread(self) -> None:
        with self._thread_lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._run, name="cgx-kv-send", daemon=True
                )
                self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                item = self._q.get(timeout=_TICK_S)
            except _queue.Empty:
                continue
            batch = [item]
            # Drain up to `depth` frames per tick: the shipping window
            # the planner sizes (solve_serve_plan) — deep enough to
            # pipeline page encode against the wire. A stop request is
            # honored between batches, never mid-batch: frames already
            # dequeued MUST ship (dropping them would leave the stream
            # permanently short of its META count — the reaper in
            # PrefillWorker.serve relies on this).
            while len(batch) < self.depth:
                try:
                    batch.append(self._q.get_nowait())
                except _queue.Empty:
                    break
            for seq, buf in batch:
                self._ship_with_retries(seq, buf)

    def _ship_with_retries(self, seq: int, buf: bytes) -> None:
        for attempt in range(_SHIP_RETRIES):
            try:
                self._ship(seq, buf)
                return
            except Exception as e:
                metrics.add("cgx.serve.ship_errors")
                log.warning(
                    "kv sender %s: shipping frame failed (attempt "
                    "%d/%d): %s", self.stream, attempt + 1, _SHIP_RETRIES, e,
                )
                if attempt + 1 == _SHIP_RETRIES:
                    metrics.add("cgx.serve.frames_lost")
                    from ..observability import flightrec

                    flightrec.record(
                        "kv_send_lost", stream=self.stream,
                        error=str(e)[:160],
                    )
                else:
                    # Backoff, but never abandon a dequeued frame on a
                    # stop request — the seq is already assigned, so an
                    # unshipped frame is a permanent hole the receiver
                    # can only resolve through a failover.
                    self._stop.wait(_SHIP_BACKOFF_S * (1 << attempt))

    def _ship(self, seq: int, buf: bytes) -> None:
        t0 = time.perf_counter()
        self._ship_inner(seq, buf)
        # Request-tagged wire span: the critical-path engine's TTFT
        # decomposition reads page-ship exposure from these.
        timeline.record(
            "kv.ship", timeline.CAT_WIRE, t0, time.perf_counter() - t0,
            key=self._payload_key(seq), req=self.stream, bytes=len(buf),
        )

    def _ship_inner(self, seq: int, buf: bytes) -> None:
        if self._throttle is not None:
            # Modeled link bandwidth (bench.py --serve): a frame costs
            # its own bytes' worth of wall time ON THE SHARED LINK
            # before it publishes, so wire-byte savings translate to
            # admission latency exactly as on a real bandwidth-bound
            # interconnect.
            self._throttle.acquire(len(buf))
        key = self._payload_key(seq)
        if self._shm is not None:
            self._shm.put(key, buf, readers=1)
        else:
            self._store.set(key, buf)
        # publish-after-write: the counter moves only once the frame is
        # readable, so the receiver's poll never waits on a half-posted
        # page.
        self._store.add(self._counter_key(), 1)
        metrics.add("cgx.serve.frames_shipped")
        metrics.add("cgx.serve.kv_bytes_wire", float(len(buf)))
        metrics.set("cgx.serve.send_backlog", float(self._q.qsize()))

    def stop(self, timeout: float = 2.0) -> None:
        """Bounded join; unshipped frames are dropped (the receiver's
        staleness probe — not this thread — owns that failure mode)."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        self._thread = None


@dataclasses.dataclass
class _StreamState:
    expected: Optional[int] = None  # frames incl. meta; None until META
    received: int = 0
    consumed_seq: int = 0
    meta: Optional[Dict] = None
    last_advance: float = 0.0
    done: bool = False
    # A frame that failed to decode (corruption, torn meta) poisons the
    # stream: it can never complete, so the scheduler's failover rung
    # takes it immediately instead of waiting out the staleness bound.
    failed: bool = False


class KvPageReceiver:
    """Decode side: non-blocking drain of every registered stream.

    ``poll()`` reads each stream's counter with ``add(0)`` and fetches
    only seqs at or below it — keys that exist by the publish-after-write
    ordering, so the gets return promptly (and the shm path's header
    fetch is store-timeout-bounded regardless). Completion comes from the
    META frame's expected count; ``stalled()`` is the prefill-death
    probe the scheduler's failover rung consumes.
    """

    def __init__(self, store, *, shm=None, transport_endpoint: str = "kvrx"):
        # PR 20: with CGX_TRANSPORT=socket the receiver registers a plane
        # endpoint (default "kvrx" — the prefill side's default peer) so
        # page frames land in its socket mailbox; unset leaves the store
        # untouched.
        self._store = maybe_socket_store(store, endpoint=transport_endpoint)
        self._shm = shm
        self._streams: Dict[str, _StreamState] = {}
        self._store_can_delete: Optional[bool] = None

    def add_stream(self, stream: str) -> None:
        self._streams.setdefault(str(stream), _StreamState(
            last_advance=time.monotonic()
        ))

    def drop_stream(self, stream: str) -> None:
        st = self._streams.pop(str(stream), None)
        if st is not None:
            self._delete_key(f"cgxkv/{stream}/n")

    def _delete_key(self, key: str) -> None:
        """Best-effort consume-side GC with a one-time capability probe
        (the async-plane ``_delete_key`` contract: stores without delete
        keep their keys — a bounded leak, never an error)."""
        if self._store_can_delete is False:
            return
        try:
            self._store.delete_key(key)
            self._store_can_delete = True
        except (NotImplementedError, AttributeError):
            self._store_can_delete = False
        except Exception as e:
            self._store_can_delete = False
            log.debug("kv store delete(%r) failed: %s", key, e)

    def meta(self, stream: str) -> Optional[Dict]:
        st = self._streams.get(str(stream))
        return st.meta if st is not None else None

    def complete(self, stream: str) -> bool:
        st = self._streams.get(str(stream))
        return bool(st is not None and st.done)

    def stalled(self, stream: str, timeout_s: float) -> bool:
        """An incomplete stream whose last frame landed more than
        ``timeout_s`` ago — the prefill worker died or wedged
        mid-stream — or one a poisoned frame already failed. Pure clock
        arithmetic; never blocks."""
        st = self._streams.get(str(stream))
        if st is None or st.done:
            return False
        return st.failed or (
            time.monotonic() - st.last_advance > timeout_s
        )

    def _fetch(self, stream: str, seq: int) -> bytes:
        """Single-consumer fetch-and-consume: the shm path's ``take``
        acks the arena region (the writer reclaims); the store path
        deletes the payload key after the read — without it every page
        ever served would sit in the store for the process lifetime."""
        key = f"cgxkv/{stream}/{seq}"
        if self._shm is not None:
            return self._shm.take(key).tobytes()
        buf = bytes(self._store.get(key))
        self._delete_key(key)
        return buf

    def poll(self) -> List:
        """Newly published frames across every stream, in (stream, seq)
        order: ``(stream, PageFrame)`` pairs. Never blocks on an
        unpublished frame."""
        out: List = []
        for stream in sorted(self._streams):
            st = self._streams[stream]
            if st.done:
                continue
            try:
                n = int(self._store.add(f"cgxkv/{stream}/n", 0))
            except Exception as e:
                metrics.add("cgx.serve.poll_errors")
                log.warning(
                    "kv poll: counter read for %s failed: %s", stream, e
                )
                continue
            for seq in range(st.consumed_seq + 1, n + 1):
                try:
                    buf = self._fetch(stream, seq)
                except Exception as e:
                    metrics.add("cgx.serve.poll_errors")
                    log.warning(
                        "kv poll: fetch %s/%d failed: %s", stream, seq, e
                    )
                    break
                st.consumed_seq = seq
                st.last_advance = time.monotonic()
                try:
                    frame = unframe_page(buf)
                    if frame.is_meta:
                        st.meta = json.loads(frame.payload.decode())
                        st.expected = int(st.meta.get("frames", 0))
                except Exception as e:
                    # Counted-never-raised (the transport contract): a
                    # corrupt/torn frame must cost ONE stream a
                    # failover, not the whole serving loop. The stream
                    # is poisoned — it can never complete — so
                    # ``stalled()`` hands it to the failover rung
                    # immediately.
                    metrics.add("cgx.serve.poll_errors")
                    st.failed = True
                    from ..observability import flightrec

                    flightrec.record_failure(
                        e, op="kv.poll", key=f"cgxkv/{stream}/{seq}"
                    )
                    log.warning(
                        "kv poll: frame %s/%d failed to decode (%s) — "
                        "stream poisoned, failing over", stream, seq, e,
                    )
                    break
                st.received += 1
                timeline.instant(
                    "kv.recv", cat=timeline.CAT_WIRE,
                    key=f"cgxkv/{stream}/{seq}", req=stream,
                    bytes=len(buf),
                )
                metrics.add("cgx.serve.frames_received")
                if st.expected is not None and st.received >= st.expected:
                    st.done = True
                out.append((stream, frame))
        return out
