"""Disaggregated prefill worker: compute KV, paginate, quantize, ship.

The prefill half of the serving plane: run the full causal forward over
a prompt (the compute-bound phase), cut the per-layer K/V into
fixed-size pages, quantize each page with the HOST codec
(``ops/codec_host.py`` — byte-identical wire to the JAX codec, so the
decode pool ingests frames without re-encoding) and ship them over a
:class:`~.transport.KvPageSender` stream. The stream opens with a META
frame carrying the prefill's own greedy argmax (``first_token``) — in
the disaggregated convention the prefill worker produces the first
output token, so decode's TTFT is bounded by page delivery, not by a
redundant forward.

Per-layer wire treatment resolves through the same
``kv_cache.resolve_kv_config`` the decode side uses; both ends must
agree (the scheduler rejects a stream whose frame specs mismatch its
pool specs and fails over to local prefill — tested).
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import numpy as np

from ..ops import codec_host
from ..utils.logging import get_logger, metrics
from ..utils.tracing import trace_span
from . import transport as tp
from .programs import _resolved_specs
from .scheduler import (
    _account_pages,
    _decode_program,
    _observe_page_qerr,
    _pad_prompt,
)

log = get_logger()


class PrefillWorker:
    """One prefill endpoint: ``serve(request_id, tokens)`` computes and
    ships a request's KV stream. Typically driven by its own thread or
    process; everything here is synchronous and bounded (the sender
    thread owns the store I/O)."""

    def __init__(
        self,
        server,
        store,
        *,
        shm=None,
        throttle_gbps: Optional[float] = None,
        transport_endpoint: str = "kvtx",
        transport_peers: Sequence[str] = ("kvrx",),
    ):
        tp.require_kv_streams(server)  # the frames are K and V pages
        self.server = server
        # PR 20: with CGX_TRANSPORT=socket every KvPageSender this worker
        # creates ships its frames over the socket plane toward
        # ``transport_peers`` (the decode receiver's endpoint); unset
        # keeps the store path byte-identical.
        self._store = tp.maybe_socket_store(
            store, endpoint=transport_endpoint, peers=transport_peers,
        )
        self._shm = shm
        # One shared modeled link across every stream this worker ships
        # (the bench contrast's shape — a per-stream rate would let N
        # concurrent streams ship at N times the link).
        self._throttle = (
            tp.LinkThrottle(throttle_gbps) if throttle_gbps else None
        )
        self._senders: list = []

    def serve(self, request_id: str, tokens: Sequence[int]) -> int:
        """Prefill + ship one request; returns the frame count (META
        included). The sender thread keeps draining after this returns —
        call :meth:`stop` to join them all (bounded)."""
        self._reap_drained()
        n_layer = self.server.n_layer
        sv = self.server.serve
        prompt = np.asarray(tokens, np.int32)
        s = prompt.shape[0]
        pt = sv.page_tokens
        n_full = s // pt
        tail_len = s - n_full * pt
        frames = 1 + 2 * n_layer * n_full + 2 * n_layer
        # Request-tagged prefill span (ISSUE 17): the critical-path
        # engine's TTFT decomposition joins it to the kv.ship stream
        # and the scheduler's submit/admit instants by ``req``.
        with trace_span(
            "serve.prefill", hist="cgx.serve.prefill_s",
            req=str(request_id), frames=frames, prompt_tokens=int(s),
        ):
            specs = _resolved_specs(self.server)
            first, ks, vs = _prefill_forward(self.server, prompt)
            sender = tp.KvPageSender(
                self._store, str(request_id), shm=self._shm,
                depth=sv.ship_depth, throttle=self._throttle,
            )
            self._senders.append(sender)
            sender.post_meta({
                "frames": frames,
                "prompt_tokens": int(s),
                "page_tokens": int(pt),
                "pages": int(n_full),
                "tail_tokens": int(tail_len),
                "first_token": int(first),
            })
            for page in range(n_full):
                lo, hi = page * pt, (page + 1) * pt
                for layer in range(n_layer):
                    spec = specs[layer]
                    for kind, cache in ((tp.K_PAGE, ks), (tp.V_PAGE, vs)):
                        row = cache[layer][lo:hi].reshape(-1)
                        sender.post_page(
                            layer, kind, page, spec.bits,
                            spec.bucket_size if spec.quantized else 0,
                            spec.flat, _encode_page(row, spec),
                        )
                    if spec.quantized:
                        _observe_page_qerr(
                            self.server.layer_name(layer), spec,
                            ks[layer][lo:hi].reshape(1, -1),
                            already_host=True,
                        )
                    _account_pages(self.server.layer_name(layer), spec, 2)
            # The not-yet-full last page ships raw f16 (it is re-quantized
            # by the decode side only when it fills and commits).
            for layer in range(n_layer):
                for kind, cache in ((tp.K_TAIL, ks), (tp.V_TAIL, vs)):
                    vals = cache[layer][n_full * pt:].astype(np.float16)
                    sender.post_page(
                        layer, kind, 0, 0, 0, int(vals.size),
                        vals.tobytes(),
                    )
            metrics.add("cgx.serve.prefills_shipped")
        return frames

    def _reap_drained(self) -> None:
        """Join senders whose queue has drained (one sender thread per
        stream — without reaping, a long-running worker accumulates one
        idle OS thread per request ever served). ``stop`` only blocks
        new dequeues; frames already dequeued still ship (the sender's
        finish-the-batch contract), so a drained queue + bounded join
        means the stream is fully on the wire."""
        still = []
        for sender in self._senders:
            if sender.pending() == 0:
                sender.stop(timeout=2.0)
            else:
                still.append(sender)
        self._senders = still

    def stop(self, timeout: float = 5.0) -> None:
        """Bounded join of every stream's sender thread."""
        deadline = time.monotonic() + timeout
        for sender in self._senders:
            sender.stop(timeout=max(0.1, deadline - time.monotonic()))
        self._senders.clear()


def _prefill_forward(server, prompt: np.ndarray):
    """(first_token, ks, vs): the full forward's greedy argmax and the
    per-layer K/V as host arrays ``(S, H, Dh) f32`` — jitted through the
    server's own program (prompts pad to a page multiple, so prefill and
    local-prefill numerics AND compiled programs are one code path)."""
    prog = _decode_program(server)
    s = prompt.shape[0]
    padded = _pad_prompt(prompt, server.serve.page_tokens)
    first, payloads = prog.prefill(
        server.p, padded[None],
        np.arange(padded.shape[0], dtype=np.int32)[None],
        np.int32(s - 1),
    )
    return (
        int(np.asarray(first)[0]),
        [np.asarray(k[0, :s], np.float32) for k in payloads["k"]],
        [np.asarray(v[0, :s], np.float32) for v in payloads["v"]],
    )


def _encode_page(row: np.ndarray, spec) -> bytes:
    """One page payload's wire bytes: host-codec meta|packed layout for
    quantized specs (identical bytes to the decode pool's own commit
    path — deterministic codec), raw f16 otherwise."""
    if not spec.quantized:
        return row.astype(np.float16).tobytes()
    q = codec_host.quantize(
        row.astype(np.float32), spec.bits, spec.bucket_size
    )
    return q.to_bytes().tobytes()


__all__ = ["PrefillWorker"]
