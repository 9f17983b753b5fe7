"""Continuous-batching decode scheduler over the paged quantized KV pool.

The scheduler serves a MODEL ADAPTER (the protocol is written out above
:class:`GPT2Server`): the adapter states, per layer, the cache streams a
token leaves behind as ``(name, PageSpec)`` — GPT-2's ``k`` and ``v``, a
latent-attention model's latent and rotated key (``serving/latent.py``) —
and gives a prefill and a decode forward over them. Pools, tails and every
compiled program below go over the streams the adapter names; nothing here
knows what a stream means. Layers may name different streams (a hybrid
model's few attention layers among its state-space ones,
``serving/hybrid.py``): a layer without a stream has no pool, tail or
payload for it. Beside the pages an adapter may state, per layer, STATE
streams ``(name, shape, dtype)``: a fixed-size recurrent state a lane,
rewritten whole by every decode step, written into a lane at admission from
what the prefill left on the device, and never committed, paged, forked or
evicted by page.

A layer's pages are of one of two CLASSES, which the adapter states
(``page_window(layer)``, "Window and global pages" in docs/SERVING.md).
*Global* pages are the above: the lane's row of ``page_table``, as many as
the sequence is long. A sliding-window layer's pages are a *ring*: ``ring =
ceil(W / page_tokens) + 1`` pool rows a sequence (``kv_cache.alloc_ring``),
named by the lane's row of a second table, ``ring_table (lanes, ring)``;
page ``n`` is written into slot ``n % ring``, over page ``n - ring``, which
no query of the lane can see again. Such a layer's pools hold ``max_batch x
ring + 1`` rows whatever ``max_seq`` is, its decode read goes over the ring
alone, and its mask is made of positions (:func:`ring_masks`). An adapter
that states no window builds exactly the programs and the state it built
before there were classes.

The decode worker runs ONE compiled step program: for every lane of a
fixed ``CGX_SERVE_MAX_BATCH``-wide batch, gather the lane's committed KV
pages (``ops/paged_kv.gather_dequant_pages`` — ``cfg.dtype`` rows as the
attention reads them, Pallas codec on TPU dispatch), attend the lane's
current token against the pages and, apart, the raw f32 tail block, and
emit the greedy next token. Admission and eviction happen per step
around that program (continuous batching): completed lanes free their
pages back to the refcounted pool and a waiting request takes the lane
on the next step — the batch never drains to refill. An admission is two
more compiled programs over the same donated state: ``prefill_pages``
(forward, the prompt's pages into the pools, its tails and its first token
left on the device) and ``admit_lane`` (one lane written in place, the
first token an operand it takes from the device).

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

The tick is cut where the host stops: a ``trace_span`` at every dispatch
(``serve.prefill.forward``, ``serve.admit_lane``, ``serve.dispatch.commit``,
``serve.dispatch.step``) and at every blocking read
(``serve.prefill.first_token``, ``serve.wait.step``). Three accounts are read
from the cuts (docs/OBSERVABILITY.md): the tick's (with the caller's time
between two ticks it adds up to the loop's wall time, and a tick far over the
running mean leaves one warning line that says where its time went:
:meth:`ContinuousBatchScheduler._close_tick`), the first token's (what was
dispatched between a request's lane write and the read of its token,
``cgx.serve.ttft_behind_s``) and the unfed device's (from a read that leaves
nothing queued to the next dispatch that carries work,
``cgx.serve.device_unfed_s``).

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
import time
from collections import OrderedDict, deque
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import config as cfg_mod
from ..models.attention import decode_attention, dense_attention
from ..models.gpt2 import GPT2Config
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
# The per-lane bookkeeping of the decode state, and what ``release_lanes``
# resets each entry of a finished or evicted lane to.
_LANE_RESET = {"active": False, "n_pages": 0, "tail_len": 0, "page_table": -1}
# The same of the second table, which a model with window layers keeps.
_RING_RESET = {"ring_table": -1}


# ---------------------------------------------------------------------------
# Config + request surface.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving geometry (static shapes of the compiled decode step)."""

    page_tokens: int
    max_batch: int
    max_pages: int
    max_seq: int
    ship_depth: int
    eos_token: Optional[int] = None

    def __post_init__(self):
        if self.max_seq < self.page_tokens:
            raise ValueError(
                f"max_seq {self.max_seq} < page_tokens {self.page_tokens}"
            )

    @property
    def pages_per_seq(self) -> int:
        return -(-self.max_seq // self.page_tokens)

    @property
    def commit_lanes(self) -> int:
        """Tails one call of the ``commit`` program promotes (its ``K``).
        With every lane decoding, ``max_batch / page_tokens`` tails fill a
        step; a power of two four times that, and at least 4, leaves a
        second call to the rare tick in which more fill at once (the start
        of a run, a burst of equal prompts): 8 of 96 lanes at 64-token
        pages, 4 of 32 or 64 at 64 or 256."""
        fills = -(-self.max_batch // self.page_tokens)
        return min(self.max_batch, max(4, 1 << (4 * fills - 1).bit_length()))

    @classmethod
    def from_env(cls, model=None,
                 eos_token: Optional[int] = None) -> "ServeConfig":
        """Knobs with the planner filling the zeros: ``CGX_KV_PAGE_TOKENS``
        / ``CGX_KV_SHIP_DEPTH`` unset lets ``planner.solve_serve_plan``
        pick page size and shipping depth from the serve cost curves.
        ``model`` (an adapter or its model config: anything with
        ``n_layer``, ``kv_bytes_per_token()`` and
        ``state_bytes_per_lane()``) says what a token's cache weighs, which
        differs sevenfold between a K/V cache and a latent one, and what a
        lane's recurrent state weighs whatever its length; without it the
        static defaults apply."""
        pt = cfg_mod.kv_page_tokens()
        depth = cfg_mod.kv_ship_depth()
        if (not pt or not depth) and model is not None:
            from ..parallel import planner

            plan = planner.solve_serve_plan(
                prompt_tokens=min(cfg_mod.serve_max_seq(), 128),
                kv_token_bytes=model.kv_bytes_per_token(),
                # a hybrid model's pages are its attention layers' alone
                n_layers=getattr(model, "n_cache_layers", model.n_layer),
                bits=cfg_mod.kv_bits(),
                bucket=cfg_mod.default_compression_config().bucket_size,
                state_lane_bytes=model.state_bytes_per_lane(),
            )
            pt = pt or plan.page_tokens
            depth = depth or plan.ship_depth
        return cls(
            page_tokens=pt or cfg_mod.DEFAULT_KV_PAGE_TOKENS,
            max_batch=cfg_mod.serve_max_batch(),
            max_pages=cfg_mod.serve_max_pages(),
            max_seq=cfg_mod.serve_max_seq(),
            ship_depth=depth or tp.DEFAULT_SHIP_DEPTH,
            eos_token=eos_token,
        )


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


# ---------------------------------------------------------------------------
# Model adapters. What the scheduler and its programs ask of one:
#
#   kind            a name for the program key ("gpt2", "mla_moe")
#   geometry        hashable model geometry, for the program key
#   n_layer, serve, p (the parameter tree, an argument of every program)
#   step_counters   names under ``cgx.serve.`` of what ``decode_forward``
#                   counts each step (empty for a model that counts nothing)
#   layer_name(l)   the layer's ``kv_page`` edge name
#   cache_streams(l)  the layer's cache streams, ``((name, PageSpec), ...)``,
#                   built with :func:`page_specs`; ``()`` for a layer that
#                   leaves no pages. The programs' stream names are the
#                   layers' union, in order of first appearance
#   state_streams(l)  the layer's recurrent state a lane, ``((name, shape,
#                   dtype), ...)``; ``()`` for a layer (or a model) with none
#   page_window(l)  optional. The class of the layer's pages: 0, global (the
#                   lane's ``page_table`` row); ``W``, the layer attends the
#                   last ``W`` positions and keeps its pages as a ring (the
#                   lane's ``ring_table`` row, :func:`ring_pages` slots). All
#                   window layers of a model state one ``W``. An adapter
#                   without the method has global pages alone
#   with_params(p)  the adapter over another (traced) parameter tree
#   kv_bytes_per_token()  float32 bytes a token's cache weighs, all layers
#   state_bytes_per_lane()  float32 bytes of a lane's state streams, all layers
#   prefill_forward(tokens, positions, last_idx) -> (logits (B, V), then one
#                   list per cache stream name, of each layer's (B, S,
#                   n_head, d_head) f32 cache payload, then one list per
#                   state stream name, of each layer's (B, *shape) state after
#                   position ``last_idx``; None in a list for a layer
#                   without that stream)
#   decode_forward(state, streams) -> (logits (B, V), {stream: [each
#                   layer's new tail (B, page_tokens, n_head * d_head) f32,
#                   rows as the attention reads them, this token's written
#                   by ``paged_kv.append_tail_rows``]} and,
#                   in the same dictionary, {state stream: [each layer's new
#                   state (B, *shape)]}, None for a layer without it,
#                   int32 vector of ``step_counters`` or None); ``state``
#                   holds ``pools[l][stream]``, ``tail_<stream>[l]`` and
#                   ``state_<state stream>[l]``
#
# ``pools[l][stream]`` is ``paged_kv.empty_pool(max_pages + 1, spec)``
# (``max_batch * ring + 1`` for a window layer): for
# a quantized stream ``(words (max_pages + 1, *spec.word_shape) int32, meta
# (max_pages + 1, num_buckets, 2) f32)``, a page's wire words as rows of
# 128 — the flat decode kernel's own blocks, so the read fetches a page from
# the pool by its id and nothing gathers or reshapes the pool first (on the
# chip a ``(n, W)`` and a ``(n * W / 128, 128)`` array tile differently:
# ``ops/paged_kv.py``, "Layouts"); ``(max_pages + 1, page_tokens, n_head,
# d_head) f16`` for a raw one. The last row is scratch: a padded slot of the
# ``commit`` program and a prefill's last page that is a tail write there.
#
# The GPT-2 adapter: explicit-parameter forward passes over the module's
# own parameter tree (models/gpt2.py) — decode against the paged cache
# needs per-layer K/V in and out, which the flax module doesn't expose.
# ---------------------------------------------------------------------------


def page_specs(layer_name: str, page_tokens: int,
               shapes: Sequence[Tuple[int, int]]) -> List[paged_kv.PageSpec]:
    """The page geometry of a layer's cache streams, one per ``(n_head,
    d_head)`` of ``shapes``, under the CURRENT ``kv_page`` resolution of the
    layer (resolved once: this runs every tick, for the program key): the
    registered edge configs (the SLO controller's writes) or the
    ``CGX_KV_BITS`` env default decide bits; the bucket is the resolved
    config's (env-back-filled) bucket clipped to each stream's page
    payload."""
    cc = kv_mod.resolve_kv_config(layer_name)
    if cc is None or not cc.enabled:
        return [paged_kv.PageSpec(page_tokens, h, d, bits=0, bucket_size=1)
                for h, d in shapes]
    return [
        paged_kv.PageSpec(
            page_tokens, h, d, bits=cc.bits,
            bucket_size=paged_kv.default_bucket(page_tokens * h * d,
                                                cc.bucket_size),
        )
        for h, d in shapes
    ]


def _ln(x, scale, bias, eps=1e-6):
    """flax.linen.LayerNorm numerics (f32 stats, rsqrt, mean2 variance)."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    mean2 = (xf * xf).mean(-1, keepdims=True)
    var = jnp.maximum(0.0, mean2 - mean * mean)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return y * scale.astype(jnp.float32) + bias.astype(jnp.float32)


def _dense(x, w, b, dtype):
    y = x.astype(dtype) @ w.astype(dtype)
    return y + b.astype(dtype) if b is not None else y


def lane_masks(serve: ServeConfig, state):
    """What every attention layer of a decode step shares: ``(tail_idx
    (B,)``, the tail row this token's cache payload goes to; ``mask_c (B,
    pages x page_tokens)``, the committed positions; ``mask_t (B,
    page_tokens))``, the tail's live positions, this token's among them."""
    pt = serve.page_tokens
    b = state["tokens"].shape[0]
    tail_idx = jnp.minimum(state["tail_len"], pt - 1)
    committed = state["n_pages"] * pt
    pos_c = jax.lax.broadcasted_iota(
        jnp.int32, (b, serve.pages_per_seq * pt), 1)
    pos_t = jax.lax.broadcasted_iota(jnp.int32, (b, pt), 1)
    mask_c = pos_c < committed[:, None]
    mask_t = pos_t <= tail_idx[:, None]
    return tail_idx, mask_c, mask_t


def ring_pages(serve: ServeConfig, window: int) -> int:
    """Pool rows a sequence's ring holds on a layer of window ``window``:
    the pages that can hold a visible key while the tail fills, and the one
    that has slid out, which the next commit writes over."""
    return -(-window // serve.page_tokens) + 1


def _slot_pages(state, slot, ring: int):
    """The newest committed page a lane's ring holds in ``slot (B, ...)``:
    the newest ``n`` with ``n % ring == slot``; under 0, never written."""
    newest = state["n_pages"][:, None] - 1
    return newest - (newest - slot) % ring


def ring_masks(serve: ServeConfig, state, window: int):
    """A window layer's ``mask_c (B, ring x page_tokens)`` beside
    :func:`lane_masks`' (whose tail mask holds as it is: a tail is never
    longer than a page, and a window never shorter). Slot ``s`` of a lane's
    ring holds the newest committed page ``n`` with ``n % ring == s``; its
    row ``r`` is position ``n * page_tokens + r``, live where the lane's
    token at ``pos`` can see it: ``pos - position < window``."""
    pt = serve.page_tokens
    ring = ring_pages(serve, window)
    b = state["tokens"].shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, (b, ring * pt), 1)
    page = _slot_pages(state, at // pt, ring)
    position = page * pt + at % pt
    return (page >= 0) & (state["pos"][:, None] - position < window)


def ring_live(serve: ServeConfig, state, window: int):
    """:func:`ring_masks` by slot, ``(B, ring) bool``: the slots that hold a
    row the lane's token can see, which is whether it sees the slot's newest
    row. A dead slot is one never written (a short lane's, a vacated lane's
    whole ring) or one whose page has slid out of the window; the read
    neither fetches nor decodes it (``paged_kv.gather_dequant_pages``). A
    step's sum over the held lanes is what the host counts as
    ``cgx.serve.kv.live_pages.window``."""
    pt = serve.page_tokens
    ring = ring_pages(serve, window)
    b = state["tokens"].shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (b, ring), 1)
    page = _slot_pages(state, slot, ring)
    newest_row = page * pt + pt - 1
    return (page >= 0) & (state["pos"][:, None] - newest_row < window)


def layer_cache_rows(state, layer: int, layer_streams, tail_idx, fresh,
                     dtype, window: bool = False, live=None):
    """A layer's cache as its attention contracts it, at a decode position:
    for each of the layer's streams, in order, this token's payload (the
    matching entry of ``fresh``, ``(B, ...)`` of the stream's width) written
    into the raw tail (``paged_kv.append_tail_rows``) and the committed
    pages read where they lie (``paged_kv.gather_dequant_pages``). Returns
    ``({stream: pages (B, P * page_tokens, width)}, {stream: tail rows (B,
    page_tokens, width)}``, both in ``dtype``, ``{stream: the new float32
    tail})``. ``window``: the layer's pages are the lane's ring, ``P`` its
    slots, in the ring's order (a softmax does not care). ``live (B, P)
    bool``: the table's entries the read decodes (:func:`ring_live`), the
    others' rows zeros; None reads every entry."""
    table = state["ring_table" if window else "page_table"]
    pages, tails, new = {}, {}, {}
    for (name, spec), value in zip(layer_streams, fresh):
        new[name], tails[name] = paged_kv.append_tail_rows(
            state[f"tail_{name}"][layer], tail_idx, value, dtype
        )
        pages[name] = paged_kv.gather_dequant_pages(
            state["pools"][layer][name], table, spec, dtype, window=window,
            live=live,
        )
    return pages, tails, new


class GPT2Server:
    """The GPT-2 adapter: prefill/decode forwards + serving geometry for
    one (GPT2Config, params) pair, cache streams ``k`` and ``v``. It is
    the dense-MLP GPT-2 block and nothing else; a model with experts is
    served by an adapter that has them (``serving/latent.py``)."""

    kind = "gpt2"
    step_counters = ()

    def __init__(self, model_cfg: GPT2Config, params,
                 serve: Optional[ServeConfig] = None):
        if model_cfg.n_experts:
            raise ValueError(
                "GPT2Server is the dense-MLP GPT-2 adapter: it has no "
                f"expert layer for n_experts={model_cfg.n_experts} (the "
                "serving plane serves experts through "
                "serving.latent.LatentMoEServer)"
            )
        self.cfg = model_cfg
        self.p = params.get("params", params)
        self.serve = serve or ServeConfig.from_env(model_cfg)
        self.n_layer = model_cfg.n_layer
        self.n_head = model_cfg.n_head
        self.d_head = model_cfg.d_model // model_cfg.n_head
        self.geometry = (
            model_cfg.n_layer, model_cfg.n_head, model_cfg.d_model,
            model_cfg.vocab_size, model_cfg.max_seq, str(model_cfg.dtype),
        )

    def layer_name(self, layer: int) -> str:
        return f"layer_{layer}"

    def cache_streams(self, layer: int):
        (spec,) = page_specs(self.layer_name(layer), self.serve.page_tokens,
                             [(self.n_head, self.d_head)])
        return (("k", spec), ("v", spec))

    def state_streams(self, layer: int):
        return ()

    def with_params(self, params) -> "GPT2Server":
        return GPT2Server(self.cfg, params, self.serve)

    def kv_bytes_per_token(self) -> int:
        return self.cfg.kv_bytes_per_token()

    def state_bytes_per_lane(self) -> int:
        return 0

    # -- forwards ----------------------------------------------------------

    def _embed(self, tokens, positions):
        wte = self.p["wte"]["embedding"]
        wpe = self.p["wpe"]["embedding"]
        x = wte[tokens] + wpe[positions]
        return x.astype(self.cfg.dtype)

    def _logits(self, x):
        x = _ln(x, self.p["ln_f"]["scale"], self.p["ln_f"]["bias"])
        wte = self.p["wte"]["embedding"].astype(jnp.float32)
        return x.astype(jnp.float32) @ wte.T

    def _block_tail(self, x, pl, attn_out):
        """Shared post-attention half of a block: proj residual + MLP."""
        dtype = self.cfg.dtype
        ap = pl["attn"]["attn_proj"]
        x = x + _dense(attn_out, ap["kernel"], ap.get("bias"), dtype)
        y = _ln(x, pl["ln_2"]["scale"], pl["ln_2"]["bias"]).astype(dtype)
        mi, mo = pl["mlp"]["mlp_in"], pl["mlp"]["mlp_out"]
        h = jax.nn.gelu(_dense(y, mi["kernel"], mi.get("bias"), dtype))
        return x + _dense(h, mo["kernel"], mo.get("bias"), dtype)

    def _qkv(self, x, pl):
        dtype = self.cfg.dtype
        aq = pl["attn"]["attn_qkv"]
        y = _ln(x, pl["ln_1"]["scale"], pl["ln_1"]["bias"]).astype(dtype)
        qkv = _dense(y, aq["kernel"], aq.get("bias"), dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):  # (B, S, Dm) -> (B, H, S, Dh)
            b, s, _ = t.shape
            return t.reshape(b, s, self.n_head, self.d_head).transpose(
                0, 2, 1, 3
            )

        return heads(q), heads(k), heads(v)

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt, returning
        the logits at ``last_idx`` and every layer's K/V.

        tokens/positions: (B, S) int32 — S is the PADDED length
        (prompts pad to a page multiple so distinct prompt lengths share
        one compiled program; under causal attention right-padding
        cannot perturb any earlier position's K/V or the ``last_idx``
        logits). Returns (logits (B, vocab), ks, vs): each a list per
        layer of (B, S, H, Dh) f32 — the cache payload the pages
        quantize (callers slice off the pad)."""
        x = self._embed(tokens, positions)
        ks: List[jax.Array] = []
        vs: List[jax.Array] = []
        for layer in range(self.cfg.n_layer):
            pl = self.p[f"h_{layer}"]
            q, k, v = self._qkv(x, pl)
            ks.append(k.transpose(0, 2, 1, 3).astype(jnp.float32))
            vs.append(v.transpose(0, 2, 1, 3).astype(jnp.float32))
            o = dense_attention(q, k, v, causal=True)
            b, _, s, _ = o.shape
            o = o.transpose(0, 2, 1, 3).reshape(b, s, self.cfg.d_model)
            x = self._block_tail(x, pl, o)
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return self._logits(x_last)[:, -1], ks, vs

    def decode_forward(self, state, streams):
        """One decode position against the paged cache: current tokens at
        their positions, KV read = gathered committed pages (decoded to
        ``cfg.dtype`` rows, contracted where they lie) and, apart, the raw
        tail with this token's K/V appended; one softmax over both.
        Returns (logits (B, vocab), the new tails by stream, None)."""
        cfg = self.cfg
        x = self._embed(state["tokens"][:, None], state["pos"][:, None])
        tail_idx, mask_c, mask_t = lane_masks(self.serve, state)
        new: Dict[str, List[jax.Array]] = {"k": [], "v": []}
        for layer in range(cfg.n_layer):
            pl = self.p[f"h_{layer}"]
            q, k, v = self._qkv(x, pl)  # (B, H, 1, Dh)
            pages, tails, written = layer_cache_rows(
                state, layer, streams[layer], tail_idx,
                (k[:, :, 0], v[:, :, 0]), cfg.dtype,
            )
            for name, tail in written.items():
                new[name].append(tail)
            o = decode_attention(
                q[:, :, 0], pages["k"], pages["v"], tails["k"], tails["v"],
                mask=mask_c, tail_mask=mask_t,
            )
            x = self._block_tail(x, pl, o[:, None])
        return self._logits(x)[:, -1], new, None


# ---------------------------------------------------------------------------
# Resolved wire specs + the compiled-program LRU.
# ---------------------------------------------------------------------------


def _resolved_streams(server) -> Tuple:
    """Every layer's cache streams ``((name, PageSpec), ...)`` under the
    CURRENT kv_page resolution (:func:`page_specs`), as the adapter states
    them; ``()`` for a layer that leaves no pages."""
    return tuple(
        tuple(server.cache_streams(layer)) for layer in range(server.n_layer)
    )


def _resolved_state_streams(server) -> Tuple:
    """Every layer's state streams as ``((name, (shape, dtype name)),
    ...)``: the cache streams' form, a name and what one lane's row is."""
    return tuple(
        tuple((name, (tuple(shape), jnp.dtype(dtype).name))
              for name, shape, dtype in server.state_streams(layer))
        for layer in range(server.n_layer)
    )


def _resolved_windows(server) -> Tuple[int, ...]:
    """Every layer's page class as the adapter states it
    (``page_window``): 0 for global pages, the window for a ring."""
    page_window = getattr(server, "page_window", None)
    if page_window is None:
        return (0,) * server.n_layer
    return tuple(int(page_window(layer)) for layer in range(server.n_layer))


def _ring(server, streams, windows) -> int:
    """Slots of a lane's ring (:func:`ring_pages` of the window layers' one
    window), 0 for a model without window layers."""
    found = sorted({w for w in windows if w})
    if not found:
        return 0
    if len(found) > 1:
        raise ValueError(
            f"adapter {server.kind!r} states the windows {found}: the lanes "
            "keep one ring table, so every window layer has the same window"
        )
    if found[0] < server.serve.page_tokens:
        raise ValueError(
            f"window {found[0]} is shorter than a page "
            f"({server.serve.page_tokens} tokens): the tail would outlive it"
        )
    bare = [l for l, w in enumerate(windows) if w and not streams[l]]
    if bare:
        raise ValueError(f"layers {bare} state a window and no cache stream")
    return ring_pages(server.serve, found[0])


def _stream_names(streams) -> Tuple[str, ...]:
    """The names the layers' streams (cache or state) go by: their union,
    in order of first appearance."""
    return tuple(dict.fromkeys(
        name for layer in streams for name, _ in layer
    ))


def _holders(streams) -> Dict[str, Dict[int, int]]:
    """``{stream: {layer: its rank among the layers that have the
    stream}}``: where a layer's entry lies in an array stacked over those
    layers (a prefill's tails and states)."""
    out: Dict[str, Dict[int, int]] = {n: {} for n in _stream_names(streams)}
    for layer, layer_streams in enumerate(streams):
        for name, _ in layer_streams:
            out[name][layer] = len(out[name])
    return out


def _leading_specs(streams) -> Tuple[Optional[paged_kv.PageSpec], ...]:
    """Each layer's leading stream's spec: the layer's wire resolution
    (bits are per layer; GPT-2's ``k`` and ``v`` share the whole spec);
    None for a layer without pages."""
    return tuple(layer[0][1] if layer else None for layer in streams)


def _resolved_specs(server) -> Tuple[paged_kv.PageSpec, ...]:
    return _leading_specs(_resolved_streams(server))


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
        _resolved_streams(server),
        _resolved_state_streams(server),
        _resolved_windows(server),
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


def _build_programs(server) -> SimpleNamespace:
    streams = _resolved_streams(server)
    names = _stream_names(streams)
    state_streams = _resolved_state_streams(server)
    state_names = _stream_names(state_streams)
    both = sorted(set(names) & set(state_names))
    if both:
        raise ValueError(
            f"adapter {server.kind!r} names {both} both a cache stream and "
            "a state stream"
        )
    holders = {**_holders(streams), **_holders(state_streams)}
    n_layer = server.n_layer
    sv = server.serve
    windows = _resolved_windows(server)
    ring = _ring(server, streams, windows)

    def decode_step(params, state):
        """One token for every lane. Returns the new state and what the
        host reads each tick, in one array: the lanes' next tokens, then
        the adapter's ``step_counters`` (none for GPT-2)."""
        srv = server.with_params(params)
        logits, new_tails, counts = srv.decode_forward(state, streams)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = dict(state)
        for name in names:
            out[f"tail_{name}"] = tuple(new_tails[name])
        for name in state_names:  # rewritten whole, every lane, every step
            out[f"state_{name}"] = tuple(new_tails[name])
        out["tail_len"] = jnp.where(
            state["active"], state["tail_len"] + 1, state["tail_len"]
        )
        out["pos"] = jnp.where(state["active"], state["pos"] + 1,
                               state["pos"])
        out["tokens"] = jnp.where(state["active"], nxt, state["tokens"])
        if counts is not None:
            nxt = jnp.concatenate([nxt, counts.astype(jnp.int32)])
        return out, nxt

    def commit(state, lanes, page_ids, ring_ids=None):
        """Promote the full tails of ``lanes (K,)`` into pool pages
        ``page_ids (K,)`` (a window layer's into ``ring_ids (K,)``, rows of
        its own pools: the lane's ring slot ``n_pages % ring``, whose last
        page has slid out of the window), ``K = ServeConfig.commit_lanes``: the K lanes'
        tails alone are gathered (rows as they are kept, flattened to ``(K,
        page_tokens * width)`` payloads), quantized and scattered, a layer
        and a stream at a time, and their ``page_table`` slot,
        ``n_pages`` and ``tail_len`` written by scatter. Tails fill at
        ``max_batch / page_tokens`` a step, so a program over every lane's
        tail would throw nearly all of its work away. A slot the
        caller has no tail for names any valid lane and the scratch row
        (``max_pages``; pools carry ``max_pages + 1`` rows): its rows land
        there and its lane's counts are left as they are, so one program
        of one width serves any number of full tails."""
        k = lanes.shape[0]
        out = dict(state)
        out["pools"] = tuple(
            {
                name: paged_kv.commit_page_rows(
                    state["pools"][layer][name],
                    ring_ids if windows[layer] else page_ids,
                    state[f"tail_{name}"][layer][lanes].reshape(k, -1), spec,
                )
                for name, spec in streams[layer]
            }
            for layer in range(n_layer)
        )
        # Out of bounds for a padded slot: the scatters below drop it.
        at = jnp.where(page_ids < sv.max_pages, lanes, sv.max_batch)
        out["page_table"] = state["page_table"].at[
            at, state["n_pages"][lanes]
        ].set(page_ids, mode="drop")
        if ring:
            out["ring_table"] = state["ring_table"].at[
                at, state["n_pages"][lanes] % ring
            ].set(ring_ids, mode="drop")
        out["n_pages"] = state["n_pages"].at[at].add(1, mode="drop")
        out["tail_len"] = state["tail_len"].at[at].set(0, mode="drop")
        return out

    def ingest(pools, layer_rows, ids):
        """Batch-write received/locally-prefetched page payload rows
        (n, flat) into pool rows ``ids (n,)`` for every layer and stream
        (``layer_rows[layer][stream]``) — the stream-completion path
        (payloads already in pool layout when quantized)."""
        return tuple(
            {
                name: _ingest_pool(
                    pools[layer][name], ids, layer_rows[layer][name], spec
                )
                for name, spec in streams[layer]
            }
            for layer in range(n_layer)
        )

    def prefill(params, tokens, positions, last_idx):
        """Forward alone, every layer's cache payload (and state) out by
        stream: the prefill worker's program (``serving/prefill.py`` ships
        the pages itself)."""
        srv = server.with_params(params)
        logits, *payloads = srv.prefill_forward(tokens, positions, last_idx)
        return (
            jnp.argmax(logits, axis=-1).astype(jnp.int32),
            dict(zip(names + state_names, payloads)),
        )

    observe_qerr = cfg_mod.qerr_stats()  # in the program key's fingerprint

    def prefill_pages(params, pools, tokens, positions, last_idx, ids,
                      tail_len, ring_ids=None):
        """The local prefill of one padded prompt, whole: forward, then
        every page of every layer's streams through ``commit_page_rows``
        into the donated pools at ``ids (padded pages,)``, and the last
        page's first ``tail_len`` rows as the lane's tails ``{stream: (L,
        page_tokens, H * Dh) f32}``, zero from ``tail_len`` on. A last page
        that is a tail has the scratch row for its id, so one program
        serves every prompt length under a padded length, whole pages or
        not. The first token is a scalar, ``admit_lane``'s operand as it
        is. Also ``{layer: its leading stream's rows as quantized}`` of the
        quantized layers, empty unless ``CGX_QERR_STATS`` was on when the
        programs were built. Last, the lane's recurrent state after
        ``last_idx`` as the adapter's prefill left it, ``{state stream: (its
        layers, *shape)}``, empty for a model without state streams. Tails
        and states are stacked over the layers that have the stream. A
        window layer writes the prompt's last ``ring_ids.shape[0]`` padded
        pages alone (at most ``ring + 1``: the pages its ring keeps are
        among them), into ``ring_ids``; the host names the scratch row for
        those that have slid out already."""
        first, payloads = prefill(params, tokens, positions, last_idx)
        n_pages = ids.shape[0]
        live = jax.lax.broadcasted_iota(
            jnp.int32, (sv.page_tokens, 1), 0
        ) < tail_len
        out, tails, qerr_rows = [], {name: [] for name in names}, {}
        for layer in range(n_layer):
            pool, written = pools[layer], {}
            for name, spec in streams[layer]:
                x = payloads[name][layer][0]  # (padded tokens, H, Dh)
                rows = x.reshape(n_pages, -1)
                written[name] = paged_kv.commit_page_rows(
                    pool[name],
                    *((ring_ids, rows[-ring_ids.shape[0]:])
                      if windows[layer] else (ids, rows)), spec,
                )
                tails[name].append(jnp.where(
                    live, x[-sv.page_tokens:].reshape(sv.page_tokens, -1),
                    0.0,
                ))
                if (observe_qerr and spec.quantized
                        and name == streams[layer][0][0]):
                    qerr_rows[layer] = rows
            out.append(written)
        states = {
            name: jnp.stack([payloads[name][layer][0]
                             for layer in holders[name]])
            for name in state_names
        }
        return (
            first[0], tuple(out),
            {name: jnp.stack(t) for name, t in tails.items()}, qerr_rows,
            states,
        )

    def admit_lane(state, lane, table_row, n_pages, tail_len, token, pos,
                   tails, states, ring_row=None):
        """Write one ready request into lane ``lane`` of the donated
        state: its page-table row, counts, first token (a scalar still on
        the device from the local prefill, or a host one from a page
        stream) and position, its stacked tails ``{stream: (L, page_tokens,
        H * Dh)}``, device or host arrays alike, and its recurrent state
        ``{state stream: (L, *shape)}`` (whatever the lane's last request
        left there is overwritten whole). ``ring_row (ring,)``: the lane's
        row of ``ring_table``, where a layer has a window."""
        out = dict(state)
        for name, value in (
            ("page_table", table_row), ("n_pages", n_pages),
            ("tail_len", tail_len), ("tokens", token), ("pos", pos),
            ("active", True),
        ) + ((("ring_table", ring_row),) if ring else ()):
            out[name] = state[name].at[lane].set(value)
        for prefix, which, written in (("tail", names, tails),
                                       ("state", state_names, states)):
            for name in which:
                out[f"{prefix}_{name}"] = tuple(
                    None if t is None
                    else t.at[lane].set(written[name][holders[name][layer]])
                    for layer, t in enumerate(state[f"{prefix}_{name}"])
                )
        return out

    def release_lanes(lanes, mask):
        """Reset the lane bookkeeping (``_LANE_RESET``) of the lanes in
        ``mask (B,) bool``: finished or evicted, once a tick."""
        reset = {**_LANE_RESET, **_RING_RESET}
        return {
            name: jnp.where(
                mask.reshape((-1,) + (1,) * (value.ndim - 1)),
                reset[name], value,
            )
            for name, value in lanes.items()
        }

    return SimpleNamespace(
        streams=streams,
        names=names,
        windows=windows,
        window=max(windows),
        ring=ring,
        # Cache streams over the layers of each class: (global, window).
        class_streams=tuple(
            sum(len(layer) for layer, w in zip(streams, windows)
                if bool(w) == ringed)
            for ringed in (False, True)
        ),
        state_streams=state_streams,
        state_names=state_names,
        specs=_leading_specs(streams),
        decode_step=jax.jit(decode_step, donate_argnums=(1,)),
        commit=jax.jit(commit, donate_argnums=(0,)),
        ingest=jax.jit(ingest, donate_argnums=(0,)),
        prefill=jax.jit(prefill),
        prefill_pages=jax.jit(prefill_pages, donate_argnums=(1,)),
        admit_lane=jax.jit(admit_lane, donate_argnums=(0,)),
        release_lanes=jax.jit(release_lanes, donate_argnums=(0,)),
    )


def _ingest_pool(pool, ids, rows, spec: paged_kv.PageSpec):
    """Scatter pre-encoded pool rows: quantized rows arrive as (words,
    meta) pairs in pool-row form (the transport's wire bytes ARE the
    pool's, ``paged_kv.pool_words``), raw rows as f32 payloads."""
    if not spec.quantized:
        pages = rows.reshape(
            -1, spec.page_tokens, spec.n_head, spec.d_head
        ).astype(jnp.float16)
        return pool.at[ids].set(pages)
    words, meta = pool
    rows_words, rows_meta = rows
    return (
        words.at[ids].set(rows_words),
        meta.at[ids].set(rows_meta),
    )


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


@dataclasses.dataclass
class _Step:
    """A decode step dispatched and not read yet: its tokens (then the
    adapter's counters) on the device, and the request each lane's token
    belongs to. A lane is struck out when its request leaves it under the
    step."""

    tokens: jax.Array
    lanes: Dict[int, Request]


class ContinuousBatchScheduler:
    """Admit/evict-per-step decode over one model adapter
    (:class:`GPT2Server`, ``latent.LatentMoEServer``).

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
        # With window layers: the state's ``n_pages`` a lane, counted here
        # like the tail lengths (the ring slot a commit writes, the live
        # pages a step reads), and the lane's ring.
        self._n_pages = np.zeros((sv.max_batch,), np.int64)
        self._ring_of = np.zeros((sv.max_batch,), np.int64)
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

    # -- state plumbing ----------------------------------------------------

    def _fresh_state(self) -> Dict:
        sv = self.server.serve
        streams = self._prog.streams
        b = sv.max_batch
        ring = self._prog.ring
        pools = tuple(
            {
                # +1 row: scratch, where a padded slot of commit() and a
                # prefill's last page that is a tail write; never read. A
                # window layer holds a ring a lane, whatever ``max_seq``.
                name: paged_kv.empty_pool(
                    (b * ring if window else sv.max_pages) + 1, spec)
                for name, spec in layer
            }
            for layer, window in zip(streams, self._prog.windows)
        )
        if ring:
            self._note_window_pools(pools)
        # A layer without the stream holds None in the stream's tuple, so
        # that every per-layer entry is found at its layer's index. A tail
        # is kept as the rows the attention contracts and the commit
        # quantizes, a position's heads side by side: no program relays it.
        tails = {
            f"tail_{name}": tuple(
                None if spec is None else jnp.zeros(
                    (b, spec.page_tokens, spec.n_head * spec.d_head),
                    jnp.float32,
                )
                for spec in (dict(layer).get(name) for layer in streams)
            )
            for name in self._prog.names
        }
        # The recurrent state, one row a lane: zeros until an admission
        # writes the lane (a free lane's rows go through every decode step
        # like any other's and reach no other lane).
        states = {
            f"state_{name}": tuple(
                None if row is None else jnp.zeros((b,) + row[0], row[1])
                for row in (dict(layer).get(name)
                            for layer in self._prog.state_streams)
            )
            for name in self._prog.state_names
        }
        state_bytes = sum(
            t.nbytes for per_layer in states.values() for t in per_layer
            if t is not None
        )
        metrics.set("cgx.serve.state.bytes", float(state_bytes))
        if state_bytes:
            if self._state_bytes:  # a rebuild drops the old state's arrays
                memledger.note_release(
                    "serve.state", n=b, nbytes=self._state_bytes
                )
            memledger.note_alloc("serve.state", n=b, nbytes=state_bytes)
        self._state_bytes = state_bytes
        return {
            "pools": pools,
            **tails,
            **states,
            "page_table": jnp.full(
                (b, sv.pages_per_seq), -1, jnp.int32
            ),
            "n_pages": jnp.zeros((b,), jnp.int32),
            "tail_len": jnp.zeros((b,), jnp.int32),
            "tokens": jnp.zeros((b,), jnp.int32),
            "pos": jnp.zeros((b,), jnp.int32),
            "active": jnp.zeros((b,), bool),
            **({"ring_table": jnp.full((b, ring), -1, jnp.int32)}
               if ring else {}),
        }

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
        (the time between steps, the unfed device) leave it out."""
        before = metrics.sums(_TICK_ACCOUNT)
        between, away = 0.0, False
        if self._step_end is not None:
            between = time.perf_counter() - self._step_end
            away = self._is_stall(between)
            if away:
                self._unfed_since = None  # nobody was there to feed it
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

    def _is_stall(self, seconds: float) -> bool:
        """Whether the tick about to end (or the gap before it) is one:
        longer than both constants allow, past the scheduler's first
        ticks."""
        return (self._ticks >= _STALL_WARMUP_TICKS
                and seconds >= _STALL_MIN_S
                and seconds >= _STALL_OVER_MEAN * self._tick_mean)

    def _close_tick(self, between: float, away: bool,
                    before: Tuple[float, ...]) -> None:
        """The stall record. A tick, with the ``between`` seconds the
        caller took before it, longer than ``_STALL_MIN_S`` and than
        ``_STALL_OVER_MEAN`` times the running mean of the ticks before
        it, counts in ``cgx.serve.stalls`` and leaves one warning line
        that says where its time went: the growth of each histogram of
        ``_TICK_ACCOUNT`` since ``before``, which ``step()`` read as the
        tick began. ``cgx.serve.stall_s`` observes it unless the caller
        was ``away`` (the gap alone was the stall: no tick of this loop
        was slow). Those histograms are the process's: two schedulers
        ticking in one process read each other's time. Full collections
        are published here, so that a pause lands in the tick it
        lengthened."""
        self._gc_pauses.publish()
        grown = [b - a for a, b in
                 zip(before, metrics.sums(_TICK_ACCOUNT))]
        whole = between + grown[0]
        stalled, mean = self._is_stall(whole), self._tick_mean
        self._ticks += 1
        self._tick_mean += ((grown[0] if away else whole) - mean) / min(
            self._ticks, _STALL_WARMUP_TICKS)
        if not stalled:
            return
        metrics.add("cgx.serve.stalls")
        if not away:
            metrics.observe("cgx.serve.stall_s", whole)
        spans = grown[1:1 + len(_TICK_SPANS)]
        parts = [("between_steps", between), *zip(_TICK_SPANS, spans),
                 ("other", grown[0] - sum(spans))]
        within = zip(_TICK_WITHIN, grown[1 + len(_TICK_SPANS):])
        log.warning(
            "serving: stalled tick %d: %.3f s, the running mean %.4f s: %s"
            " | inside those: %s",
            self._ticks, whole, mean,
            " ".join(f"{name}={v:.3f}" for name, v in parts),
            " ".join(f"{name}={v:.3f}" for name, v in within),
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
        pools (of a window layer no more than its ring keeps): the wire
        plane's ``kv_page`` accounting."""
        if not n_pages:
            return
        for layer, layer_streams in enumerate(self._prog.streams):
            kept = (min(n_pages, self._prog.ring)
                    if self._prog.windows[layer] else n_pages)
            for _, spec in layer_streams:
                _account_pages(self.server.layer_name(layer), spec, kept)

    # -- local prefill (colocated mode + the failover rung) ---------------

    def _local_prefill(self, req: Request) -> Optional[_Ready]:
        sv = self.server.serve
        prompt = np.asarray(req.tokens, np.int32)
        s = prompt.shape[0]
        if s < 1 or s + req.max_new_tokens > sv.max_seq:
            raise ValueError(
                f"request {req.id!r}: prompt {s} + max_new "
                f"{req.max_new_tokens} exceeds CGX_SERVE_MAX_SEQ "
                f"{sv.max_seq}"
            )
        pids: List[int] = []
        for _ in range(s // sv.page_tokens):
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
                padded = _pad_prompt(prompt, pt)
                # A last page that is a tail goes to the scratch row.
                ids = np.full((padded.shape[0] // pt,), sv.max_pages,
                              np.int32)
                ids[:n_full] = pids
                first, pools, tails, qerr_rows, states = (
                    self._prog.prefill_pages(
                        self.server.p, self._state["pools"], padded[None],
                        np.arange(padded.shape[0], dtype=np.int32)[None],
                        np.int32(s - 1), ids, np.int32(tail_len),
                        *self._ring_rows(ring, n_full, len(ids)),
                    )
                )
                self._state["pools"] = pools
                self._fed()
        except BaseException:
            self._close_prefill_span((start, fields), ok=False)
            raise
        self._note_pages(n_full)
        metrics.add("cgx.serve.local_prefills")
        return _Ready(
            req=req, page_ids=pids, tails=tails,
            tail_len=tail_len, first_token=first, pos=s,
            states=states, span=(start, fields), qerr_rows=qerr_rows,
            ring=ring,
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
            n_full, ring = len(ready.page_ids), self._prog.ring
            ring_row = ()
            if ring:  # the slots the prefill wrote (``_ring_rows``)
                pages = np.arange(max(n_full - ring, 0), n_full)
                ring_row = np.full((ring,), -1, np.int32)
                ring_row[pages % ring] = self._slot_rows(ready.ring, pages)
                ring_row = (ring_row,)
                self._n_pages[lane] = n_full
                self._ring_of[lane] = ready.ring
            self._state = self._prog.admit_lane(
                self._state, np.int32(lane), table_row,
                np.int32(n_full), np.int32(ready.tail_len),
                np.int32(first) if isinstance(first, int) else first,
                np.int32(ready.pos), ready.tails, ready.states, *ring_row,
            )
            if ready.states:
                metrics.add("cgx.serve.state.lane_writes")
            self._lanes[lane] = req
            self._tail_len[lane] = ready.tail_len
            self._left[lane] = max(req.max_new_tokens - 1, 0)
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
                        first = int(first)
                    self._note_read()
                for layer, rows in ready.qerr_rows.items():
                    _observe_page_qerr(
                        self.server.layer_name(layer),
                        self._prog.specs[layer],
                        np.asarray(rows)[: len(ready.page_ids)],
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
            now = time.monotonic()
            req.first_token_at = now
            ttft_ms = (now - req.submitted_at) * 1e3
            metrics.observe("cgx.serve.ttft_ms", ttft_ms)
            timeline.instant(
                "serve.admit", cat=timeline.CAT_TRACE, req=req.id,
                lane=int(lane), ttft_ms=round(ttft_ms, 3),
            )
            self._emit(lane, req, first)
            self._note_tokens(1)
        self._release_lanes()  # a first token can finish its request
        return bool(n)

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
        if late:
            metrics.add("cgx.serve.decode.discarded_tokens", float(late))
        metrics.add("cgx.serve.requests_completed")

    def _vacate(self, lane: int) -> int:
        """Take the lane from its request (finished, evicted or failed).
        Returns the tokens that steps in flight were decoding for it:
        struck out, nobody reads them."""
        self._lanes[lane] = None
        self._left[lane] = 0
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
        self._tail_len[mask] = _LANE_RESET["tail_len"]
        self._n_pages[mask] = _LANE_RESET["n_pages"]
        names = (*_LANE_RESET, *(_RING_RESET if self._prog.ring else ()))
        self._state.update(self._prog.release_lanes(
            {name: self._state[name] for name in names}, mask
        ))

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
                nxt = np.asarray(step.tokens)
            self._note_read()
        with trace_span("serve.decode.emit", hist="cgx.serve.decode_emit_s"):
            metrics.add("cgx.serve.decode_steps")
            # What the adapter counted this step, read with the tokens.
            for name, count in zip(self.server.step_counters,
                                   nxt[sv.max_batch:]):
                metrics.add(f"cgx.serve.{name}", float(count))
            metrics.set(
                "cgx.serve.batch_occupancy", len(step.lanes) / sv.max_batch
            )
            for lane, req in step.lanes.items():
                self._emit(lane, req, int(nxt[lane]))
            self._release_lanes()
            self._note_tokens(len(step.lanes))
        return True

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
            self._fed()
        held = [i for i, r in enumerate(self._lanes) if r is not None]
        if self._prog.ring:
            self._note_live_pages(held)
        self._tail_len[held] += 1
        lanes = {i: self._lanes[i] for i in held if self._left[i] > 0}
        self._left[list(lanes)] -= 1
        self._steps.append(_Step(tokens=tokens, lanes=lanes))

    def _note_live_pages(self, held: List[int]) -> None:
        """The pages the step just dispatched has a visible key in, summed
        over the held lanes, by class and for one layer of the class, from
        the host's own counts: every committed page of a global layer; of a
        window layer those from the page that holds the oldest position the
        lane's token (at ``n_pages * page_tokens + tail_len``) can see. Those
        are the slots the step's read leaves open (:func:`ring_live` on the
        device), counted again as ``kv.decoded_pages.window``: what the
        window read decodes, which was every slot of every lane's ring
        before the read had a guard."""
        pt, window = self.server.serve.page_tokens, self._prog.window
        n_pages = self._n_pages[held]
        oldest = np.maximum(
            n_pages * pt + self._tail_len[held] - window + 1, 0) // pt
        live = float(np.maximum(n_pages - oldest, 0).sum())
        metrics.add("cgx.serve.kv.live_pages.global", float(n_pages.sum()))
        metrics.add("cgx.serve.kv.live_pages.window", live)
        metrics.add("cgx.serve.kv.decoded_pages.window", live)

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
                    rows = np.asarray(
                        st[f"tail_{lead}"][layer]
                    )[committed].reshape(len(committed), -1)
                    metrics.add("cgx.serve.host_reads")
                    _observe_page_qerr(
                        self.server.layer_name(layer), spec,
                        rows, already_host=True,
                    )
        k = sv.commit_lanes
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
                self._fed()
        calls = -(-len(committed) // k)
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
            self._n_pages[committed] += 1
            metrics.add("cgx.serve.window.pages_committed",
                        float(of_window * len(committed)))
            metrics.add("cgx.serve.window.pages_recycled",
                        float(of_window * recycled))

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

    def _note_read(self) -> None:
        """A blocking copy from the device has just returned: count it,
        and where it leaves nothing dispatched and unread the device has
        nothing of ours to run from now until :meth:`_fed`."""
        metrics.add("cgx.serve.host_reads")
        if not self._steps and not self._unread:
            self._unfed_since = time.perf_counter()

    def _fed(self) -> None:
        """A program that carries work (``prefill_pages``, ``commit``,
        ``decode_step``; not a lane write or a release) has just been
        dispatched: ``cgx.serve.device_unfed_s`` observes how long the
        device had stood with nothing queued, the host's own estimate of
        its idle gap."""
        if self._unfed_since is not None:
            metrics.observe("cgx.serve.device_unfed_s",
                            time.perf_counter() - self._unfed_since)
            self._unfed_since = None

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
    pool's rows of 128 — zero re-encoding), or the raw f32 payload row."""
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
        np.asarray(q.meta, np.float32),
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
