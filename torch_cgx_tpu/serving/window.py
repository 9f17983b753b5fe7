"""The window/global adapters: grouped-query decoders most of whose layers
attend a sliding window, behind the one scheduler. :class:`WindowMoEServer`
serves ``models/window_moe.py`` (dropless experts routed from the block's
input); :class:`AfmoeServer` serves ``models/afmoe.py`` (sandwich norms, a
gated and QK-normed attention, a held share of sigmoid-routed experts beside
a shared expert, leading dense layers) over the same streams and masks.

Every layer leaves ``k`` and ``v`` pages, rows of ``n_kv_head * d_head``
(``k`` as the scores contract it: rotated on a window layer, bare on a
global one). What differs between layers is the pages' CLASS
(``page_window``, "Window and global pages" in docs/SERVING.md): a global
layer's are the lane's page table, as long as the sequence; a window
layer's are the lane's ring, ``W / page_tokens + 1`` pool rows whatever the
sequence's length, read under a kernel name of its own
(``cgx_dequantize_window``) and masked by position
(``adapter.ring_masks``). Prefill attends in query blocks, a window
layer's block over the band of keys it can see
(``window_moe.attend_blocks``).

Page geometry is the streams' arithmetic (``serving/hybrid.py`` says the
same of its own): at 256 tokens a page and bucket 512 a ``k`` or ``v`` page
of four heads of 128 is 256 buckets, eight whole 32-bucket chunks, one
bucket a token, rows of 512: the flat Mosaic kernels at commit and the paged
read at decode. Eight heads of 128 are rows of 1,024: two buckets a token,
512 buckets and sixteen chunks a page, the same kernels over a row twice as
wide.

The disaggregated path cannot address a ring and refuses this adapter
(``transport.require_kv_streams``); it is served with local prefill.
"""

from __future__ import annotations

import jax
import numpy as np

from ..models import afmoe
from ..models import window_moe as wm
from ..models.mla_moe import rms_norm
from ..parallel import moe
from .adapter import (
    Adapter,
    attend_paged,
    lane_masks,
    page_live,
    page_specs,
    ring_live,
    ring_masks,
)


class WindowMoEServer(Adapter):
    """Model adapter for one ``(WindowMoeConfig, params)`` pair; cache
    streams ``k`` and ``v`` on every layer, the window layers' as rings."""

    kind = "window_moe"
    # What a decode step counts over its expert layers, as
    # ``cgx.serve.<name>``: ``moe.STATS`` in order.
    step_counters = tuple(f"moe.{name}" for name in moe.STATS)
    guards_global_read = True

    def cache_streams(self, layer: int):
        (spec,) = page_specs(self.layer_name(layer), self.serve.page_tokens,
                             [(self.cfg.n_kv_head, self.cfg.d_head)])
        return (("k", spec), ("v", spec))

    def page_window(self, layer: int) -> int:
        return self.cfg.windows[layer]

    # -- forwards ----------------------------------------------------------

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt: the logits at
        ``last_idx``, then every layer's ``k`` and ``v (B, S, Hk, dh)`` f32,
        ``k`` rotated where the layer rotates. Right-padding is inert for
        every real position under the causal mask; a padded token does go
        through the experts (dropless: it takes no real token's place)."""
        cfg = self.cfg
        x = wm.embed(cfg, self.p, tokens)
        ks, vs = [], []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            y = rms_norm(x, pl["in_norm"], cfg.eps)
            q, k, v = wm.attn_project(cfg, layer, y, pl["attn"], positions)
            ks.append(k)
            vs.append(v)
            o = wm.attend_blocks(cfg, q, k, v, cfg.windows[layer])
            x, _ = wm.block_tail(cfg, pl, x, y, o)
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return wm.logits(cfg, self.p, x_last)[:, -1], ks, vs

    def _masks(self, state):
        """What a decode step's layers share: :func:`lane_masks`' three, the
        page table's live slots and, where the model has window layers, the
        ring's row mask and its live slots (one of each a step, for every
        layer of the class and both streams)."""
        shared = lane_masks(self.serve, state) + (
            page_live(self.serve, state),)
        window = max(self.cfg.windows)
        if not window:
            return shared + (None, None)
        return shared + (ring_masks(self.serve, state, window),
                         ring_live(self.serve, state, window))

    def _attend(self, state, streams, masks, layer, q, k, v):
        """One decode position of ``layer`` over the lane's cache
        (:func:`attend_paged`): a global layer over the page table, a window
        layer over its ring; either read takes its table's live slots."""
        tail_idx, mask_c, mask_t, live_c, mask_r, live_r = masks
        ringed = bool(self.cfg.windows[layer])
        return attend_paged(
            state, layer, streams[layer],
            (tail_idx, mask_r if ringed else mask_c, mask_t), q, k, v,
            self.cfg.dtype, np.sqrt(self.cfg.d_head), window=ringed,
            live=live_r if ringed else live_c,
        )

    def decode_forward(self, state, streams):
        """One decode position: this token's ``k`` and ``v`` into the raw
        tails, a global layer's committed pages read through the page table
        and a window layer's through the ring (either table's live slots
        alone: ``page_live``, ``ring_live``), one ``decode_attention`` over
        pages and tail under the class's mask (:meth:`_attend`). Returns
        ``(logits (B, V), the new tails by stream, moe.STATS summed over the
        layers (``load_max`` their largest) counted over the active
        lanes)``."""
        cfg = self.cfg
        x = wm.embed(cfg, self.p, state["tokens"])  # (B, D)
        positions = state["pos"][:, None]
        masks = self._masks(state)
        new = {"k": [], "v": []}
        counts = []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            y = rms_norm(x, pl["in_norm"], cfg.eps)
            q, k, v = wm.attn_project(cfg, layer, y[:, None], pl["attn"],
                                      positions)
            o, tails = self._attend(state, streams, masks, layer, q, k, v)
            for name, tail in tails.items():
                new[name].append(tail)
            x, stats = wm.block_tail(cfg, pl, x, y, o,
                                     count_mask=state["active"])
            counts.append(stats)
        return wm.logits(cfg, self.p, x), new, moe.total_stats(counts)


class AfmoeServer(WindowMoEServer):
    """Model adapter for one ``(AfmoeConfig, params)`` pair: the streams,
    the page classes and the masks are :class:`WindowMoEServer`'s, the block
    is ``models/afmoe.py``'s. A leading dense layer is no expert layer and
    counts nothing; the others count a step as ``moe.HELD_STATS`` where the
    chip holds a share of their experts."""

    kind = "afmoe"

    def __init__(self, model_cfg, params, serve=None):
        super().__init__(model_cfg, params, serve)
        names = (moe.STATS if model_cfg.experts_held is None
                 else moe.HELD_STATS)
        self.step_counters = tuple(f"moe.{name}" for name in names)

    def prefill_forward(self, tokens, positions, last_idx):
        """As :meth:`WindowMoEServer.prefill_forward`; ``k`` is normed a
        head before it is rotated."""
        cfg = self.cfg
        x = afmoe.embed(cfg, self.p, tokens)
        ks, vs = [], []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            a = rms_norm(x, pl["in_norm"], cfg.eps)
            q, k, v = afmoe.attn_project(cfg, layer, a, pl["attn"], positions)
            ks.append(k)
            vs.append(v)
            o = wm.attend_blocks(cfg, q, k, v, cfg.windows[layer])
            x, _ = afmoe.ffn_half(cfg, pl, afmoe.attn_out(cfg, pl, x, a, o))
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return wm.logits(cfg, self.p, x_last)[:, -1], ks, vs

    def decode_forward(self, state, streams):
        """As :meth:`WindowMoEServer.decode_forward`; the counts are the
        expert layers' alone."""
        cfg = self.cfg
        x = afmoe.embed(cfg, self.p, state["tokens"])  # (B, D)
        positions = state["pos"][:, None]
        masks = self._masks(state)
        new = {"k": [], "v": []}
        counts = []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            a = rms_norm(x, pl["in_norm"], cfg.eps)
            q, k, v = afmoe.attn_project(cfg, layer, a[:, None], pl["attn"],
                                         positions)
            o, tails = self._attend(state, streams, masks, layer, q, k, v)
            for name, tail in tails.items():
                new[name].append(tail)
            x, stats = afmoe.ffn_half(
                cfg, pl, afmoe.attn_out(cfg, pl, x, a, o),
                count_mask=state["active"])
            if stats is not None:
                counts.append(stats)
        return wm.logits(cfg, self.p, x), new, moe.total_stats(counts)
