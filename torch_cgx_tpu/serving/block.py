"""The block-diffusion adapter: a grouped-query decoder with dropless experts
that generates a BLOCK of ``L`` positions at a time, behind the one
scheduler. :class:`BlockDiffusionServer` serves ``models/sdar_moe.py``
(SDAR-30B-A3B-Chat's block).

Every layer leaves ``k`` and ``v`` pages, rows of ``n_kv_head * d_head``
(``k`` as the scores contract it: normed a head and rotated), all of them
global pages. What differs from every other adapter is the step
(``serving/adapter.py``, "A step is not a token"; docs/SERVING.md,
"Blocks"): the adapter states ``block_tokens = L``, ``state["tokens"]`` is a
lane's open block ``(B, L)``, and one ``decode_forward`` runs the ``L``
positions of every lane's block over the lane's committed pages (decoded
once for the ``L`` queries), its tail's live rows and the block's own keys,
all visible to one another. The program around it
(``programs.build``'s ``decode_block_step``) decides what the forward was:
on a lane whose block is all known it was the STORE forward, its K and V
are in the tail now and the block's tokens are emitted; on every other lane
it was a DENOISE forward, whose K and V are dropped and whose confident
positions are unmasked (``adapter.unmask_block``: ``low_confidence_static``
or, with a threshold under 1, ``low_confidence_dynamic``). Greedy only: a
sampling temperature is not served. A store forward is never folded into a
denoise forward: the K and V of a block that still holds a mask are not the
stored K and V (ROADMAP A has the fusion of a store with the next block's
first denoise as a later step).

Page geometry is the streams' arithmetic (``serving/window.py`` says the
same of its own): at 64 tokens a page and bucket 512 a ``k`` or ``v`` page
of four heads of 128 is 64 buckets, two whole 32-bucket chunks, one bucket a
token, rows of 512: the flat Mosaic kernels at commit and the paged read at
decode.

The disaggregated path brings a first token and this adapter has none: it
is refused (``transport.require_kv_streams``) and served with local prefill.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..models import sdar_moe as sdar
from ..models.mla_moe import rms_norm
from ..parallel import moe
from .adapter import (
    BLOCK_COUNTERS,
    Adapter,
    attend_paged_block,
    block_masks,
    block_stores,
    page_live,
    page_specs,
)


class BlockDiffusionServer(Adapter):
    """Model adapter for one ``(SdarMoeConfig, params)`` pair; cache streams
    ``k`` and ``v`` on every layer, global pages, a block of
    ``cfg.block_tokens`` positions a lane a step."""

    kind = "block_diffusion"
    # What a decode step counts over its expert layers (``moe.STATS`` in
    # order), then what the block step itself counts.
    step_counters = tuple(f"moe.{name}" for name in moe.STATS) + (
        BLOCK_COUNTERS)
    guards_global_read = True

    def __init__(self, model_cfg, params, serve=None):
        super().__init__(model_cfg, params, serve)
        self.block_tokens = model_cfg.block_tokens
        self.denoise_steps = model_cfg.denoise_steps
        self.unmask_threshold = model_cfg.unmask_threshold
        self.mask_token = model_cfg.mask_token

    def cache_streams(self, layer: int):
        (spec,) = page_specs(self.layer_name(layer), self.serve.page_tokens,
                             [(self.cfg.n_kv_head, self.cfg.d_head)])
        return (("k", spec), ("v", spec))

    # -- forwards ----------------------------------------------------------

    def prefill_forward(self, tokens, positions, last_idx):
        """The forward of a prompt's whole blocks (right-padded to whole
        pages) under the block mask: None in the logits' place (a prefill
        yields no token), then every layer's ``k`` and ``v (B, S, Hk, dh)``
        f32. Right-padding lies in later blocks than every real position
        and is inert; a padded token does go through the experts (dropless:
        it takes no real token's place)."""
        cfg = self.cfg
        x = sdar.embed(cfg, self.p, tokens)
        ks, vs = [], []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            a = rms_norm(x, pl["in_norm"], cfg.eps)
            q, k, v = sdar.attn_project(cfg, a, pl["attn"], positions)
            ks.append(k)
            vs.append(v)
            o = sdar.attend_blocks(cfg, q, k, v)
            x, _ = sdar.block_tail(cfg, pl, x, o)
        return None, ks, vs

    def decode_forward(self, state, streams):
        """One forward of every lane's open block ``state["tokens"] (B,
        L)`` at positions ``pos`` onward: the committed pages read through
        the page table (its live slots alone), the tail's live rows and the
        block's own keys in one softmax (:func:`attend_paged_block`); the
        block's ``k`` and ``v`` go to the tails of the lanes that store
        (:func:`block_stores`) and of no other. Returns ``(logits (B, L,
        V), the new tails by stream, moe.STATS summed over the layers
        (``load_max`` their largest) counted over the active lanes'
        positions)``."""
        cfg = self.cfg
        n = cfg.block_tokens
        x = sdar.embed(cfg, self.p, state["tokens"])  # (B, L, D)
        positions = state["pos"][:, None] + np.arange(n, dtype=np.int32)
        masks = block_masks(self.serve, state)
        live = page_live(self.serve, state)
        store = block_stores(state)
        counted = jnp.repeat(state["active"], n)  # a position's lane
        new = {"k": [], "v": []}
        counts = []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            a = rms_norm(x, pl["in_norm"], cfg.eps)
            q, k, v = sdar.attn_project(cfg, a, pl["attn"], positions)
            o, tails = attend_paged_block(
                state, layer, streams[layer], masks, q, k, v, cfg.dtype,
                np.sqrt(cfg.d_head), store, live=live)
            for name, tail in tails.items():
                new[name].append(tail)
            x, stats = sdar.block_tail(cfg, pl, x, o, count_mask=counted)
            counts.append(stats)
        return sdar.logits(cfg, self.p, x), new, moe.total_stats(counts)
