"""The latent-attention adapter: an MLA decoder with dropless experts
(``models/mla_moe.py``) behind the one scheduler.

A token leaves two cache streams a layer, shared by all heads and neither
of them a key or a value: ``c``, the normalised latent (``kv_lora_rank``
values), and ``kr``, the rotated position key (``d_rope`` values). They
differ in width and in scale, so each is a stream of its own with its own
pool, pages and buckets. Prefill takes the expanded attention (keys and
values of the whole prompt rebuilt from ``c``, in query blocks); decode
takes the absorbed one against the gathered latents, so no per-head key or
value of a cached token is ever rebuilt.

Page geometry is the streams' arithmetic (``ops/codec_pallas``: the flat
Mosaic kernels want whole 32-bucket chunks a page): at 256 tokens a page
and bucket 512, a ``c`` page of a 512-wide latent is 256 buckets (eight
chunks, one bucket a token) and a ``kr`` page of a 64-wide key is 32
buckets (one chunk, eight tokens a bucket).

The disaggregated path ships K and V frames and refuses this adapter
(``transport.require_kv_streams``); it is served with local prefill.
"""

from __future__ import annotations

import jax

from ..models import mla_moe
from ..parallel import moe
from .adapter import Adapter, lane_masks, layer_cache_rows, page_specs


class LatentMoEServer(Adapter):
    """Model adapter for one ``(MlaMoeConfig, params)`` pair; cache streams
    ``c`` and ``kr``."""

    kind = "mla_moe"
    # What a decode step counts over its expert layers, as
    # ``cgx.serve.<name>``: ``moe.STATS`` in order.
    step_counters = tuple(f"moe.{name}" for name in moe.STATS)

    def cache_streams(self, layer: int):
        c, kr = page_specs(
            self.layer_name(layer), self.serve.page_tokens,
            [(1, self.cfg.kv_lora_rank), (1, self.cfg.d_rope)],
        )
        return (("c", c), ("kr", kr))

    # -- forwards ----------------------------------------------------------

    def _block_tail(self, x, pl, attn_out, count_mask=None):
        """Output projection, residual, then the layer's feed-forward."""
        cfg = self.cfg
        x = x + mla_moe._mm(attn_out, pl["attn"]["o"], cfg.dtype)
        out, stats = mla_moe.ffn(
            cfg, pl, mla_moe.rms_norm(x, pl["ffn_norm"], cfg.eps),
            count_mask=count_mask,
        )
        return x + out, stats

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt: the logits at
        ``last_idx``, then every layer's ``c (B, S, 1, Rkv)`` and ``kr (B,
        S, 1, dr)`` f32. Right-padding is inert for every real position
        under the causal mask; a padded token does go through the experts
        (dropless: it takes no real token's place)."""
        cfg = self.cfg
        x = mla_moe.embed(cfg, self.p, tokens)
        cs, krs = [], []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            y = mla_moe.rms_norm(x, pl["attn_norm"], cfg.eps)
            q_nope, q_rope, c, k_r = mla_moe.mla_project(
                cfg, y, pl["attn"], positions
            )
            cs.append(c[:, :, None])
            krs.append(k_r[:, :, None])
            o = mla_moe.attend_expanded(
                cfg, pl["attn"], q_nope, q_rope, c, k_r
            )
            x, _ = self._block_tail(x, pl, o)
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return mla_moe.logits(cfg, self.p, x_last)[:, -1], cs, krs

    def decode_forward(self, state, streams):
        """One decode position against the paged latent cache: ``(logits
        (B, V), the new tails by stream, moe.STATS summed over the expert
        layers (``load_max`` their largest) counted over the active
        lanes)``."""
        cfg, dt = self.cfg, self.cfg.dtype
        x = mla_moe.embed(cfg, self.p, state["tokens"][:, None])
        positions = state["pos"][:, None]
        tail_idx, mask_c, mask_t = lane_masks(self.serve, state)
        new_tails = {"c": [], "kr": []}
        counts = []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            y = mla_moe.rms_norm(x, pl["attn_norm"], cfg.eps)
            q_nope, q_rope, c, k_r = mla_moe.mla_project(
                cfg, y, pl["attn"], positions
            )
            pages, tails, written = layer_cache_rows(
                state, layer, streams[layer], tail_idx, (c, k_r), dt
            )
            for name, tail in written.items():
                new_tails[name].append(tail)
            o = mla_moe.attend_absorbed(
                cfg, pl["attn"], q_nope[:, 0], q_rope[:, 0], pages["c"],
                pages["kr"], mask_c, tail=(tails["c"], tails["kr"], mask_t),
            )
            x, stats = self._block_tail(
                x, pl, o[:, None], count_mask=state["active"]
            )
            if stats is not None:
                counts.append(stats)
        logits = mla_moe.logits(cfg, self.p, x)[:, -1]
        return logits, new_tails, moe.total_stats(counts)
