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

A config with ``hc_mult`` carries that many residual streams a token,
``(B, S, n, D)``, mixed around every sublayer and read out in front of the
final norm (``models/mhc.py``: the tree then holds ``layer_<i>/hc_attn``,
``hc_ffn`` and ``hc_head``); what a sublayer reads and what it leaves is
:meth:`LatentMoEServer._enter` and :meth:`~LatentMoEServer._leave`, and a
config without it is the one stream and the program it always was.
Positions are rotated as the config's ``yarn`` says (``mla_moe.rope``).

The disaggregated path ships K and V frames and refuses this adapter
(``transport.require_kv_streams``); it is served with local prefill.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import mhc, mla_moe
from ..parallel import moe
from .adapter import Adapter, lane_masks, layer_cache_rows, page_specs


class LatentMoEServer(Adapter):
    """Model adapter for one ``(MlaMoeConfig, params)`` pair; cache streams
    ``c`` and ``kr``."""

    kind = "mla_moe"
    # What a decode step counts over its expert layers, as
    # ``cgx.serve.<name>``: ``moe.STATS`` in order.
    step_counters = tuple(f"moe.{name}" for name in moe.STATS)

    def __init__(self, model_cfg, params, serve=None):
        super().__init__(model_cfg, params, serve)
        if model_cfg.hc_mult:
            # Beside the expert layers' counts, how far the step's
            # stream-to-stream mixes were from doubly stochastic.
            self.step_counters += ("mhc.res_err_ppm",)
            # ``phi`` as the kernel reads it, made once: the tree is the
            # programs' argument, and what a program computes from a weight
            # it computes in every call.
            self.p = mhc.with_kernel_phi(params)

    def cache_streams(self, layer: int):
        c, kr = page_specs(
            self.layer_name(layer), self.serve.page_tokens,
            [(1, self.cfg.kv_lora_rank), (1, self.cfg.d_rope)],
        )
        return (("c", c), ("kr", kr))

    # -- forwards ----------------------------------------------------------

    def _enter(self, x, hc, norm_w, kernel, errors=None, count_mask=None):
        """What a sublayer reads, normed, and what :meth:`_leave` needs to
        write its output back: of one residual stream ``x (B, S, D)``,
        itself; of hyper-connected streams ``x (B, S, n, D)``
        (``models/mhc.py``), their ``pre`` mix and the two mixes of the way
        back, under the kernel name ``kernel``. ``errors`` collects
        ``mhc.res_error`` over ``count_mask``'s lanes."""
        cfg = self.cfg
        if not cfg.hc_mult:
            return mla_moe.rms_norm(x, norm_w, cfg.eps), x
        u, h_post, h_res = mhc.pre(cfg, x, hc, kernel)
        if errors is not None:
            errors.append(mhc.res_error(h_res, cfg.hc_mult, count_mask))
        return mla_moe.rms_norm(u, norm_w, cfg.eps), (x, h_post, h_res)

    @staticmethod
    def _leave(carry, out):
        """The residual: ``x + out``, or the streams mixed and ``out``
        spread over them."""
        if isinstance(carry, tuple):
            return mhc.mix(carry[0], out, *carry[1:])
        return carry + out

    def _embed(self, tokens):
        x = mla_moe.embed(self.cfg, self.p, tokens)
        return mhc.spread(x, self.cfg.hc_mult) if self.cfg.hc_mult else x

    def _logits(self, x, kernel):
        """Final norm and head over ``x (B, S, D)`` or, read out first, the
        streams ``(B, S, n, D)``."""
        if self.cfg.hc_mult:
            x = mhc.read_out(self.cfg, x, self.p["hc_head"], kernel)
        return mla_moe.logits(self.cfg, self.p, x)

    def _block_tail(self, carry, pl, attn_out, kernel, errors=None,
                    count_mask=None):
        """Output projection, residual, then the layer's feed-forward."""
        cfg = self.cfg
        x = self._leave(
            carry, mla_moe._mm(attn_out, pl["attn"]["o"], cfg.dtype))
        y, carry = self._enter(x, pl.get("hc_ffn"), pl["ffn_norm"], kernel,
                               errors, count_mask)
        out, stats = mla_moe.ffn(cfg, pl, y, count_mask=count_mask)
        return self._leave(carry, out), stats

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt: the logits at
        ``last_idx``, then every layer's ``c (B, S, 1, Rkv)`` and ``kr (B,
        S, 1, dr)`` f32. Right-padding is inert for every real position
        under the causal mask; a padded token does go through the experts
        (dropless: it takes no real token's place)."""
        cfg, kernel = self.cfg, mhc.PREFILL_KERNEL
        x = self._embed(tokens)
        cs, krs = [], []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            y, carry = self._enter(x, pl.get("hc_attn"), pl["attn_norm"],
                                   kernel)
            q_nope, q_rope, c, k_r = mla_moe.mla_project(
                cfg, y, pl["attn"], positions
            )
            cs.append(c[:, :, None])
            krs.append(k_r[:, :, None])
            o = mla_moe.attend_expanded(
                cfg, pl["attn"], q_nope, q_rope, c, k_r
            )
            x, _ = self._block_tail(carry, pl, o, kernel)
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return self._logits(x_last, kernel)[:, -1], cs, krs

    def decode_forward(self, state, streams):
        """One decode position against the paged latent cache: ``(logits
        (B, V), the new tails by stream, moe.STATS summed over the expert
        layers (``load_max`` their largest) counted over the active
        lanes)``; a hyper-connected model's counts end with the step's
        largest ``mhc.res_error`` over the active lanes, in millionths."""
        cfg, dt, kernel = self.cfg, self.cfg.dtype, mhc.DECODE_KERNEL
        active = state["active"]
        x = self._embed(state["tokens"][:, None])
        positions = state["pos"][:, None]
        tail_idx, mask_c, mask_t = lane_masks(self.serve, state)
        new_tails = {"c": [], "kr": []}
        counts, errors = [], []
        for layer in range(cfg.n_layer):
            pl = self.p[f"layer_{layer}"]
            y, carry = self._enter(x, pl.get("hc_attn"), pl["attn_norm"],
                                   kernel, errors, active)
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
                carry, pl, o[:, None], kernel, errors, active
            )
            if stats is not None:
                counts.append(stats)
        logits = self._logits(x, kernel)[:, -1]
        counts = moe.total_stats(counts)
        if errors:
            ppm = jnp.round(1e6 * jnp.max(jnp.stack(errors)))
            counts = jnp.concatenate([counts, ppm.astype(counts.dtype)[None]])
        return logits, new_tails, counts
