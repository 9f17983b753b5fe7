"""The hybrid adapters: decoders whose layers are mostly recurrent with a
few attention layers among them, behind the one scheduler.
:class:`HybridSSMServer` serves ``models/granite_hybrid.py`` (Mamba-2 layers
and grouped-query attention), :class:`HybridGDNServer`
``models/olmo_hybrid.py`` (gated delta-rule layers and multi-head attention
with normed queries and keys), :class:`HybridLatentMoEServer`
``models/ling_hybrid.py`` (KDA layers and latent attention, routed experts
of which the tree may hold a share).

Their layers name different streams. An attention layer leaves pages: ``k``
and ``v``, rows of ``n_kv_head * d_head`` (no positional embedding, so
what is cached is the projection itself, normed where the model norms it). A
recurrent layer leaves none: what a lane keeps of it is a fixed-size state,
float32, which every decode step rewrites whole (one kernel a layer over
all lanes, in place) and an admission overwrites with what the prefill left
at the prompt's last token:

* Mamba-2: ``conv (d_conv - 1, d_inner + 2 d_state)`` (the convolution's
  last inputs) and ``ssm (d_state, d_inner)``; ``ops.dispatch.ssm_update``.
* gated delta rule: ``conv (d_conv - 1, heads x (2 d_k + d_v))`` and ``gdn
  (d_k, heads x d_v)``, a matrix a head; ``ops.dispatch.gdn_update``.
* KDA (a delta rule gated a key channel): ``conv (d_conv - 1, 3 heads x
  d_head)`` and ``kda (d_head, heads x d_head)``;
  ``ops.dispatch.kda_update``. Its attention layers leave no ``k`` and ``v``
  but ``serving/latent.py``'s latent streams ``c`` and ``kr``, read by the
  absorbed attention: state streams and latent streams in one model.

``state_streams`` states them; the scheduler carries them in the donated
state beside pools and tails. What an attention layer's decode position does
is the same in both (``adapter.lane_masks``, ``adapter.page_live``,
``adapter.attend_paged``): the token's ``k`` and ``v`` into the raw tail, the
pages the lane has committed read where they lie (a slot of its table it has
not reached is neither fetched nor decoded), one ``decode_attention`` over
both. Ling's latent layer reads its whole table (``layer_cache_rows`` without
a guard: its ``kr`` stream takes the gathered lowering, where a guard is a
``where`` and saves nothing).

Page geometry is the streams' arithmetic (``serving/latent.py`` says the
same of its own). granite-4.0-h-micro at 256 tokens a page and bucket 512:
a ``k`` or ``v`` page of eight heads of 64 is 256 buckets, eight whole
32-bucket chunks, one bucket a token, rows of 512. Olmo-Hybrid-7B at 64
tokens a page: thirty heads of 128 are 480 buckets, fifteen whole chunks,
rows of 3,840. Both take the flat Mosaic kernels at commit and the paged
read at decode.

The disaggregated path ships K and V frames of every layer and no state;
it refuses these adapters (``transport.require_kv_streams``), which are
served with local prefill.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import granite_hybrid as gh
from ..models import ling_hybrid as lh
from ..models import mla_moe
from ..models import olmo_hybrid as oh
from ..models.mla_moe import _mm, rms_norm
from ..parallel import moe
from .adapter import (
    Adapter,
    ServeConfig,
    attend_paged,
    lane_masks,
    layer_cache_rows,
    page_live,
    page_specs,
)


class _HybridAdapter(Adapter):
    """What the hybrid adapters add to :class:`~.adapter.Adapter`: pages on
    the layers the config lists as ``attention_layers`` (``k`` and ``v``
    unless a subclass names other streams), and on the others the state
    streams a subclass names, kept in ``state_dtype`` (float32 unless a
    control asks for less)."""

    def __init__(self, model_cfg, params,
                 serve: Optional[ServeConfig] = None,
                 state_dtype: Any = jnp.float32):
        super().__init__(model_cfg, params, serve)
        self.state_dtype = jnp.dtype(state_dtype)

    def cache_streams(self, layer: int):
        cfg = self.cfg
        if layer not in cfg.attention_layers:
            return ()
        (spec,) = page_specs(self.layer_name(layer), self.serve.page_tokens,
                             [(cfg.n_kv_head, cfg.d_head)])
        return (("k", spec), ("v", spec))

    def with_params(self, params):
        return type(self)(self.cfg, params, self.serve, self.state_dtype)

    def state_bytes_per_lane(self) -> int:
        return (self.cfg.state_bytes_per_lane() // 4
                * self.state_dtype.itemsize)


class HybridSSMServer(_HybridAdapter):
    """Model adapter for one ``(HybridConfig, params)`` pair: state streams
    ``conv`` and ``ssm`` on the Mamba layers."""

    kind = "hybrid_ssm"
    guards_global_read = True

    def state_streams(self, layer: int):
        cfg = self.cfg
        if cfg.layer_types[layer] != "mamba":
            return ()
        return (
            ("conv", (cfg.d_conv - 1, cfg.d_xbc), self.state_dtype),
            ("ssm", (cfg.d_state, cfg.d_inner), self.state_dtype),
        )

    # -- forwards ----------------------------------------------------------

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt: the logits at
        ``last_idx``; each attention layer's ``k`` and ``v`` ``(B, S, Hk,
        dh)`` f32 (right-padding is inert under the causal mask); each
        Mamba layer's ``conv`` and ``ssm`` state after position
        ``last_idx``, which the pad does not reach
        (``granite_hybrid.mamba_prefill``). One list a stream, None for a
        layer without it. ``positions`` is not used: nothing is rotated."""
        cfg = self.cfg
        x = gh.embed(cfg, self.p, tokens)
        out = {name: [None] * cfg.n_layer for name in ("k", "v", "conv",
                                                       "ssm")}
        for layer, kind in enumerate(cfg.layer_types):
            pl = self.p[f"layer_{layer}"]
            y = rms_norm(x, pl["mixer_norm"], cfg.eps)
            if kind == "mamba":
                mixed, conv, state = gh.mamba_prefill(
                    cfg, pl["mamba"], y, last_idx
                )
                out["conv"][layer] = conv.astype(self.state_dtype)
                out["ssm"][layer] = state.astype(self.state_dtype)
            else:
                q, k, v = gh.attn_project(cfg, y, pl["attn"])
                out["k"][layer] = k.astype(jnp.float32)
                out["v"][layer] = v.astype(jnp.float32)
                mixed = _mm(gh.attend_grouped(cfg, q, k, v),
                            pl["attn"]["o"], cfg.dtype)
            x = gh.mlp_half(cfg, pl, gh.residual(cfg, x, mixed))
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return (gh.logits(cfg, self.p, x_last)[:, -1], out["k"], out["v"],
                out["conv"], out["ssm"])

    def decode_forward(self, state, streams):
        """One decode position: an attention layer reads the pages its
        lane has committed (``adapter.page_live``; ``cfg.dtype`` rows,
        contracted where they lie) and, apart, its raw tail with this
        token's K and V appended; a Mamba layer takes one step of its
        recurrence and hands back its state, rewritten. Returns (logits (B,
        V), the new tails and states by stream, None)."""
        cfg, dt = self.cfg, self.cfg.dtype
        x = gh.embed(cfg, self.p, state["tokens"][:, None])[:, 0]  # (B, D)
        masks = lane_masks(self.serve, state)
        live = page_live(self.serve, state)
        new = {name: [None] * cfg.n_layer for name in ("k", "v", "conv",
                                                       "ssm")}
        for layer, kind in enumerate(cfg.layer_types):
            pl = self.p[f"layer_{layer}"]
            y = rms_norm(x, pl["mixer_norm"], cfg.eps)
            if kind == "mamba":
                mixed, new["conv"][layer], new["ssm"][layer] = gh.mamba_step(
                    cfg, pl["mamba"], y, state["state_conv"][layer],
                    state["state_ssm"][layer],
                )
            else:
                q, k, v = gh.attn_project(cfg, y[:, None], pl["attn"])
                o, tails = attend_paged(
                    state, layer, streams[layer], masks, q, k, v, dt,
                    1.0 / cfg.attention_multiplier, live=live,
                )
                for name, tail in tails.items():
                    new[name][layer] = tail
                mixed = _mm(o, pl["attn"]["o"], dt)
            x = gh.mlp_half(cfg, pl, gh.residual(cfg, x, mixed))
        return gh.logits(cfg, self.p, x), new, None


class HybridGDNServer(_HybridAdapter):
    """Model adapter for one ``(OlmoHybridConfig, params)`` pair: state
    streams ``conv`` and ``gdn`` on the gated delta-rule layers."""

    kind = "hybrid_gdn"
    guards_global_read = True

    def state_streams(self, layer: int):
        cfg = self.cfg
        if cfg.layer_types[layer] != "linear_attention":
            return ()
        return (
            ("conv", (cfg.d_conv - 1, cfg.d_qkv), self.state_dtype),
            ("gdn", (cfg.d_k, cfg.d_value), self.state_dtype),
        )

    # -- forwards ----------------------------------------------------------

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt: the logits at
        ``last_idx``; each full-attention layer's normed ``k`` and its ``v``
        ``(B, S, Hk, dh)`` f32, as the projections left them (right-padding
        is inert under the causal mask); each delta-rule layer's ``conv``
        and ``gdn`` state after
        position ``last_idx``, which the pad does not reach
        (``olmo_hybrid.gdn_prefill``). One list a stream, None for a layer
        without it. ``positions`` is not used: nothing is rotated."""
        cfg = self.cfg
        x = oh.embed(cfg, self.p, tokens)
        out = {name: [None] * cfg.n_layer for name in ("k", "v", "conv",
                                                       "gdn")}
        for layer, kind in enumerate(cfg.layer_types):
            pl = self.p[f"layer_{layer}"]
            if kind == "linear_attention":
                mixed, conv, state = oh.gdn_prefill(
                    cfg, pl["gdn"], x, last_idx
                )
                out["conv"][layer] = conv.astype(self.state_dtype)
                out["gdn"][layer] = state.astype(self.state_dtype)
            else:
                q, k, v = oh.attn_project(cfg, x, pl["attn"])
                out["k"][layer], out["v"][layer] = k, v
                mixed = oh.attend(cfg, q, k, v, pl["attn"])
            x = oh.mlp_half(cfg, pl, oh.post_norm_residual(
                cfg, x, mixed, pl["mixer_norm"]))
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return (oh.logits(cfg, self.p, x_last)[:, -1], out["k"], out["v"],
                out["conv"], out["gdn"])

    def decode_forward(self, state, streams):
        """One decode position: a full-attention layer reads the pages its
        lane has committed (``adapter.page_live``) and, apart, its raw tail
        with this token's K and V appended (``adapter.attend_paged``); a
        delta-rule layer takes one step of its recurrence and hands back its
        state, rewritten. Returns (logits (B, V), the new tails and states
        by stream, None)."""
        cfg, dt = self.cfg, self.cfg.dtype
        x = oh.embed(cfg, self.p, state["tokens"])  # (B, D)
        masks = lane_masks(self.serve, state)
        live = page_live(self.serve, state)
        new = {name: [None] * cfg.n_layer for name in ("k", "v", "conv",
                                                       "gdn")}
        for layer, kind in enumerate(cfg.layer_types):
            pl = self.p[f"layer_{layer}"]
            if kind == "linear_attention":
                mixed, new["conv"][layer], new["gdn"][layer] = oh.gdn_step(
                    cfg, pl["gdn"], x, state["state_conv"][layer],
                    state["state_gdn"][layer],
                )
            else:
                q, k, v = oh.attn_project(cfg, x[:, None], pl["attn"])
                o, tails = attend_paged(
                    state, layer, streams[layer], masks, q, k, v, dt,
                    np.sqrt(cfg.d_head), live=live,
                )
                for name, tail in tails.items():
                    new[name][layer] = tail
                mixed = oh.attn_out(cfg, o, pl["attn"])
            x = oh.mlp_half(cfg, pl, oh.post_norm_residual(
                cfg, x, mixed, pl["mixer_norm"]))
        return oh.logits(cfg, self.p, x), new, None


class HybridLatentMoEServer(_HybridAdapter):
    """Model adapter for one ``(LingHybridConfig, params)`` pair: state
    streams ``conv`` and ``kda`` on the KDA layers, cache streams ``c`` and
    ``kr`` (``serving/latent.py``'s geometry) on the latent-attention
    layers, and the expert layers' counts every decode step."""

    kind = "hybrid_kda_mla"

    def __init__(self, model_cfg, params,
                 serve: Optional[ServeConfig] = None,
                 state_dtype: Any = jnp.float32):
        super().__init__(model_cfg, params, serve, state_dtype)
        # What a decode step counts over its expert layers, as
        # ``cgx.serve.<name>``: the layer's ``stats`` in order.
        names = (moe.STATS if model_cfg.experts_held is None
                 else moe.HELD_STATS)
        self.step_counters = tuple(f"moe.{name}" for name in names)

    def cache_streams(self, layer: int):
        cfg = self.cfg
        if layer not in cfg.attention_layers:
            return ()
        c, kr = page_specs(
            self.layer_name(layer), self.serve.page_tokens,
            [(1, cfg.kv_lora_rank), (1, cfg.d_rope)],
        )
        return (("c", c), ("kr", kr))

    def state_streams(self, layer: int):
        cfg = self.cfg
        if cfg.layer_types[layer] != "kda":
            return ()
        return (
            ("conv", (cfg.d_conv - 1, cfg.d_qkv), self.state_dtype),
            ("kda", (cfg.d_head, cfg.d_inner), self.state_dtype),
        )

    # -- forwards ----------------------------------------------------------

    def prefill_forward(self, tokens, positions, last_idx):
        """Full causal forward over a (right-padded) prompt: the logits at
        ``last_idx``; each latent-attention layer's ``c (B, S, 1, Rkv)`` and
        rotated ``kr (B, S, 1, dr)`` f32 (right-padding is inert under the
        causal mask; a padded token does go through the experts, dropless);
        each KDA layer's ``conv`` and ``kda`` state after position
        ``last_idx``, which the pad does not reach
        (``ling_hybrid.kda_prefill``). One list a stream, None for a layer
        without it."""
        cfg = self.cfg
        x = lh.embed(cfg, self.p, tokens)
        out = {name: [None] * cfg.n_layer for name in ("c", "kr", "conv",
                                                       "kda")}
        for layer, kind in enumerate(cfg.layer_types):
            pl = self.p[f"layer_{layer}"]
            y = rms_norm(x, pl["mixer_norm"], cfg.eps)
            if kind == "kda":
                mixed, conv, state = lh.kda_prefill(
                    cfg, pl["kda"], y, last_idx
                )
                out["conv"][layer] = conv.astype(self.state_dtype)
                out["kda"][layer] = state.astype(self.state_dtype)
            else:
                pa = pl["attn"]
                q_nope, q_rope, c, k_r = mla_moe.mla_project(
                    cfg, y, pa, positions
                )
                out["c"][layer] = c[:, :, None]
                out["kr"][layer] = k_r[:, :, None]
                mixed = lh.mla_out(cfg, pa, mla_moe.attend_expanded(
                    cfg, pa, q_nope, q_rope, c, k_r), y)
            x, _ = lh.ffn_half(cfg, pl, x + mixed)
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1)
        return (lh.logits(cfg, self.p, x_last)[:, -1], out["c"], out["kr"],
                out["conv"], out["kda"])

    def decode_forward(self, state, streams):
        """One decode position: a latent-attention layer writes this token's
        ``c`` and ``kr`` into its raw tails and reads its committed pages
        where they lie, the absorbed attention over both; a KDA layer takes
        one step of its recurrence and hands back its state, rewritten.
        Returns (logits (B, V), the new tails and states by stream, the
        expert layers' counts over the active lanes,
        ``moe.total_stats``)."""
        cfg, dt = self.cfg, self.cfg.dtype
        x = lh.embed(cfg, self.p, state["tokens"])  # (B, D)
        positions = state["pos"][:, None]
        tail_idx, mask_c, mask_t = lane_masks(self.serve, state)
        new = {name: [None] * cfg.n_layer for name in ("c", "kr", "conv",
                                                       "kda")}
        counts = []
        for layer, kind in enumerate(cfg.layer_types):
            pl = self.p[f"layer_{layer}"]
            y = rms_norm(x, pl["mixer_norm"], cfg.eps)
            if kind == "kda":
                mixed, new["conv"][layer], new["kda"][layer] = lh.kda_step(
                    cfg, pl["kda"], y, state["state_conv"][layer],
                    state["state_kda"][layer],
                )
            else:
                pa = pl["attn"]
                q_nope, q_rope, c, k_r = mla_moe.mla_project(
                    cfg, y[:, None], pa, positions
                )
                pages, tails, written = layer_cache_rows(
                    state, layer, streams[layer], tail_idx, (c, k_r), dt
                )
                for name, tail in written.items():
                    new[name][layer] = tail
                o = mla_moe.attend_absorbed(
                    cfg, pa, q_nope[:, 0], q_rope[:, 0], pages["c"],
                    pages["kr"], mask_c,
                    tail=(tails["c"], tails["kr"], mask_t),
                )
                mixed = lh.mla_out(cfg, pa, o, y)
            x, stats = lh.ffn_half(cfg, pl, x + mixed,
                                   count_mask=state["active"])
            if stats is not None:
                counts.append(stats)
        return lh.logits(cfg, self.p, x), new, moe.total_stats(counts)
